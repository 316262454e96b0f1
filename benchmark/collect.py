"""What the harness reads of the program from outside it: JAX's own
monitoring events, the program's counter registries, the resident store's
entries, QueryInfo over HTTP, and the device's memory peak."""
import json
import threading
import urllib.request

TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                "/jax/compilation_cache/cache_misses")


class JaxEvents:
    """Counts and seconds of JAX's tracing, lowering and compile events,
    and of its persistent-cache hits and misses."""

    def __init__(self):
        self._lock = threading.Lock()
        self.values = {"jax_trace_s": 0.0, "jax_traces": 0,
                       "jax_backend_compile_s": 0.0, "jax_backend_compiles": 0,
                       "jax_cache_hits": 0, "jax_cache_misses": 0,
                       "jax_cache_requests": 0}
        import jax
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        if event in CACHE_EVENTS:
            with self._lock:
                self.values["jax_" + event.rsplit("/", 1)[-1]] += 1
        elif event == CACHE_REQUEST_EVENT:
            with self._lock:
                self.values["jax_cache_requests"] += 1

    def _duration(self, event, seconds, **_kw):
        with self._lock:
            if event in TRACE_EVENTS:
                self.values["jax_trace_s"] += seconds
                self.values["jax_traces"] += 1
            elif event == COMPILE_EVENT:
                self.values["jax_backend_compile_s"] += seconds
                self.values["jax_backend_compiles"] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self.values)


def counters(jax_events: JaxEvents) -> dict:
    """One flat snapshot of every registry a per-layer metric reads."""
    from presto_tpu.parallel.fabric import FABRIC_METRICS
    from presto_tpu.serving import SERVING_METRICS
    from presto_tpu.storage import STORAGE_METRICS
    from presto_tpu.worker.exchange import EXCHANGE_METRICS
    out = dict(jax_events.snapshot())
    out.update({f"storage_{k}": v for k, v in STORAGE_METRICS.snapshot().items()})
    out.update({f"serving_{k}": v for k, v in SERVING_METRICS.snapshot().items()
                if isinstance(v, (int, float))})
    out.update({f"exchange_{k}": v for k, v in EXCHANGE_METRICS.snapshot().items()
                if isinstance(v, (int, float))})
    for fabric, s in FABRIC_METRICS.snapshot().items():
        out.update({f"fabric_{fabric}_{k}": v for k, v in s.items()
                    if isinstance(v, (int, float))})
    return out


def _store():
    """The resident store of the server-default ExecutionConfig."""
    from presto_tpu.exec.pipeline import tuned_config
    from presto_tpu.storage import get_store
    cfg = tuned_config()
    return get_store(cfg.storage_budget_bytes, cfg.storage_max_column_bytes)


def resident_columns() -> dict:
    """{"table.column": bytes} of the store's entries, each column once:
    what the tables hold in device memory (the pool's `resident_bytes`
    charges a column once per task that asked at the same time)."""
    return {f"{table}.{column}": int(entry.nbytes)
            for (_cid, table, column, _sf, _i32), entry
            in _store().entries.items()}


def free_program_state(servers) -> None:
    """Servers down and the resident columns dropped, so that the
    reference does not share the host's memory with them."""
    import gc
    import jax
    servers.close()
    _store().clear()
    jax.clear_caches()
    gc.collect()


def query_info(uri: str, query_id: str):
    """QueryInfo as the coordinator serves it, or None."""
    try:
        with urllib.request.urlopen(f"{uri}/v1/query/{query_id}",
                                    timeout=10) as resp:
            return json.loads(resp.read())
    except (OSError, ValueError):
        return None


def device_record(devices) -> dict:
    """The device as JAX reports it, and the peak on the fullest chip."""
    stats = [d.memory_stats() or {} for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max((s.get("peak_bytes_in_use") or 0)
                                     for s in stats)}
