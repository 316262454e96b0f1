"""JAX's own monitoring events, attributed to the query or task that
caused them.

One duration listener and one event listener, registered once per process
by the first WorkerServer (`install()`; never at import).  JAX fires
`jaxpr_trace_duration`, `jaxpr_to_mlir_module_duration` and
`backend_compile_duration` on the thread that traces and compiles, with
the program's name as `fun_name`, so the thread-local owner of
utils/runtime_stats.py receives the seconds:

  jaxTraceWallNanos + jaxTraces          tracing a jitted function
  jaxLowerWallNanos                      jaxpr -> MLIR module
  jaxBackendCompileWallNanos             "backend compile": a persistent-
    + jaxBackendCompiles                 cache lookup that deserialises an
                                         executable, or a true compile
  jaxCacheHits / jaxCacheMisses          the persistent cache's answers
  jaxTrueCompiles                        compile events with no cache hit
                                         inside them on the same thread

Beside the per-owner keys one bounded process table keeps, per program
name, {traces, trace_s, loads, load_s, true_compiles}: served under
`processMetrics.programs` of /v1/query/{id} and under `programs` of
/v1/status, it names the programs a window's compile count counts.
"""
from __future__ import annotations

import threading
import time
from typing import Dict

from ..utils.runtime_stats import current_stats

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

MAX_PROGRAMS = 256
OTHER = "(other)"

_tls = threading.local()


def program_name(fun_name: str) -> str:
    """`jit(scan_agg_direct)` / `jit_scan_agg_direct` (the module's name
    at lowering and compile) and `scan_agg_direct` (the traced
    function's) are one program."""
    name = str(fun_name or "(unnamed)")
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name[4:] if name.startswith("jit_") else name


class ProgramTable:
    """{program: traces, trace_s, loads, load_s, true_compiles}, at most
    MAX_PROGRAMS names; later names share one `(other)` row."""

    def __init__(self, max_programs: int = MAX_PROGRAMS):
        self._lock = threading.Lock()
        self._max = max_programs
        self._rows: Dict[str, dict] = {}

    def _row(self, name: str) -> dict:
        row = self._rows.get(name)
        if row is None:
            if len(self._rows) >= self._max:
                name = OTHER
                row = self._rows.get(name)
            if row is None:
                row = self._rows[name] = {
                    "traces": 0, "trace_s": 0.0, "loads": 0, "load_s": 0.0,
                    "true_compiles": 0}
        return row

    def traced(self, name: str, seconds: float) -> None:
        with self._lock:
            row = self._row(name)
            row["traces"] += 1
            row["trace_s"] += seconds

    def loaded(self, name: str, seconds: float, true_compile: bool) -> None:
        with self._lock:
            row = self._row(name)
            row["loads"] += 1
            row["load_s"] += seconds
            row["true_compiles"] += int(true_compile)

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            return {n: dict(r) for n, r in sorted(self._rows.items())}

    def clear(self) -> None:
        with self._lock:
            self._rows.clear()


PROGRAMS = ProgramTable()


def _record(s, name: str, seconds: float, count: str = "") -> None:
    """JAX reports a duration when it is over: the interval ended now, on
    this thread; nobody measured its CPU time (-1)."""
    nanos = int(seconds * 1e9)
    s.record(name, time.perf_counter_ns() - nanos, nanos, -1, count)


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event == TRACE_EVENT:
        PROGRAMS.traced(program_name(kw.get("fun_name")), seconds)
        s = current_stats()
        if s is not None:
            _record(s, "jaxTrace", seconds, "jaxTraces")
    elif event == LOWER_EVENT:
        s = current_stats()
        if s is not None:
            _record(s, "jaxLower", seconds)
    elif event == COMPILE_EVENT:
        true_compile = not getattr(_tls, "hits", 0)
        _tls.hits = 0
        PROGRAMS.loaded(program_name(kw.get("fun_name")), seconds,
                        true_compile)
        s = current_stats()
        if s is not None:
            _record(s, "jaxBackendCompile", seconds, "jaxBackendCompiles")
            if true_compile:
                s.add("jaxTrueCompiles", 1)


def _on_event(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT:
        _tls.hits = getattr(_tls, "hits", 0) + 1
        s = current_stats()
        if s is not None:
            s.add("jaxCacheHits", 1)
    elif event == CACHE_MISS_EVENT:
        s = current_stats()
        if s is not None:
            s.add("jaxCacheMisses", 1)


_install_lock = threading.Lock()
_installed = False


def install() -> None:
    """Register the two listeners, once per process."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True
