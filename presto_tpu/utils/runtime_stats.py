"""RuntimeStats + the one span primitive + tracer SPI.

The analog of the reference's fine-grained engine profiling (§5.1):

  * RuntimeStats (presto-common/.../common/RuntimeStats.java): a
    thread-safe name -> {sum, count, min, max, unit} metric map threaded
    through query execution and mergeable task -> stage -> query
    (`merge`, `merge_dict`).  Phases are recorded with `span(name)`, the
    way SqlQueryExecution.java:556-614 wraps analysis/optimization/
    fragmentation in recordWallAndCpuTime.

  * `RuntimeStats.span(name, **attrs)` does three things at once: adds
    `<name>WallNanos` to the map; opens
    `jax.profiler.TraceAnnotation("presto:" + name, ...)`, which costs
    under a microsecond with no profiler session and otherwise lands on
    the SAME timeline as the device's `XLA Ops` (the shared clock); and,
    only when the owner carries a recording tracer, appends a `Span`
    with its real interval and the enclosing span as parent.

  * A thread-local owner (`RuntimeStats.activate`, `current_stats`) lets
    deep code find the query's or task's stats without new arguments:
    `host_get` (the one sanctioned device->host transfer), `named_jit`
    (the one wrapper around jax.jit) and the JAX event listener
    (telemetry/jax_events.py) all record into it.

  * Tracer SPI (TracerProviderManager / SimpleTracer,
    presto-main-base/.../tracing/): pluggable `TracerProvider`; the
    in-tree SimpleTracer records a per-query span tree, queryable for
    tests/ops.  NoopTracer is the default.
"""
from __future__ import annotations

import functools
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

NANO = 1_000_000_000
# spans one tracer keeps (a task of ~900 launches records one span each);
# beyond it spans are dropped and counted in `spansDropped`
MAX_SPANS_PER_TRACE = 4096

_perf_ns = time.perf_counter_ns


@dataclass
class Metric:
    unit: str = "NANO"      # NANO | BYTE | NONE (RuntimeUnit analog)
    sum: float = 0.0
    count: int = 0
    min: float = float("inf")
    max: float = float("-inf")

    def add(self, value: float) -> None:
        self.sum += value
        self.count += 1
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value

    def merge(self, other: "Metric") -> None:
        self.sum += other.sum
        self.count += other.count
        self.min = min(self.min, other.min)
        self.max = max(self.max, other.max)

    def to_dict(self) -> dict:
        return {"unit": self.unit, "sum": self.sum, "count": self.count,
                "min": self.min if self.count else 0,
                "max": self.max if self.count else 0}


# ---------------------------------------------------------------------------
# the thread-local owner
# ---------------------------------------------------------------------------

_tls = threading.local()


def current_stats() -> Optional["RuntimeStats"]:
    """The RuntimeStats that owns this thread's work (the task's on a
    task thread, the query's on a statement-executor thread), or None."""
    return getattr(_tls, "stats", None)


def current_span() -> str:
    """Name of the innermost recorded span open on this thread ("" when
    nothing records)."""
    return getattr(_tls, "span", "")


_annotation_cls = None


def _annotate(name: str, ids: dict, attrs: Optional[dict] = None):
    """An entered jax.profiler.TraceAnnotation(`name`); its metadata is
    encoded only while a profiler session is active (the keyword form
    costs twice as much when none is).  Imported on first use: importing
    this module (and `presto_tpu`) must load nothing of the profiler."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation
        _annotation_cls = TraceAnnotation
    ann = _annotation_cls(name)
    ann.__enter__()
    if (ids or attrs) and _annotation_cls.is_enabled():
        ann.set_metadata(**{**ids, **(attrs or {})})
    return ann


class _SpanScope:
    """One open `RuntimeStats.span`: a plain context manager (a generator
    based one costs a microsecond more on paths taken ~900 times a
    query)."""

    __slots__ = ("stats", "name", "attrs", "t0", "ann", "rec", "prev")

    def __init__(self, stats: "RuntimeStats", name: str, attrs: dict):
        self.stats = stats
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        s = self.stats
        self.ann = _annotate("presto:" + self.name, s.ids, self.attrs)
        self.rec = None
        if s.tracer is not None:
            self.prev = getattr(_tls, "span", "")
            self.rec = s.tracer.open_span(
                self.name, s.scope, self.prev or s.root,
                {**s.ids, **self.attrs})
            if self.rec is not None:
                _tls.span = self.rec.name
        self.t0 = _perf_ns()
        return self

    def __exit__(self, *exc):
        dt = _perf_ns() - self.t0
        self.stats.add(self.name + "WallNanos", dt, "NANO")
        rec = self.rec
        if rec is not None:
            rec.end = rec.start + dt / NANO
            _tls.span = self.prev
        self.ann.__exit__(*exc)
        return False


class RuntimeStats:
    def __init__(self, tracer: Optional["Tracer"] = None, scope: str = "",
                 root: str = "", **ids):
        """`ids` (query_id=..., task_id=...) ride every TraceAnnotation
        this owner opens.  `tracer` is kept only when it records;
        recorded spans are named `<name> <scope>` (unique within one
        query's trace, as telemetry/otlp.py's span ids need) and hang off
        `root` unless an enclosing span is open on the thread."""
        self._metrics: Dict[str, Metric] = {}
        self._lock = threading.Lock()
        self.tracer = tracer if tracer is not None and tracer.recording \
            else None
        self.scope = scope
        self.root = root
        self.ids = ids

    def add(self, name: str, value: float, unit: str = "NONE") -> None:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = Metric(unit)
            m.add(value)

    def add_wall(self, nanos: float, *walls: str, count: str = "") -> None:
        """`nanos` into every key of `walls` and +1 into the counter
        `count`, under one lock acquisition: the whole update of a
        `named_jit` call, a `host_get` or a JAX event."""
        with self._lock:
            for name in walls:
                m = self._metrics.get(name)
                if m is None:
                    m = self._metrics[name] = Metric("NANO")
                m.add(nanos)
            if count:
                m = self._metrics.get(count)
                if m is None:
                    m = self._metrics[count] = Metric("NONE")
                m.add(1)

    def span(self, name: str, **attrs) -> _SpanScope:
        """recordWallAndCpuTime analog (wall only; CPU time is not
        meaningful for device-side work): `<name>WallNanos`, a
        `presto:<name>` profiler annotation of the same extent and, with
        a recording tracer, a Span."""
        return _SpanScope(self, name, attrs)

    # the name every caller used before spans existed; keys unchanged
    record_wall = span

    @contextmanager
    def activate(self, parent_span: Optional[str] = None):
        """Make this the owner of the calling thread's work until exit.
        `parent_span` carries the enclosing recorded span across a thread
        hand-off (exec/local_exchange.py's producer threads)."""
        prev = getattr(_tls, "stats", None)
        prev_span = getattr(_tls, "span", "")
        _tls.stats = self
        if parent_span is not None:
            _tls.span = parent_span
        try:
            yield self
        finally:
            _tls.stats = prev
            _tls.span = prev_span

    def merge(self, other: "RuntimeStats") -> None:
        with other._lock:
            items = list(other._metrics.items())
        with self._lock:
            for name, m in items:
                mine = self._metrics.get(name)
                if mine is None:
                    mine = self._metrics[name] = Metric(m.unit)
                mine.merge(m)

    def merge_dict(self, other: Optional[Dict[str, dict]]) -> None:
        """Merge the `to_dict()` form (a task's `runtimeStats` as TaskInfo
        serves it): the task -> stage -> query roll-up of both served
        paths and of EXPLAIN ANALYZE's footer."""
        if not other:
            return
        with self._lock:
            for name, d in other.items():
                count = int(d.get("count", 1))
                if not count:
                    continue
                mine = self._metrics.get(name)
                if mine is None:
                    mine = self._metrics[name] = Metric(
                        d.get("unit", "NONE"))
                mine.merge(Metric(mine.unit, d["sum"], count,
                                  d.get("min", d["sum"]),
                                  d.get("max", d["sum"])))

    def get(self, name: str) -> Optional[Metric]:
        with self._lock:
            return self._metrics.get(name)

    def to_dict(self) -> Dict[str, dict]:
        with self._lock:
            return {n: m.to_dict() for n, m in sorted(self._metrics.items())}


# ---------------------------------------------------------------------------
# the one device->host transfer, and the one wrapper around jax.jit
# ---------------------------------------------------------------------------

def host_get(x, why: str):
    """Fetch `x` (any pytree of device values) to the host.  THE
    sanctioned blocking transfer of the execution layer: analysis/lint.py
    honours the host-sync pragma inside presto_tpu/ on this line alone.
    Counts `hostSyncs` and adds the wait to `hostSyncWaitWallNanos` and
    to the site's own `hostSync.<why>` (sum: nanoseconds waited there,
    count: syncs there) in the thread's owner."""
    import jax
    s = getattr(_tls, "stats", None)
    ann = None if s is None else _annotate("presto:hostSync", s.ids,
                                           {"why": why})
    t0 = _perf_ns()
    try:
        out = jax.device_get(x)  # lint: allow-host-sync
    finally:
        if ann is not None:
            dt = _perf_ns() - t0
            ann.__exit__(None, None, None)
    if s is not None:
        s.add_wall(dt, "hostSyncWaitWallNanos", "hostSync." + why,
                   count="hostSyncs")
        if s.tracer is not None:
            s.tracer.closed_span("hostSync", s, dt, {"why": why})
    return out


class NamedJit:
    """A jitted program under a structural name (`jit_<name>` in the
    device trace and the compile cache).  Calling it counts
    `pipelineLaunches` and the host's time inside the call
    (`pipelineDispatchWallNanos`) into the thread's owner; everything
    else (`lower`, `clear_cache`, `trace`, ...) forwards to the jit."""

    __slots__ = ("name", "_jit", "__weakref__")

    def __init__(self, name: str, jitted):
        self.name = name
        self._jit = jitted

    def __call__(self, *args, **kwargs):
        s = getattr(_tls, "stats", None)
        if s is None:
            return self._jit(*args, **kwargs)
        ann = _annotate("presto:pipelineDispatch", s.ids,
                        {"program": self.name})
        t0 = _perf_ns()
        try:
            out = self._jit(*args, **kwargs)
        finally:
            dt = _perf_ns() - t0
            ann.__exit__(None, None, None)
        s.add_wall(dt, "pipelineDispatchWallNanos", count="pipelineLaunches")
        if s.tracer is not None:
            s.tracer.closed_span("pipelineDispatch", s, dt,
                                 {"program": self.name})
        return out

    def __getattr__(self, attr):
        return getattr(self._jit, attr)


def named_jit(name: str, fn, **jit_kwargs) -> NamedJit:
    """`jax.jit(fn, **jit_kwargs)` whose program is called `name`.

    Names say purpose and shape class (`scan_agg_direct`,
    `chain_materialize`, `gen_lineitem_l_quantity`) and are STRUCTURAL
    only -- never a node id, query id, literal or scale factor -- so the
    number of distinct executables and persistent-cache entries does not
    grow with the number of plans."""
    import jax

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return NamedJit(name, jax.jit(program, **jit_kwargs))


def jit_as(name: str, **jit_kwargs):
    """Decorator form: `@jit_as("chain_counts")` for `@jax.jit`."""
    return lambda fn: named_jit(name, fn, **jit_kwargs)


# ---------------------------------------------------------------------------
# tracer SPI
# ---------------------------------------------------------------------------

@dataclass
class Span:
    """One named interval in the query's span tree (query -> fragment ->
    task -> operator / phase).  `parent` is the parent span's name
    ("" = root); `start`/`end` are unix seconds."""
    name: str
    parent: str = ""
    start: float = 0.0
    end: float = 0.0
    attributes: Dict[str, object] = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"name": self.name, "parent": self.parent,
                "start": self.start, "end": self.end,
                "attributes": dict(self.attributes)}


class Tracer:
    """SPI (presto-spi tracing.Tracer analog)."""

    recording = False

    @contextmanager
    def span(self, name: str, parent: str = "", **attributes):
        """Nested interval recording; no-op in the base/Noop tracers.
        `parent` names the enclosing span explicitly so spans opened on
        worker threads (stage tasks) attach to the right parent."""
        yield name

    def open_span(self, name: str, scope: str, parent: str,
                  attributes: dict) -> Optional[Span]:
        return None

    def closed_span(self, name: str, stats: RuntimeStats, nanos: float,
                    attributes: dict) -> None:
        pass

    def add_span(self, name: str, parent: str, start: float, end: float,
                 **attributes) -> None:
        """A span whose interval was measured elsewhere (an operator's
        first pull .. last batch)."""


class NoopTracer(Tracer):
    pass


class SimpleTracer(Tracer):
    """In-memory recording tracer (tracing/SimpleTracer.java): the span
    tree of one query (or of one task's slice of it)."""

    recording = True

    def __init__(self, trace_token: str = ""):
        self.trace_token = trace_token
        self.spans: List[Span] = []
        self.dropped = 0
        self._names: Dict[str, int] = {}
        self._lock = threading.Lock()

    def _append(self, base: str, parent: str, start: float, end: float,
                attributes: dict) -> Optional[Span]:
        with self._lock:
            if len(self.spans) >= MAX_SPANS_PER_TRACE:
                self.dropped += 1
                return None
            n = self._names[base] = self._names.get(base, 0) + 1
            s = Span(base if n == 1 else f"{base}#{n}", parent, start, end,
                     attributes)
            self.spans.append(s)
            return s

    @contextmanager
    def span(self, name: str, parent: str = "", **attributes):
        s = self._append(name, parent, time.time(), 0.0, attributes)
        t0 = _perf_ns()
        try:
            yield s.name if s is not None else name
        finally:
            if s is not None:
                s.end = s.start + (_perf_ns() - t0) / NANO

    def open_span(self, name: str, scope: str, parent: str,
                  attributes: dict) -> Optional[Span]:
        """A span of `RuntimeStats.span`, closed by its scope's exit."""
        return self._append(f"{name} {scope}" if scope else name, parent,
                            time.time(), 0.0, attributes)

    def closed_span(self, name: str, stats: RuntimeStats, nanos: float,
                    attributes: dict) -> None:
        """A span recorded after the fact (a launch, a host sync): it
        ended now and lasted `nanos`."""
        end = time.time()
        self._append(f"{name} {stats.scope}" if stats.scope else name,
                     getattr(_tls, "span", "") or stats.root,
                     end - nanos / NANO, end, attributes)

    def add_span(self, name: str, parent: str, start: float, end: float,
                 **attributes) -> None:
        self._append(name, parent, start, end, attributes)

    def span_children(self, parent: str = "") -> List[Span]:
        with self._lock:
            return [s for s in self.spans if s.parent == parent]

    def span_tree(self) -> List[dict]:
        """Nested {name, attributes, children} forest rooted at parent=""."""
        def build(parent: str) -> List[dict]:
            return [{"name": s.name, "attributes": dict(s.attributes),
                     "children": build(s.name)}
                    for s in self.span_children(parent)]
        return build("")


class TracerProvider:
    """Selected once per process (TracerProviderManager analog)."""

    def __init__(self, kind: str = "noop"):
        self.kind = kind
        self._traces: Dict[str, SimpleTracer] = {}
        self._lock = threading.Lock()

    def new_tracer(self, trace_token: str) -> Tracer:
        if self.kind != "simple":
            return NoopTracer()
        t = SimpleTracer(trace_token)
        with self._lock:
            self._traces[trace_token] = t
        return t

    def get_trace(self, trace_token: str) -> Optional[SimpleTracer]:
        with self._lock:
            return self._traces.get(trace_token)

    def pop_trace(self, trace_token: str) -> Optional[SimpleTracer]:
        """Detach a finished trace (export pipelines take ownership so
        long-lived providers do not accumulate span trees forever)."""
        with self._lock:
            return self._traces.pop(trace_token, None)
