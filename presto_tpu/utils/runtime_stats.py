"""RuntimeStats + the one span primitive + tracer SPI.

The analog of the reference's fine-grained engine profiling (§5.1):

  * RuntimeStats (presto-common/.../common/RuntimeStats.java): a
    thread-safe name -> {sum, count, min, max, unit} metric map threaded
    through query execution and mergeable task -> stage -> query
    (`merge`, `merge_dict`).  Phases are recorded with `span(name)`, the
    way SqlQueryExecution.java:556-614 wraps analysis/optimization/
    fragmentation in recordWallAndCpuTime.

  * `RuntimeStats.span(name, **attrs)` opens
    `jax.profiler.TraceAnnotation("presto:" + name, ...)`, which costs
    under a microsecond with no profiler session and otherwise lands on
    the SAME timeline as the device's `XLA Ops`, and closes through ONE
    path (`RuntimeStats.close_span`, shared with `host_get`, `named_jit`
    and every interval measured elsewhere, `RuntimeStats.record`): it
    adds `<name>WallNanos` to the map, keeps the interval as one record
    of the owner's bounded timeline (thread, name, start, wall, the
    thread's own CPU time: recordWallAndCpuTime's pair; a span reads
    the CPU clock at both ends, a launch leaves its CPU time to the
    span around it, a blocking sync has none) and, only when
    the owner carries a recording tracer, hands it on as a `Span` with
    the enclosing span as parent.

  * The timeline is what telemetry/query_wall.py partitions a query's
    wall from.  Always on, at most MAX_SPANS_PER_TRACE records an owner
    (later ones are counted, not kept).  One clock: a record leaves its
    owner (`timeline()`) in unix microseconds, `perf_counter_ns` plus
    the anchor this process took once at import, so the records of a
    coordinator and of its workers on one host lie on one axis (across
    hosts the clocks are NTP's).

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
from array import array
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

NANO = 1_000_000_000
# spans one tracer keeps, and records one owner's timeline keeps (a task
# of ~900 launches records one each); beyond it they are dropped and
# counted (`spansDropped`, `queryWallIntervalsDropped`)
MAX_SPANS_PER_TRACE = 4096
# integers a record takes where a timeline leaves its owner: thread ident,
# index into the timeline's name table, start, wall, the thread's CPU time
RECORD_WIDTH = 5

_perf_ns = time.perf_counter_ns
_cpu_ns = time.thread_time_ns
_ident = threading.get_ident
# the one clock: `perf_counter_ns() + CLOCK_ANCHOR_NS` is unix
# nanoseconds, the anchor taken once a process
CLOCK_ANCHOR_NS = time.time_ns() - _perf_ns()


def unix_ns() -> int:
    """Now, on the clock a timeline leaves its owner in."""
    return _perf_ns() + CLOCK_ANCHOR_NS


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

    __slots__ = ("stats", "name", "attrs", "t0", "c0", "ann", "rec",
                 "prev")

    def __init__(self, stats: "RuntimeStats", name: str, attrs: dict):
        self.stats = stats
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        # the CPU clock is read outside the annotation and the wall
        # clock inside it, last and first: a record and its annotation
        # are of one extent, to the annotation's own cost
        s = self.stats
        self.c0 = _cpu_ns()
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
        t0 = self.t0
        dt = _perf_ns() - t0
        self.ann.__exit__(*exc)
        cpu = _cpu_ns() - self.c0
        if self.rec is not None:
            _tls.span = self.prev
        self.stats.close_span(self.name, t0, dt, cpu,
                              (self.name + "WallNanos",), rec=self.rec)
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
        # the timeline: (thread ident, name, start on perf_counter_ns's
        # clock, wall, CPU) a record; records past the bound; and the
        # timelines merged in (a task's, a batch's), each under its
        # source's label and never summed
        self._rows: List[tuple] = []
        self._dropped = 0
        self._merged: List[Tuple[str, dict]] = []

    def add(self, name: str, value: float, unit: str = "NONE") -> None:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = Metric(unit)
            m.add(value)

    def close_span(self, name: str, t0: int, wall: int, cpu: int = 0,
                   walls: Tuple[str, ...] = (), count: str = "",
                   attrs: Optional[dict] = None, rec=None) -> None:
        """THE close path of every span, launch, sync and interval
        measured elsewhere: `wall` nanoseconds into every key of `walls`
        and +1 into the counter `count`, one record (`name`, begun at
        `t0` on perf_counter_ns's clock, `cpu` nanoseconds of the calling
        thread's own CPU time; -1 where nobody measured it) onto the
        timeline, all under one lock acquisition; then the span to a
        recording tracer: `rec` (opened by `open_span`) gets its end,
        anything else is handed over as a span that ended now."""
        with self._lock:
            metrics = self._metrics
            for key in walls:
                m = metrics.get(key)
                if m is None:
                    m = metrics[key] = Metric("NANO")
                m.add(wall)
            if count:
                m = metrics.get(count)
                if m is None:
                    m = metrics[count] = Metric("NONE")
                m.add(1)
            rows = self._rows
            if len(rows) < MAX_SPANS_PER_TRACE:
                rows.append((_ident(), name, t0, wall, cpu))
            else:
                self._dropped += 1
        tracer = self.tracer
        if tracer is not None:
            if rec is not None:
                rec.end = rec.start + wall / NANO
            else:
                tracer.closed_span(name, self, wall, attrs or {})

    def record(self, name: str, t0: int, wall: float, cpu: int = 0,
               count: str = "", **attrs) -> None:
        """An interval measured elsewhere (a queue's wait taken from two
        clock readings, a duration JAX reports after the fact): it began
        at `t0` on perf_counter_ns's clock and lasted `wall` nanoseconds.
        `<name>WallNanos` and a record, as a span's exit gives."""
        self.close_span(name, int(t0), int(wall), cpu,
                        (name + "WallNanos",), count, attrs)

    def span(self, name: str, **attrs) -> _SpanScope:
        """recordWallAndCpuTime analog: `<name>WallNanos`, a
        `presto:<name>` profiler annotation of the same extent, a record
        of the interval with the thread's own CPU time over it and, with
        a recording tracer, a Span."""
        return _SpanScope(self, name, attrs)

    # -- the timeline -----------------------------------------------------
    def timeline(self) -> dict:
        """This owner's own records as they leave it: `names` (the table
        the rows index), `rows` (flat, RECORD_WIDTH integers a record:
        thread ident, name index, start in unix microseconds, wall and
        CPU microseconds; CPU -1 = not measured) and `dropped`."""
        with self._lock:
            records = list(self._rows)
            dropped = self._dropped
        names: Dict[str, int] = {}
        rows: List[int] = []
        for ident, name, t0, wall, cpu in records:
            rows += (ident, names.setdefault(name, len(names)),
                     (t0 + CLOCK_ANCHOR_NS) // 1000, wall // 1000,
                     cpu // 1000 if cpu > 0 else cpu)
        return {"names": list(names), "rows": rows, "dropped": dropped}

    def timelines(self, source: str = "") -> List[Tuple[str, dict]]:
        """(source label, timeline) of this owner's own records and of
        every timeline merged into it.  The label keeps one source's
        thread idents apart from another's."""
        with self._lock:
            merged = list(self._merged)
        return [(source, self.timeline())] + [
            (f"{source}/{label}" if source else label, t)
            for label, t in merged]

    def add_timeline(self, source: str, timeline: Optional[dict]) -> None:
        """Keep a timeline recorded elsewhere (a task's, from its
        TaskInfo) beside this owner's own: concatenated, never summed."""
        if timeline and (timeline.get("rows") or timeline.get("dropped")):
            # (kept until the query's wall is reduced, which may be
            # never: 8 bytes an integer, not a Python object each)
            timeline = dict(timeline, rows=array("q", timeline["rows"]))
            with self._lock:
                self._merged.append((source, timeline))

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

    def release_timelines(self) -> None:
        """Forget every record (the query's partition has been reduced
        from them and a finished query is kept for its QueryInfo)."""
        with self._lock:
            self._rows = []
            self._merged = []

    def merge(self, other: "RuntimeStats", source: str = "") -> None:
        """Sum `other`'s map into this one and keep its timelines beside
        this owner's own, under `source`."""
        with other._lock:
            items = list(other._metrics.items())
        lines = other.timelines(
            source or str(other.ids.get("task_id", "merged")))
        with self._lock:
            for name, m in items:
                mine = self._metrics.get(name)
                if mine is None:
                    mine = self._metrics[name] = Metric(m.unit)
                mine.merge(m)
        for label, t in lines:
            self.add_timeline(label, t)

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
    if s is not None:
        ann = _annotate("presto:hostSync", s.ids, {"why": why})
        t0 = _perf_ns()
    try:
        return jax.device_get(x)  # lint: allow-host-sync
    finally:
        if s is not None:
            dt = _perf_ns() - t0
            ann.__exit__(None, None, None)
            # a blocked thread burns no CPU time: 0, unread (the thread
            # CPU clock is a system call, 6 us on the chip's host)
            s.close_span("hostSync", t0, dt, 0,
                         ("hostSyncWaitWallNanos", "hostSync." + why),
                         "hostSyncs", {"why": why})


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
            return self._jit(*args, **kwargs)
        finally:
            dt = _perf_ns() - t0
            ann.__exit__(None, None, None)
            # a launch's CPU time is its enclosing span's (-1: unread;
            # half a join query's records are launches, and the thread
            # CPU clock is a 6 us system call on the chip's host)
            s.close_span("pipelineDispatch", t0, dt, -1,
                         ("pipelineDispatchWallNanos",),
                         "pipelineLaunches", {"program": self.name})

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
