"""Spans and counters inside the worker (tier-1, CPU).

One span primitive (`RuntimeStats.span`) on the profiler's clock, the
thread-local owner behind `host_get` / `named_jit` / the JAX listener,
the task -> query roll-up on both served paths, structural program
names, and the lint that keeps every device->host transfer inside
`host_get`.
"""
import contextlib
import io
import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from presto_tpu.analysis.lint import SYNC_EXPLICIT, lint_paths, lint_source
from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner
from presto_tpu.telemetry import CollectorSink, gaps, trace_id_for
from presto_tpu.utils.runtime_stats import (RuntimeStats, SimpleTracer,
                                            current_stats, host_get,
                                            named_jit)

Q6 = ("select sum(l_extendedprice * l_discount) as revenue from lineitem "
      "where l_shipdate >= date '1994-01-01' "
      "and l_shipdate < date '1995-01-01' "
      "and l_discount between 0.05 and 0.07 and l_quantity < 24")
Q1 = ("select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
      "avg(l_discount) as avg_disc, count(*) as count_order from lineitem "
      "where l_shipdate <= date '1998-09-02' "
      "group by l_returnflag, l_linestatus "
      "order by l_returnflag, l_linestatus")
Q6_OTHER_LITERALS = Q6.replace("1994", "1995").replace("1995-01-01'",
                                                       "1996-01-01'", 1) \
    .replace("0.05 and 0.07", "0.02 and 0.04").replace("< 24", "< 25")
# a scale factor no other test file touches: the first query builds its
# columns in this process, so `storageBuild` is on the record
SCHEMA = "sf0.013"


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read())


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_span_adds_wall():
    s = RuntimeStats(query_id="q")
    with s.span("phase", why="test"):
        time.sleep(0.002)
    with s.span("phase"):
        pass
    m = s.get("phaseWallNanos")
    assert m.unit == "NANO" and m.count == 2
    assert m.max >= 2e6 > m.min >= 0 and m.sum >= m.max


def test_recorded_spans_nest_under_the_enclosing_span():
    tracer = SimpleTracer("t")
    s = RuntimeStats(tracer=tracer, scope="task7", root="task task7")
    with s.activate():
        with s.span("outer"):
            with s.span("inner"):
                host_get(jnp.ones(2), "probe")
            with s.span("inner"):
                pass
    by = {sp.name: sp for sp in tracer.spans}
    assert by["outer task7"].parent == "task task7"
    assert by["inner task7"].parent == "outer task7"
    assert by["inner task7#2"].parent == "outer task7"
    assert by["hostSync task7"].parent == "inner task7"
    outer, inner = by["outer task7"], by["inner task7"]
    assert outer.start <= inner.start <= inner.end <= outer.end
    assert inner.end <= by["inner task7#2"].start + 1e-6
    # nothing records without a recording tracer: only the walls
    plain = RuntimeStats()
    with plain.span("outer"):
        pass
    assert plain.tracer is None and plain.get("outerWallNanos").count == 1


def test_activate_is_per_thread_and_restores():
    import threading
    a, b = RuntimeStats(), RuntimeStats()
    seen = []
    assert current_stats() is None
    with a.activate():
        t = threading.Thread(target=lambda: seen.append(current_stats()))
        t.start()
        t.join()
        with b.activate():
            assert current_stats() is b
        assert current_stats() is a
    assert current_stats() is None and seen == [None]


def test_merge_dict_is_the_roll_up():
    task_a, task_b, query = RuntimeStats(), RuntimeStats(), RuntimeStats()
    task_a.add("taskQueuedWallNanos", 5, "NANO")
    task_b.add("taskQueuedWallNanos", 9, "NANO")
    task_b.add("hostSyncs", 1)
    query.add("hostSyncs", 1)
    for t in (task_a, task_b):
        query.merge_dict(t.to_dict())
    query.merge_dict(None)
    q = query.get("taskQueuedWallNanos")
    assert (q.sum, q.count, q.min, q.max, q.unit) == (14, 2, 5, 9, "NANO")
    assert query.get("hostSyncs").sum == 2


def test_roomy_frame_spares_deep_calls_the_chunk_edges():
    """utils/stack.py: a frame with a data-stack chunk of its own, so no
    call below it maps and unmaps a chunk (CPython >= 3.11); arguments
    and results pass through."""
    from presto_tpu.utils.stack import FRAME_SLOTS, roomy
    assert roomy(lambda a, b=1: a + b, 2, b=3) == 5
    assert roomy.__code__.co_stacksize == FRAME_SLOTS >= 1 << 15

    def leaf():
        return 0

    def at_depth(k, n):
        if k:
            return at_depth(k - 1, n)
        t0 = time.perf_counter_ns()
        for _ in range(n):
            leaf()
        return (time.perf_counter_ns() - t0) / n
    plain = [at_depth(d, 2000) for d in range(0, 200)]
    room = [roomy(at_depth, d, 2000) for d in range(0, 200)]
    # somewhere in 200 frames the plain stack crosses a 16 KiB edge and a
    # leaf call there costs a map and an unmap; the roomy one never does
    # (medians, so that one descheduled sample decides nothing)
    assert sorted(room)[100] < 4 * sorted(plain)[100]
    assert max(room) < max(max(plain), 20 * sorted(plain)[100])


# ---------------------------------------------------------------------------
# host_get and the lint
# ---------------------------------------------------------------------------

def test_host_get_counts_one_sync_per_call():
    s = RuntimeStats()
    x = jnp.arange(4)
    assert host_get(x, "unowned").tolist() == [0, 1, 2, 3]   # no owner
    with s.activate():
        host_get(x, "site_a")
        host_get((x, x), "site_a")
        assert bool(host_get(jnp.any(x > 2), "site_b"))
    d = s.to_dict()
    assert d["hostSyncs"]["sum"] == 3
    assert d["hostSyncWaitWallNanos"]["count"] == 3
    assert d["hostSync.site_a"]["count"] == 2
    assert d["hostSync.site_b"]["count"] == 1
    assert d["hostSyncWaitWallNanos"]["sum"] == pytest.approx(
        d["hostSync.site_a"]["sum"] + d["hostSync.site_b"]["sum"])


@pytest.mark.parametrize("path,marked,findings", [
    ("presto_tpu/exec/pipeline.py", True, 1),     # the pragma buys nothing
    ("presto_tpu/exec/pipeline.py", False, 1),
    ("presto_tpu/utils/runtime_stats.py", True, 0),   # host_get's home
    ("presto_tpu/utils/runtime_stats.py", False, 1),
    ("<string>", True, 0),                        # fixtures keep the pragma
])
def test_lint_allows_device_get_in_host_get_only(path, marked, findings):
    src = ("import jax\n"
           "def f(x):\n"
           "    return jax.device_get(x)"
           + ("  # lint: allow-host-sync\n" if marked else "\n"))
    found = lint_source(src, path)
    assert [f.code for f in found] == [SYNC_EXPLICIT] * findings


def test_shipped_tree_has_the_pragma_in_host_get_only():
    import pathlib

    import presto_tpu
    root = pathlib.Path(presto_tpu.__file__).parent
    holders = sorted(
        str(p.relative_to(root)) for p in root.rglob("*.py")
        if "lint: allow-host-sync" in p.read_text()
        and p.name != "lint.py")
    assert holders == ["utils/runtime_stats.py"]
    assert lint_paths([str(root)]) == []
    # host_get results are host values: branching on them is no finding
    assert lint_source(
        "import jax.numpy as jnp\n"
        "from presto_tpu.utils.runtime_stats import host_get\n"
        "def f(x):\n"
        "    if host_get(jnp.any(x), 'why'):\n"
        "        return 1\n", "presto_tpu/exec/x.py") == []


# ---------------------------------------------------------------------------
# named programs
# ---------------------------------------------------------------------------

def test_named_jit_names_the_program_and_counts_launches():
    f = named_jit("scan_agg_demo", lambda a, n: a * n, static_argnums=1)
    x = jnp.ones(3)
    assert "jit_scan_agg_demo" in f.lower(x, 2).as_text()
    s = RuntimeStats()
    assert f(x, 2).tolist() == [2, 2, 2]          # unowned: not counted
    with s.activate():
        f(x, 2)
        f(x, 3)
    d = s.to_dict()
    assert d["pipelineLaunches"]["sum"] == 2
    assert d["pipelineDispatchWallNanos"]["count"] == 2
    f.clear_cache()                               # forwards to the jit


def _programs_of(sql):
    """Module names of every named program one in-process run lowers."""
    real_jit, names = jax.jit, []

    def recording_jit(fun, *a, **k):
        names.append(fun.__name__)
        return real_jit(fun, *a, **k)
    from presto_tpu.serving import FRAGMENT_JIT_CACHE, PlanCache
    runner = LocalQueryRunner("sf0.01", plan_cache=PlanCache(),
                              config=ExecutionConfig(batch_rows=1 << 13))
    # a program the process has built once is not built again, whoever
    # asks (serving/fragments.py): start from none to see every name
    FRAGMENT_JIT_CACHE.invalidate_all()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "jit", recording_jit)
        runner.execute(sql)
    return sorted(set(names))


def test_program_names_are_structural():
    """Two plans that differ only in literals (and so in node ids of a
    fresh planner) produce the same program names; none carries a digit
    run that could be an id, a literal or a scale factor."""
    _programs_of(Q6)        # process-wide programs (column generators,
    # module-level jits) are built once, by whichever query comes first
    a, b = _programs_of(Q6), _programs_of(Q6_OTHER_LITERALS)
    assert a == b and "scan_agg_direct" in a
    assert all(not any(ch.isdigit() for ch in n) for n in a), a


# ---------------------------------------------------------------------------
# the in-process path: owner, JAX events, the profiler's clock
# ---------------------------------------------------------------------------

def test_repeated_in_process_query_traces_nothing():
    from presto_tpu.telemetry import jax_events
    jax_events.install()
    from presto_tpu.serving import FRAGMENT_JIT_CACHE, PlanCache
    r = LocalQueryRunner("sf0.01", plan_cache=PlanCache(),
                         config=ExecutionConfig(batch_rows=1 << 13))
    FRAGMENT_JIT_CACHE.invalidate_all()    # `first` builds its programs
    first = r.execute(Q6).runtime_stats
    before = jax_events.PROGRAMS.snapshot()
    second = r.execute(Q6).runtime_stats
    after = jax_events.PROGRAMS.snapshot()
    assert first["jaxTraces"]["sum"] >= 1
    assert first["jaxBackendCompiles"]["sum"] >= 1
    # the second run lowers and loads nothing and traces nothing: the
    # fused chain's shape probe, the one trace a warm query used to pay
    # on every execution, is an entry of the program cache like the
    # programs it decides the key of
    assert "jaxLowerWallNanos" not in second
    assert "jaxBackendCompiles" not in second
    retraced = {n for n in after
                if after[n]["traces"] > before.get(n, {"traces": 0})["traces"]}
    assert not retraced, retraced
    assert after["chain_shape_probe"]["traces"] >= 1     # the first run's
    assert "jaxTraces" not in second and "jaxTraceWallNanos" not in second
    assert first["shapeProbeMisses"]["sum"] >= 1
    assert second["shapeProbeHits"]["sum"] >= 1
    assert "shapeProbeMisses" not in second
    for key in ("pipelineLaunches", "hostSyncs", "pipelineBuildWallNanos",
                "pipelineDispatchWallNanos", "hostSyncWaitWallNanos"):
        assert second[key]["count"] >= 1, key
    # the process table names what was traced and loaded
    assert after["scan_agg_direct"]["traces"] >= 1
    assert after["scan_agg_direct"]["loads"] >= 1


def test_profiler_capture_holds_nested_presto_spans(tmp_path):
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        batch_rows=1 << 13))
    r.execute(Q6)                                  # warm: capture a run
    jax.profiler.start_trace(str(tmp_path))
    try:
        r.execute(Q6)
    finally:
        jax.profiler.stop_trace()
    _ops, _programs, spans = gaps.load(str(tmp_path))
    names = {s[3] for s in spans}
    assert {"queryParse", "queryExecute", "pipelineBuild",
            "pipelineDispatch", "hostSync"} <= names, names
    pairs = set(gaps.nesting(spans))
    assert ("pipelineBuild", "queryExecute") in pairs
    assert ("pipelineDispatch", "queryExecute") in pairs
    assert ("hostSync", "queryExecute") in pairs
    reduced = gaps.reduce(_ops, _programs, spans)
    assert reduced["spans"] == len(spans) and reduced["window_s"] > 0


def test_records_and_their_annotations_lie_on_one_axis(tmp_path):
    """The query's own records (unix nanoseconds) against a capture of it
    (nanoseconds from the session's start): ONE offset fits every pair,
    so the device's `XLA Ops` and the partition of the wall
    (telemetry/query_wall.py) can be laid side by side."""
    from presto_tpu.telemetry.query_wall import records_of
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        batch_rows=1 << 13))
    r.execute(Q6)                                  # warm: capture a run
    jax.profiler.start_trace(str(tmp_path))
    try:
        result = r.execute(Q6)
    finally:
        jax.profiler.stop_trace()
    _ops, _programs, spans = gaps.load(str(tmp_path))
    records, dropped = records_of(result.timeline)
    assert not dropped
    paired = gaps.pair_records(spans, records)
    by_record = {id(rec): span for span, rec, _off in paired["pairs"]}
    wanted = [rec for rec in records
              if rec[1] in ("queryExecute", "pipelineDispatch", "hostSync")]
    assert {rec[1] for rec in wanted} == {
        "queryExecute", "pipelineDispatch", "hostSync"}
    for rec in wanted:
        span = by_record.get(id(rec))
        assert span is not None, (rec, paired["unpaired"])
        assert span[3] == rec[1]
        assert abs((span[2] - span[1]) - (rec[3] - rec[2])) <= 200_000
    # the shared clock: the offsets of all pairs lie within a millisecond
    assert len(paired["pairs"]) >= len(wanted) + 2
    assert paired["spread_ns"] <= 1_000_000, paired["spread_ns"]
    # the capture counts from its session's start, the records from 1970
    assert paired["offset_ns"] > 10 ** 18
    assert gaps.pair_records(spans, [])["offset_ns"] is None


# ---------------------------------------------------------------------------
# the served paths: coordinator -> workers, and the single node
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_cluster():
    """Coordinator with a CollectorSink + 2 workers, and one finished Q6:
    (coordinator, sink, token, QueryInfo, what the tasks printed)."""
    from presto_tpu.client import StatementClient
    from presto_tpu.worker.server import WorkerServer
    sink = CollectorSink()
    coordinator = WorkerServer(coordinator=True, environment="test",
                               telemetry_sink=sink,
                               telemetry_flush_interval_s=0.02)
    workers = [WorkerServer(discovery_uri=coordinator.uri,
                            announce_interval_s=0.1,
                            environment="test") for _ in range(2)]
    deadline = time.time() + 10
    while len(coordinator.worker_uris()) < 2 and time.time() < deadline:
        time.sleep(0.05)
    assert len(coordinator.worker_uris()) == 2, "workers failed to announce"
    token = "spans-trace-0001"
    client = StatementClient(coordinator.uri, schema=SCHEMA,
                             trace_token=token)
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        res = client.execute(Q6)
    assert len(res.rows) == 1
    info = _get_json(f"{coordinator.uri}/v1/query/{res.query_id}")
    assert coordinator.telemetry.flush(timeout_s=10.0)
    yield coordinator, sink, token, info, printed.getvalue()
    for w in workers:
        w.close()
    coordinator.close()


# table B of the issue, the keys of the coordinator -> worker path
CLUSTER_KEYS = [
    "statementQueuedWallNanos", "statementDrainWallNanos",
    "queryParseWallNanos", "queryPlanWallNanos", "queryOptimizeWallNanos",
    "queryFragmentWallNanos",
    "schedCreateTasksWallNanos", "schedAwaitStagesWallNanos",
    "taskQueuedWallNanos",
    "pipelineBuildWallNanos", "pipelineDrainWallNanos",
    "jaxTraceWallNanos", "jaxTraces", "jaxLowerWallNanos",
    "jaxBackendCompileWallNanos", "jaxBackendCompiles",
    "pipelineLaunches", "pipelineDispatchWallNanos",
    "hostSyncs", "hostSyncWaitWallNanos", "hostSync.page_fetch",
    "taskSerializeWallNanos",
    "exchangeClientWaitWallNanos", "exchangeClientPullWallNanos",
    "exchangeClientDecodeWallNanos",
    "storageBuildWallNanos", "storageBuilds",
]


@pytest.mark.parametrize("key", CLUSTER_KEYS)
def test_distributed_query_info_holds_every_key(traced_cluster, key):
    info = traced_cluster[3]
    assert info["state"] == "FINISHED"
    assert info["runtimeStats"][key]["count"] >= 1, sorted(
        info["runtimeStats"])


@pytest.mark.parametrize("key", [
    "taskQueuedWallNanos", "pipelineBuildWallNanos", "pipelineLaunches",
    "hostSyncs", "taskSerializeWallNanos", "jaxTraces"])
def test_task_keys_equal_the_sum_over_taskinfo(traced_cluster, key):
    info = traced_cluster[3]
    tasks = [t for st in info["stages"] for t in st["tasks"]]
    assert len(tasks) == 3                   # 2 source tasks + the gather
    per_task = [t["stats"]["runtimeStats"].get(key) for t in tasks]
    per_task = [m for m in per_task if m]
    assert per_task
    rolled = info["runtimeStats"][key]
    assert rolled["sum"] == pytest.approx(sum(m["sum"] for m in per_task))
    assert rolled["count"] == sum(m["count"] for m in per_task)
    assert rolled["max"] == max(m["max"] for m in per_task)


@pytest.mark.parametrize("sql", [Q6, Q1], ids=["q6", "q1"])
def test_warm_distributed_statement_traces_nothing(traced_cluster, sql):
    """Coordinator -> worker, the same text a second time: every task is
    a new PlanCompiler, every program and every chain's shape probe comes
    from the process-wide cache, so JAX traces nothing."""
    from presto_tpu.client import StatementClient
    from presto_tpu.exec.runner import _assert_rows_equal
    coordinator = traced_cluster[0]
    client = StatementClient(coordinator.uri, schema="sf0.01")
    client.execute(sql)                     # builds columns and programs
    res = client.execute(sql)
    info = _get_json(f"{coordinator.uri}/v1/query/{res.query_id}")
    rs = info["runtimeStats"]
    assert info["state"] == "FINISHED"
    sources = [t for st in info["stages"] for t in st["tasks"]
               if "shapeProbeHits" in t["stats"]["runtimeStats"]]
    assert len(sources) == 2                        # the source stage's
    assert rs["shapeProbeHits"]["sum"] == sum(
        t["stats"]["runtimeStats"]["shapeProbeHits"]["sum"]
        for t in sources) >= 2
    assert "shapeProbeMisses" not in rs
    assert "jaxTraces" not in rs and "jaxTraceWallNanos" not in rs
    assert "programCacheMisses" not in rs
    oracle = LocalQueryRunner("sf0.01")
    # both are ORDER BY'd or a single row
    _assert_rows_equal(res, oracle.execute_reference(sql), ordered=True)


def test_coordinator_spans_cover_the_query(traced_cluster):
    """What the coordinator recorded accounts for the server's elapsed
    time of the query (the acceptance bar is 90 % on the chip; a loaded
    CPU test asks for most of it)."""
    info = traced_cluster[3]
    rs = info["runtimeStats"]
    covered = sum(rs[k]["sum"] for k in (
        "statementQueuedWallNanos", "statementRunnerLookupWallNanos",
        "queryParseWallNanos", "queryPlanWallNanos",
        "queryOptimizeWallNanos", "queryFragmentWallNanos",
        "schedCreateTasksWallNanos", "schedAwaitStagesWallNanos",
        "schedRollUpTasksWallNanos", "schedCloseTasksWallNanos",
        "statementQueryInfoSnapshotWallNanos") if k in rs) / 1e6
    elapsed = info["queryStats"]["elapsedTimeMillis"]
    assert covered >= 0.7 * elapsed, (covered, elapsed)
    assert covered <= elapsed + 50


def test_exported_spans_are_nested_with_real_intervals(traced_cluster):
    _c, sink, token, _info, _out = traced_cluster
    spans = [s for s in sink.spans() if s["traceId"] == trace_id_for(token)]
    by_id = {s["spanId"]: s for s in spans}

    def interval(s):
        return int(s["startTimeUnixNano"]), int(s["endTimeUnixNano"])

    def named(prefix):
        return [s for s in spans if s["name"].startswith(prefix)]
    query = named("query")[0]
    assert query["name"] == "query" and query["parentSpanId"] == ""
    tasks = named("task ")
    assert len(tasks) == 3
    slack = 50_000_000          # two clocks (time.time, perf_counter): 50 ms
    for t in tasks:
        kids = [s for s in spans if s["parentSpanId"] == t["spanId"]]
        assert {k["name"].split(" ")[0] for k in kids} >= {
            "pipelineBuild", "pipelineDrain", "taskSerialize"}, kids
        t0, t1 = interval(t)
        for k in kids:
            k0, k1 = interval(k)
            assert t0 - slack <= k0 <= k1 <= t1 + slack, (k["name"], t)
        # the old export gave every span of a task the task's interval
        assert len({interval(k) for k in kids}) == len(kids)
        build = next(k for k in kids if k["name"].startswith(
            "pipelineBuild"))
        drain = next(k for k in kids if k["name"].startswith(
            "pipelineDrain"))
        assert interval(build)[1] <= interval(drain)[0] + 1_000_000
    # a launch hangs off the drain that pulled it -- or off the span of the
    # operator inside that drain that launched it (PR 34: the grouped
    # update of a stream, the TopN, a fused join's build) -- a column
    # generator's off the storage build that ran it, on the worker's
    # side; the coordinator's phases hang off the query
    launches = named("pipelineDispatch ")
    parents = {by_id[s["parentSpanId"]]["name"].split(" ")[0]
               for s in launches}
    assert launches and "pipelineDrain" in parents
    assert parents <= {"pipelineDrain", "storageBuild", "pipelineBuild",
                       "aggUpdate", "aggFinalize", "topN", "joinBuild"}
    for phase in ("queryParse", "queryPlan", "schedCreateTasks",
                  "schedAwaitStages"):
        s = next(s for s in spans if s["name"] == phase)
        assert by_id[s["parentSpanId"]]["name"] == "query", phase
        q0, q1 = interval(query)
        assert q0 - slack <= interval(s)[0] <= interval(s)[1] <= q1 + slack
    # operator spans only where operator stats were collected, over the
    # interval in which the node produced
    for o in named("operator "):
        assert by_id[o["parentSpanId"]]["name"].startswith("task ")
        assert interval(o)[0] <= interval(o)[1]


def test_a_task_prints_nothing(traced_cluster):
    assert traced_cluster[4] == ""


def test_status_and_query_info_serve_the_program_table(traced_cluster):
    coordinator = traced_cluster[0]
    programs = _get_json(f"{coordinator.uri}/v1/status")["programs"]
    counted = programs["scan_agg_direct_counted"]
    assert counted["traces"] >= 1 and counted["loads"] >= 1
    assert any(n.startswith("gen_lineitem_") for n in programs)
    assert traced_cluster[3]["processMetrics"]["programs"]


SINGLE_KEYS = [
    "statementQueuedWallNanos", "statementDrainWallNanos",
    "queryParseWallNanos", "pipelineBuildWallNanos", "pipelineLaunches",
    "pipelineDispatchWallNanos", "hostSyncs", "hostSyncWaitWallNanos",
    "servingBatchWaitWallNanos", "servingBatchOccupancy",
    "compilerCheckoutWaitWallNanos",
]


@pytest.fixture(scope="module")
def single_node_info():
    """QueryInfo of a prepared Q6 on a single-node server, second run."""
    from presto_tpu.client import StatementClient
    from presto_tpu.worker.server import WorkerServer
    server = WorkerServer(coordinator=True, environment="test")
    try:
        c = StatementClient(server.uri, schema="sf0.01")
        c.execute("prepare q6 from " + Q6.replace(
            "date '1994-01-01'", "?").replace("< 24", "< ?"))
        for _ in range(2):
            r = c.execute("execute q6 using date '1994-01-01', 24")
        yield _get_json(f"{server.uri}/v1/query/{r.query_id}")
    finally:
        server.close()


@pytest.mark.parametrize("key", SINGLE_KEYS)
def test_single_node_query_info_holds_every_key(single_node_info, key):
    assert single_node_info["runtimeStats"][key]["count"] >= 1, sorted(
        single_node_info["runtimeStats"])


# ---------------------------------------------------------------------------
# the partition of the wall in QueryInfo, on both served paths
# (telemetry/query_wall.py; the reduction itself: tests/test_query_wall.py)
# ---------------------------------------------------------------------------

from presto_tpu.telemetry.query_wall import STATES  # noqa: E402
from presto_tpu.utils.runtime_stats import RECORD_WIDTH  # noqa: E402

WALL = ["queryWall." + s for s in STATES]
HOST_STATES = ("pipeline", "exchange", "sched", "plan", "statement")
PARTITION_KEYS = WALL + ["queryWallCpu." + s for s in STATES[:-1]] + [
    "queryWallIntervals", "queryWallIntervalsDropped"]


def assert_sums_to_elapsed(info):
    rs = info["runtimeStats"]
    total_ms = sum(rs[k]["sum"] for k in WALL) / 1e6
    elapsed = info["queryStats"]["elapsedTimeMillis"]
    # elapsedTimeMillis is cut to whole milliseconds
    assert elapsed - 1 <= total_ms <= elapsed + 2, (total_ms, elapsed)


@pytest.mark.parametrize("key", PARTITION_KEYS)
def test_distributed_query_info_carries_the_partition(traced_cluster, key):
    info = traced_cluster[3]
    assert info["runtimeStats"][key]["count"] == 1, sorted(
        info["runtimeStats"])
    assert info["runtimeStats"][key]["sum"] >= 0


def test_distributed_partition_sums_to_elapsed(traced_cluster):
    info = traced_cluster[3]
    assert_sums_to_elapsed(info)
    rs = info["runtimeStats"]
    assert rs["queryWallIntervalsDropped"]["sum"] == 0
    assert rs["queryWallIntervals"]["sum"] > 30
    waits = sum(v["sum"] for k, v in rs.items()
                if k.startswith("queryWall.wait."))
    assert waits == rs["queryWall.wait"]["sum"]
    for state in ("pipeline", "sched", "plan"):
        assert rs["queryWall." + state]["sum"] > 0, state


def test_tasks_carry_their_records_and_no_partition(traced_cluster):
    info = traced_cluster[3]
    tasks = [t for st in info["stages"] for t in st["tasks"]]
    assert len(tasks) == 3
    n_records = 0
    for t in tasks:
        stats = t["stats"]
        assert not [k for k in stats["runtimeStats"]
                    if k.startswith("queryWall")]
        line = stats["runtimeTimeline"]
        assert len(line["rows"]) % RECORD_WIDTH == 0 and not line["dropped"]
        assert {"taskQueued", "taskCreateDecode", "taskCreateStart",
                "pipelineBuild", "pipelineDrain"} <= set(line["names"])
        n_records += len(line["rows"]) // RECORD_WIDTH
        # the handler's two spans, in the new task's own stats
        for key in ("taskCreateDecodeWallNanos", "taskCreateStartWallNanos"):
            assert stats["runtimeStats"][key]["count"] == 1, key
    rs = info["runtimeStats"]
    assert rs["queryWallIntervals"]["sum"] > n_records
    assert rs["taskCreateStartWallNanos"]["count"] == 3
    # the fragment's JSON, inside the POST's span
    assert rs["schedTaskEncodeWallNanos"]["count"] == 3
    assert rs["schedTaskEncodeWallNanos"]["sum"] \
        < rs["schedCreateTasksWallNanos"]["sum"]


def test_warm_distributed_q6_is_attributed(traced_cluster):
    from presto_tpu.client import StatementClient
    coordinator = traced_cluster[0]
    client = StatementClient(coordinator.uri, schema="sf0.01")
    client.execute(Q6)                      # builds columns and programs
    res = client.execute(Q6)
    info = _get_json(f"{coordinator.uri}/v1/query/{res.query_id}")
    assert_sums_to_elapsed(info)
    rs = info["runtimeStats"]
    whole = sum(rs[k]["sum"] for k in WALL)
    assert rs["queryWall.unattributed"]["sum"] < max(0.2 * whole, 10e6), {
        k: rs[k]["sum"] / 1e6 for k in WALL}
    # host work that burnt CPU: the share is a share
    host = sum(rs["queryWall." + s]["sum"] for s in HOST_STATES)
    cpu = sum(rs["queryWallCpu." + s]["sum"] for s in HOST_STATES)
    assert 0 < cpu <= host * 1.05


@pytest.mark.parametrize("key", PARTITION_KEYS)
def test_single_node_query_info_carries_the_partition(single_node_info, key):
    assert single_node_info["runtimeStats"][key]["count"] == 1, sorted(
        single_node_info["runtimeStats"])


def test_single_node_partition_sums_to_elapsed(single_node_info):
    assert_sums_to_elapsed(single_node_info)
    rs = single_node_info["runtimeStats"]
    whole = sum(rs[k]["sum"] for k in WALL)
    # (a 15 ms query: on a loaded machine one late wake-up of a thread,
    # which no span covers, is milliseconds)
    assert rs["queryWall.unattributed"]["sum"] < max(0.2 * whole, 10e6)
    assert rs["queryWall.sched"]["sum"] == rs["queryWall.exchange"]["sum"] == 0
    # the batcher's window is dead time, and named
    assert "queryWall.wait.servingBatchWait" in rs or \
        rs["queryWall.wait"]["sum"] == 0
