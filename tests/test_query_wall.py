"""A query's wall, partitioned (tier-1, CPU): the record the span
primitive keeps, the reduction of telemetry/query_wall.py on synthetic
records, the partition of a library call's wall, the state table against
every span name in the tree, and the nine `wall.*` readers of the
benchmark.  The partition in QueryInfo on both served paths is tested on
tests/test_spans.py's servers, there."""
import importlib.util
import os
import pathlib
import re
import sys
import threading
import time

import pytest

import presto_tpu
from presto_tpu.telemetry import query_wall
from presto_tpu.telemetry.query_wall import (STATE_OF, STATES, partition,
                                             records_of)
from presto_tpu.utils.runtime_stats import (CLOCK_ANCHOR_NS,
                                            MAX_SPANS_PER_TRACE,
                                            RECORD_WIDTH, RuntimeStats,
                                            _annotate)
from test_spans import Q6

ROOT = pathlib.Path(presto_tpu.__file__).parent
MS = 1_000_000
WALL = ["queryWall." + s for s in STATES]


def rec(thread, name, start_ms, end_ms, cpu_ms=0.0, task=False):
    return (thread, name, int(start_ms * MS), int(end_ms * MS),
            int(cpu_ms * MS) if cpu_ms >= 0 else -1, task)


def states_of(out):
    return {k[len("queryWall."):]: v / MS for k, v in out.items()
            if k in WALL and v}


# ---------------------------------------------------------------------------
# the reduction, on synthetic records (milliseconds; the extent is 0..100)
# ---------------------------------------------------------------------------

CASES = {
    # a thread inside exchangeClientWait inside joinProbe is waiting
    "innermost_on_a_thread": (
        [rec("a", "joinProbe", 0, 100), rec("a", "exchangeClientWait", 20, 60)],
        {"pipeline": 60, "wait": 40}),
    # across threads the first state of STATES wins the instant
    "order_across_threads": (
        [rec("a", "hostSync", 0, 10), rec("b", "pipelineDrain", 0, 30),
         rec("c", "taskSerialize", 0, 50), rec("d", "schedCreateTasks", 0, 60),
         rec("e", "queryPlan", 0, 70), rec("f", "statementDrain", 0, 80),
         rec("g", "schedAwaitStages", 0, 100)],
        {"device": 10, "pipeline": 20, "exchange": 20, "sched": 10,
         "plan": 10, "statement": 10, "wait": 20}),
    # wait wins only while no thread of the query does anything else
    "wait_wins_only_alone": (
        [rec("a", "statementPollWait", 0, 100),
         rec("b", "schedAwaitStages", 0, 100),
         rec("c", "pipelineDrain", 30, 50), rec("c", "taskSerialize", 50, 60)],
        {"wait": 70, "pipeline": 20, "exchange": 10}),
    # no record open on any thread: unattributed (a stretch under the
    # floor stays with the state before it)
    "no_record_is_unattributed": (
        [rec("a", "queryParse", 10, 20), rec("a", "queryPlan", 20.05, 30),
         rec("b", "hostSync", 60, 70)],
        {"plan": 20, "device": 10, "unattributed": 70}),
    # records are clipped to the query's own extent
    "clipped_to_the_extent": (
        [rec("a", "statementQueued", -50, 5), rec("a", "statementDrain", 90, 300)],
        {"statement": 15, "unattributed": 85}),
    # a name the table lacks: pipeline on a task's thread, statement on
    # the query's
    "unknown_name_by_thread": (
        [rec("a", "brandNewSpan", 0, 40, task=True),
         rec("b", "brandNewSpan", 0, 100)],
        {"pipeline": 40, "statement": 60}),
    # records that overlap without nesting (one measured after the fact):
    # the one begun last is innermost while it lasts
    "overlap_without_nesting": (
        [rec("a", "taskQueued", 0, 30), rec("a", "pipelineBuild", 20, 50),
         rec("a", "exchangeFabricIciDrain", 40, 100),
         rec("a", "hostSync", 60, 70)],
        {"sched": 20, "pipeline": 20, "exchange": 50, "device": 10}),
    # two threads in one state are one state's time, not twice it
    "one_state_on_two_threads": (
        [rec("a", "pipelineDrain", 0, 60), rec("b", "pipelineDrain", 40, 100)],
        {"pipeline": 100}),
    # a zero-length record covers nothing
    "empty_record": (
        [rec("a", "exchangeFabricIciWait", 50, 50),
         rec("a", "queryExecute", 0, 100)],
        {"pipeline": 100}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_partition_of_synthetic_records(case):
    records, expected = CASES[case]
    out = partition(records, 0, 100 * MS)
    assert states_of(out) == pytest.approx(expected, abs=0.11), out
    assert sum(out[k] for k in WALL) == 100 * MS
    assert out["queryWallIntervals"] == len(records)


def test_partition_names_the_wait_that_won():
    out = partition(CASES["wait_wins_only_alone"][0], 0, 100 * MS)
    waits = {k: v for k, v in out.items() if k.startswith("queryWall.wait.")}
    # the two waiting threads are both asleep: one of them is named
    assert sum(waits.values()) == out["queryWall.wait"] == 70 * MS
    assert set(waits) <= {"queryWall.wait.statementPollWait",
                          "queryWall.wait.schedAwaitStages"}


@pytest.mark.parametrize("seed", range(4))
def test_states_sum_to_the_extent_to_the_nanosecond(seed):
    import random
    rng = random.Random(seed)
    names = sorted(STATE_OF) + ["notInTheTable"]
    records = []
    for _ in range(300):
        start = rng.randrange(-10 * MS, 110 * MS)
        records.append((rng.randrange(5), rng.choice(names), start,
                        start + rng.randrange(0, 20 * MS),
                        rng.choice([-1, 0, rng.randrange(1, MS)]),
                        rng.random() < 0.5))
    start, end = 1_000_003, 99_999_989
    out = partition(records, start, end, dropped=7)
    assert sum(out[k] for k in WALL) == end - start
    assert all(out[k] >= 0 for k in out)
    assert out["queryWallIntervalsDropped"] == 7
    # CPU time never exceeds the wall it is charged over (a record's CPU
    # is at most its wall here)
    for state in STATES[:-1]:
        assert out["queryWallCpu." + state] <= out["queryWall." + state] + 1


def test_cpu_is_pro_rata_and_a_records_own():
    # pipelineDrain 0..100 with 30 ms of CPU, 20 of them inside a nested
    # dispatch; a sync 50..90 in which it burns nothing: its own 10 ms of
    # CPU lie in the 50 ms it is innermost (0..30, 40..50, 90..100).  A
    # second thread waits on the device 0..20, which wins those instants
    records = [rec("a", "pipelineDrain", 0, 100, 30),
               rec("a", "pipelineDispatch", 30, 40, 20),
               rec("a", "hostSync", 50, 90, 0),
               rec("b", "hostSync", 0, 20, 1)]
    out = partition(records, 0, 100 * MS)
    assert states_of(out) == {"device": 60, "pipeline": 40}
    # pipeline: 20..30, 40..50 and 90..100 of pipelineDrain's own time at
    # 10 ms of CPU over 50 (2 + 2 + 2), and 30..40 the dispatch's 20
    assert out["queryWallCpu.pipeline"] / MS == pytest.approx(26.0)
    assert out["queryWallCpu.device"] / MS == pytest.approx(1.0)


def test_unmeasured_cpu_shares_its_enclosers():
    # JAX reports a trace when it is over: nobody measured its CPU time
    # (-1), so the 40 ms it was innermost count as pipelineBuild's own
    records = [rec("a", "pipelineBuild", 0, 100, 80),
               rec("a", "jaxTrace", 10, 50, -1)]
    out = partition(records, 0, 100 * MS)
    assert states_of(out) == {"pipeline": 100}
    assert out["queryWallCpu.pipeline"] / MS == pytest.approx(80)


# ---------------------------------------------------------------------------
# the record the primitive keeps
# ---------------------------------------------------------------------------

def test_every_close_is_one_record_on_the_unix_clock():
    import jax.numpy as jnp

    from presto_tpu.utils.runtime_stats import host_get, named_jit
    s = RuntimeStats(task_id="t1")
    # (traced and compiled before the owner is on: where another test of
    # the process has installed the JAX listener, its events are records)
    demo, x = named_jit("wall_demo", lambda a: a + 1), jnp.ones(2)
    demo(x)
    before = time.time_ns()
    with s.activate():
        with s.span("outer"):
            demo(x)
            host_get(x, "demo")
        s.record("taskQueued", time.perf_counter_ns() - 5 * MS, 5 * MS)
    after = time.time_ns()
    line = s.timeline()
    assert line["dropped"] == 0 and len(line["rows"]) == 4 * RECORD_WIDTH
    records, dropped = records_of([("t1", line)])
    assert dropped == 0
    by = {r[1]: r for r in records}
    assert sorted(by) == ["hostSync", "outer", "pipelineDispatch",
                          "taskQueued"]
    me = threading.get_ident()
    for thread, _n, start, end, cpu, on_task in records:
        assert thread == ("t1", me) and on_task
        assert before - 6 * MS <= start <= end <= after + MS
        assert cpu <= end - start + MS
    # a span reads the thread's CPU clock at both ends; a launch leaves
    # its CPU time to the span around it, a blocking sync has none
    assert by["outer"][4] >= 0 and by["hostSync"][4] == 0
    assert by["pipelineDispatch"][4] == -1
    outer = by["outer"]
    for inner in ("pipelineDispatch", "hostSync"):
        assert outer[2] <= by[inner][2] <= by[inner][3] <= outer[3] + 1000
    assert by["taskQueued"][3] - by["taskQueued"][2] == 5 * MS
    # the keys a span always gave are unchanged
    d = s.to_dict()
    assert d["outerWallNanos"]["count"] == d["taskQueuedWallNanos"]["count"] == 1
    assert d["hostSyncs"]["sum"] == d["pipelineLaunches"]["sum"] == 1
    assert abs(CLOCK_ANCHOR_NS
               - (time.time_ns() - time.perf_counter_ns())) < 50 * MS


def test_a_record_carries_the_threads_own_cpu_time():
    s = RuntimeStats()
    with s.span("busy"):
        t0 = time.thread_time_ns()
        while time.thread_time_ns() - t0 < 5 * MS:
            pass
    with s.span("asleep"):
        time.sleep(0.02)
    (busy, asleep), _ = records_of([("", s.timeline())])
    assert busy[1] == "busy" and busy[4] >= 4 * MS
    assert asleep[1] == "asleep" and asleep[3] - asleep[2] >= 19 * MS
    assert asleep[4] < 5 * MS and not asleep[5]


def test_records_past_the_bound_are_counted_and_unattributed():
    s = RuntimeStats()
    began = time.perf_counter_ns() + CLOCK_ANCHOR_NS
    extra = 904
    for _ in range(MAX_SPANS_PER_TRACE):
        with s.span("queryPlan"):
            pass
    kept_until = time.perf_counter_ns() + CLOCK_ANCHOR_NS
    for _ in range(extra):
        with s.span("queryPlan"):
            time.sleep(0.0002)
    ended = time.perf_counter_ns() + CLOCK_ANCHOR_NS
    line = s.timeline()
    assert len(line["rows"]) == MAX_SPANS_PER_TRACE * RECORD_WIDTH
    assert line["dropped"] == extra
    # the walls are still summed
    assert s.get("queryPlanWallNanos").count == MAX_SPANS_PER_TRACE + extra
    out = query_wall.runtime_stats_keys(s.timelines(), began, ended)
    assert out["queryWallIntervals"]["sum"] == MAX_SPANS_PER_TRACE
    assert out["queryWallIntervalsDropped"]["sum"] == extra
    assert out["queryWallIntervalsDropped"]["unit"] == "NONE"
    # what the dropped records would have covered is nobody's
    assert out["queryWall.unattributed"]["sum"] >= 0.9 * (ended - kept_until)
    assert out["queryWall.plan"]["sum"] <= kept_until - began


def test_merge_concatenates_timelines_and_merge_dict_sums_maps():
    task, query = RuntimeStats(task_id="q.0.1"), RuntimeStats()
    with task.span("pipelineDrain"):
        pass
    with query.span("queryPlan"):
        pass
    query.merge_dict(task.to_dict())          # the map: summed, no records
    assert [src for src, _l in query.timelines()] == [""]
    query.add_timeline("q.0.0", task.timeline())     # a TaskInfo's
    query.merge(task)                                # an in-process task's
    assert [src for src, _l in query.timelines()] == ["", "q.0.0", "q.0.1"]
    assert query.get("pipelineDrainWallNanos").count == 2
    records, _ = records_of(query.timelines())
    assert sorted((r[1], r[5]) for r in records) == [
        ("pipelineDrain", True), ("pipelineDrain", True),
        ("queryPlan", False)]
    query.release_timelines()
    assert records_of(query.timelines()) == ([], 0)


class _ParentScope:
    """`RuntimeStats.span` as the parent of this PR had it: the
    annotation, the wall clock at both ends, `add` at the end."""

    __slots__ = ("stats", "name", "t0", "ann")

    def __init__(self, stats, name):
        self.stats, self.name = stats, name

    def __enter__(self):
        self.ann = _annotate("presto:" + self.name, self.stats.ids, {})
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self.t0
        self.stats.add(self.name + "WallNanos", dt, "NANO")
        self.ann.__exit__(*exc)
        return False


def span_costs_ns(*scopes, rounds: int = 25, n: int = 1500):
    """Least over `rounds` of the mean cost of one enter + exit, for each
    of `scopes` (each round a new owner: no round runs past the bound;
    the scopes take turns, so that a busy stretch of a shared machine
    slows them alike)."""
    best = [float("inf")] * len(scopes)
    for _ in range(rounds):
        for k, scope in enumerate(scopes):
            s = RuntimeStats()
            t0 = time.perf_counter_ns()
            for _ in range(n):
                with scope(s, "phase"):
                    pass
            best[k] = min(best[k], (time.perf_counter_ns() - t0) / n)
    return best


def test_a_record_adds_under_two_microseconds_a_span():
    before, after = span_costs_ns(_ParentScope,
                                  lambda s, name: s.span(name))
    print(f"span enter+exit: {before:.0f} ns before, {after:.0f} ns with "
          f"the record (+{after - before:.0f} ns)")
    # 2 us at the speed at which the parent's span costs its 1.9 us (a
    # shared machine runs everything slower for seconds at a time)
    assert after - before <= 2000 * max(1.0, before / 1900), (before, after)


# ---------------------------------------------------------------------------
# a library call (the partition in QueryInfo, on both served paths, is
# tested on tests/test_spans.py's own servers, in that file)
# ---------------------------------------------------------------------------

def test_a_library_call_is_its_own_query_level():
    from presto_tpu.exec.pipeline import ExecutionConfig
    from presto_tpu.exec.runner import LocalQueryRunner
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(batch_rows=1 << 13))
    r.execute(Q6)
    t0 = time.perf_counter_ns()
    res = r.execute(Q6)
    wall = time.perf_counter_ns() - t0
    rs = res.runtime_stats
    total = sum(rs[k]["sum"] for k in WALL)
    assert 0.5 * wall <= total <= wall
    records, _ = records_of(res.timeline)
    assert {"queryParse", "queryExecute", "pipelineDispatch",
            "hostSync"} <= {r[1] for r in records}
    footer = r.execute("explain analyze " + Q6).rows[0][0]
    assert "Query wall: " in footer and "pipeline" in footer.split(
        "Query wall: ")[1]


# ---------------------------------------------------------------------------
# the state table against the tree: a new span cannot vanish
# ---------------------------------------------------------------------------

# where a name enters the primitive: `RuntimeStats.span` (a tracer's own
# `span` is another thing), pipeline.py's `_span(rs, ...)`, `record` (the
# fabric's metrics table has a `record` of its own), the JAX listener's
# `_record`, `close_span` (host_get, named_jit), dense_batches' key and
# the join's `timed(name, stream)`
_NAME_SITES = (
    r"(?<!tracer)\.span\(\s*\"(\w+)\"",
    r"\b_span\(\s*\w+(?:\.\w+)*,\s*\"(\w+)\"",
    r"(?<!METRICS)\.record\(\s*\"(\w+)\"",
    r"\b_record\(\s*\w+,\s*\"(\w+)\"",
    r"\.close_span\(\s*\"(\w+)\"",
    r"\"(\w+Coalesce)\"",
    r"\btimed\(\s*\"(\w+)\"",
)


def span_names_in_the_tree():
    found = {}
    for path in sorted(ROOT.rglob("*.py")):
        text = path.read_text()
        for pattern in _NAME_SITES:
            for name in re.findall(pattern, text):
                found.setdefault(name, str(path.relative_to(ROOT)))
    return found


def test_every_span_name_in_the_tree_is_in_the_state_table():
    found = span_names_in_the_tree()
    # the scan sees what it is meant to see
    assert {"hostSync", "pipelineDispatch", "jaxTrace", "jaxBackendCompile",
            "taskQueued", "statementQueued", "exchangeClientPull",
            "servingBatchWait", "topN", "joinBuild", "aggUpdate",
            "outputCoalesce", "schedTaskEncode", "taskCreateStart",
            "storageBuild", "meshGather"} <= set(found)
    assert len(found) >= 40
    missing = {n: where for n, where in found.items() if n not in STATE_OF}
    assert not missing, (
        f"spans without a state in telemetry/query_wall.py: {missing}")
    # and the table names nothing the tree no longer records
    assert not set(STATE_OF) - set(found), set(STATE_OF) - set(found)


# ---------------------------------------------------------------------------
# the benchmark's readers (benchmark/tests is not tier-1)
# ---------------------------------------------------------------------------

BENCH = ROOT.parent / "benchmark"
READERS = ["wall.device_ms", "wall.pipeline_ms", "wall.exchange_ms",
           "wall.sched_ms", "wall.plan_ms", "wall.statement_ms",
           "wall.wait_ms", "wall.unattributed_share", "wall.host_cpu_share"]


def reader(name):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def run_of(*queries):
    def stat(v):
        return {"unit": "NANO", "sum": v, "count": 1, "min": v, "max": v}
    infos = {f"q{i}": {"runtimeStats": {k: stat(v) for k, v in q.items()}}
             for i, q in enumerate(queries)}
    return {"requests": [{"ok": True, "query_id": qid} for qid in infos],
            "query_info": infos}


A_QUERY = {"pipelineLaunches": 7, "queryWall.device": 40 * MS,
           "queryWall.pipeline": 20 * MS, "queryWall.exchange": 10 * MS,
           "queryWall.sched": 10 * MS, "queryWall.plan": 4 * MS,
           "queryWall.statement": 6 * MS, "queryWall.wait": 8 * MS,
           "queryWall.unattributed": 2 * MS,
           "queryWallCpu.pipeline": 15 * MS, "queryWallCpu.exchange": 5 * MS,
           "queryWallCpu.sched": 2 * MS, "queryWallCpu.plan": 4 * MS,
           "queryWallCpu.statement": 4 * MS}
EXPECTED = {"wall.device_ms": 40, "wall.pipeline_ms": 20,
            "wall.exchange_ms": 10, "wall.sched_ms": 10, "wall.plan_ms": 4,
            "wall.statement_ms": 6, "wall.wait_ms": 8,
            "wall.unattributed_share": 2.0, "wall.host_cpu_share": 60.0}


@pytest.mark.parametrize("name", READERS)
def test_wall_reader(name):
    read = reader(name)
    # the parent: instrumented queries that carry no partition
    assert read(run_of({"pipelineLaunches": 7})) is None
    assert read(run_of()) is None
    assert read(run_of(A_QUERY)) == pytest.approx(EXPECTED[name])
    twice = {k: 2 * v for k, v in A_QUERY.items()}
    both = read(run_of(A_QUERY, twice))
    scale = 1.0 if name.endswith("_share") else 1.5
    assert both == pytest.approx(EXPECTED[name] * scale)


def test_benchmark_lists_the_nine_readers_for_all_six_cells():
    import json
    bench = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    # (by name: metrics later PRs append follow them)
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in READERS}
    mine = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == READERS
    for m in mine:
        assert m["workloads"] == cells and m["moves"] == "rows_per_s"
        assert m["source"] == "program_span" and m["layer"] in layers
        assert m["better"] == ("higher" if m["name"] == "wall.host_cpu_share"
                               else "lower")
        assert os.path.exists(BENCH / "layer_metrics" / (m["name"] + ".py"))
