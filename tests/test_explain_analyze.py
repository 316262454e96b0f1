"""EXPLAIN ANALYZE / observability spine (tier-1).

Covers the four legs of the operator-stats work:
  - fused-path ANALYZE: the fused chain's device-side row counters agree
    with the interpreted (analyze_unfused) per-node instrumentation
  - distributed ANALYZE: every fragment of the 2-task plan is annotated
    from the task-rolled-up operator stats
  - tracer SPI: the query -> fragment -> task -> operator span hierarchy
    recorded by SimpleTracer
  - /v1/query/{id}: the QueryInfo surface over a real loopback cluster
    (trace token, stage/task/operator breakdown, process metrics)
"""
import json
import re
import time
import urllib.request

import pytest

from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import DistributedQueryRunner, LocalQueryRunner
from presto_tpu.utils.runtime_stats import SimpleTracer, TracerProvider

from test_queries import TPCH_Q1, TPCH_Q6


# ---------------------------------------------------------------------------
# fused vs unfused ANALYZE parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sql,cfg", [
    (TPCH_Q1, dict(batch_rows=1 << 13)),
    (TPCH_Q6, dict(batch_rows=1 << 13)),
    (TPCH_Q6, {}),      # one 64K-row chunk: what a served query runs under
], ids=["q1", "q6", "q6-default-config"])
def test_fused_vs_unfused_analyze_row_parity(sql, cfg):
    """ANALYZE over the fused path reports the same per-node row counts as
    the old interpreted instrumentation — the device-side counters riding
    the jitted program are exact, not estimates."""
    fused = LocalQueryRunner("sf0.01", config=ExecutionConfig(**cfg))
    unfused = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        analyze_unfused=True, **cfg))
    text_f = fused.execute("EXPLAIN ANALYZE " + sql).rows[0][0]
    text_u = unfused.execute("EXPLAIN ANALYZE " + sql).rows[0][0]
    assert "[fused]" in text_f          # the fused chain actually ran
    assert "[fused]" not in text_u      # the knob retains the old path
    sf, su = fused.last_operator_stats, unfused.last_operator_stats
    shared = set(sf) & set(su)
    assert shared, "no common instrumented nodes between the two paths"
    for nid in shared:
        assert sf[nid]["rows"] == su[nid]["rows"], nid
    for s in sf.values():
        assert s["rows"] >= 0 and s["wall_s"] >= 0 and s["batches"] >= 1


def test_analyze_footer_reports_fused_programs():
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        batch_rows=1 << 13))
    text = r.execute("EXPLAIN ANALYZE " + TPCH_Q6).rows[0][0]
    assert "Fused program wall:" in text


# ---------------------------------------------------------------------------
# distributed ANALYZE
# ---------------------------------------------------------------------------

def test_distributed_analyze_annotates_every_fragment():
    r = DistributedQueryRunner("sf0.01", n_tasks=2,
                               config=ExecutionConfig(batch_rows=1 << 13))
    text = r.execute("EXPLAIN ANALYZE " + TPCH_Q1).rows[0][0]
    fragments = re.split(r"(?m)^Fragment ", text)
    header, fragments = fragments[0], fragments[1:]
    assert len(fragments) >= 2          # partial-agg + final-agg stages
    for frag in fragments:
        # every fragment carries rolled-up task stats on its nodes
        assert "rows:" in frag and "wall:" in frag, frag
    assert r.last_operator_stats       # the side channel fed the annotations


# ---------------------------------------------------------------------------
# span hierarchy
# ---------------------------------------------------------------------------

def test_span_tree_query_fragment_task_operator():
    tp = TracerProvider("simple")
    r = DistributedQueryRunner("sf0.01", n_tasks=2, tracer_provider=tp,
                               config=ExecutionConfig(batch_rows=1 << 13))
    sql = "EXPLAIN ANALYZE " + TPCH_Q6
    r.execute(sql)
    trace = tp.get_trace(sql)
    assert isinstance(trace, SimpleTracer)
    roots = [t for t in trace.span_tree() if t["name"] == "query"]
    assert len(roots) == 1
    fragments = roots[0]["children"]
    assert fragments
    assert all(f["name"].startswith("fragment ") for f in fragments)
    tasks = [t for f in fragments for t in f["children"]]
    assert tasks
    assert all(t["name"].startswith("task ") for t in tasks)
    operators = [o for t in tasks for o in t["children"]]
    assert operators
    for o in operators:
        assert o["name"].startswith("operator ")
        assert "rows" in o["attributes"] and "wall_s" in o["attributes"]


# ---------------------------------------------------------------------------
# /v1/query QueryInfo surface (loopback cluster)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster():
    from presto_tpu.worker import WorkerServer
    coordinator = WorkerServer(coordinator=True, environment="test")
    workers = [WorkerServer(discovery_uri=coordinator.uri,
                            announce_interval_s=0.1,
                            environment="test") for _ in range(2)]
    deadline = time.time() + 10
    while len(coordinator.worker_uris()) < 2 and time.time() < deadline:
        time.sleep(0.05)
    assert len(coordinator.worker_uris()) == 2, "workers failed to announce"
    yield coordinator, workers
    for w in workers:
        w.close()
    coordinator.close()


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read())


def test_query_info_schema_golden(cluster):
    """GET /v1/query/{id} after a distributed run: the QueryInfo snapshot
    carries the trace token, per-stage task breakdown with per-operator
    stats, the cross-task operator rollup, and process metrics."""
    from presto_tpu.client import StatementClient
    coordinator, _ = cluster
    c = StatementClient(coordinator.uri, schema="sf0.01",
                        trace_token="trace-test-qinfo")
    r = c.execute(TPCH_Q6)
    assert r.rows

    listing = _get_json(f"{coordinator.uri}/v1/query")
    assert any(q["queryId"] == r.query_id for q in listing)

    info = _get_json(f"{coordinator.uri}/v1/query/{r.query_id}")
    # identity + terminal state
    assert info["queryId"] == r.query_id
    assert info["state"] == "FINISHED"
    # the client-supplied token survived dispatch and is the join key
    assert info["traceToken"] == "trace-test-qinfo"
    assert isinstance(info["peakMemoryBytes"], int)
    # metric-map shape (names differ between local and distributed paths)
    assert info["runtimeStats"]
    assert all({"sum", "count"} <= set(m)
               for m in info["runtimeStats"].values())

    # stage/task breakdown (terminal snapshot from the history ring)
    stages = info["stages"]
    assert len(stages) >= 2
    # stage ids are {execution id}.{stage path}: one shared execution id
    # (the runner's internal id, distinct from the statement query id),
    # one distinct path per stage
    assert len({s["stageId"].split(".", 1)[0] for s in stages}) == 1
    assert len({s["stageId"] for s in stages}) == len(stages)
    for stage in stages:
        assert stage["nTasks"] == len(stage["tasks"]) >= 1
        for task in stage["tasks"]:
            assert task["traceToken"] == "trace-test-qinfo"
            ops = task["pipelines"][0]["operators"]
            assert ops
            assert any("stats" in op for op in ops)

    # cross-task operator rollup: every entry has the stats-spine fields
    rollup = info["operatorStats"]
    assert rollup
    for s in rollup.values():
        assert s["rows"] >= 0 and s["wall_s"] >= 0 and s["batches"] >= 0

    # process metrics ride along for a single-snapshot health read
    assert set(info["processMetrics"]) == {"exchange", "fabric", "serving",
                                           "storage", "memory", "adaptive",
                                           "programs"}
    # the program table names what JAX traced and loaded, by program
    programs = info["processMetrics"]["programs"]
    assert programs and all(
        {"traces", "trace_s", "loads", "load_s", "true_compiles"} == set(r)
        for r in programs.values())
    assert "resident_bytes" in info["processMetrics"]["storage"]
    assert "spilled_bytes" in info["processMetrics"]["memory"]
    assert "filters_applied" in info["processMetrics"]["adaptive"]


def test_query_info_has_no_kernel_surface(cluster):
    """There is one scan path: a fused Q6 served over the cluster reports
    no kernelDeclined* counter, no `kernel` process section, no scanKernel
    on its tasks and no kernel family among the /v1/metrics lines."""
    from presto_tpu.client import StatementClient
    coordinator, _ = cluster
    r = StatementClient(coordinator.uri, schema="sf0.01").execute(TPCH_Q6)
    info = _get_json(f"{coordinator.uri}/v1/query/{r.query_id}")
    assert info["runtimeStats"]
    assert not [k for k in info["runtimeStats"] if "kernel" in k.lower()]
    assert "kernel" not in info["processMetrics"]
    tasks = [t for stage in info["stages"] for t in stage["tasks"]]
    assert tasks and not any("scanKernel" in t.get("stats", {})
                             for t in tasks)
    with urllib.request.urlopen(f"{coordinator.uri}/v1/metrics",
                                timeout=10) as resp:
        assert "kernel" not in resp.read().decode()


def test_explain_analyze_prints_no_kernel_line(cluster):
    """...and EXPLAIN ANALYZE of it names the fused program and nothing of
    a kernel: the footer's decline line is the fusion's alone."""
    from presto_tpu.client import StatementClient
    coordinator, _ = cluster
    text = StatementClient(coordinator.uri, schema="sf0.01").execute(
        "EXPLAIN ANALYZE " + TPCH_Q6).rows[0][0]
    assert "[fused]" in text
    assert not re.search(r"kernel|pallas", text, re.IGNORECASE)


def test_metrics_namespace_consistency(cluster):
    """/v1/metrics exposes the storage gauges alongside the other metric
    families under the one presto_tpu_ prefix."""
    coordinator, _ = cluster
    with urllib.request.urlopen(f"{coordinator.uri}/v1/metrics",
                                timeout=10) as resp:
        body = resp.read().decode()
    assert "presto_tpu_storage_resident_bytes" in body
    assert "presto_tpu_storage_cache_hits_total" in body
    for family in ("presto_tpu_exchange_", "presto_tpu_serving_"):
        assert family in body
