"""RuntimeStats metric map + tracer SPI (§5.1 analog: RuntimeStats.java,
TracerProviderManager/SimpleTracer) and their flow through the runner and
the statement protocol's query info."""
import json
import urllib.request

from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner
from presto_tpu.utils.runtime_stats import (Metric, RuntimeStats,
                                            SimpleTracer, TracerProvider)


def test_metric_merge():
    a, b = RuntimeStats(), RuntimeStats()
    a.add("x", 5)
    b.add("x", 7)
    b.add("y", 1)
    a.merge(b)
    m = a.get("x")
    assert m.sum == 12 and m.count == 2 and m.min == 5 and m.max == 7
    assert a.get("y").sum == 1


def test_record_wall():
    s = RuntimeStats()
    with s.span("phase"):
        pass
    m = s.get("phaseWallNanos")
    assert m is not None and m.count == 1 and m.sum >= 0


def test_runner_records_phases():
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        batch_rows=1 << 13))
    res = r.execute("SELECT count(*) c FROM orders")
    assert "queryParseWallNanos" in res.runtime_stats
    assert "queryExecuteWallNanos" in res.runtime_stats
    # first run plans; cached re-run may skip the plan phase
    assert "queryPlanWallNanos" in res.runtime_stats


def test_simple_tracer_through_runner():
    tp = TracerProvider("simple")
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        batch_rows=1 << 13), tracer_provider=tp)
    sql = "SELECT count(*) c FROM orders"
    r.execute(sql)
    trace = tp.get_trace(sql)
    assert isinstance(trace, SimpleTracer)
    # the query's phases are spans under the `query` root, in the order
    # they began, each inside its parent's interval
    by_name = {s.name: s for s in trace.spans}
    assert trace.spans[0].name == "query" and trace.spans[0].parent == ""
    for phase in ("queryParse", "queryPlan", "queryExecute"):
        assert by_name[phase].parent == "query", phase
    assert by_name["pipelineBuild"].parent == "queryExecute"
    starts = [by_name[n].start for n in ("query", "queryParse", "queryPlan",
                                         "queryExecute", "pipelineBuild")]
    assert starts == sorted(starts)
    assert all(s.end >= s.start for s in trace.spans)
    assert by_name["queryExecute"].end <= by_name["query"].end + 1e-3


def test_runtime_stats_in_query_info():
    from presto_tpu.client import StatementClient
    from presto_tpu.worker import WorkerServer
    server = WorkerServer(coordinator=True, environment="test",
                          config=ExecutionConfig(batch_rows=1 << 13))
    try:
        c = StatementClient(server.uri, schema="sf0.01")
        r = c.execute("SELECT count(*) c FROM orders")
        with urllib.request.urlopen(
                f"{server.uri}/v1/query/{r.query_id}") as resp:
            info = json.loads(resp.read())
        assert "runtimeStats" in info
        assert "queryExecuteWallNanos" in info["runtimeStats"]
    finally:
        server.close()


def test_grouped_bucket_walls_exposed():
    """Grouped execution reports per-bucket generation and compute walls
    plus the whole-run wall, keyed by lifespan count."""
    from presto_tpu.exec.pipeline import ExecutionConfig
    from presto_tpu.exec.runner import LocalQueryRunner
    r = LocalQueryRunner("sf0.01",
                         config=ExecutionConfig(grouped_lifespans=4))
    res = r.execute(
        "select l_orderkey, sum(l_quantity) q from lineitem "
        "group by l_orderkey order by q desc limit 5")
    stats = res.runtime_stats
    assert stats["groupedBucketGenWallNanos"]["count"] == 4
    assert stats["groupedBucketComputeWallNanos"]["count"] == 4
    assert stats["groupedBucketGenWallNanos"]["sum"] > 0
    assert stats["groupedBucketComputeWallNanos"]["sum"] > 0
    assert stats["groupedRunWallNanos"]["count"] == 1
    assert stats["groupedRunWallNanos"]["sum"] >= \
        stats["groupedBucketComputeWallNanos"]["sum"]
