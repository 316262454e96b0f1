"""The arithmetic of the end-to-end metrics and of every per-layer
reader, on canned inputs."""
import json
import os

import pytest

import metrics
from cells import BENCH, ROOT, Query

ROWS = {"tpch/q6": 60e6, "tpch/q1": 60e6}


def req(template="tpch/q6", wall=1.0, ok=True, elapsed_ms=900, qid="q"):
    return {"template": template, "wall_s": wall, "ok": ok, "rows": [[1]],
            "server_elapsed_ms": elapsed_ms, "query_id": qid,
            "values": {}}


def test_rows_per_s_is_all_the_work_over_all_the_time():
    done = [req() for _ in range(10)]
    assert metrics.rows_per_s(done, ROWS, 10.0) == 60e6
    # a stall anywhere in the window lowers it: same work, longer window
    assert metrics.rows_per_s(done, ROWS, 15.0) == 40e6
    # a failed request adds no rows
    assert metrics.rows_per_s(done + [req(ok=False)], ROWS, 10.0) == 60e6


def test_wall_p95_is_the_tail_of_all_requests():
    walls = [req(wall=w / 1000) for w in range(1, 101)]
    assert metrics.wall_p95_ms(walls) == 95.0
    assert metrics.percentile([5.0], 95) == 5.0
    # a failed request counts as slower than any that completed
    assert metrics.wall_p95_ms(walls[:10] + [req(ok=False)]) == float("inf")
    with pytest.raises(ValueError):
        metrics.percentile([], 95)


def canned_run():
    q = {t: Query(t) for t in ROWS}
    requests = [req("tpch/q6", 2.0, elapsed_ms=1900, qid="a"),
                req("tpch/q1", 4.0, elapsed_ms=3800, qid="b"),
                req("tpch/q6", 1.0, ok=False, qid="c")]
    stat = lambda ns: {"sum": ns, "count": 1}  # noqa: E731
    return {
        "requests": requests, "window_s": 10.0, "clients": 1,
        "rows_per_query": ROWS, "queries": q,
        "counters": {
            "before": {"jax_trace_s": 1.0, "jax_backend_compiles": 5, "jax_cache_hits": 4,
                       "storage_cache_hits": 10, "storage_cache_misses": 7,
                       "serving_planCacheHits": 1, "serving_planCacheMisses": 2,
                       "serving_servingBatchLaunchesSaved": 0,
                       "exchange_uncompressed_bytes": 100},
            "after": {"jax_trace_s": 2.0, "jax_backend_compiles": 15, "jax_cache_hits": 11,
                      "storage_cache_hits": 28, "storage_cache_misses": 9,
                      "serving_planCacheHits": 10, "serving_planCacheMisses": 3,
                      "serving_servingBatchLaunchesSaved": 1,
                      "exchange_uncompressed_bytes": 4100}},
        "query_info": {
            "a": {"runtimeStats": {"queryParseWallNanos": stat(1e6),
                                   "queryPlanWallNanos": stat(3e6)},
                  "stages": [{"wallTimeInNanos": 2e9}, {"wallTimeInNanos": 1e9}]},
            "b": {"runtimeStats": {"queryParseWallNanos": stat(2e6)},
                  "stages": [{"wallTimeInNanos": 5e9}]}},
        "trace": {"busy_s": 0.5, "window_s": 10.0},
        "resident": {"lineitem.shipdate": 240e6, "lineitem.discount": 60e6,
                     "lineitem.quantity": 60e6, "lineitem.extendedprice": 480e6,
                     "lineitem.tax": 60e6, "lineitem.returnflag": 15e6,
                     "lineitem.linestatus": 15e6},
        "peaks": {"hbm_bytes_per_s": 819e9},
    }


# per-layer metric -> what it reads on the canned run
EXPECTED = {
    "client.wall_max_ms": 4000.0,
    "client.wall_p95_ms": float("inf"),        # one of three requests failed
    "statement.overhead_ms": 150.0,             # (100 + 200) / 2
    "plan.wall_ms": 3.0,                        # (1 + 3 + 2) / 2
    "plan.cache_hit_share": 90.0,               # 9 / (9 + 1)
    "serving.queries_per_launch": 2.0,          # 2 / (2 - 1)
    "sched.stage_wall_ms": 4000.0,              # (3000 + 5000) / 2
    "pipeline.trace_ms": 500.0,                 # 1 s over 2 queries
    "pipeline.compiles": 3,
    "exchange.page_bytes": 2000.0,
    "storage.hit_share": 90.0,                  # 18 / 20
    # q6 840 MB + q1 930 MB, one launch saved of two -> half
    "scan_hbm_roofline": 100 * (1770e6 / 2 / 819e9) / 0.5,
    "window.hbm_peak_share": 100 * (1770e6 / 2 / 819e9) / 10.0,
    "device.idle_share": 95.0,
}


def test_every_per_layer_metric_of_the_benchmark_has_a_canned_case():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        named = {m["name"] for m in json.load(f)["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
             if f.endswith(".py")}
    # a reader may wait for its cell (exchange.page_bytes: join-power)
    assert named <= files == set(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_layer_metric_arithmetic(name):
    got = metrics.layer_reader(name)(canned_run())
    assert got == pytest.approx(EXPECTED[name], rel=1e-12)


def test_wall_p95_reads_the_whole_window_where_the_span_is_shorter():
    run = canned_run()
    run["window_requests"] = [req(wall=w / 1000) for w in range(1, 101)]
    assert metrics.layer_reader("client.wall_p95_ms")(run) == 95.0


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    empty = {"requests": [], "window_s": 10.0, "clients": 1,
             "rows_per_query": ROWS, "queries": {},
             "counters": {"before": {}, "after": {}}, "query_info": {},
             "trace": None, "resident": {}, "peaks": None}
    got = metrics.layer_reader(name)(empty)
    assert got is None


def test_metric_values_leaves_out_what_is_none():
    entries = [{"name": "a", "unit": "ms"}, {"name": "b", "unit": "%"},
               {"name": "c", "unit": "ms"}]
    out = metrics.metric_values(
        entries, {"a": 1.5, "b": None, "c": float("inf")}.get)
    assert out == {"a": {"value": 1.5, "unit": "ms"}}
