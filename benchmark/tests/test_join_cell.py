"""The cell tpch10-joins.join-power: its entries and files, the readers
it brought (arithmetic on a canned QueryInfo, the nothing-to-read case of
each: the parent of the PR that brought the join's spans records none),
and one rehearsed traced run on the CPU that ends with a result line, no
wrong answer and a value of every reader a rehearsal can feed."""
import json
import os
import subprocess
import sys

import pytest

import metrics
from cells import BENCH, ROOT, Cell, Query

CELL = "tpch10-joins.join-power"
MS = 1e6


def stat(total, count=1):
    return {"sum": total, "count": count, "min": 0, "max": total,
            "unit": "NANO"}


def req(qid, template, ok=True):
    return {"template": template, "wall_s": 1.5, "ok": ok, "query_id": qid}


def canned_run():
    """A Q14 whose two join tasks build and probe (`a`), a Q12 with one
    probe batch a task (`b`), a request of a program without spans (`c`),
    a failed one (`d`)."""
    a = {"pipelineLaunches": stat(60, 60),
         "joinBuildWallNanos": stat(240 * MS, 6),
         "joinProbeWallNanos": stat(500 * MS, 16),
         "joinProbeBatches": stat(14, 14),
         "joinProbeRowsIn": stat(827_130, 2),
         "joinOutputRows": stat(827_130, 2)}
    b = {"pipelineLaunches": stat(40, 40),
         "joinBuildWallNanos": stat(160 * MS, 6),
         "joinProbeWallNanos": stat(100 * MS, 8),
         "joinProbeBatches": stat(6, 6)}
    return {"requests": [req("a", "tpch/q14"), req("b", "tpch/q12"),
                         req("c", "tpch/q14"), req("d", "tpch/q12", ok=False)],
            "window_s": 10.0,
            "query_info": {"a": {"runtimeStats": a}, "b": {"runtimeStats": b},
                           "c": {"runtimeStats": {}},
                           "d": {"runtimeStats": a}},
            "counters": {"before": {"exchange_uncompressed_bytes": 1_000},
                         "after": {"exchange_uncompressed_bytes": 91_000}},
            "queries": {t: Query(t) for t in ("tpch/q12", "tpch/q14")},
            "resident": {}, "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": None}


EXPECTED = {
    "join.build_ms": (240 + 160) / 2,
    "join.probe_ms": (500 + 100) / 2,
    "join.probe_batches": (14 + 6) / 2,
    "exchange.page_bytes": 90_000 / 3,      # a, b and c completed
}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_its_configuration_and_its_metrics_are_listed():
    b = bench()
    cell = Cell(CELL)
    assert cell.listed and cell.chips == 1
    assert cell.config["scale_factor"] == 10
    assert cell.config["architecture"] is None
    servers = cell.config["servers"]
    assert servers["workers"] == 1
    assert servers["coordinator"]["properties"]["coordinator"] == "true"
    assert servers["worker"]["properties"] == {
        "coordinator": "false", "node.environment": "test",
        "exchange.max-buffer-size": "32MB",
        "exchange.max-response-size": "1MB"}
    # the standing traffic file, as it stands
    assert cell.traffic["clients"] == 1 and cell.traffic["loop"] == "closed"
    assert cell.traffic["templates"] == ["tpch/q12", "tpch/q14"]
    assert cell.traffic["rows_per_query"] == {"tpch/q12": 75_000_000,
                                              "tpch/q14": 62_000_000}
    assert cell.traffic["check"] == {"sample": 64,
                                     "control": "scan_stops_a_batch_short"}
    assert [m["name"] for m in cell.end_to_end] == ["rows_per_s", "setup_s"]
    entry = next(c for c in b["configs"] if c["name"] == "tpch10-joins")
    assert entry["file"] == "benchmark/configs/tpch10-joins.json"
    assert entry["reduced"] == cell.config["reduced"] \
        == ["scale_factor", "queries"]
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200
    for clause in ("4.1.3", "2.4.12", "2.4.14", "Deploying Presto"):
        assert clause in entry["source"]
    mine = {m["name"] for m in cell.per_layer}
    assert set(EXPECTED) <= mine
    assert {"scan_hbm_roofline", "window.hbm_peak_share", "device.idle_share",
            "client.wall_max_ms", "pipeline.host_syncs",
            "exchange.fetch_wait_ms"} <= mine
    # the mesh's and the single node's readers find nothing here
    assert not any(n.startswith(("mesh.", "serving.", "exchange.ici_"))
                   for n in mine)
    for name in EXPECTED:
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "rows_per_s"
    for m in cell.per_layer:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
    assert [w["name"] for w in b["workloads"]
            if w["config"] == "tpch10-joins"] == [CELL]
    # the contract's limits on what this PR wrote
    mine_entry = next(w for w in b["workloads"] if w["name"] == CELL)
    assert len(mine_entry["why"]) <= 200 and len(entry["why"]) <= 200


def test_the_parents_server_refuses_the_configuration_at_once():
    """What makes the parent commit fail on this cell before any table is
    built: its WorkerServer takes no `properties`."""
    import inspect
    from presto_tpu.worker import WorkerServer
    spec = Cell(CELL).config["servers"]
    assert "properties" in spec["coordinator"] \
        and "properties" in spec["worker"]
    inspect.signature(WorkerServer.__init__).bind(
        None, coordinator=True, **spec["coordinator"])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_join_metric_arithmetic(name):
    got = metrics.layer_reader(name)(canned_run())
    assert got == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_join_reader_with_nothing_to_read_returns_nothing(name):
    """No request; a program without spans; and one whose queries carry
    none of the join's keys (every join fused into its scan chain, or the
    parent's program): nothing read, nothing raised."""
    read = metrics.layer_reader(name)
    empty = dict(canned_run(), requests=[], query_info={},
                 counters={"before": {}, "after": {}})
    assert read(empty) is None
    joinless = dict(canned_run(), counters={"before": {}, "after": {}})
    joinless["query_info"] = {
        q: {"runtimeStats": {"pipelineLaunches": stat(4, 4)}}
        for q in "abcd"}
    assert read(joinless) is None
    joinless["query_info"] = {"a": None}
    assert read(joinless) is None


def test_a_rehearsed_traced_run_of_the_cell_prints_its_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3200000021", "--seconds", "8", "--trace", "1",
         "--rehearse-sf", "0.1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert result["compared"]["answers_wrong"]["value"] == 0
    assert result["compared"]["answers_compared"]["value"] >= 2
    values = {n: m["value"] for n, m in result["metrics"].items()}
    # (at sf0.1 every join side is under the broadcast threshold and is
    # fused into its probe's scan chain: the join operators' own keys
    # have their canned cases; no peak rate of a CPU for the rooflines)
    for name in ("exchange.page_bytes", "exchange.fetch_wait_ms",
                 "client.wall_max_ms", "plan.coordinator_ms",
                 "sched.stage_wall_ms", "sched.task_start_ms",
                 "pipeline.launches", "pipeline.host_syncs",
                 "device.idle_share", "storage.hit_share"):
        assert name in values, (name, sorted(values))
    assert values["exchange.page_bytes"] > 0
    assert values["pipeline.compiles"] == 0
    assert values["pipeline.program_cache_hit_share"] == 100.0
    assert values["storage.hit_share"] == 100.0
