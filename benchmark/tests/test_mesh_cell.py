"""The cell tpch30-mesh4.scan-power: its entries and files, the five
readers it brought (arithmetic on a canned run, the nothing-to-read case
of each: the parent of the PR that brought the mesh's spans records
none), and one rehearsed traced run on four virtual CPU devices that ends
with a result line, no wrong answer and a value of every reader that a
rehearsal can feed."""
import json
import os
import subprocess
import sys

import pytest

import metrics
from cells import BENCH, ROOT, Cell, Query

CELL = "tpch30-mesh4.scan-power"
MS = 1e6


def stat(total, count=1):
    return {"sum": total, "count": count, "min": 0, "max": total,
            "unit": "NANO"}


def req(qid, template, ok=True):
    return {"template": template, "wall_s": 0.5, "ok": ok, "query_id": qid}


def canned_run():
    """A Q6 (`a`: no hashed edge, so no ICI key) and a Q1 (`b`) of a mesh
    of four, a request of a program without spans (`c`), a failed one."""
    a = {"pipelineLaunches": stat(8, 8),
         "meshGatherWallNanos": stat(400 * MS),
         **{f"meshTaskLaunches.{i}": stat(1) for i in range(4)}}
    b = {"pipelineLaunches": stat(21, 21),
         "meshGatherWallNanos": stat(100 * MS),
         "exchangeFabricIciBytes": stat(1_736_704),
         "exchangeFabricIciDispatchWallNanos": stat(20 * MS),
         "exchangeFabricIciWaitWallNanos": stat(4 * MS, 4),
         "exchangeFabricIciDrainWallNanos": stat(300 * MS, 4),
         "meshTaskLaunches.0": stat(4, 2), "meshTaskLaunches.1": stat(3, 2),
         "meshTaskLaunches.2": stat(3, 2), "meshTaskLaunches.3": stat(3, 2)}
    return {"requests": [req("a", "tpch/q6"), req("b", "tpch/q1"),
                         req("c", "tpch/q6"), req("d", "tpch/q1", ok=False)],
            "window_s": 10.0,
            "query_info": {"a": {"runtimeStats": a}, "b": {"runtimeStats": b},
                           "c": {"runtimeStats": {}},
                           "d": {"runtimeStats": b}},
            "counters": {"before": {}, "after": {}},
            "queries": {t: Query(t) for t in ("tpch/q6", "tpch/q1")},
            # each column's bytes over all four shards, as `entries` has it
            "resident": {"lineitem.shipdate": 720e6, "lineitem.discount": 180e6,
                         "lineitem.quantity": 180e6,
                         "lineitem.extendedprice": 1440e6,
                         "lineitem.tax": 180e6, "lineitem.returnflag": 45e6,
                         "lineitem.linestatus": 45e6},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"busy_s": 2.0, "window_s": 10.0, "device_planes": 4}}


# q6 touches 2520 MB, q1 2790 MB; `c` completed and counts as a Q6
SCANNED = 2 * 2520e6 + 2790e6
EXPECTED = {
    "exchange.ici_wall_ms": (20 + 4) / 2,          # a: no hashed edge, 0
    "exchange.ici_bytes": 1_736_704 / 2,
    "mesh.gather_ms": (400 + 100) / 2,
    "mesh.launch_balance": 100.0 * 4 / 5,           # chips 1-3: 4, chip 0: 5
    "mesh.scan_hbm_roofline": 100 * (SCANNED / (4 * 819e9)) / 2.0,
}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_its_configuration_and_its_metrics_are_listed():
    b = bench()
    cell = Cell(CELL)
    assert cell.listed and cell.chips == 4
    assert cell.config["scale_factor"] == 30
    assert cell.config["servers"] == {"coordinator": {"devices": 4},
                                      "workers": 0}
    assert cell.traffic["clients"] == 1 and \
        cell.traffic["templates"] == ["tpch/q6", "tpch/q1"]
    assert [m["name"] for m in cell.end_to_end] == ["rows_per_s", "setup_s"]
    entry = next(c for c in b["configs"] if c["name"] == "tpch30-mesh4")
    assert entry["reduced"] == cell.config["reduced"] \
        == ["scale_factor", "queries"]
    assert entry["source"] == cell.config["source"]
    mine = {m["name"] for m in cell.per_layer}
    assert set(EXPECTED) <= mine and len(mine) == 18
    # one chip's peak rate: these two would read four times too high here
    assert not mine & {"scan_hbm_roofline", "window.hbm_peak_share"}
    for name in EXPECTED:
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "rows_per_s"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    # the one four-chip cell of the benchmark
    assert [w["name"] for w in b["workloads"] if w["chips"] == 4] == [CELL]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_mesh_metric_arithmetic(name):
    got = metrics.layer_reader(name)(canned_run())
    assert got == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_mesh_reader_with_nothing_to_read_returns_nothing(name):
    """No request; a program without spans; and one whose queries carry
    none of the mesh's keys (a one-device server: the parent's, too):
    nothing read, nothing raised."""
    read = metrics.layer_reader(name)
    empty = dict(canned_run(), requests=[], query_info={}, trace=None)
    assert read(empty) is None
    meshless = dict(canned_run(), trace=None)
    meshless["query_info"] = {
        q: {"runtimeStats": {"pipelineLaunches": stat(4, 4)}}
        for q in "abcd"}
    if name in ("mesh.gather_ms", "mesh.launch_balance",
                "mesh.scan_hbm_roofline"):
        assert read(meshless) is None
    else:       # an instrumented query without the key moved 0 bytes
        assert read(meshless) == 0
    meshless["query_info"] = {"a": None}
    assert read(meshless) is None


def test_a_rehearsed_traced_run_of_the_cell_prints_the_mesh_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "8", "--trace", "1",
         "--rehearse-sf", "0.01"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert result["compared"]["answers_wrong"]["value"] == 0
    assert result["compared"]["answers_compared"]["value"] >= 2
    # (no peak rate of a CPU: the roofline share has its canned case)
    for name in sorted(set(EXPECTED) - {"mesh.scan_hbm_roofline"}):
        assert name in result["metrics"], (name, sorted(result["metrics"]))
    values = {n: m["value"] for n, m in result["metrics"].items()}
    assert values["mesh.launch_balance"] == 100.0
    assert values["exchange.ici_bytes"] > 0 and values["mesh.gather_ms"] > 0
    assert values["pipeline.compiles"] == 0
    assert values["pipeline.program_cache_hit_share"] == 100.0
    assert values["storage.hit_share"] == 100.0


def test_without_four_devices_the_cell_stops_at_once():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0",
         "--rehearse-sf", "0.01"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs 4 chips" in p.stderr
