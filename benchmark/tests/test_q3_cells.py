"""The two cells PR 34 brought, `tpch10-q3.q3-power` and
`tpch10-cluster.scan-streams`: their entries and files, the readers the
first one brought (arithmetic on a canned QueryInfo, the nothing-to-read
case of each: the parent records none of the keys), and one rehearsed
traced run of each on the CPU that ends with a result line, no wrong
answer and a value of every reader a rehearsal can feed."""
import json
import os
import subprocess
import sys

import pytest

import metrics
from cells import BENCH, ROOT, Cell, Query, read_json

Q3 = "tpch10-q3.q3-power"
STREAMS = "tpch10-cluster.scan-streams"
MS = 1e6


def stat(total, count=1):
    return {"sum": total, "count": count, "min": 0, "max": total,
            "unit": "NANO"}


def req(qid, ok=True):
    return {"template": "tpchx/q3", "wall_s": 1.5, "ok": ok,
            "query_id": qid}


def canned_run():
    """A Q3 whose two partial and two final aggregations and three TopNs
    recorded (`a`), one a little larger (`b`), a request of a program
    without the keys (`c`), a failed one (`d`)."""
    a = {"pipelineLaunches": stat(60, 60),
         "aggUpdateWallNanos": stat(800 * MS, 4),
         "aggGroups": stat(230_000, 4), "aggTableSlots": stat(0, 4),
         "aggRestreams": stat(0, 4), "aggFinalizeWallNanos": stat(40 * MS, 4),
         "topNWallNanos": stat(300 * MS, 3), "topNRowsIn": stat(115_020, 3)}
    b = {"pipelineLaunches": stat(64, 64),
         "aggUpdateWallNanos": stat(1000 * MS, 4),
         "aggGroups": stat(250_000, 4), "aggRestreams": stat(0, 4),
         "topNWallNanos": stat(500 * MS, 3)}
    return {"requests": [req("a"), req("b"), req("c"), req("d", ok=False)],
            "window_s": 10.0,
            "query_info": {"a": {"runtimeStats": a}, "b": {"runtimeStats": b},
                           "c": {"runtimeStats": {}},
                           "d": {"runtimeStats": a}},
            "counters": {"before": {}, "after": {}},
            "queries": {"tpchx/q3": Query("tpchx/q3")},
            "resident": {}, "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": None}


EXPECTED = {
    "agg.update_ms": (800 + 1000) / 2,
    "agg.groups": (230_000 + 250_000) / 2,
    "agg.restreams": 0.0,
    "topn.wall_ms": (300 + 500) / 2,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_arithmetic_on_a_recorded_run(name):
    assert metrics.layer_reader(name)(canned_run()) \
        == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_where_the_key_is_absent(name):
    """The parent of PR 34 records none of the keys: no reading, no raise."""
    run = canned_run()
    for info in run["query_info"].values():
        info["runtimeStats"] = {"pipelineLaunches": stat(60, 60)}
    assert metrics.layer_reader(name)(run) is None
    run["query_info"] = {}
    assert metrics.layer_reader(name)(run) is None


@pytest.mark.parametrize("workload", [Q3, STREAMS])
def test_cell_loads_with_its_entries_and_files(workload):
    cell = Cell(workload)
    bench = read_json(ROOT, "BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == workload)
    assert cell.listed and cell.chips == entry["chips"] == 1
    assert len(entry["why"]) <= 200
    config = next(c for c in bench["configs"]
                  if c["name"] == entry["config"])
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert cell.config["name"] == entry["config"]
    assert len(cell.config["source"]) <= 200
    assert {m["name"] for m in cell.end_to_end} == {"rows_per_s", "setup_s"}
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for m in cell.per_layer:    # every listed reader has its file
        assert callable(metrics.layer_reader(m["name"]))


def test_q3_cell_is_what_the_issue_names():
    cell = Cell(Q3)
    assert cell.traffic["templates"] == ["tpchx/q3"]
    assert cell.traffic["clients"] == 1
    assert cell.traffic["rows_per_query"] == {"tpchx/q3": 76_500_000}
    assert cell.traffic["check"] == {"sample": 64, "control": "float32_sums"}
    assert cell.traffic["parameters"]["pool_seed"] == 25
    servers = cell.config["servers"]
    for role in ("coordinator", "worker"):
        assert servers[role]["catalogs"] == {
            "tpch": {"connector.name": "tpch"}}
    props = servers["coordinator"]["properties"]
    assert props["join-distribution-type"] == "AUTOMATIC"
    assert props["join-max-broadcast-table-size"] == "100MB"
    assert cell.config["reduced"] == ["scale_factor", "queries"]
    mine = {m["name"] for m in cell.per_layer}
    assert set(EXPECTED) <= mine
    assert {"join.build_ms", "exchange.page_bytes",
            "client.wall_max_ms"} <= mine


def test_scan_streams_is_scan_power_with_three_clients():
    streams, power = Cell(STREAMS), Cell("tpch10-cluster.scan-power")
    assert streams.config == power.config
    differing = {k for k in power.traffic
                 if power.traffic[k] != streams.traffic[k]}
    assert differing == {"why", "clients"}
    assert streams.traffic["clients"] == 3
    names = lambda cell: {m["name"] for m in cell.per_layer}  # noqa: E731
    assert names(streams) == (names(power) - {"client.wall_max_ms"}) \
        | {"client.wall_p95_ms"}


@pytest.mark.parametrize("workload,sf,absent", [
    # a rehearsal's device is the CPU: no HBM rate to hold a program to
    (Q3, 0.1, {"scan_hbm_roofline", "window.hbm_peak_share"}),
    (STREAMS, 0.01, {"scan_hbm_roofline", "window.hbm_peak_share"}),
])
def test_rehearsed_traced_run_ends_with_every_reader(workload, sf, absent):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", "3400000034", "--seconds", "4", "--trace", "1",
         "--rehearse-sf", str(sf)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["failed"] == 0 and result["attempted"] >= 1
    compared = result["compared"]
    assert compared["answers_wrong"]["value"] == 0
    assert compared["answers_compared"]["value"] >= 1
    listed = {m["name"] for m in Cell(workload).per_layer}
    assert listed - set(result["metrics"]) <= absent
    if workload == Q3:
        assert result["metrics"]["agg.restreams"]["value"] == 0
        assert result["metrics"]["agg.groups"]["value"] > 0
