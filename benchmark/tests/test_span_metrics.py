"""The ten readers of the program's own spans and counters
(layer_metrics/ over span_stats.py): arithmetic on a canned QueryInfo,
the nothing-to-read case of each, that every entry has its file, and one
rehearsed run of each cell that prints every one of them."""
import json
import os
import subprocess
import sys

import pytest

import metrics
from cells import BENCH, ROOT

MS = 1e6


def stat(total, count=1, highest=None):
    return {"sum": total, "count": count, "min": 0,
            "max": total if highest is None else highest, "unit": "NANO"}


def req(qid, ok=True):
    return {"template": "tpch/q6", "wall_s": 1.0, "ok": ok, "query_id": qid}


def canned_run():
    """Three completed requests: `a` and `b` instrumented (b compiled
    nothing and waited for no page), `c` from a program without spans;
    `d` failed."""
    a = {"pipelineLaunches": stat(12, 12), "pipelineDispatchWallNanos": stat(9 * MS, 12),
         "queryParseWallNanos": stat(1 * MS), "queryPlanWallNanos": stat(2 * MS),
         "queryOptimizeWallNanos": stat(3 * MS), "queryFragmentWallNanos": stat(4 * MS, 2),
         "schedCreateTasksWallNanos": stat(30 * MS, 3),
         "taskQueuedWallNanos": stat(9 * MS, 3, highest=6 * MS),
         "pipelineBuildWallNanos": stat(8 * MS, 3),
         "jaxBackendCompileWallNanos": stat(700 * MS, 20), "jaxTraces": stat(90, 90),
         "hostSyncs": stat(7, 7), "hostSyncWaitWallNanos": stat(1500 * MS, 7),
         "exchangeClientWaitWallNanos": stat(400 * MS, 2),
         "servingBatchWaitWallNanos": stat(3 * MS),
         "compilerCheckoutWaitWallNanos": stat(1 * MS)}
    b = {"pipelineLaunches": stat(4, 4),
         "queryParseWallNanos": stat(1 * MS),
         "schedCreateTasksWallNanos": stat(10 * MS, 3),
         "taskQueuedWallNanos": stat(5 * MS, 3, highest=2 * MS),
         "pipelineBuildWallNanos": stat(2 * MS, 3),
         "hostSyncs": stat(3, 3), "hostSyncWaitWallNanos": stat(500 * MS, 3),
         "servingBatchWaitWallNanos": stat(5 * MS)}
    return {"requests": [req("a"), req("b"), req("c"), req("d", ok=False)],
            "window_s": 10.0,
            "query_info": {"a": {"runtimeStats": a}, "b": {"runtimeStats": b},
                           "c": {"runtimeStats": {"queryParseWallNanos": stat(9 * MS)}},
                           "d": {"runtimeStats": a}},
            "counters": {"before": {}, "after": {}}, "trace": None}


# per-layer metric -> its reading on the canned run (means over a and b)
EXPECTED = {
    "plan.coordinator_ms": (10 + 1) / 2,
    "sched.task_start_ms": (30 + 10) / 2 + (6 + 2) / 2,
    "pipeline.build_ms": (8 + 2) / 2,
    "pipeline.executable_load_ms": 700 / 2,      # b: nothing compiled, 0
    "pipeline.programs_traced": 90 / 2,
    "pipeline.launches": (12 + 4) / 2,
    "pipeline.host_syncs": (7 + 3) / 2,
    "pipeline.host_sync_ms": (1500 + 500) / 2,
    "exchange.fetch_wait_ms": 400 / 2,
    "serving.batch_wait_ms": (3 + 1 + 5) / 2,
}


def entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


def test_every_new_entry_has_its_file_and_its_canned_case():
    listed = entries()
    for name in EXPECTED:
        assert name in listed, name
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
        m = listed[name]
        assert m["moves"] == "rows_per_s" and m["better"] == "lower"
        assert m["source"] in ("program_span", "program_counter")
        assert m["workloads"]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_metric_arithmetic(name):
    got = metrics.layer_reader(name)(canned_run())
    assert got == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_span_reader_with_nothing_to_read_returns_nothing(name):
    """No request, and a program that records no spans (the parent of
    the PR that brought them): the reader finds nothing, raises nothing."""
    read = metrics.layer_reader(name)
    empty = {"requests": [], "window_s": 10.0, "query_info": {},
             "counters": {"before": {}, "after": {}}, "trace": None}
    assert read(empty) is None
    unspanned = canned_run()
    for info in unspanned["query_info"].values():
        info["runtimeStats"].pop("pipelineLaunches", None)
    assert read(unspanned) is None
    unspanned["query_info"] = {"a": None}
    assert read(unspanned) is None


@pytest.mark.parametrize("cell", ["tpch10-cluster.scan-power",
                                  "tpch10-single.dash-8c"])
def test_a_rehearsed_traced_run_prints_every_new_metric(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "20", "--trace", "1",
         "--rehearse-sf", "0.01"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    mine = [n for n, m in entries().items()
            if n in EXPECTED and cell in m["workloads"]]
    assert mine
    for name in mine:
        assert name in result["metrics"], (name, sorted(result["metrics"]))
        assert result["metrics"][name]["value"] >= 0
