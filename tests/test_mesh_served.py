"""One served node that owns four chips (WorkerServer(coordinator=True,
devices=4)): statements run through StatementClient -> /v1/statement ->
DistributedQueryRunner over a mesh of 4 of the 8 virtual devices, every
source stage four tasks, task i pinned to device i over shard i of the
resident columns, Q1's hashed edge over the ICI all_to_all.

Held against the numpy oracle (exec/reference.py) and a `devices=1`
server, row for row and in order; the warm execution runs with implicit
device-to-device copies disallowed; the new spans and counters have to
reach QueryInfo `runtimeStats` and the profiler's timeline."""
import json
import urllib.request

import jax
import pytest

from presto_tpu.client import StatementClient
from presto_tpu.exec.runner import LocalQueryRunner
from presto_tpu.telemetry import gaps
from presto_tpu.worker.server import WorkerServer

SCHEMA = "sf0.05"
DEVICES = 4
Q6 = ("select sum(l_extendedprice * l_discount) as revenue from lineitem "
      "where l_shipdate >= date '1994-01-01' "
      "and l_shipdate < date '1995-01-01' "
      "and l_discount between 0.05 and 0.07 and l_quantity < 24")
Q1 = ("select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
      "sum(l_extendedprice) as sum_base_price, "
      "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
      "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge, "
      "avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price, "
      "avg(l_discount) as avg_disc, count(*) as count_order "
      "from lineitem where l_shipdate <= date '1998-09-02' "
      "group by l_returnflag, l_linestatus "
      "order by l_returnflag, l_linestatus")
QUERIES = {"q6": Q6, "q1": Q1}


def _query_info(server, query_id):
    with urllib.request.urlopen(
            f"{server.uri}/v1/query/{query_id}", timeout=10) as resp:
        return json.loads(resp.read())


@pytest.fixture(scope="module")
def served():
    """Every query cold and then warm on the mesh node -- the warm run
    with implicit device-to-device transfers disallowed in every thread
    -- and once on a one-device node: {name: {"cold", "warm", "single"}}
    of (rows, QueryInfo runtimeStats)."""
    runs = {name: {} for name in QUERIES}

    def execute(server, client, sql):
        res = client.execute(sql)
        return res.rows, _query_info(server, res.query_id)["runtimeStats"]

    mesh = WorkerServer(coordinator=True, devices=DEVICES)
    client = StatementClient(mesh.uri, schema=SCHEMA, catalog="tpch",
                             timeout_s=300.0)
    for name, sql in QUERIES.items():
        runs[name]["cold"] = execute(mesh, client, sql)
    # the server's threads take the process-wide setting
    jax.config.update("jax_transfer_guard_device_to_device", "disallow")
    try:
        for name, sql in QUERIES.items():
            runs[name]["warm"] = execute(mesh, client, sql)
    finally:
        jax.config.update("jax_transfer_guard_device_to_device", "allow")
    runs["prepared"] = (
        client.execute("prepare p6 from " + Q6.replace("24", "?")),
        client.execute("execute p6 using 24").rows)
    mesh.close()
    single = WorkerServer(coordinator=True)
    client = StatementClient(single.uri, schema=SCHEMA, catalog="tpch",
                             timeout_s=300.0)
    for name, sql in QUERIES.items():
        runs[name]["single"] = execute(single, client, sql)
    single.close()
    return runs


@pytest.mark.parametrize("name", sorted(QUERIES))
@pytest.mark.parametrize("run", ["cold", "warm"])
def test_mesh_rows_equal_the_oracle_and_one_device(served, name, run):
    oracle = LocalQueryRunner(SCHEMA).execute_reference(QUERIES[name])
    rows, _stats = served[name][run]
    assert rows == oracle.rows                      # in order: Q1 sorts
    assert rows == served[name]["single"][0]


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_every_chip_ran_its_task_and_the_root_gathered(served, name):
    for run in ("cold", "warm"):
        _rows, stats = served[name][run]
        launches = {k: v["sum"] for k, v in stats.items()
                    if k.startswith("meshTaskLaunches.")}
        assert sorted(launches) == [f"meshTaskLaunches.{i}"
                                    for i in range(DEVICES)]
        assert all(n > 0 for n in launches.values()), launches
        assert stats["meshGatherWallNanos"]["sum"] > 0
        # one pinned task a chip and pinned stage, its ordinal recorded
        assert stats["taskDevice"]["min"] == 0
        assert stats["taskDevice"]["max"] == DEVICES - 1
        assert stats["taskDevice"]["count"] % DEVICES == 0
        assert stats["pipelineBuildWallNanos"]["count"] >= DEVICES
    _rows, single = served[name]["single"]
    assert not any(k.startswith(("mesh", "taskDevice", "exchangeFabricIci"))
                   for k in single)


def test_q1_hashed_edge_rides_ici_and_q6_has_none(served):
    for run in ("cold", "warm"):
        q1 = served["q1"][run][1]
        assert q1["exchangeFabricIciBytes"]["sum"] > 0
        for key in ("exchangeFabricIciChunks",
                    "exchangeFabricIciDispatchWallNanos",
                    "exchangeFabricIciDrainWallNanos",
                    "exchangeFabricIciWaitWallNanos"):
            assert q1[key]["count"] >= 1, key
        assert "exchangeFabricHttpBytes" not in q1
        assert "exchangeFabricIciBytes" not in served["q6"][run][1]


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_warm_mesh_query_builds_loads_and_compiles_nothing(served, name):
    _rows, cold = served[name]["cold"]
    _rows, warm = served[name]["warm"]
    assert cold["storageShardBuilds"]["sum"] \
        == DEVICES * cold["storageBuilds"]["sum"] > 0
    assert "storageBuilds" not in warm
    # every program comes from the process-wide cache: a second device
    # is a second executable of one jit object, a warm query none at all
    assert "programCacheMisses" not in warm
    assert warm["programCacheHits"]["sum"] >= DEVICES
    assert "jaxBackendCompiles" not in warm
    assert "jaxLowerWallNanos" not in warm
    # and nothing is traced: each pinned task's shape probe is a hit
    assert "jaxTraces" not in warm
    assert warm["shapeProbeHits"]["sum"] >= DEVICES
    assert "shapeProbeMisses" not in warm


def test_prepared_statements_run_through_the_mesh(served):
    _prepare, rows = served["prepared"]
    assert rows == served["q6"]["warm"][0]


def test_mesh_gather_is_on_the_profilers_timeline(tmp_path):
    from presto_tpu.exec.runner import DistributedQueryRunner
    from presto_tpu.parallel.mesh import make_mesh
    r = DistributedQueryRunner(SCHEMA, n_tasks=DEVICES,
                               mesh=make_mesh(DEVICES))
    r.execute(Q6)                                  # warm: capture a run
    jax.profiler.start_trace(str(tmp_path))
    try:
        stats = r.execute(Q6).runtime_stats
    finally:
        jax.profiler.stop_trace()
    _ops, _programs, spans = gaps.load(str(tmp_path))
    names = {s[3] for s in spans}
    assert {"meshGather", "pipelineBuild", "queryExecute"} <= names, names
    assert ("meshGather", "queryExecute") in set(gaps.nesting(spans))
    assert stats["meshGatherWallNanos"]["count"] == 1


def test_one_device_is_todays_runner_and_devices_come_from_one_argument(
        tmp_path):
    from presto_tpu.exec.runner import DistributedQueryRunner
    from presto_tpu.worker.properties import server_kwargs_from_etc
    one = WorkerServer(coordinator=True)
    four = WorkerServer(coordinator=True, devices=DEVICES)
    try:
        assert one.devices == 1
        runner, uris = one._runner_for(SCHEMA, "tpch", {})
        assert type(runner) is LocalQueryRunner and not uris
        runner, uris = four._runner_for(SCHEMA, "tpch", {})
        assert type(runner) is DistributedQueryRunner and not uris
        assert runner.n_tasks == DEVICES
        assert list(runner.mesh.devices.flat) == jax.devices()[:DEVICES]
    finally:
        one.close()
        four.close()
    (tmp_path / "config.properties").write_text("coordinator=true\n")
    (tmp_path / "node.properties").write_text("node.devices=4\n")
    kwargs, _props = server_kwargs_from_etc(str(tmp_path))
    assert kwargs["devices"] == 4 and kwargs["coordinator"] is True
    (tmp_path / "node.properties").write_text("node.devices=0\n")
    with pytest.raises(ValueError, match="node.devices"):
        server_kwargs_from_etc(str(tmp_path))
