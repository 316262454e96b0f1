"""The path of the deployment `tpch10-q3` at test size: TPC-H Q3 through
coordinator -> worker against the benchmark's independent reference, the
join distribution chosen by bytes, a filtered dense key addressed
directly, the aggregation of a stream (one pass, the table grown in
place, the source never executed again), TopN without a sort, the
block-local lookup, and the door for Presto's catalog properties in
WorkerServer."""
import os
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

from presto_tpu.exec import operators as ops
from presto_tpu.exec.batch import Batch, Column
from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner
from presto_tpu.spi import plan as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
DATES = ("1995-03-01", "1995-03-15", "1995-03-31")
CELL = "tpch10-q3.q3-power"


def _sum(stats, key):
    m = (stats or {}).get(key)
    return 0 if m is None else m["sum"]


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules (cells, load, check, sampler), imported
    as the harness imports them."""
    added = [p for p in (BENCH, ROOT) if p not in sys.path]
    sys.path[:0] = added
    import cells
    import check
    import load
    import sampler
    cell = cells.Cell(CELL)
    plan = load.Plan(cell.traffic, cell.queries, 1)
    yield cell, plan, check, sampler
    for p in added:
        sys.path.remove(p)


@pytest.fixture(scope="module")
def references(bench):
    cell, _plan, check, _sampler = bench
    made = {}

    def at(sf):
        if sf not in made:
            made[sf] = check.Reference(cell.queries, sf)
        return made[sf]
    return at


@pytest.fixture(scope="module")
def cluster(bench):
    """The configuration's servers, from its Presto properties and its
    catalog file's keys; one client a scale factor."""
    from presto_tpu.client import StatementClient
    from presto_tpu.worker import WorkerServer
    cell = bench[0]
    spec = cell.config["servers"]
    coordinator = WorkerServer(coordinator=True, **spec["coordinator"])
    worker = WorkerServer(discovery_uri=coordinator.uri, **spec["worker"])
    deadline = time.time() + 30
    while not coordinator.worker_uris() and time.time() < deadline:
        time.sleep(0.05)
    assert coordinator.worker_uris(), "the worker never announced itself"

    def client(sf):
        return StatementClient(coordinator.uri, schema=f"sf{sf:g}",
                               catalog="tpch", source="test",
                               timeout_s=600.0)
    yield coordinator, client
    worker.close()
    coordinator.close()


def _query_info(coordinator, query_id):
    import collect
    return collect.query_info(coordinator.uri, query_id)


# ---------------------------------------------------------------------------
# Q3, coordinator -> worker, against benchmark/reference/tpchx/q3.py
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("date", DATES)
@pytest.mark.parametrize("segment", SEGMENTS)
@pytest.mark.parametrize("sf", [0.01, 0.1])
def test_q3_equals_the_reference_row_for_row(bench, references, cluster,
                                             sf, segment, date):
    cell, _plan, _check, sampler = bench
    _coordinator, client = cluster
    values = {"SEGMENT": segment, "DATE": date}
    query = cell.queries["tpchx/q3"]
    got = client(sf).execute(sampler.inline(query, values)).rows
    want = references(sf).answer("tpchx/q3", values)
    assert len(want) == 10
    assert [list(r) for r in got] == [list(r) for r in want]


def test_q3_runtime_stats_name_the_aggregation_the_topn_and_the_choice(
        bench, cluster):
    """One Q3's QueryInfo carries the new spans and counters, and the
    aggregation never executed its source again."""
    cell, plan, _check, sampler = bench
    coordinator, client = cluster
    values = plan.pool["tpchx/q3"][0]
    result = client(0.1).execute(
        sampler.inline(cell.queries["tpchx/q3"], values))
    stats = _query_info(coordinator, result.query_id)["runtimeStats"]
    for key in ("aggUpdateWallNanos", "aggFinalizeWallNanos", "aggGroups",
                "aggTableSlots", "aggRestreams", "topNWallNanos",
                "topNRowsIn", "joinBuildBroadcastBytes", "joinsReplicated",
                "joinsPartitioned", "joinBuildWallNanos"):
        assert key in stats, key
    assert _sum(stats, "aggRestreams") == 0
    assert _sum(stats, "aggGroups") > 0
    assert _sum(stats, "topNRowsIn") >= 10
    # both joins' build sides are a few megabytes: replicated
    assert _sum(stats, "joinsReplicated") == 2
    assert _sum(stats, "joinsPartitioned") == 0
    assert 0 < _sum(stats, "joinBuildBroadcastBytes") < 100 << 20


@pytest.mark.parametrize("k", range(4))
def test_float32_sums_control_differs_for_every_pool_tuple(bench, references,
                                                           k):
    _cell, plan, _check, _sampler = bench
    values = plan.pool["tpchx/q3"][k]
    reference = references(0.1)
    exact = reference.answer("tpchx/q3", values)
    control = reference.answer("tpchx/q3", values, "float32_sums")
    assert len(exact) == 10 and exact != control


def test_pool_tuples_are_in_the_spec_domains(bench):
    cell, plan, _check, sampler = bench
    query = cell.queries["tpchx/q3"]
    for values in plan.pool["tpchx/q3"]:
        assert sampler.in_domain(query.parameters, values)
        assert values["SEGMENT"] in SEGMENTS
        assert "1995-03-01" <= values["DATE"] <= "1995-03-31"
    assert query.slots == ["SEGMENT", "DATE", "DATE"]


def test_reference_refuses_a_tie_among_the_first_rows(bench):
    """Two orders with equal revenue and order date among the first ten
    leave their order to the system: the reference raises."""
    sys.path[:0] = [BENCH]
    try:
        from reference.tpchx import q3
    finally:
        sys.path.remove(BENCH)
    n = 12
    tables = {
        "customer": {"custkey": np.arange(1, 3), "mktsegment": np.array([1, 1])},
        "orders": {"orderkey": np.arange(1, n + 1),
                   "custkey": np.ones(n, np.int64),
                   "orderdate": np.full(n, 9000),
                   "shippriority": np.zeros(n, np.int64)},
        "lineitem": {"orderkey": np.arange(1, n + 1),
                     "extendedprice": np.arange(n, 0, -1) * 1000,
                     "discount": np.zeros(n, np.int64),
                     "shipdate": np.full(n, 9999)}}
    params = {"SEGMENT": "BUILDING", "DATE": "1995-03-15"}
    assert len(q3.answer(tables, params, {})) == 10
    tables["lineitem"]["extendedprice"][3] = \
        tables["lineitem"]["extendedprice"][4]
    with pytest.raises(ValueError, match="tie"):
        q3.answer(tables, params, {})


# ---------------------------------------------------------------------------
# the reference's own data against the engine's generator
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("table,column,ref_column", [
    ("customer", "custkey", "custkey"),
    ("customer", "mktsegment", "mktsegment"),
    ("orders", "custkey", "custkey"),
    ("orders", "orderdate", "orderdate"),
    ("orders", "shippriority", "shippriority"),
    ("orders", "orderkey", "orderkey"),
])
def test_tpchx_data_equals_device_gen(bench, table, column, ref_column):
    from presto_tpu.connectors import device_gen
    from presto_tpu.connectors import tpch as H
    from reference import tpchx_data
    sf = 0.01
    n = H._table_rows(table, sf)
    assert tpchx_data.table_rows(table, sf) == n
    idx = jnp.arange(n, dtype=jnp.int64)
    fn = {"customer": device_gen._tpch_customer,
          "orders": device_gen._tpch_orders}[table]
    device = np.asarray(fn(column, idx, sf)).astype(np.int64)
    mine = tpchx_data.table(table, [ref_column], sf)[ref_column]
    assert np.array_equal(device, mine.astype(np.int64))
    if column == "mktsegment":
        assert tuple(H.SEGMENTS) == tpchx_data.SEGMENTS


# ---------------------------------------------------------------------------
# the distribution choice: bytes against join-max-broadcast-table-size
# ---------------------------------------------------------------------------

Q3_TEXT = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10"""


def _joins(sql, schema, **kw):
    from presto_tpu.worker.coordinator import HttpQueryRunner
    sub, _names, _types = HttpQueryRunner([], schema=schema, **kw) \
        .plan_subplan(sql)
    return [n for f in sub.all_fragments() for n in P.walk_plan(f.root)
            if isinstance(n, P.JoinNode)]


@pytest.mark.parametrize("limit,expected", [
    (100 << 20, P.REPLICATED),      # ~30 MB of orders x customer: under
    (16 << 20, P.PARTITIONED),      # over a 16MB limit
    (0, P.PARTITIONED),
])
def test_build_side_is_replicated_by_bytes_at_sf10(limit, expected):
    """Q3 at SF10: the filtered orders x customer side is ~1.5 M rows of
    three narrow columns, over any sensible row count and far under
    Presto's 100MB; lineitem's 30 M filtered rows stay where they are
    scanned only where it is replicated."""
    joins = _joins(Q3_TEXT, "sf10", join_max_broadcast_table_size=limit)
    top = next(j for j in joins
               if {l.name.split("_")[1] for l, _r in j.criteria}
               == {"orderkey"})
    assert top.distribution == expected
    assert 10 << 20 < top.planned_build_bytes < 100 << 20
    assert top.planned_build_rows > 600_000


@pytest.mark.parametrize("kind,expected", [
    ("BROADCAST", P.REPLICATED), ("PARTITIONED", P.PARTITIONED),
    ("automatic", P.REPLICATED)])
def test_join_distribution_type_property(kind, expected):
    joins = _joins(Q3_TEXT, "sf10", join_distribution_type=kind,
                   join_max_broadcast_table_size=100 << 20)
    assert {j.distribution for j in joins} == {expected}


def test_join_distribution_type_refuses_an_unknown_value():
    from presto_tpu.sql.fragmenter import FragmenterConfig
    with pytest.raises(ValueError, match="join-distribution-type"):
        FragmenterConfig(join_distribution_type="SOMETIMES")


def test_replication_weighs_the_bytes_it_moves():
    """AUTOMATIC replicates a side that fits the limit only where sending
    it to every task moves no more than partitioning both sides."""
    from presto_tpu.sql.fragmenter import FragmenterConfig
    cfg = FragmenterConfig(n_tasks=2)
    assert cfg.replicates(30e6, 800e6)
    assert not cfg.replicates(66e6, 21e6)       # Q14's part against lineitem
    assert cfg.replicates(21e6, 66e6)           # ... and the other way round
    assert not cfg.replicates(345e6, 9e6)       # Q12's orders: over 100MB
    assert not cfg.replicates(None, 1.0)
    assert FragmenterConfig(n_tasks=4).replicates(10e6, 30e6)
    assert not FragmenterConfig(n_tasks=4).replicates(10e6, 29e6)


def test_the_properties_reach_the_fragmenter():
    from presto_tpu.worker.properties import server_kwargs_from_properties
    kwargs = server_kwargs_from_properties({
        "join-distribution-type": "partitioned",
        "join-max-broadcast-table-size": "16MB"})
    assert kwargs["join_distribution_type"] == "PARTITIONED"
    assert kwargs["join_max_broadcast_table_size"] == 16 << 20


def test_type_bytes_are_what_a_page_holds():
    from presto_tpu.common.types import (BIGINT, DATE, DOUBLE, INTEGER,
                                         DecimalType, VarcharType)
    from presto_tpu.sql.fragmenter import type_bytes
    assert [type_bytes(t) for t in (BIGINT, INTEGER, DATE, DOUBLE)] \
        == [8, 4, 4, 8]
    assert type_bytes(DecimalType(12, 2)) == 8
    assert type_bytes(DecimalType(38, 4)) == 16
    assert type_bytes(VarcharType(25)) == 25
    assert type_bytes(VarcharType()) == 32


# ---------------------------------------------------------------------------
# a filtered scan of a dense key builds a direct-address table
# ---------------------------------------------------------------------------

def test_filtered_dense_key_builds_a_direct_table():
    """One key in ten of a dense span (Q3's orders side at SF10 keeps
    1.46 M of 15 M): direct, where the span ratio of 8 declined it."""
    from presto_tpu.exec.fused import DirectTable, try_direct_table
    n, span = 4096, 40960
    keys = np.sort(np.random.default_rng(3).choice(span, n, replace=False))
    batch = Batch({"k": Column(jnp.asarray(keys + 7, dtype=jnp.int64)),
                   "v": Column(jnp.arange(n, dtype=jnp.int32))},
                  jnp.ones(n, dtype=bool))
    dt = try_direct_table(batch, "k", allow_dup=False)
    assert isinstance(dt, DirectTable)
    probe = Batch({"k": Column(jnp.asarray(
        [keys[0] + 7, keys[5] + 7, 3, span + 100], dtype=jnp.int64))},
        jnp.ones(4, dtype=bool))
    hit, row = ops.direct_lookup(probe, dt, "k")
    assert hit.tolist() == [True, True, False, False]
    assert row.tolist()[:2] == [0, 5]
    # a key in 200 of its span is sparse: the hash table keeps it
    sparse = Batch({"k": Column(jnp.asarray(keys * 20, dtype=jnp.int64))},
                   jnp.ones(n, dtype=bool))
    assert try_direct_table(sparse, "k", allow_dup=False) is None


# ---------------------------------------------------------------------------
# one aggregation-sizing rule: a stream is read once
# ---------------------------------------------------------------------------

GROUPS_SQL = """
select o_custkey, o_orderdate, count(*) as n, sum(o_totalprice) as total
from orders, customer
where o_custkey = c_custkey and c_nationkey >= 0
group by o_custkey, o_orderdate"""


def _unfused(**over):
    return ExecutionConfig(**{"batch_rows": 1 << 12, "fuse_pipelines": False,
                              **over})


@pytest.mark.parametrize("route,over", [
    ("sort", {}),
    ("hash", {"agg_slots": 256}),
])
def test_more_groups_than_the_first_table_holds(monkeypatch, route, over):
    """15,000 groups above a join against a first table of 256 slots (or
    none: the sort): the answer is whole, the join ran once, and the hash
    table grew in place."""
    import presto_tpu.exec.pipeline as pipeline
    if route == "hash":     # an input longer than the sort holds
        monkeypatch.setattr(pipeline, "SORT_STREAM_MAX_ROWS", 1 << 12)
    runner = LocalQueryRunner("sf0.01", config=_unfused(**over))
    result = runner.execute("explain analyze " + GROUPS_SQL)
    stats = result.runtime_stats
    assert _sum(stats, "aggRestreams") == 0
    assert _sum(stats, "aggGroups") > 14000     # of 15,000 orders
    if route == "hash":
        assert _sum(stats, "aggTableGrowths") >= 1
        assert _sum(stats, "aggTableSlots") >= 2 * 14000
    else:
        assert _sum(stats, "aggTableSlots") == 0
    got = runner.execute(GROUPS_SQL)
    want = runner.execute_reference(GROUPS_SQL)
    from presto_tpu.exec.runner import _assert_rows_equal
    _assert_rows_equal(got, want, ordered=False)


def test_no_retry_count_is_left_to_limit_the_answer():
    """`max_agg_retries` went with the loops it bounded: a table grows
    for as long as the memory pool lets it."""
    assert not hasattr(ExecutionConfig(), "max_agg_retries")
    import presto_tpu.exec.pipeline as pipeline
    import inspect
    assert "retries exhausted" not in inspect.getsource(pipeline)


def test_hash_aggregate_grows_in_place_from_a_table_of_eight_slots():
    """The loop itself: 3,000 distinct keys through a first table of 8
    slots, the window doubling; every key ends in the table once with
    its sum, and no batch is asked of the source twice."""
    from presto_tpu.exec.pipeline import hash_aggregate
    specs = (ops.AggSpec("sum", "total", False, None),)
    names = ("k",)
    rng = np.random.default_rng(5)
    keys = rng.permutation(np.repeat(np.arange(3000), 2))
    served = []

    def batches():
        for i in range(0, len(keys), 500):
            served.append(i)
            k = jnp.asarray(keys[i:i + 500], dtype=jnp.int64)
            yield Batch({"k": Column(k), "v": Column(k * 10)},
                        jnp.ones(500, dtype=bool))

    def update(n):
        return lambda st, b: ops.agg_update(
            st, b, [b.columns["k"]], {"total": b.columns["v"]}, specs, n, 0,
            names)

    def grow(_old, n):
        return lambda st: ops.agg_merge(
            ops.agg_init(n, specs, names, [jnp.int64]), st, specs, names, n)
    state, slots = hash_aggregate(
        ops.agg_init(8, specs, names, [jnp.int64]), [], 8, batches(),
        update, grow)
    assert slots >= 4096 and served == list(range(0, 6000, 500))
    out = ops.agg_finalize(state, specs, names, {}, {})
    live = np.asarray(out.mask)
    got = dict(zip(np.asarray(out.columns["k"].values)[live].tolist(),
                   np.asarray(out.columns["total"].values)[live].tolist()))
    assert got == {k: 20 * k for k in range(3000)}


# ---------------------------------------------------------------------------
# TopN picks its rows; a sort would give the same ones
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("keys", [
    [("a", "DESC_NULLS_FIRST"), ("f", "ASC_NULLS_LAST")],
    [("f", "DESC_NULLS_LAST"), ("a", "ASC_NULLS_FIRST"),
     ("d", "DESC_NULLS_LAST")],
    [("d", "ASC_NULLS_LAST")],
])
def test_topn_selection_equals_the_sort(keys):
    rng = np.random.default_rng(0)
    n = 5000
    batch = Batch(
        {"a": Column(jnp.asarray(rng.integers(0, 50, n)),
                     jnp.asarray(rng.random(n) < 0.1)),
         "f": Column(jnp.asarray(np.where(rng.random(n) < 0.05, np.nan,
                                          rng.random(n).round(1)))),
         "d": Column(jnp.asarray(rng.integers(0, 3, n), dtype=jnp.int32))},
        jnp.asarray(rng.random(n) < 0.8))
    want = ops.sort_indices(batch, keys)[:10]
    picked = ops.topn(batch, keys, 10)
    for name in batch.columns:
        assert np.array_equal(np.asarray(picked.columns[name].values),
                              np.asarray(batch.columns[name].values[want]),
                              equal_nan=True)
    # past the selection's limit the sort takes over, same rows
    many = ops.topn(batch, keys, ops.TOPN_SELECT_MAX + 1)
    assert np.array_equal(
        np.asarray(many.columns["d"].values),
        np.asarray(batch.columns["d"].values[
            ops.sort_indices(batch, keys)[:ops.TOPN_SELECT_MAX + 1]]))


# ---------------------------------------------------------------------------
# WorkerServer(catalogs=...): etc/catalog/<name>.properties as a dict
# ---------------------------------------------------------------------------

def test_worker_server_mounts_catalogs_and_refuses_an_unknown_connector():
    from presto_tpu.connectors import catalog as registry
    from presto_tpu.worker import WorkerServer
    server = WorkerServer(coordinator=True, catalogs={
        "tpch": {"connector.name": "tpch"},
        "scratch_q3": {"connector.name": "memory"}})
    try:
        assert "scratch_q3" in registry._CONNECTORS
    finally:
        server.close()
        registry.unregister_connector("scratch_q3")
    with pytest.raises(ValueError, match="unknown connector.name"):
        WorkerServer(coordinator=True,
                     catalogs={"lake": {"connector.name": "iceberg9"}})


def test_catalog_files_and_the_dict_mount_through_one_function(tmp_path):
    from presto_tpu.worker.properties import (register_catalogs,
                                              register_catalogs_from_etc)
    (tmp_path / "catalog").mkdir()
    (tmp_path / "catalog" / "tpch.properties").write_text(
        "connector.name=tpch\n")
    assert register_catalogs_from_etc(str(tmp_path)) == {"tpch": "tpch"}
    assert register_catalogs({"tpch": {"connector.name": "tpch"}}) \
        == {"tpch": "tpch"}
    (tmp_path / "catalog" / "bad.properties").write_text(
        "connector.name=nosuch\n")
    with pytest.raises(ValueError, match="unknown connector.name"):
        register_catalogs_from_etc(str(tmp_path))


# ---------------------------------------------------------------------------
# a chain with a join: cut at the lookup, dense, finished a batch at a time
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("analyze", [False, True],
                         ids=["plain", "with_operator_statistics"])
def test_chain_with_a_join_is_cut_and_made_dense(analyze):
    """Q3 over ten 64K-row chunks of lineitem in one task: the chain
    stops at its join's lookup in both passes, the write pass reuses what
    the count pass found, the build columns and the projection are
    computed for the dense batches -- also where operator statistics are
    collected, as every task of coordinator -> worker collects them."""
    from presto_tpu.serving import FRAGMENT_JIT_CACHE
    from presto_tpu.telemetry import jax_events
    jax_events.install()
    FRAGMENT_JIT_CACHE.invalidate_all()
    runner = LocalQueryRunner(
        "sf0.1", config=ExecutionConfig(batch_rows=1 << 16))
    before = jax_events.PROGRAMS.snapshot()
    if analyze:
        result = runner.execute("explain analyze " + Q3_TEXT)
        text = result.rows[0][0]
        # the join's own count, and the steps above it read it
        assert "rows: 3,203" in text
    result = runner.execute("explain analyze " + Q3_TEXT) if analyze \
        else runner.assert_same_as_reference(Q3_TEXT)
    traced = {n for n, row in jax_events.PROGRAMS.snapshot().items()
              if row["traces"] > before.get(n, {}).get("traces", 0)}
    assert {"chain_dense_counts", "chain_dense_write",
            "chain_dense_finish", "agg_sort"} <= traced
    assert "scan_agg_runtime_span" not in traced
    if analyze:
        stats = result.runtime_stats
        assert _sum(stats, "denseStreamChunks") >= 10
        assert _sum(stats, "aggRestreams") == 0
        # lineitem's sorted orderkeys read the orders table block by block
        assert _sum(stats, "chainLookupBlockedChunks") > 0
    runner.assert_same_as_reference(Q3_TEXT)
