"""The two-table join path of the deployment `tpch10-joins` at test size:
TPC-H Q12 and Q14 through coordinator -> worker against the benchmark's
independent reference, the probe stream made dense (by the stream's own
live counts, never by a knob), operator statistics without a sync a
batch, the exchange's page coalescing, the planner's side choice, and
the door for Presto's properties in WorkerServer."""
import math
import os
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

from presto_tpu.exec.pipeline import ExecutionConfig, tuned_config
from presto_tpu.exec.runner import LocalQueryRunner
from presto_tpu.serving.cache import PlanCache
from presto_tpu.spi import plan as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SF = 0.1


def _count(stats, key):
    m = (stats or {}).get(key)
    return 0 if m is None else m["count"]


def _sum(stats, key):
    m = (stats or {}).get(key)
    return 0 if m is None else m["sum"]


# ---------------------------------------------------------------------------
# Q12 and Q14, coordinator -> worker, against benchmark/reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules (cells, load, check), imported as the
    harness imports them."""
    added = [p for p in (BENCH, ROOT) if p not in sys.path]
    sys.path[:0] = added
    import cells
    import check
    import load
    cell = cells.Cell("tpch10-joins.join-power")
    plan = load.Plan(cell.traffic, cell.queries, 1)
    reference = check.Reference(cell.queries, SF)
    yield cell, plan, reference
    for p in added:
        sys.path.remove(p)


@pytest.fixture(scope="module")
def cluster(bench):
    """The configuration's servers, from its Presto properties."""
    from presto_tpu.client import StatementClient
    from presto_tpu.worker import WorkerServer
    cell, _plan, _ref = bench
    spec = cell.config["servers"]
    coordinator = WorkerServer(coordinator=True, **spec["coordinator"])
    worker = WorkerServer(discovery_uri=coordinator.uri, **spec["worker"])
    deadline = time.time() + 30
    while not coordinator.worker_uris() and time.time() < deadline:
        time.sleep(0.05)
    assert coordinator.worker_uris(), "the worker never announced itself"
    yield StatementClient(coordinator.uri, schema=f"sf{SF:g}", catalog="tpch",
                          source="test", timeout_s=600.0)
    worker.close()
    coordinator.close()


@pytest.mark.parametrize("k", range(4))
@pytest.mark.parametrize("template", ["tpch/q12", "tpch/q14"])
def test_pool_tuple_equals_the_reference_row_for_row(bench, cluster,
                                                     template, k):
    _cell, plan, reference = bench
    values = plan.pool[template][k]
    got = cluster.execute(plan.statement(template, values)).rows
    want = reference.answer(template, values)
    assert [list(r) for r in got] == [list(r) for r in want]


# ---------------------------------------------------------------------------
# the probe stream: dense batches, by what the stream shows
# ---------------------------------------------------------------------------

BATCH = 1 << 12
# no fused chain: the unfused join operators and the stream coalescer
UNFUSED = dict(batch_rows=BATCH, join_out_capacity=1 << 14,
               fuse_pipelines=False, splits_per_scan=1)
PREDICATES = {"none": ("l_orderkey < 0", 0),
              "1/1024": ("l_orderkey % 1024 = 0", None),
              "1/64": ("l_orderkey % 64 = 0", None),
              "all": ("l_orderkey > 0", None)}


@pytest.fixture(scope="module")
def unfused():
    return LocalQueryRunner("sf0.01", plan_cache=PlanCache(),
                            config=ExecutionConfig(**UNFUSED))


@pytest.mark.parametrize("join", ["join", "left join", "full join"])
@pytest.mark.parametrize("selectivity", list(PREDICATES))
def test_probe_batches_follow_live_rows(unfused, join, selectivity):
    predicate, _ = PREDICATES[selectivity]
    sql = ("select l_orderkey, l_linenumber, o_custkey from "
           f"(select l_orderkey, l_linenumber from lineitem where {predicate}) l {join} orders "
           "on l_orderkey = o_orderkey")
    res = unfused.execute(sql)
    _same_rows(res, unfused.execute_reference(sql))
    stats = res.runtime_stats
    live = unfused.execute(
        f"select count(*) from lineitem where {predicate}").rows[0][0]
    scan_batches = math.ceil(60000 / BATCH)
    steps = _sum(stats, "joinProbeBatches")
    assert steps <= math.ceil(live / BATCH) + 1, (steps, live)
    assert _sum(stats, "joinProbeRowsIn") == live
    if selectivity == "all":
        # a dense probe takes no coalescing step: every scan batch goes
        # to the join as it is
        assert steps == scan_batches
        assert _count(stats, "probeCoalescedBatches") == 0
    elif live:
        assert _sum(stats, "probeCoalescedBatches") == scan_batches - steps
    assert _count(stats, "joinBuildWallNanos") >= 1
    assert _count(stats, "joinProbeWallNanos") >= 1
    assert _sum(stats, "joinBuildRows") == 15000


def _same_rows(res, oracle):
    got = sorted(map(repr, res.rows))
    want = sorted(map(repr, oracle.rows))
    assert got == want


def test_an_overflowing_probe_batch_is_split_and_counted(unfused):
    """orders probe lineitem's repeated keys: 4096 probe rows give ~16K
    pairs, over join_out_capacity, so batches split (and each piece that
    reaches the step is a probe batch)."""
    sql = ("select o_orderkey, l_linenumber from orders left join lineitem "
           "on o_orderkey = l_orderkey")
    res = unfused.execute(sql)
    _same_rows(res, unfused.execute_reference(sql))
    stats = res.runtime_stats
    assert _sum(stats, "joinProbeBatches") > math.ceil(15000 / BATCH)
    assert _sum(stats, "joinOutputRows") == 60000
    assert _sum(stats, "joinProbeRowsIn") == 15000


# ---------------------------------------------------------------------------
# operator statistics: one fetch an operator, whatever the batch count
# ---------------------------------------------------------------------------

STATS_SQL = ("select l_orderkey, o_custkey from "
             "(select l_orderkey, l_linenumber from lineitem "
             "where l_orderkey % 256 = 0) l "
             "join orders on l_orderkey = o_orderkey")


@pytest.mark.parametrize("batch_rows", [1 << 13, 1 << 12],
                         ids=["74-batches", "147-batches"])
def test_host_syncs_do_not_grow_with_the_batch_count(batch_rows):
    """A scan of 74 and of 147 batches under a join (sf0.1: a chain's
    chunks are never under 4096 rows): the operators' rows are fetched
    once each, and the whole query's host syncs stay within the few
    windows the stream coalescer looks through."""
    from presto_tpu.serving.builds import JOIN_BUILD_CACHE
    runs = {}
    for rows in (1 << 13, batch_rows):
        # both runs build their build side: a kept one costs no sync
        JOIN_BUILD_CACHE.invalidate_all()
        r = LocalQueryRunner("sf0.1", plan_cache=PlanCache(),
                             config=ExecutionConfig(**dict(
                                 UNFUSED, batch_rows=rows)))
        res = r.execute("explain analyze " + STATS_SQL)
        runs[rows] = (res.runtime_stats, res.rows[0][0])
    stats, text = runs[batch_rows]
    operators = text.count("{rows: ")
    # (the build side is materialized by one fused program, whose rows
    # come back with its own counters: every other operator fetches once)
    assert operators == 6
    assert _count(stats, "hostSync.operator_stats_rows") == operators - 1
    base = _count(runs[1 << 13][0], "hostSyncs")
    assert _count(stats, "hostSyncs") <= base + 2, (base, stats["hostSyncs"])
    assert _count(stats, "hostSyncs") < 24
    # EXPLAIN ANALYZE reads what it read when every batch was fetched
    assert "{rows: 600,000, " in text         # the scan of lineitem
    assert text.count("{rows: 2,273, ") == 4  # its filter ... the output


# ---------------------------------------------------------------------------
# the exchange: small pages become dense batches on the host
# ---------------------------------------------------------------------------

def _page(keys, flags, dictionary):
    from presto_tpu.common.block import (DictionaryBlock, FixedWidthBlock,
                                         VariableWidthBlock)
    from presto_tpu.common.page import Page
    return Page([FixedWidthBlock(np.asarray(keys, dtype=np.int64)),
                 DictionaryBlock(np.asarray(flags, dtype=np.int32),
                                 VariableWidthBlock.from_strings(dictionary))],
                len(keys))


@pytest.mark.parametrize("capacity,batches", [(8, 2), (4, 3), (16, 1)])
def test_pages_are_coalesced_into_batches_of_the_capacity(capacity, batches):
    from presto_tpu.common.types import BIGINT, VARCHAR
    from presto_tpu.exec.batch import pages_to_batches
    pages = [_page([1, 2, 3], [0, 1, 0], ["N", "A"]),
             _page([], [], ["Z"]),
             _page([4, 5], [1, 1], ["R", "A"]),
             _page([6, 7, 8, 9, 10], [0, 0, 1, 2, 2], ["A", "N", "R"])]
    out = list(pages_to_batches(pages, ["k", "f"], [BIGINT, VARCHAR],
                                capacity))
    assert len(out) == batches
    keys, flags = [], []
    for b in out:
        assert b.capacity == capacity
        live = np.asarray(b.mask)
        n = int(live.sum())
        assert live[:n].all()                 # a dense prefix
        # one union dictionary for every batch of the stream
        assert b.columns["f"].dictionary == ("A", "N", "R")
        keys += np.asarray(b.columns["k"].values)[:n].tolist()
        flags += [b.columns["f"].dictionary[c]
                  for c in np.asarray(b.columns["f"].values)[:n]]
    assert keys == list(range(1, 11))
    assert flags == ["N", "A", "N", "A", "A", "A", "A", "N", "R", "R"]


def test_an_enumerated_lazy_column_leaves_as_a_dictionary_block():
    """o_orderpriority leaves a task as int32 codes and five strings, not
    as a Python string a row."""
    from presto_tpu.common.block import DictionaryBlock
    from presto_tpu.common.types import VARCHAR
    from presto_tpu.connectors import catalog
    from presto_tpu.exec.batch import Batch, Column, batch_to_page
    ids = np.arange(64, dtype=np.int64)
    col = Column(jnp.asarray(ids), None, None,
                 ("tpch", "orders", "orderpriority", 0.01))
    page = batch_to_page(Batch({"p": col}, jnp.arange(64) % 2 == 0),
                         ["p"], [VARCHAR])
    block = page.blocks[0]
    assert isinstance(block, DictionaryBlock) and page.position_count == 32
    assert block.to_pylist() == catalog.generate_values_at(
        "orders", "orderpriority", 0.01, ids[::2], "tpch")


# ---------------------------------------------------------------------------
# the planner at the deployment's scale
# ---------------------------------------------------------------------------

def _fragments(sql, schema):
    from presto_tpu.worker.coordinator import HttpQueryRunner
    sub, _names, _types = HttpQueryRunner(
        ["http://127.0.0.1:1"], schema, n_tasks=2).plan_subplan(sql)
    out = []

    def walk(sp):
        out.append(sp.fragment)
        for c in sp.children:
            walk(c)
    walk(sub)
    return out


def _join_of(fragments):
    return next(n for f in fragments for n in P.walk_plan(f.root)
                if isinstance(n, P.JoinNode))


def test_q12_at_sf10_builds_on_orders_and_broadcasts_the_filtered_probe(
        bench):
    """The filter's estimate comes from a sample (two of its conjuncts
    compare two columns: 2,109,149 rows by the coefficient, 332,061 in
    truth), so the filtered lineitem side is seen under the broadcast
    threshold; orders, scanned on its dense primary key, builds a
    direct-address table over each task's own splits and never moves."""
    from presto_tpu.sql.fragmenter import (FragmenterConfig, estimate_bytes,
                                           estimate_rows)
    _cell, plan, _ref = bench
    sql = plan.statement("tpch/q12", plan.pool["tpch/q12"][0])
    fragments = _fragments(sql, "sf10")
    join = _join_of(fragments)
    assert isinstance(join.right, P.TableScanNode) \
        and join.right.table.table_name == "orders"
    assert isinstance(join.left, P.RemoteSourceNode)
    assert join.distribution == P.REPLICATED
    filtered = next(f.root for f in fragments
                    if isinstance(f.root, P.FilterNode))
    assert estimate_rows(filtered) > 200_000
    assert FragmenterConfig().replicates(
        estimate_bytes(filtered), estimate_bytes(join.right))
    # nothing waits for a key summary of a whole table
    scans = [n for f in fragments for n in P.walk_plan(f.root)
             if isinstance(n, P.TableScanNode)]
    assert not any(s.runtime_filters for s in scans)


def test_q14_at_sf10_moves_the_smaller_side_and_builds_on_part(bench):
    """Since the distribution is chosen by bytes (PR 34): lineitem's month
    (~758k rows of four narrow columns, ~21 MB) sent to both tasks moves
    42 MB where partitioning both sides moves it and part's 2 M rows
    (~66 MB with p_type); part, scanned on its dense primary key, builds
    over each task's own splits and never moves -- Q12's plan.  With the
    limit at 0 nothing is replicated: both sides are hash-partitioned, as
    the row-count threshold had it."""
    _cell, plan, _ref = bench
    sql = plan.statement("tpch/q14", plan.pool["tpch/q14"][0])
    fragments = _fragments(sql, "sf10")
    join = _join_of(fragments)
    assert join.distribution == P.REPLICATED
    assert isinstance(join.left, P.RemoteSourceNode)
    assert isinstance(join.right, P.TableScanNode) \
        and join.right.table.table_name == "part"
    from presto_tpu.worker.coordinator import HttpQueryRunner
    sub, _names, _types = HttpQueryRunner(
        [], schema="sf10", join_max_broadcast_table_size=0).plan_subplan(sql)
    fragments = sub.all_fragments()
    join = _join_of(fragments)
    assert join.distribution == P.PARTITIONED
    assert isinstance(join.left, P.RemoteSourceNode) \
        and isinstance(join.right, P.RemoteSourceNode)


def test_a_table_the_sample_would_hold_whole_is_not_sampled():
    from presto_tpu.sql.stats import SAMPLE_ROWS, UNKNOWN_FILTER_COEFFICIENT
    r = LocalQueryRunner("sf0.01")
    text = r.execute("explain select * from orders "
                     "where o_orderkey + 0 < 30").rows[0][0]
    assert 15000 <= SAMPLE_ROWS
    assert f"rows≈{int(15000 * UNKNOWN_FILTER_COEFFICIENT):,}" in text


# ---------------------------------------------------------------------------
# a quotient of sums that leaves int64 on the way, not at the end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sql", [
    "select -cast(123456789012 as decimal(38,6)) / cast(3 as decimal(38,4))",
    "select cast(123456789012 as decimal(38,6)) / cast(-7 as decimal(38,4))",
    "select cast(5 as decimal(38,6)) / cast(0 as decimal(38,4))",
    "select cast(1 as decimal(10,2)) / cast(3 as decimal(10,2))",
], ids=["neg-num", "neg-den", "zero", "short"])
def test_long_decimal_division_is_exact(sql):
    from decimal import Decimal
    r = LocalQueryRunner("sf0.01")
    got, want = r.execute(sql).rows, r.execute_reference(sql).rows
    assert [[None if v is None else Decimal(v) for v in row] for row in got] \
        == [[None if v is None else Decimal(v) for v in row] for row in want]


# ---------------------------------------------------------------------------
# run-length decode a chunk: one search, no gather a row
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.int64, np.int32])
def test_rle_chunk_decode_equals_the_plain_rows(dtype):
    import jax
    from presto_tpu.storage.encodings import encode_column
    rng = np.random.default_rng(7)
    for trial in range(3):
        n_runs = int(rng.integers(2, 600))
        runs = rng.integers(7, 20, size=n_runs)      # > 2x smaller as runs
        values = np.cumsum(rng.integers(-5, 50, size=n_runs)).astype(dtype)
        body = np.repeat(values, runs)
        cap = int(rng.choice([16, 128]))
        padded = np.concatenate([body, np.zeros(cap, dtype=dtype)])
        col = encode_column(jnp.asarray(padded), len(body), hint="rle")
        assert col.kind == "rle"
        decode = jax.jit(lambda p, col=col, cap=cap: col.slice_decode(p, cap))
        for pos in [0, max(0, len(body) - cap), len(body) - 1,
                    *rng.integers(0, len(body), size=4)]:
            live = min(cap, len(body) - int(pos))
            got = np.asarray(decode(jnp.int64(int(pos))))
            assert (got[:live] == padded[pos:pos + live]).all(), (trial, pos)
        assert (np.asarray(col.decode_full())[:len(body)] == body).all()


# ---------------------------------------------------------------------------
# WorkerServer(properties=...)
# ---------------------------------------------------------------------------

PROPERTIES = {"coordinator": "false", "node.environment": "test",
              "node.id": "w-7", "exchange.max-buffer-size": "16MB",
              "exchange.max-response-size": "2MB",
              "announcement-interval-ms": "250",
              "task.batch-rows": "8192"}


def test_properties_give_what_the_etc_dir_gives(tmp_path):
    from presto_tpu.worker import WorkerServer
    from presto_tpu.worker.properties import (server_kwargs_from_etc,
                                              server_kwargs_from_properties)
    config_keys = {k: v for k, v in PROPERTIES.items()
                   if not k.startswith("node.")}
    node_keys = {k: v for k, v in PROPERTIES.items() if k.startswith("node.")}
    (tmp_path / "config.properties").write_text(
        "".join(f"{k}={v}\n" for k, v in config_keys.items()))
    (tmp_path / "node.properties").write_text(
        "".join(f"{k}={v}\n" for k, v in node_keys.items()))
    from_files, props = server_kwargs_from_etc(str(tmp_path))
    assert props == PROPERTIES
    assert from_files == server_kwargs_from_properties(PROPERTIES)
    server = WorkerServer(properties=PROPERTIES)
    try:
        assert server.exec_config == from_files["config"]
        assert server.exec_config.exchange_max_buffer_bytes == 16 << 20
        assert server.exec_config.batch_rows == 8192
        # what no key names stays the server's tuned default
        assert server.exec_config.join_out_capacity \
            == tuned_config().join_out_capacity
        assert (server.node_id, server.environment, server.coordinator) \
            == ("w-7", "test", False)
    finally:
        server.close()


def test_an_explicit_argument_wins_over_a_property():
    from presto_tpu.worker import WorkerServer
    config = tuned_config(agg_slots=8192)
    server = WorkerServer(node_id="named", config=config, coordinator=True,
                          properties=PROPERTIES)
    try:
        assert server.node_id == "named" and server.coordinator
        assert server.exec_config is config
        assert server.environment == "test"      # from the properties
    finally:
        server.close()


def test_without_properties_the_server_is_what_it_was():
    from presto_tpu.worker import WorkerServer
    server = WorkerServer()
    try:
        assert server.exec_config == tuned_config()
        assert not server.coordinator
    finally:
        server.close()
