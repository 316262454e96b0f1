"""Fused join-chain execution (exec/fused.py): chain assembly, fanout
expansion, span aggregation, and NULL join-key semantics — each checked
differentially against the numpy oracle on BOTH the fused path and the
streaming fallback (fuse_pipelines=False), so the two executors cannot
drift apart (the round-1 review's NULL=NULL divergence class).

Reference fixture: exec/reference.py _exec_JoinNode (NULL keys never
match, presto-main-base LookupJoinOperator semantics).
"""
import numpy as np
import pytest

from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner

from test_queries import TPCH_Q6 as Q6


def runner_pair():
    fused = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        batch_rows=1 << 14, join_out_capacity=1 << 16))
    streaming = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        batch_rows=1 << 14, join_out_capacity=1 << 16,
        fuse_pipelines=False))
    return fused, streaming


FANOUT1_JOIN_AGG = """
SELECT o.orderpriority, count(*) AS c, sum(l.extendedprice) AS s
FROM lineitem l JOIN orders o ON l.orderkey = o.orderkey
WHERE o.orderdate < DATE '1995-06-01'
GROUP BY o.orderpriority
"""

EXPANSION_JOIN = """
SELECT c.mktsegment, count(*) AS c
FROM customer c JOIN orders o ON c.custkey = o.custkey
GROUP BY c.mktsegment
"""

SPAN_AGG = """
SELECT l.orderkey, sum(l.quantity) AS q, count(*) AS c
FROM lineitem l
GROUP BY l.orderkey
"""

LEFT_JOIN_FILTER = """
SELECT c.custkey, count(o.orderkey) AS c
FROM customer c LEFT JOIN orders o
  ON c.custkey = o.custkey AND o.totalprice > 100000
GROUP BY c.custkey
"""

NULL_KEY_JOIN = """
SELECT count(*) AS c
FROM (SELECT CASE WHEN custkey % 3 = 0 THEN NULL ELSE custkey END AS k
      FROM orders) o
JOIN customer c ON o.k = c.custkey
"""

NULL_KEY_LEFT = """
SELECT count(*) AS total, count(c.name) AS matched
FROM (SELECT CASE WHEN custkey % 3 = 0 THEN NULL ELSE custkey END AS k
      FROM orders) o
LEFT JOIN customer c ON o.k = c.custkey
"""

SEMI_NULL = """
SELECT count(*) AS c
FROM (SELECT CASE WHEN custkey % 3 = 0 THEN NULL ELSE custkey END AS k
      FROM orders) o
WHERE o.k IN (SELECT custkey FROM customer WHERE nationkey < 10)
"""


@pytest.mark.parametrize("name,sql", [
    ("fanout1_join_agg", FANOUT1_JOIN_AGG),
    ("expansion_join", EXPANSION_JOIN),
    ("span_agg", SPAN_AGG),
    ("left_join_filter", LEFT_JOIN_FILTER),
    ("null_key_join", NULL_KEY_JOIN),
    ("null_key_left", NULL_KEY_LEFT),
    ("semi_null", SEMI_NULL),
])
def test_fused_vs_streaming_vs_oracle(name, sql):
    fused, streaming = runner_pair()
    fused.assert_same_as_reference(sql)
    streaming.assert_same_as_reference(sql)


def test_chain_assembles_for_join_query():
    """The fused path must actually engage for the canonical join+agg
    shape (guards against silent universal fallback)."""
    from presto_tpu.exec import fused as F
    engaged = {"n": 0}
    orig = F.FusedChain.prep

    def spy(self):
        r = orig(self)
        if r is not None:
            engaged["n"] += 1
        return r
    F.FusedChain.prep = spy
    try:
        # isolated plan cache: the process-global one may hold a warm
        # compiler for this exact shape (prep legitimately skipped)
        from presto_tpu.serving import PlanCache
        r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
            batch_rows=1 << 14, join_out_capacity=1 << 16),
            plan_cache=PlanCache())
        r.assert_same_as_reference(FANOUT1_JOIN_AGG)
    finally:
        F.FusedChain.prep = orig
    assert engaged["n"] >= 1, "fused chain never engaged on join+agg query"


# ---------------------------------------------------------------------------
# the one scan path under the DEFAULT config (what a served query runs
# under): each shape against the numpy oracle.  TPC-H money columns are
# unscaled int64 decimals, so sums and averages are exact, not close.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def default_runner():
    return LocalQueryRunner("sf0.01")


Q1 = """
    select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
           sum(l_extendedprice) as sum_base_price,
           sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
           avg(l_quantity) as avg_qty, min(l_quantity) as min_qty,
           max(l_extendedprice) as max_price, count(*) as count_order
    from lineitem where l_shipdate <= date '1998-09-02'
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus
"""

Q3_SHAPE = """
    select o_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           count(*) as cnt
    from lineitem, orders
    where l_orderkey = o_orderkey
      and o_orderdate < date '1995-03-15'
      and l_shipdate > date '1995-03-15'
    group by o_orderkey
"""

Q18_SHAPE = """
    select l_orderkey, max(o_totalprice) as price, sum(l_quantity) as qty
    from lineitem, orders
    where l_orderkey = o_orderkey
    group by l_orderkey
"""

# 3*2*7*4 = 168 groups of closed dictionary domains: over the one-hot
# grid (G <= 64), inside the static span
SPAN_4KEYS = (
    "select l_returnflag, l_linestatus, l_shipmode, l_shipinstruct, "
    "sum(l_quantity), avg(l_discount), count(*) from lineitem "
    "group by 1, 2, 3, 4")

# one open-domain integer key: the anchored (runtime) span
ORDERKEY_COUNT = "select l_orderkey, count(*) from lineitem group by l_orderkey"

# a computed key (nullable, no anchor): sort-based grouping, or the
# scatter hash table once the stacked chain output is over the sort budget
MODULUS_KEY = ("select gkey, sum(quantity) from (select orderkey % 777 as "
               "gkey, quantity from lineitem) group by gkey")

DEFAULT_CONFIG_SHAPES = {
    "q6": Q6,
    "q1": Q1,
    # moment aggregates are outside BASIC_AGGS: the sort path carries them
    "nonbasic_stddev": "select l_returnflag, stddev(l_quantity) "
                       "from lineitem group by l_returnflag",
    "join_chain_count": "select count(*) from lineitem, orders "
                        "where l_orderkey = o_orderkey",
    "q3_shape": Q3_SHAPE,
    "q18_shape": Q18_SHAPE,
    # IN-subquery lowers to a semi step with the three-valued marker
    "semi_join": "select count(*) from lineitem "
                 "where l_orderkey in (select o_orderkey from orders "
                 "where o_orderdate < date '1995-01-01')",
    # probe -> probe: two build tables in one program
    "multi_probe": "select count(*), sum(l_quantity) from lineitem, orders, "
                   "customer where l_orderkey = o_orderkey "
                   "and o_custkey = c_custkey and c_nationkey < 10",
    # NULL probe keys never match (reference LookupJoinOperator)
    "null_probe_keys": "select count(*) from "
                       "(select case when l_orderkey % 3 = 0 then null "
                       "else l_orderkey end as k, l_quantity from lineitem) "
                       "join orders on k = o_orderkey where l_quantity < 30",
    "semi_null_probe_keys": SEMI_NULL,
    # customer |x| orders on custkey expands rows (fanout-k)
    "fanout_join": "select c_mktsegment, count(*) from customer, orders "
                   "where c_custkey = o_custkey group by c_mktsegment",
    # a residual ON predicate over both sides: the post-probe filter
    "residual_on_filter": "select count(*), sum(l_quantity) from lineitem "
                          "join orders on l_orderkey = o_orderkey "
                          "and l_extendedprice < o_totalprice / 4",
}


@pytest.mark.parametrize("name", sorted(DEFAULT_CONFIG_SHAPES))
def test_default_config_vs_oracle(default_runner, name):
    default_runner.assert_same_as_reference(DEFAULT_CONFIG_SHAPES[name],
                                            ordered=name == "q1")


def test_rle_column_vs_oracle(default_runner):
    # l_orderkey is monotone: the predicate prunes the chunk list by its
    # zone maps.  Its 4-row runs are no longer hinted into the RLE
    # encoding (PR 32: the run decode cost 4.6 ms a 64K-row chunk on the
    # chip); a column that IS run-length encoded decodes against the
    # plain rows in test_join_path.py and test_storage.py
    default_runner.assert_same_as_reference(
        "select count(*), sum(l_extendedprice), max(l_orderkey) "
        "from lineitem where l_orderkey < 150")
    from presto_tpu.storage.store import get_store
    kinds = {k[2]: e.column.kind for k, e in get_store().entries.items()
             if k[1] == "lineitem"}
    assert kinds.get("orderkey") == "plain", kinds


@pytest.mark.parametrize("name,config", [
    # columns generated per chunk instead of read from the resident store
    ("columns_not_resident", dict(storage_enabled=False)),
    # a chunk capacity that is no power of two: the 5000-row tail
    ("misaligned_chunk_tail", dict(batch_rows=5000)),
])
def test_q6_under_config_vs_oracle(name, config):
    LocalQueryRunner("sf0.01", config=ExecutionConfig(**config)) \
        .assert_same_as_reference(Q6)


def test_group_cardinality_overflows_configured_table():
    # 15,000 groups against a 16-slot table: the streaming executor's
    # collision retries grow it until the answer is exact
    LocalQueryRunner("sf0.01", config=ExecutionConfig(
        agg_slots=16, fuse_pipelines=False)).assert_same_as_reference(
        ORDERKEY_COUNT)


def test_constrained_q18_shape_arbitrates():
    # the q18 shape unconstrained (fused) and under a quarter of its peak:
    # the budgeted run keeps the streaming build/spill discipline (fusion
    # declines BudgetedPool) yet returns identical rows, and the
    # arbitration counters prove the pool actually worked for it
    from presto_tpu.exec.memory import MEMORY_METRICS
    from presto_tpu.exec.runner import _assert_rows_equal
    free = LocalQueryRunner("sf0.01")
    fres = free.execute(Q18_SHAPE)
    peak = fres.peak_memory_bytes or 0
    assert peak > 0
    MEMORY_METRICS.reset()
    constrained = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        spill_enabled=True, memory_budget_bytes=max(1, peak // 4)))
    cres = constrained.execute(Q18_SHAPE)
    _assert_rows_equal(cres, fres, ordered=False)
    m = MEMORY_METRICS.snapshot()
    assert m["arbitrations"] + m["revocations"] >= 1


# ---------------------------------------------------------------------------
# seeded fuzz: randomized predicates x encodings x agg shapes vs the oracle
# ---------------------------------------------------------------------------

_AGGS = ["count(*)", "sum(l_quantity)", "sum(l_extendedprice)",
         "sum(l_extendedprice * l_discount)", "min(l_quantity)",
         "max(l_extendedprice)", "avg(l_discount)"]
_GROUPS = ["", "l_returnflag", "l_returnflag, l_linestatus"]


def _fuzz_sql(seed: int) -> str:
    rng = np.random.default_rng(seed)
    conj = [f"l_quantity < {int(rng.integers(5, 45))}"]
    if rng.integers(2):
        lo = int(rng.integers(0, 7)) / 100.0
        hi = lo + int(rng.integers(1, 4)) / 100.0
        conj.append(f"l_discount between {lo:.2f} and {hi:.2f}")
    if rng.integers(2):
        y = int(rng.integers(1992, 1998))
        conj.append(f"l_shipdate >= date '{y}-01-01' "
                    f"and l_shipdate < date '{y + 1}-07-01'")
    if rng.integers(2):
        # RLE column + zone pruning of the chunk list
        conj.append(f"l_orderkey < {int(rng.integers(100, 20_000))}")
    n_aggs = int(rng.integers(2, 5))
    aggs = [_AGGS[i] for i in rng.choice(len(_AGGS), n_aggs,
                                         replace=False)]
    group = _GROUPS[int(rng.integers(len(_GROUPS)))]
    sql = (f"select {group + ', ' if group else ''}{', '.join(aggs)} "
           f"from lineitem where {' and '.join(conj)}")
    if group:
        sql += f" group by {group}"
    return sql


# G randomized across the direct / static-span / anchored-span / sort
# boundaries (6, 168, open-domain key, computed modulus)
_GROUPED_KEYS = [
    "l_returnflag, l_linestatus",
    "l_returnflag, l_linestatus, l_shipmode, l_shipinstruct",
    "l_orderkey",
]


def _grouped_fuzz_sql(seed: int) -> str:
    rng = np.random.default_rng(seed)
    n_aggs = int(rng.integers(2, 5))
    aggs = [_AGGS[i] for i in rng.choice(len(_AGGS), n_aggs,
                                         replace=False)]
    qty = int(rng.integers(10, 45))
    if seed % 2:
        group = _GROUPED_KEYS[int(rng.integers(len(_GROUPED_KEYS)))]
        return (f"select {group}, {', '.join(aggs)} from lineitem "
                f"where l_quantity < {qty} group by {group}")
    g = int(rng.integers(65, 20_000))
    aggs = [a.replace("l_", "") for a in aggs]
    return (f"select gkey, {', '.join(aggs)} from "
            f"(select orderkey % {g} as gkey, quantity, "
            f"extendedprice, discount from lineitem) "
            f"where quantity < {qty} group by gkey")


_JOIN_AGGS = ["count(*)", "sum(l_quantity)", "sum(l_extendedprice)",
              "max(o_totalprice)", "min(l_quantity)", "avg(l_discount)"]


def _join_fuzz_sql(seed: int) -> str:
    rng = np.random.default_rng(seed)
    conj = ["l_orderkey = o_orderkey",
            f"l_quantity < {int(rng.integers(10, 45))}"]
    if rng.integers(2):
        y = int(rng.integers(1992, 1998))
        conj.append(f"l_shipdate >= date '{y}-01-01'")
    if rng.integers(2):
        # build-side filter: the probe runs against a sparse key domain
        y = int(rng.integers(1993, 1998))
        conj.append(f"o_orderdate < date '{y}-06-01'")
    if rng.integers(2):
        # RLE probe-key column + zone pruning
        conj.append(f"l_orderkey < {int(rng.integers(1000, 30_000))}")
    n_aggs = int(rng.integers(2, 4))
    aggs = [_JOIN_AGGS[i] for i in rng.choice(len(_JOIN_AGGS), n_aggs,
                                              replace=False)]
    group = ["", "o_orderkey", "l_returnflag"][int(rng.integers(3))]
    sql = (f"select {group + ', ' if group else ''}{', '.join(aggs)} "
           f"from lineitem, orders where {' and '.join(conj)}")
    if group:
        sql += f" group by {group}"
    return sql


@pytest.mark.parametrize("make_sql,seed", [
    *[(_fuzz_sql, s) for s in (1, 2, 3, 4)],
    *[(_grouped_fuzz_sql, s) for s in (11, 12, 13, 14)],
    *[(_join_fuzz_sql, s) for s in (21, 22, 23, 24, 25, 26, 27, 28)],
], ids=lambda v: v.__name__.strip("_") if callable(v) else str(v))
def test_fuzz_vs_oracle(default_runner, make_sql, seed):
    default_runner.assert_same_as_reference(make_sql(seed))


# ---------------------------------------------------------------------------
# which program the selector of _compile_AggregationNode builds for a shape
# ---------------------------------------------------------------------------

def _agg_programs_traced(sql, **config):
    """The aggregation programs a first run of `sql` traces (by the name
    named_jit gives them), the fusion refusals and the bytes spilled."""
    from presto_tpu.exec.memory import MEMORY_METRICS
    from presto_tpu.serving import FRAGMENT_JIT_CACHE
    from presto_tpu.serving.cache import PlanCache
    from presto_tpu.telemetry import jax_events
    jax_events.install()
    # a first run: neither the plan nor the process-wide program cache
    # may serve it, or nothing is traced
    FRAGMENT_JIT_CACHE.invalidate_all()
    runner = LocalQueryRunner("sf0.01", plan_cache=PlanCache(),
                              config=ExecutionConfig(**config))
    before = jax_events.PROGRAMS.snapshot()
    spilled = MEMORY_METRICS.snapshot()["spilled_bytes"]
    res = runner.assert_same_as_reference(sql)
    traced = {n for n, row in jax_events.PROGRAMS.snapshot().items()
              if row["traces"] > before.get(n, {}).get("traces", 0)
              and (n.startswith("scan_agg_") or n.startswith("agg_"))}
    declined = {k[len("fusionDeclined"):]
                for k in (res.runtime_stats or {})
                if k.startswith("fusionDeclined")}
    return (traced, declined,
            MEMORY_METRICS.snapshot()["spilled_bytes"] - spilled)


TINY_POOL = dict(batch_rows=1 << 14, memory_budget_bytes=200_000,
                 spill_partitions=4)


@pytest.mark.parametrize("sql,config,sort_budget,programs,declined", [
    (Q6, {}, None, {"scan_agg_direct"}, set()),
    (Q1, {}, None, {"scan_agg_direct"}, set()),
    (SPAN_4KEYS, {}, None, {"scan_agg_static_span"}, set()),
    (ORDERKEY_COUNT, {}, None,
     {"scan_agg_span_probe", "scan_agg_runtime_span"}, set()),
    (MODULUS_KEY, {}, None, {"scan_agg_sort"}, set()),
    # over the sort budget the chain streams and the stream is aggregated
    # once through: held and grouped by one sort (PR 34; before it a fused
    # scatter hash table, re-run whole for every doubling)
    (MODULUS_KEY, {}, 0, {"agg_sort"}, set()),
    # a budgeted pool keeps the streaming executor; its table does not fit
    # 200 kB, so the keys are hash-partitioned into host-staged buckets
    (ORDERKEY_COUNT, TINY_POOL, None, {"agg_upd"}, {"BudgetedPool"}),
], ids=["q6-direct", "q1-direct", "4keys-static_span",
        "orderkey-anchored_span", "modulus-sort", "modulus-stream_sort",
        "orderkey-spilled_buckets"])
def test_agg_strategy_by_shape(monkeypatch, sql, config, sort_budget,
                               programs, declined):
    """direct -> static span -> anchored span -> sort -> the stream's
    own aggregation, and the streaming executor's spilled buckets under
    a budget: the shape decides,
    and the answer is the oracle's whichever program ran."""
    if sort_budget is not None:
        from presto_tpu.exec import pipeline
        monkeypatch.setattr(pipeline, "SORT_AGG_MAX_BYTES", sort_budget)
    traced, refused, spilled = _agg_programs_traced(sql, **config)
    assert traced == programs
    assert refused == declined
    assert (spilled > 0) == ("memory_budget_bytes" in config)
