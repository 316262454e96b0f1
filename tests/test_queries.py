"""SQL conformance tests: TPU engine vs numpy reference executor on identical
generated TPC-H data (differential testing in the style of the reference's
AbstractTestQueries / QueryAssertions-vs-H2, presto-tests/.../QueryAssertions.java:52).
"""
import pytest

from presto_tpu.exec.runner import LocalQueryRunner


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner("sf0.01")


def check(runner, sql, ordered=False):
    return runner.assert_same_as_reference(sql, ordered=ordered)


# ---------------------------------------------------------------------------
# scans / filters / projections
# ---------------------------------------------------------------------------

def test_scan_limit(runner):
    res = runner.execute("select n_name, n_regionkey from nation limit 5")
    assert len(res.rows) == 5


def test_filter_arith(runner):
    check(runner, "select n_nationkey + 1, n_nationkey * 2 from nation "
                  "where n_nationkey >= 10 and n_nationkey < 15")


def test_string_predicates(runner):
    check(runner, "select n_name from nation where n_name like 'A%'")
    check(runner, "select count(*) from customer "
                  "where c_mktsegment in ('BUILDING', 'MACHINERY')")


def test_case_expression(runner):
    check(runner, """
        select n_regionkey,
               case when n_regionkey < 2 then 'west' else 'east' end
        from nation""")


def test_date_functions(runner):
    check(runner, "select o_orderkey, year(o_orderdate), month(o_orderdate) "
                  "from orders where o_orderkey < 100")


def test_distinct(runner):
    check(runner, "select distinct o_orderstatus from orders")


def test_order_by_limit(runner):
    check(runner, "select c_custkey, c_acctbal from customer "
                  "order by c_acctbal desc, c_custkey limit 20", ordered=True)


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

def test_global_agg(runner):
    check(runner, "select count(*), sum(l_quantity), min(l_discount), "
                  "max(l_tax), avg(l_extendedprice) from lineitem")


def test_group_by_small(runner):
    check(runner, "select o_orderstatus, count(*), sum(o_totalprice) "
                  "from orders group by o_orderstatus")


def test_group_by_high_cardinality(runner):
    # forces table growth beyond the initial slot count
    check(runner, "select l_orderkey, count(*), sum(l_quantity) "
                  "from lineitem group by l_orderkey")


def test_having(runner):
    check(runner, "select c_nationkey, count(*) as c from customer "
                  "group by c_nationkey having count(*) > 50")


def test_group_by_expression(runner):
    check(runner, "select year(o_orderdate), count(*) from orders "
                  "group by year(o_orderdate)")


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

def test_inner_join(runner):
    check(runner, """
        select n_name, r_name from nation
        join region on n_regionkey = r_regionkey""")


def test_left_join(runner):
    check(runner, """
        select c_custkey, o_orderkey from customer
        left join orders on c_custkey = o_custkey
        where c_custkey < 50""")


def test_join_with_agg(runner):
    check(runner, """
        select r_name, count(*) from nation, region
        where n_regionkey = r_regionkey group by r_name""")


def test_three_way_join(runner):
    check(runner, """
        select s_name, n_name, r_name from supplier, nation, region
        where s_nationkey = n_nationkey and n_regionkey = r_regionkey
        and s_suppkey < 20""")


# ---------------------------------------------------------------------------
# TPC-H benchmark queries
# ---------------------------------------------------------------------------

TPCH_Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

TPCH_Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""

TPCH_Q5 = """
select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA' and o_orderdate >= date '1994-01-01'
  and o_orderdate < date '1995-01-01'
group by n_name
order by revenue desc
"""

TPCH_Q6 = """
select sum(l_extendedprice * l_discount) as revenue from lineitem
where l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07 and l_quantity < 24
"""


def test_tpch_q1(runner):
    res = check(runner, TPCH_Q1, ordered=True)
    assert len(res.rows) == 4


def test_tpch_q3(runner):
    res = check(runner, TPCH_Q3, ordered=True)
    assert len(res.rows) == 10


def test_tpch_q5(runner):
    res = check(runner, TPCH_Q5, ordered=True)
    assert len(res.rows) > 0


def test_tpch_q6(runner):
    res = check(runner, TPCH_Q6)
    assert res.rows[0][0] is not None


# ---------------------------------------------------------------------------
# regression tests from review findings
# ---------------------------------------------------------------------------

def test_left_join_on_filter_null_extends(runner):
    # ON-clause extra conjuncts filter PAIRS, then unmatched rows null-extend
    res = check(runner, """
        select c_custkey, o_orderkey from customer
        left join orders on c_custkey = o_custkey and o_orderkey < 10
        where c_custkey < 30""")
    custs = {r[0] for r in res.rows}
    assert custs == set(range(1, 30))  # every customer survives


def test_customers_without_orders_exist(runner):
    # generator spec: custkeys % 3 == 0 never get orders; others can
    res = runner.execute(
        "select count(*) from orders where o_custkey % 3 = 0")
    assert res.rows[0][0] == 0
    res2 = runner.execute(
        "select count(*) from orders where o_custkey % 3 = 1")
    assert res2.rows[0][0] > 0


def test_like_literal_metachars():
    from presto_tpu.exec.lowering import like_matcher
    assert like_matcher("50*%")("50*abc")
    assert not like_matcher("50*%")("50abc")
    assert like_matcher("a[b]_")("a[b]c")
    assert not like_matcher("a[b]_")("ab")
    assert like_matcher("%special%requests%")("xx special yy requests zz")


def test_not_in_three_valued(runner):
    """NOT IN under SQL three-valued logic (reference HashSemiJoinOperator):
    a NULL in the subquery makes every non-matching row UNKNOWN (dropped),
    and a NULL probe key is UNKNOWN regardless of the build side.
    Hand-checked counts — the oracle shares the semi-join semantics, so a
    differential test alone cannot anchor this."""
    # build = {NULL,1,2,3,4}: matches are definite FALSE for NOT IN, all
    # other rows UNKNOWN -> zero rows survive
    r = runner.execute(
        "SELECT count(*) FROM nation WHERE n_nationkey NOT IN "
        "(SELECT nullif(r_regionkey, 0) FROM region)")
    assert int(r.rows[0][0]) == 0
    # build = {1,2,3,4}, no NULL: plain anti-join, 25 - 4
    r = runner.execute(
        "SELECT count(*) FROM nation WHERE n_nationkey NOT IN "
        "(SELECT r_regionkey FROM region WHERE r_regionkey > 0)")
    assert int(r.rows[0][0]) == 21
    # NULL probe key (nationkey=3) is UNKNOWN even without build NULLs
    r = runner.execute(
        "SELECT count(*) FROM nation WHERE nullif(n_nationkey, 3) NOT IN "
        "(SELECT r_regionkey FROM region WHERE r_regionkey > 0)")
    assert int(r.rows[0][0]) == 21
    # positive IN: matches still found, misses vs NULL-bearing build drop
    r = runner.execute(
        "SELECT count(*) FROM nation WHERE n_nationkey IN "
        "(SELECT nullif(r_regionkey, 0) FROM region)")
    assert int(r.rows[0][0]) == 4
    runner.assert_same_as_reference(
        "SELECT count(*) FROM nation WHERE n_nationkey NOT IN "
        "(SELECT nullif(r_regionkey, 0) FROM region)")


def test_nullif_null_argument(runner):
    res = runner.execute(
        "select nullif(n_nationkey, null), nullif(0, 0) from nation "
        "where n_nationkey = 0")
    assert res.rows[0][0] == 0      # NULLIF(0, NULL) = 0
    assert res.rows[0][1] is None   # NULLIF(0, 0) = NULL


def test_month_interval_clamps():
    from presto_tpu.sql.planner import Planner
    import presto_tpu.sql.parser as A
    p = Planner()
    e = p.plan_expr(A.parse_sql(
        "select date '1996-01-31' + interval '1' month from nation"
    ).select_items[0].expr, __import__(
        "presto_tpu.sql.planner", fromlist=["Scope"]).Scope([]))
    assert e.value == "1996-02-29"


def test_cte_referenced_twice(runner):
    res = check(runner, """
        with t as (select n_nationkey k, n_regionkey r from nation)
        select a.k, b.k from t a, t b
        where a.r = b.r and a.k < b.k and a.k < 5""")
    assert len(res.rows) > 0


def test_generator_process_deterministic():
    import subprocess, sys
    code = ("from presto_tpu.connectors import tpch;"
            "print(tpch.generate_column('orders','custkey',0.01,0,5).tolist())")
    outs = {subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, cwd="/root/repo").stdout for _ in range(2)}
    assert len(outs) == 1 and "[" in outs.pop()


def test_left_join_where_on_right_side_not_pushed(runner):
    # WHERE on the null-producing side applies AFTER null-extension: no
    # null-extended row may survive o_orderkey < 10.
    res = check(runner, """
        select c_custkey, o_orderkey from customer
        left join orders on c_custkey = o_custkey
        where o_orderkey < 10 and c_custkey < 100""")
    assert all(r[1] is not None and r[1] < 10 for r in res.rows)


def test_cte_where_survives_second_reference(runner):
    res = check(runner, """
        with t as (select n_nationkey k from nation where n_nationkey < 3)
        select a.k, b.k from t a, t b""")
    assert len(res.rows) == 9


def test_left_join_null_string_column(runner):
    # NULL varchar values must round-trip through the dictionary block.
    res = check(runner, """
        select c_custkey, n_name from customer
        left join nation on c_custkey = n_nationkey
        where c_custkey between 23 and 27""")
    by_key = {r[0]: r[1] for r in res.rows}
    assert by_key[23] is not None
    assert by_key[25] is None and by_key[26] is None


def test_group_by_same_column_name_two_tables(runner):
    res = check(runner, """
        select a.n_regionkey, b.n_regionkey, count(*) from nation a, nation b
        where a.n_nationkey + 1 = b.n_nationkey
        group by a.n_regionkey, b.n_regionkey""")
    assert any(r[0] != r[1] for r in res.rows)


def test_group_by_small_pool_lazy_column(runner):
    # orders.clerk is open-domain but drawn from a small pool (sf*1000
    # values): grouping must be by value, not by row identity
    res = check(runner, "select o_clerk, count(*) from orders group by o_clerk")
    assert len(res.rows) <= 10 * 3  # sf0.01 -> 10 clerks


def test_scalar_subquery_multi_row_raises(runner):
    import pytest as _pytest
    with _pytest.raises(Exception, match="more than one row"):
        runner.execute("select count(*) from region where r_regionkey = "
                       "(select n_regionkey from nation where n_regionkey < 2)")


# ---------------------------------------------------------------------------
# window functions (reference: WindowOperator.java:69, AbstractTestWindowQueries)
# ---------------------------------------------------------------------------

def test_window_row_number(runner):
    check(runner, """
        select o_custkey, o_orderkey,
               row_number() over (partition by o_custkey order by o_orderkey)
        from orders where o_custkey < 100""")


def test_window_rank_dense_rank_ties(runner):
    # l_quantity has heavy ties within a partition
    check(runner, """
        select l_suppkey, l_quantity,
               rank() over (partition by l_suppkey order by l_quantity),
               dense_rank() over (partition by l_suppkey order by l_quantity)
        from lineitem where l_suppkey < 20""")


def test_window_running_sum(runner):
    check(runner, """
        select l_orderkey, l_linenumber,
               sum(l_quantity) over (partition by l_orderkey
                                     order by l_linenumber)
        from lineitem where l_orderkey < 200""")


def test_window_running_agg_includes_peers(runner):
    # RANGE default frame: rows tied on the order key share the aggregate
    check(runner, """
        select l_suppkey, l_quantity,
               sum(l_extendedprice) over (partition by l_suppkey
                                          order by l_quantity),
               count(l_quantity) over (partition by l_suppkey
                                       order by l_quantity)
        from lineitem where l_suppkey < 10""")


def test_window_partition_only_aggs(runner):
    # no ORDER BY -> frame is the whole partition
    check(runner, """
        select o_orderkey, o_totalprice,
               avg(o_totalprice) over (partition by o_orderstatus),
               count(*) over (partition by o_orderstatus),
               min(o_totalprice) over (partition by o_orderstatus),
               max(o_totalprice) over (partition by o_orderstatus)
        from orders where o_orderkey < 500""")


def test_window_no_partition(runner):
    check(runner, """
        select n_nationkey,
               sum(n_nationkey) over (order by n_nationkey),
               row_number() over (order by n_nationkey desc)
        from nation""")


def test_window_desc_order(runner):
    check(runner, """
        select c_nationkey, c_custkey,
               rank() over (partition by c_nationkey order by c_acctbal desc)
        from customer where c_custkey < 300""")


def test_window_string_partition(runner):
    # partition key is a lazy open-domain string column (encode path)
    check(runner, """
        select o_clerk, o_orderkey,
               row_number() over (partition by o_clerk order by o_orderkey)
        from orders where o_orderkey < 300""")


def test_window_over_grouped_aggregation(runner):
    # window over the result of a GROUP BY; sum(count(*)) over (...)
    check(runner, """
        select o_orderpriority, count(*) cnt,
               sum(count(*)) over (order by o_orderpriority)
        from orders group by o_orderpriority""")


def test_window_in_order_by_and_topn(runner):
    check(runner, """
        select c_custkey,
               row_number() over (order by c_acctbal desc) rn
        from customer
        order by rn limit 10""", ordered=True)


def test_window_two_specs_one_query(runner):
    check(runner, """
        select l_orderkey, l_linenumber,
               row_number() over (partition by l_orderkey
                                  order by l_linenumber),
               sum(l_quantity) over (partition by l_suppkey
                                     order by l_extendedprice)
        from lineitem where l_orderkey < 100""")


def test_window_distinct_rejected(runner):
    import pytest as _pytest
    with _pytest.raises(Exception, match="DISTINCT"):
        runner.execute("select count(distinct o_orderstatus) over "
                       "(partition by o_custkey) from orders")


def test_window_lazy_rowid_distinct_partition_key(runner):
    # c_phone is ROWID_DISTINCT but not usable as a sort key via row ids:
    # must be dictionary-encoded before the window sort
    check(runner, """
        select c_custkey,
               row_number() over (partition by c_phone order by c_custkey)
        from customer where c_custkey < 50""")


def test_window_min_varchar_reference(runner):
    # min/max over strings: reference must not hit the sum accumulator
    from presto_tpu.exec.reference import execute_reference
    from presto_tpu.exec.runner import LocalQueryRunner as _R
    plan = runner.plan("select min(n_name) over (partition by n_regionkey) "
                       "from nation")
    rows = execute_reference(plan)
    assert all(isinstance(r[0], str) for r in rows)


# ---------------------------------------------------------------------------
# set operations (reference: SetOperationNode, ImplementIntersectAsUnion)
# ---------------------------------------------------------------------------

def test_union_all(runner):
    res = check(runner, """
        select n_regionkey from nation where n_nationkey < 5
        union all select r_regionkey from region""")
    assert len(res.rows) == 10


def test_union_distinct(runner):
    check(runner, "select n_regionkey from nation "
                  "union select r_regionkey from region")


def test_union_strings_merged_dictionaries(runner):
    check(runner, """
        select n_name from nation where n_nationkey < 5
        union all select r_name from region""")


def test_union_type_coercion(runner):
    # bigint union double -> double on both branches
    check(runner, """
        select n_nationkey from nation where n_nationkey < 3
        union all select c_acctbal from customer where c_custkey < 3""")


def test_union_order_limit(runner):
    check(runner, """
        select n_name from nation where n_nationkey < 2
        union select r_name from region order by 1 limit 4""", ordered=True)


def test_union_three_way_aggregated(runner):
    check(runner, """
        select count(*), sum(k) from (
          select n_nationkey k from nation
          union all select r_regionkey from region
          union all select o_orderkey from orders where o_orderkey < 10) t""")


def test_intersect(runner):
    check(runner, """
        select n_regionkey from nation
        intersect select r_regionkey from region where r_regionkey < 3""")


def test_except(runner):
    check(runner, """
        select n_nationkey from nation
        except select o_custkey from orders""")


def test_intersect_binds_tighter_than_union(runner):
    # a union (b intersect c): intersect of region 0..4 with 0..2 is 0..2
    res = check(runner, """
        select n_regionkey from nation where n_nationkey = 0
        union select r_regionkey from region
        intersect select n_regionkey from nation where n_regionkey < 3""")
    assert sorted(r[0] for r in res.rows) == [0, 1, 2]


def test_union_in_subquery(runner):
    check(runner, """
        select count(*) from customer where c_nationkey in
          (select n_nationkey from nation where n_regionkey = 0
           union select n_nationkey from nation where n_regionkey = 1)""")


def test_union_in_cte(runner):
    check(runner, """
        with keys as (select n_regionkey k from nation
                      union select r_regionkey from region)
        select count(*) from keys""")


def test_union_aliased_branch_names(runner):
    # output names come from the first branch
    res = runner.execute("select n_nationkey as id from nation where "
                         "n_nationkey < 2 union all select r_regionkey "
                         "from region where r_regionkey < 1")
    assert res.column_names == ["id"]


def test_intersect_all_rejected(runner):
    import pytest as _pytest
    with _pytest.raises(Exception, match="not supported"):
        runner.execute("select n_regionkey from nation intersect all "
                       "select r_regionkey from region")


def test_window_min_max_varchar_engine(runner):
    # dictionary-encoded strings: min/max must compare lexically, not by code
    check(runner, """
        select n_regionkey, n_name,
               min(n_name) over (partition by n_regionkey),
               max(n_name) over (partition by n_regionkey)
        from nation""")


def test_window_min_lazy_string(runner):
    # customer.name is ROWID_ORDERED: min over row ids, late-materialized
    check(runner, """
        select c_nationkey,
               min(c_name) over (partition by c_nationkey)
        from customer where c_custkey < 100""")
    # clerk is NOT rowid-ordered: must be dictionary-encoded first
    check(runner, """
        select o_orderstatus,
               max(o_clerk) over (partition by o_orderstatus)
        from orders where o_orderkey < 200""")


def test_union_order_by_after_parenthesized_branch(runner):
    res = check(runner, """
        select n_regionkey from nation where n_nationkey < 2
        union (select r_regionkey from region) order by 1 limit 3""",
        ordered=True)
    assert len(res.rows) == 3


def test_scalar_subquery_union_multi_column_rejected(runner):
    import pytest as _pytest
    with _pytest.raises(Exception, match="one column"):
        runner.execute("""
            select count(*) from region where r_regionkey =
              (select n_regionkey, n_nationkey from nation where n_nationkey = 1
               union select n_regionkey, n_nationkey from nation
               where n_nationkey = 1)""")


# ---------------------------------------------------------------------------
# EXPLAIN / EXPLAIN ANALYZE (reference PlanPrinter, ExplainAnalyzeOperator)
# ---------------------------------------------------------------------------

def test_explain_plan_text(runner):
    res = runner.execute("explain select o_orderstatus, count(*) from orders "
                         "where o_orderkey < 100 group by o_orderstatus")
    assert res.column_names == ["Query Plan"]
    text = res.rows[0][0]
    assert "TableScan" in text and "Aggregation" in text
    assert "tpch.orders" in text and "o_orderstatus" in text


def test_explain_analyze_has_stats(runner):
    res = runner.execute("explain analyze select count(*) from nation")
    text = res.rows[0][0]
    assert "rows:" in text and "wall:" in text
    assert "rows: 25" in text  # the scan's output rows


def test_explain_distributed_fragments():
    from presto_tpu.exec.runner import DistributedQueryRunner
    d = DistributedQueryRunner("sf0.01", n_tasks=2)
    text = d.execute("explain select o_orderstatus, count(*) from orders "
                     "group by o_orderstatus").rows[0][0]
    assert "Fragment 0 [SINGLE]" in text
    assert "PARTIAL" in text and "FINAL" in text
    assert "RemoteSource" in text


def test_explain_window_and_join_details(runner):
    text = runner.execute("""
        explain select n_name, r_name,
               row_number() over (partition by r_name order by n_name)
        from nation join region on n_regionkey = r_regionkey""").rows[0][0]
    assert "Window" in text and "partitionBy" in text
    assert "Join" in text and "criteria" in text


# ---------------------------------------------------------------------------
# GROUPING SETS / ROLLUP / CUBE (reference GroupIdOperator + GroupingSetAnalysis)
# ---------------------------------------------------------------------------

def test_rollup(runner):
    res = check(runner, """
        select o_orderstatus, o_orderpriority, count(*), sum(o_totalprice)
        from orders group by rollup(o_orderstatus, o_orderpriority)""")
    # 3 statuses x 5 priorities + 3 subtotals + 1 grand total
    n_detail = len([r for r in res.rows if r[1] is not None])
    assert any(r[0] is None and r[1] is None for r in res.rows)
    assert n_detail >= 3


def test_cube(runner):
    res = check(runner, """
        select n_regionkey, n_nationkey, count(*)
        from nation group by cube(n_regionkey, n_nationkey)""")
    # 25 detail + 5 region subtotals + 25 nation subtotals + 1 total
    assert len(res.rows) == 56


def test_grouping_sets_explicit(runner):
    check(runner, """
        select o_orderstatus, o_orderpriority, count(*)
        from orders
        group by grouping sets ((o_orderstatus), (o_orderpriority), ())""")


def test_rollup_with_join_and_distinct_agg(runner):
    check(runner, """
        select n_regionkey, r_name, count(distinct n_nationkey), count(*)
        from nation join region on n_regionkey = r_regionkey
        group by rollup(n_regionkey, r_name)""")


def test_mixed_plain_and_rollup_cross_product(runner):
    check(runner, """
        select o_orderstatus, year(o_orderdate) y, count(*)
        from orders group by o_orderstatus, rollup(y)""")


def test_rollup_having_and_order(runner):
    check(runner, """
        select o_orderstatus, o_orderpriority, count(*) c
        from orders group by rollup(o_orderstatus, o_orderpriority)
        having count(*) > 100
        order by c desc limit 5""", ordered=True)


def test_sort_narrow_int_nulls_last():
    """Regression (round-5 / q14_1): a narrow-int (int32) nullable sort
    key must honor NULLS LAST — the INT64_MAX null sentinel used to wrap
    to -1 when jnp.where cast it into the int32 key, so rollup-NULL rows
    sorted FIRST under ASC (Presto default is NULLS LAST, ORDER BY docs /
    TopNOperator.java:32)."""
    import jax.numpy as jnp

    from presto_tpu.exec import operators as ops
    from presto_tpu.exec.operators import Batch, Column

    vals = jnp.asarray([5, 3, 0, 8], dtype=jnp.int32)   # 0 is a null row
    nulls = jnp.asarray([False, False, True, False])
    b = Batch({"k": Column(vals, nulls)}, jnp.ones(4, dtype=bool))
    out = ops.topn(b, [("k", "ASC_NULLS_LAST")], 4)
    got = [(int(v), bool(n)) for v, n in
           zip(out.columns["k"].values, out.columns["k"].null_mask())]
    assert got == [(3, False), (5, False), (8, False), (0, True)]
    out = ops.topn(b, [("k", "DESC_NULLS_FIRST")], 4)
    got = [(int(v), bool(n)) for v, n in
           zip(out.columns["k"].values, out.columns["k"].null_mask())]
    assert got == [(0, True), (8, False), (5, False), (3, False)]
    # DESC negates the key: -INT32_MIN wraps at the narrow width, so
    # non-null narrow ints must also promote under DESC
    vals = jnp.asarray([5, -2147483648, 7, 0], dtype=jnp.int32)
    b = Batch({"k": Column(vals)}, jnp.ones(4, dtype=bool))
    out = ops.topn(b, [("k", "DESC_NULLS_LAST")], 4)
    assert [int(v) for v in out.columns["k"].values] \
        == [7, 5, 0, -2147483648]


# ---------------------------------------------------------------------------
# arrays / UNNEST (round-5; reference ArrayFunctions.java,
# ArraySubscriptOperator.java, UnnestOperator.java)
# ---------------------------------------------------------------------------

def test_array_literal_and_subscript(runner):
    check(runner, "select array[1, 2, 3][2], array[10, 20][1]")
    check(runner, "select array[n_nationkey, n_regionkey][1] from nation "
                  "where n_nationkey < 5")


def test_array_functions(runner):
    check(runner, "select cardinality(array[1,2,3]), "
                  "element_at(array[10,20], 2), "
                  "element_at(array[10,20], 7)")
    check(runner, "select contains(array[1,2,3], n_regionkey), "
                  "array_max(array[n_nationkey, n_regionkey]), "
                  "array_min(array[n_nationkey, n_regionkey]), "
                  "array_position(array[2,4,6], n_regionkey * 2) "
                  "from nation")


def test_unnest_basic(runner):
    check(runner, "select x from unnest(array[3,1,2]) as u(x)")
    check(runner, "select x from unnest(sequence(1, 6)) as u(x) "
                  "where x % 2 = 0")


def test_unnest_zip_null_pads(runner):
    # multiple arrays align by position; the shorter null-extends
    check(runner, "select x, y from unnest(array[1,2], "
                  "array[10,20,30]) as u(x, y)")


def test_unnest_lateral_with_ordinality(runner):
    check(runner, """
        select n_name, x, i from nation
        cross join unnest(array[n_nationkey, n_regionkey])
            with ordinality as u(x, i)
        where n_nationkey < 5 order by n_name, i""", ordered=True)


def test_unnest_feeds_aggregation(runner):
    check(runner, """
        select sum(x), count(*) from nation
        cross join unnest(array[n_nationkey, n_regionkey, 7]) as u(x)""")


def test_array_output_column(runner):
    check(runner, "select n_name, array[n_nationkey, n_regionkey] "
                  "from nation where n_nationkey < 4")


# ---------------------------------------------------------------------------
# RIGHT / FULL OUTER joins
# ---------------------------------------------------------------------------

def test_right_join(runner):
    check(runner, """
        select n_name, r_name from region right join nation
        on n_regionkey = r_regionkey""")


def test_right_join_null_extension(runner):
    # customers without orders survive with null order columns
    res = check(runner, """
        select c_custkey, o_orderkey from orders
        right join customer on c_custkey = o_custkey
        where c_custkey < 100""")
    assert any(r[1] is None for r in res.rows)


def test_full_outer_join(runner):
    res = check(runner, """
        select a.n_nationkey, b.k from nation a
        full outer join (select n_nationkey + 20 k from nation) b
        on a.n_nationkey = b.k""")
    # 25 left rows (5 matched) + 20 unmatched right rows
    assert len(res.rows) == 45
    assert any(r[0] is None for r in res.rows)
    assert any(r[1] is None for r in res.rows)


def test_full_join_distributed():
    from presto_tpu.exec.runner import DistributedQueryRunner
    d = DistributedQueryRunner("sf0.01", n_tasks=3, join_max_broadcast_table_size=0)
    d.assert_same_as_reference("""
        select a.n_nationkey, b.k from nation a
        full outer join (select n_nationkey + 20 k from nation) b
        on a.n_nationkey = b.k""")


def test_full_join_under_spill_budget():
    from presto_tpu.exec.pipeline import ExecutionConfig
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        batch_rows=1 << 14, join_out_capacity=1 << 16,
        memory_budget_bytes=200_000, spill_partitions=4))
    r.assert_same_as_reference("""
        select c_custkey, o_orderkey from customer
        full outer join orders on c_custkey = o_custkey
        where c_custkey < 500 or c_custkey is null""")


def test_join_overflow_split_after_exhaustion():
    """Recursive-halving overflow retry must still run when the overflow
    is detected AFTER the probe iterator is exhausted (regression: the
    windowed-drain refill loop must pull split pieces unconditionally).
    supplier x supplier on nationkey has fanout ~4 at sf0.01; a tiny
    join_out_capacity forces every probe batch to overflow and split."""
    from presto_tpu.exec.pipeline import ExecutionConfig
    from presto_tpu.exec.runner import LocalQueryRunner
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        batch_rows=1 << 12, join_out_capacity=128))
    res = r.execute("""
        SELECT count(*) FROM supplier s1 JOIN supplier s2
        ON s1.s_nationkey = s2.s_nationkey""")
    # exact pair count cross-checked with the oracle
    exp = r.execute_reference("""
        SELECT count(*) FROM supplier s1 JOIN supplier s2
        ON s1.s_nationkey = s2.s_nationkey""")
    assert int(res.rows[0][0]) == int(exp.rows[0][0])
