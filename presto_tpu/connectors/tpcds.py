"""TPC-DS connector: deterministic in-memory columnar data generator.

The analog of the reference's presto-tpcds connector (presto-tpcds/
src/main/java/com/facebook/presto/tpcds/TpcdsConnectorFactory.java, backed by
the teradata dsdgen port) built on the same counter-hash scheme as the tpch
module: every cell is a pure function of (table, column, row index, scale
factor), so splits are stateless and workers generate their own shards.

Covers the dimensional core of the TPC-DS schema (date_dim, item, customer,
customer_address, store, web_site, warehouse, promotion) and the two biggest
fact-table families exercised by the BASELINE queries (store_sales,
web_sales + web_returns — TPC-DS Q95 is baseline config 5).  Row counts
follow the spec's SF1 values scaled linearly (dimension tables fixed or
floored); value distributions are self-consistent rather than dsdgen
bit-exact — correctness testing is differential (TPU engine vs the numpy
reference interpreter over identical generated data), as for tpch.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from ..common.types import (BIGINT, DATE, INTEGER, Type, DecimalType,
                            VarcharType)
# hashing core shared with tpch; seeds are namespaced "tpcds.<table>" so the
# two connectors' value streams stay independent
from .tpch import TableBucket, _splitmix64, _stream_seed


def _hash(table: str, column: str, idx: np.ndarray) -> np.ndarray:
    seed = _stream_seed("tpcds." + table, column)
    with np.errstate(over="ignore"):
        return _splitmix64(idx.astype(np.uint64)
                           * np.uint64(0x9E3779B97F4A7C15) + seed)


def _uniform(table, column, idx, lo, hi):
    h = _hash(table, column, idx)
    span = np.uint64(hi - lo + 1)
    return (h % span).astype(np.int64) + lo


def _days(datestr: str) -> int:
    return int(np.datetime64(datestr, "D").astype(np.int64))


# d_date_sk convention: Julian day number, 2415022 == 1900-01-02 (spec);
# date_dim row i is calendar day 1900-01-02 + i
JULIAN_BASE = 2415022
EPOCH_1900 = _days("1900-01-02")          # days since unix epoch (negative)
DATE_DIM_ROWS = 73049                     # 1900-01-02 .. 2100-01-01

# fact sales window (spec: 5 years ending 2003-01-02)
SALES_MIN = _days("1998-01-02") - EPOCH_1900
SALES_MAX = _days("2002-11-02") - EPOCH_1900

STATES = ["AL", "CA", "CO", "FL", "GA", "IA", "IL", "IN", "KS", "KY", "LA",
          "MI", "MN", "MO", "NC", "ND", "NE", "NY", "OH", "OK", "PA", "SD",
          "TN", "TX", "VA"]
CITIES = [f"{a} {b}" for a in ("Pleasant", "Oak", "Spring", "Center",
                               "Fair", "Green", "Union", "Walnut", "Cedar",
                               "Liberty")
          for b in ("Hill", "Grove", "Valley", "Ridge", "Creek", "Point")]
COUNTIES = [f"{c} County" for c in ("Williamson", "Walker", "Barrow",
                                    "Franklin", "Bronx", "Orange", "Jackson",
                                    "Mobile", "Salem", "Ziebach")]
DAY_NAMES = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
             "Friday", "Saturday"]
CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry", "Men",
              "Music", "Shoes", "Sports", "Women"]
CLASSES = [f"{c} class {i}" for c in ("value", "economy", "standard",
                                      "premium", "luxury") for i in range(1, 5)]
COLORS = ["almond", "azure", "beige", "black", "blue", "brown", "coral",
          "cream", "cyan", "gold", "green", "grey", "indigo", "ivory",
          "khaki", "lime", "maroon", "navy", "olive", "orange", "peach",
          "pink", "plum", "purple", "red"]
BRANDS = [f"{m}brand #{i}" for m in ("amalg", "edu pack", "expo", "scholar",
                                     "import", "corp", "brand", "univ",
                                     "name", "max")
          for i in range(1, 11)]
FIRST_NAMES = ["James", "John", "Robert", "Michael", "William", "David",
               "Mary", "Patricia", "Linda", "Barbara", "Elizabeth", "Susan",
               "Jose", "Carlos", "Anna", "Laura", "Kevin", "Brian", "Sarah",
               "Emily", "Daniel", "Matthew", "Nancy", "Karen", "Paul"]
LAST_NAMES = ["Smith", "Johnson", "Williams", "Brown", "Jones", "Garcia",
              "Miller", "Davis", "Rodriguez", "Martinez", "Hernandez",
              "Lopez", "Gonzalez", "Wilson", "Anderson", "Thomas", "Taylor",
              "Moore", "Jackson", "Martin", "Lee", "Perez", "Thompson",
              "White", "Harris"]
COMPANY_NAMES = ["pri", "able", "ought", "ation", "eing", "bar"]
WAREHOUSE_NAMES = ["Conventional childr", "Important issues liv",
                   "Doors canno", "Bad cards must make.", "Rooms cook "]
YN = ["N", "Y"]

LINES_PER_ORDER = 3


INVENTORY_WEEKS = 261       # weekly snapshots, 1998-01-01 .. 2002-12-31


def _table_rows(table: str, sf: float) -> int:
    fixed = {"date_dim": DATE_DIM_ROWS, "web_site": 30, "warehouse": 5,
             "promotion": 300, "ship_mode": 20, "reason": 35,
             "income_band": 20, "household_demographics": 7_200,
             "customer_demographics": 1_920_800, "time_dim": 86_400,
             "call_center": 6, "catalog_page": 11_718, "web_page": 60}
    if table in fixed:
        return fixed[table]
    if table == "store":
        return max(2, int(12 * sf))
    if table == "inventory":
        # weekly (item x warehouse) snapshots, spec 2.5 layout
        return INVENTORY_WEEKS * _table_rows("item", sf) \
            * _table_rows("warehouse", sf)
    base = {
        "item": 18_000, "customer": 100_000, "customer_address": 50_000,
        "store_sales": 2_880_000, "web_sales": 720_000,
        "web_returns": 72_000, "catalog_sales": 1_440_000,
        "catalog_returns": 144_000, "store_returns": 288_000,
    }
    floor = {"item": 200, "customer": 1_000, "customer_address": 500,
             "store_sales": 10_000, "web_sales": 7_200, "web_returns": 720,
             "catalog_sales": 9_000, "catalog_returns": 900,
             "store_returns": 1_000}
    return max(floor[table], int(base[table] * sf))


D7_2 = DecimalType(7, 2)
D5_2 = DecimalType(5, 2)

SCHEMAS: Dict[str, List[Tuple[str, Type]]] = {
    "date_dim": [
        ("d_date_sk", BIGINT), ("d_date_id", VarcharType(16)),
        ("d_date", DATE), ("d_month_seq", INTEGER), ("d_week_seq", INTEGER),
        ("d_quarter_seq", INTEGER), ("d_year", INTEGER), ("d_dow", INTEGER),
        ("d_moy", INTEGER), ("d_dom", INTEGER), ("d_qoy", INTEGER),
        ("d_day_name", VarcharType(9)),
        ("d_quarter_name", VarcharType(6)),
    ],
    "item": [
        ("i_item_sk", BIGINT), ("i_item_id", VarcharType(16)),
        ("i_current_price", D7_2), ("i_brand_id", INTEGER),
        ("i_brand", VarcharType(50)), ("i_class_id", INTEGER),
        ("i_class", VarcharType(50)), ("i_category_id", INTEGER),
        ("i_category", VarcharType(50)), ("i_manufact_id", INTEGER),
        ("i_color", VarcharType(20)), ("i_manager_id", INTEGER),
        ("i_manufact", VarcharType(50)), ("i_product_name", VarcharType(50)),
        ("i_item_desc", VarcharType(200)), ("i_size", VarcharType(20)),
        ("i_units", VarcharType(10)), ("i_wholesale_cost", D7_2),
    ],
    "customer": [
        ("c_customer_sk", BIGINT), ("c_customer_id", VarcharType(16)),
        ("c_current_addr_sk", BIGINT), ("c_current_cdemo_sk", BIGINT),
        ("c_current_hdemo_sk", BIGINT),
        ("c_first_name", VarcharType(20)),
        ("c_last_name", VarcharType(30)), ("c_birth_year", INTEGER),
        ("c_birth_month", INTEGER), ("c_birth_country", VarcharType(20)),
        ("c_email_address", VarcharType(50)),
        ("c_preferred_cust_flag", VarcharType(1)),
        ("c_salutation", VarcharType(10)), ("c_login", VarcharType(13)),
        ("c_birth_day", INTEGER), ("c_first_sales_date_sk", BIGINT),
        ("c_first_shipto_date_sk", BIGINT),
        ("c_last_review_date_sk", BIGINT),
    ],
    "customer_address": [
        ("ca_address_sk", BIGINT), ("ca_address_id", VarcharType(16)),
        ("ca_city", VarcharType(60)), ("ca_county", VarcharType(30)),
        ("ca_state", VarcharType(2)), ("ca_zip", VarcharType(10)),
        ("ca_country", VarcharType(20)), ("ca_gmt_offset", D5_2),
        ("ca_street_number", VarcharType(10)),
        ("ca_street_name", VarcharType(60)),
        ("ca_street_type", VarcharType(15)),
        ("ca_suite_number", VarcharType(10)),
        ("ca_location_type", VarcharType(20)),
    ],
    "store": [
        ("s_store_sk", BIGINT), ("s_store_id", VarcharType(16)),
        ("s_store_name", VarcharType(50)), ("s_number_employees", INTEGER),
        ("s_floor_space", INTEGER), ("s_market_id", INTEGER),
        ("s_state", VarcharType(2)), ("s_company_id", INTEGER),
        ("s_city", VarcharType(60)), ("s_county", VarcharType(30)),
        ("s_zip", VarcharType(10)), ("s_gmt_offset", D5_2),
        ("s_street_number", VarcharType(10)),
        ("s_street_name", VarcharType(60)),
        ("s_street_type", VarcharType(15)),
        ("s_suite_number", VarcharType(10)),
        ("s_company_name", VarcharType(50)),
    ],
    "web_site": [
        ("web_site_sk", BIGINT), ("web_site_id", VarcharType(16)),
        ("web_name", VarcharType(50)), ("web_company_id", INTEGER),
        ("web_company_name", VarcharType(50)),
    ],
    "warehouse": [
        ("w_warehouse_sk", BIGINT), ("w_warehouse_name", VarcharType(20)),
        ("w_warehouse_sq_ft", INTEGER), ("w_state", VarcharType(2)),
        ("w_city", VarcharType(60)), ("w_county", VarcharType(30)),
        ("w_country", VarcharType(20)),
    ],
    "promotion": [
        ("p_promo_sk", BIGINT), ("p_promo_id", VarcharType(16)),
        ("p_channel_dmail", VarcharType(1)), ("p_channel_email", VarcharType(1)),
        ("p_channel_tv", VarcharType(1)),
        ("p_channel_event", VarcharType(1)),
        ("p_channel_catalog", VarcharType(1)),
    ],
    "store_sales": [
        ("ss_sold_date_sk", BIGINT), ("ss_sold_time_sk", BIGINT),
        ("ss_item_sk", BIGINT),
        ("ss_customer_sk", BIGINT), ("ss_cdemo_sk", BIGINT),
        ("ss_hdemo_sk", BIGINT), ("ss_addr_sk", BIGINT),
        ("ss_store_sk", BIGINT),
        ("ss_promo_sk", BIGINT), ("ss_ticket_number", BIGINT),
        ("ss_quantity", INTEGER), ("ss_wholesale_cost", D7_2),
        ("ss_list_price", D7_2), ("ss_sales_price", D7_2),
        ("ss_ext_discount_amt", D7_2), ("ss_ext_sales_price", D7_2),
        ("ss_ext_list_price", D7_2), ("ss_coupon_amt", D7_2),
        ("ss_net_paid", D7_2), ("ss_net_profit", D7_2),
        ("ss_ext_tax", D7_2), ("ss_ext_wholesale_cost", D7_2),
        ("ss_net_paid_inc_tax", D7_2),
    ],
    "web_sales": [
        ("ws_sold_date_sk", BIGINT), ("ws_ship_date_sk", BIGINT),
        ("ws_ship_mode_sk", BIGINT),
        ("ws_item_sk", BIGINT), ("ws_bill_customer_sk", BIGINT),
        ("ws_ship_addr_sk", BIGINT), ("ws_web_site_sk", BIGINT),
        ("ws_warehouse_sk", BIGINT), ("ws_promo_sk", BIGINT),
        ("ws_order_number", BIGINT), ("ws_quantity", INTEGER),
        ("ws_sales_price", D7_2), ("ws_ext_sales_price", D7_2),
        ("ws_ext_ship_cost", D7_2), ("ws_net_paid", D7_2),
        ("ws_net_profit", D7_2), ("ws_sold_time_sk", BIGINT),
        ("ws_bill_addr_sk", BIGINT), ("ws_bill_cdemo_sk", BIGINT),
        ("ws_bill_hdemo_sk", BIGINT), ("ws_ship_customer_sk", BIGINT),
        ("ws_ship_cdemo_sk", BIGINT), ("ws_ship_hdemo_sk", BIGINT),
        ("ws_web_page_sk", BIGINT), ("ws_wholesale_cost", D7_2),
        ("ws_list_price", D7_2), ("ws_ext_list_price", D7_2),
        ("ws_ext_discount_amt", D7_2), ("ws_ext_wholesale_cost", D7_2),
        ("ws_ext_tax", D7_2), ("ws_coupon_amt", D7_2),
        ("ws_net_paid_inc_tax", D7_2), ("ws_net_paid_inc_ship", D7_2),
    ],
    "web_returns": [
        ("wr_returned_date_sk", BIGINT), ("wr_item_sk", BIGINT),
        ("wr_refunded_customer_sk", BIGINT), ("wr_order_number", BIGINT),
        ("wr_return_quantity", INTEGER), ("wr_return_amt", D7_2),
        ("wr_net_loss", D7_2), ("wr_returning_customer_sk", BIGINT),
        ("wr_refunded_addr_sk", BIGINT), ("wr_returning_addr_sk", BIGINT),
        ("wr_refunded_cdemo_sk", BIGINT), ("wr_returning_cdemo_sk", BIGINT),
        ("wr_refunded_hdemo_sk", BIGINT), ("wr_web_page_sk", BIGINT),
        ("wr_reason_sk", BIGINT), ("wr_returned_time_sk", BIGINT),
        ("wr_refunded_cash", D7_2), ("wr_reversed_charge", D7_2),
        ("wr_account_credit", D7_2), ("wr_fee", D7_2),
        ("wr_return_ship_cost", D7_2), ("wr_return_amt_inc_tax", D7_2),
        ("wr_return_tax", D7_2),
    ],
    "store_returns": [
        ("sr_returned_date_sk", BIGINT), ("sr_item_sk", BIGINT),
        ("sr_customer_sk", BIGINT), ("sr_cdemo_sk", BIGINT),
        ("sr_hdemo_sk", BIGINT), ("sr_store_sk", BIGINT),
        ("sr_reason_sk", BIGINT), ("sr_ticket_number", BIGINT),
        ("sr_return_quantity", INTEGER), ("sr_return_amt", D7_2),
        ("sr_net_loss", D7_2),
    ],
    "catalog_sales": [
        ("cs_sold_date_sk", BIGINT), ("cs_ship_date_sk", BIGINT),
        ("cs_bill_customer_sk", BIGINT), ("cs_bill_cdemo_sk", BIGINT),
        ("cs_bill_hdemo_sk", BIGINT), ("cs_bill_addr_sk", BIGINT),
        ("cs_ship_addr_sk", BIGINT), ("cs_call_center_sk", BIGINT),
        ("cs_catalog_page_sk", BIGINT), ("cs_ship_mode_sk", BIGINT),
        ("cs_warehouse_sk", BIGINT), ("cs_item_sk", BIGINT),
        ("cs_promo_sk", BIGINT), ("cs_order_number", BIGINT),
        ("cs_quantity", INTEGER), ("cs_wholesale_cost", D7_2),
        ("cs_list_price", D7_2), ("cs_sales_price", D7_2),
        ("cs_ext_discount_amt", D7_2), ("cs_ext_sales_price", D7_2),
        ("cs_ext_ship_cost", D7_2), ("cs_net_paid", D7_2),
        ("cs_net_profit", D7_2), ("cs_sold_time_sk", BIGINT),
        ("cs_ship_customer_sk", BIGINT), ("cs_ship_cdemo_sk", BIGINT),
        ("cs_ship_hdemo_sk", BIGINT), ("cs_coupon_amt", D7_2),
        ("cs_ext_list_price", D7_2), ("cs_ext_wholesale_cost", D7_2),
        ("cs_ext_tax", D7_2), ("cs_net_paid_inc_tax", D7_2),
        ("cs_net_paid_inc_ship", D7_2), ("cs_net_paid_inc_ship_tax", D7_2),
    ],
    "catalog_returns": [
        ("cr_returned_date_sk", BIGINT), ("cr_item_sk", BIGINT),
        ("cr_refunded_customer_sk", BIGINT),
        ("cr_returning_customer_sk", BIGINT),
        ("cr_call_center_sk", BIGINT), ("cr_reason_sk", BIGINT),
        ("cr_order_number", BIGINT), ("cr_return_quantity", INTEGER),
        ("cr_return_amount", D7_2), ("cr_net_loss", D7_2),
        ("cr_catalog_page_sk", BIGINT), ("cr_refunded_addr_sk", BIGINT),
        ("cr_returning_addr_sk", BIGINT), ("cr_refunded_cash", D7_2),
        ("cr_reversed_charge", D7_2), ("cr_store_credit", D7_2),
        ("cr_fee", D7_2), ("cr_return_ship_cost", D7_2),
        ("cr_return_amt_inc_tax", D7_2), ("cr_return_tax", D7_2),
        ("cr_warehouse_sk", BIGINT),
    ],
    "inventory": [
        ("inv_date_sk", BIGINT), ("inv_item_sk", BIGINT),
        ("inv_warehouse_sk", BIGINT), ("inv_quantity_on_hand", INTEGER),
    ],
    "catalog_page": [
        ("cp_catalog_page_sk", BIGINT), ("cp_catalog_page_id", VarcharType(16)),
        ("cp_department", VarcharType(50)), ("cp_catalog_number", INTEGER),
        ("cp_catalog_page_number", INTEGER),
    ],
    "ship_mode": [
        ("sm_ship_mode_sk", BIGINT), ("sm_ship_mode_id", VarcharType(16)),
        ("sm_type", VarcharType(30)), ("sm_code", VarcharType(10)),
        ("sm_carrier", VarcharType(20)),
    ],
    "reason": [
        ("r_reason_sk", BIGINT), ("r_reason_id", VarcharType(16)),
        ("r_reason_desc", VarcharType(100)),
    ],
    "income_band": [
        ("ib_income_band_sk", BIGINT), ("ib_lower_bound", INTEGER),
        ("ib_upper_bound", INTEGER),
    ],
    "household_demographics": [
        ("hd_demo_sk", BIGINT), ("hd_income_band_sk", BIGINT),
        ("hd_buy_potential", VarcharType(15)), ("hd_dep_count", INTEGER),
        ("hd_vehicle_count", INTEGER),
    ],
    "customer_demographics": [
        ("cd_demo_sk", BIGINT), ("cd_gender", VarcharType(1)),
        ("cd_marital_status", VarcharType(1)),
        ("cd_education_status", VarcharType(20)),
        ("cd_purchase_estimate", INTEGER),
        ("cd_credit_rating", VarcharType(10)),
        ("cd_dep_count", INTEGER), ("cd_dep_employed_count", INTEGER),
        ("cd_dep_college_count", INTEGER),
    ],
    "time_dim": [
        ("t_time_sk", BIGINT), ("t_time_id", VarcharType(16)),
        ("t_time", INTEGER), ("t_hour", INTEGER), ("t_minute", INTEGER),
        ("t_second", INTEGER), ("t_am_pm", VarcharType(2)),
        ("t_shift", VarcharType(20)), ("t_meal_time", VarcharType(20)),
    ],
    "call_center": [
        ("cc_call_center_sk", BIGINT), ("cc_call_center_id", VarcharType(16)),
        ("cc_name", VarcharType(50)), ("cc_class", VarcharType(50)),
        ("cc_employees", INTEGER), ("cc_manager", VarcharType(40)),
        ("cc_county", VarcharType(30)), ("cc_state", VarcharType(2)),
    ],
    "web_page": [
        ("wp_web_page_sk", BIGINT), ("wp_web_page_id", VarcharType(16)),
        ("wp_url", VarcharType(100)), ("wp_char_count", INTEGER),
        ("wp_link_count", INTEGER),
    ],
}

# every table already carries its spec prefix in the column names
PREFIXES: Dict[str, str] = {t: "" for t in SCHEMAS}


def column_type(table: str, column: str) -> Type:
    for name, typ in SCHEMAS[table]:
        if name == column:
            return typ
    raise KeyError(f"{table}.{column}")


# open-domain (late-materialized) string columns, and which of them have
# row-id-compatible order / identity (see tpch.py for the rules)
OPEN_DOMAIN = {
    ("item", "i_item_id"), ("customer", "c_customer_id"),
    ("item", "i_product_name"), ("item", "i_item_desc"),
    ("store", "s_street_number"), ("store", "s_suite_number"),
    ("customer_address", "ca_street_number"),
    ("customer_address", "ca_suite_number"),
    ("customer", "c_email_address"), ("customer_address", "ca_address_id"),
    ("customer_address", "ca_zip"), ("store", "s_store_id"),
    ("store", "s_zip"),
    ("web_site", "web_site_id"), ("promotion", "p_promo_id"),
    ("catalog_page", "cp_catalog_page_id"), ("ship_mode", "sm_ship_mode_id"),
    ("reason", "r_reason_id"), ("time_dim", "t_time_id"),
    ("call_center", "cc_call_center_id"), ("web_page", "wp_web_page_id"),
}
ROWID_ORDERED = {
    ("item", "i_item_id"), ("customer", "c_customer_id"),
    ("item", "i_product_name"),
    ("customer_address", "ca_address_id"), ("store", "s_store_id"),
    ("web_site", "web_site_id"), ("promotion", "p_promo_id"),
    ("catalog_page", "cp_catalog_page_id"), ("ship_mode", "sm_ship_mode_id"),
    ("reason", "r_reason_id"), ("time_dim", "t_time_id"),
    ("call_center", "cc_call_center_id"), ("web_page", "wp_web_page_id"),
}
ROWID_DISTINCT = {
    ("item", "i_item_id"), ("customer", "c_customer_id"),
    ("item", "i_product_name"),
    ("customer", "c_email_address"), ("customer_address", "ca_address_id"),
    ("store", "s_store_id"), ("web_site", "web_site_id"),
    ("promotion", "p_promo_id"),
    ("catalog_page", "cp_catalog_page_id"), ("ship_mode", "sm_ship_mode_id"),
    ("reason", "r_reason_id"), ("time_dim", "t_time_id"),
    ("call_center", "cc_call_center_id"), ("web_page", "wp_web_page_id"),
}


# ---------------------------------------------------------------------------
# co-bucketed layout for grouped (lifespan) execution (see tpch.py for the
# model): web_sales rows map to order numbers through fixed
# LINES_PER_ORDER blocks, and wr_order_number is generated monotone in the
# row index, so a ws_order_number RANGE is a contiguous ROW RANGE in both
# tables — a bucket is a pair of row-range splits, no repartitioning.
# This is the layout BASELINE config #5 (TPC-DS Q95, whose 72M-row
# web_sales self-join build exhausts HBM at SF100) needs to run one
# lifespan at a time.  Bucket keys are NON-NULL by the catalog contract
# (connectors/catalog.py bucket_column).
# ---------------------------------------------------------------------------

BUCKET_COLUMNS = {"web_sales": "ws_order_number",
                  "web_returns": "wr_order_number"}


def _wr_rows_below(key: int, n_orders: int, n_returns: int) -> int:
    """Number of web_returns rows with wr_order_number < key.  The
    generator maps row idx -> (idx*n_orders)//n_returns + 1, so the first
    row at-or-above `key` is ceil((key-1)*n_returns/n_orders)."""
    k = min(max(key - 1, 0), n_orders)
    return min(n_returns, -(-(k * n_returns) // n_orders))


def bucket_layout(sf: float, n_buckets: int) -> List[TableBucket]:
    """Split the ws_order_number domain into up to n_buckets lifespans;
    the last bucket absorbs any partial tail order of web_sales."""
    n_ws = _table_rows("web_sales", sf)
    n_wr = _table_rows("web_returns", sf)
    n_orders = max(1, n_ws // LINES_PER_ORDER)
    # distinct order numbers (a partial tail block still owns one key)
    n_keys = -(-n_ws // LINES_PER_ORDER)
    if n_buckets <= 1 or n_keys <= 1:
        return [TableBucket(1, n_keys + 1, {"web_sales": (0, n_ws),
                                            "web_returns": (0, n_wr)})]
    per = max(1, -(-n_keys // n_buckets))           # ceil(n_keys / K)
    out: List[TableBucket] = []
    k0 = 1
    while k0 <= n_keys:
        k1 = min(k0 + per, n_keys + 1)
        ws = ((k0 - 1) * LINES_PER_ORDER,
              n_ws if k1 > n_keys else (k1 - 1) * LINES_PER_ORDER)
        wr = (_wr_rows_below(k0, n_orders, n_wr),
              _wr_rows_below(k1, n_orders, n_wr))
        out.append(TableBucket(k0, k1,
                               {"web_sales": ws, "web_returns": wr}))
        k0 = k1
    return out


# ---------------------------------------------------------------------------
# per-table generators (same contract as tpch: numeric ndarray, or
# (codes, values) dictionary, or list[str] for OPEN_DOMAIN columns)
# ---------------------------------------------------------------------------

def _gen_date_dim(column: str, idx: np.ndarray, sf: float):
    days = EPOCH_1900 + idx                       # days since unix epoch
    dt = days.astype("datetime64[D]")
    if column == "d_date_sk":
        return JULIAN_BASE + idx
    if column == "d_date_id":
        return [f"AAAAAAAA{int(v):08d}" for v in JULIAN_BASE + idx]
    if column == "d_date":
        return days
    if column == "d_year":
        return dt.astype("datetime64[Y]").astype(np.int64) + 1970
    if column == "d_moy":
        return (dt.astype("datetime64[M]")
                - dt.astype("datetime64[Y]")).astype(np.int64) + 1
    if column == "d_dom":
        return (dt - dt.astype("datetime64[M]")).astype(np.int64) + 1
    if column == "d_qoy":
        moy = _gen_date_dim("d_moy", idx, sf)
        return (moy - 1) // 3 + 1
    if column == "d_dow":
        return (days + 4) % 7                     # 1970-01-01 was a Thursday
    if column == "d_day_name":
        return (((days + 4) % 7).astype(np.int32), DAY_NAMES)
    if column == "d_month_seq":
        y = _gen_date_dim("d_year", idx, sf)
        m = _gen_date_dim("d_moy", idx, sf)
        return (y - 1900) * 12 + m - 1
    if column == "d_week_seq":
        return idx // 7 + 1
    if column == "d_quarter_seq":
        y = _gen_date_dim("d_year", idx, sf)
        q = _gen_date_dim("d_qoy", idx, sf)
        return (y - 1900) * 4 + q - 1
    if column == "d_quarter_name":
        y = _gen_date_dim("d_year", idx, sf)
        q = _gen_date_dim("d_qoy", idx, sf)
        # closed domain (years x 4): dictionary codes
        names = [f"{yy}Q{qq}" for yy in range(1900, 2101)
                 for qq in range(1, 5)]
        return (((y - 1900) * 4 + q - 1).astype(np.int32), names)
    raise KeyError(column)


def _gen_item(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "i_item_sk":
        return sk
    if column == "i_item_id":
        return [f"AAAAAAAA{int(v):08d}" for v in sk]
    if column == "i_current_price":
        return _uniform("item", "price", idx, 99, 9999)
    if column == "i_brand_id":
        return _uniform("item", "brand", idx, 0, len(BRANDS) - 1) + 1001
    if column == "i_brand":
        return (_uniform("item", "brand", idx, 0,
                         len(BRANDS) - 1).astype(np.int32), BRANDS)
    if column == "i_class_id":
        return _uniform("item", "class", idx, 0, len(CLASSES) - 1) + 1
    if column == "i_class":
        return (_uniform("item", "class", idx, 0,
                         len(CLASSES) - 1).astype(np.int32), CLASSES)
    if column == "i_category_id":
        return _uniform("item", "category", idx, 0, len(CATEGORIES) - 1) + 1
    if column == "i_category":
        return (_uniform("item", "category", idx, 0,
                         len(CATEGORIES) - 1).astype(np.int32), CATEGORIES)
    if column == "i_manufact_id":
        return _uniform("item", "manufact", idx, 1, 1000)
    if column == "i_color":
        return (_uniform("item", "color", idx, 0,
                         len(COLORS) - 1).astype(np.int32), COLORS)
    if column == "i_manager_id":
        return _uniform("item", "manager", idx, 1, 100)
    if column == "i_manufact":
        m = _gen_item("i_manufact_id", idx, sf)
        names = [f"manufact#{i}" for i in range(1001)]
        return (m.astype(np.int32), names)
    if column == "i_product_name":
        return [f"product{int(v):011d}" for v in idx + 1]
    if column == "i_item_desc":
        h = _hash("item", "desc", idx)
        return [f"Item description {int(v) % 10000:04d} text body"
                for v in h]
    if column == "i_size":
        return (_uniform("item", "size", idx, 0, 6).astype(np.int32),
                ["N/A", "petite", "small", "medium", "large",
                 "extra large", "economy"])
    if column == "i_units":
        return (_uniform("item", "units", idx, 0, 4).astype(np.int32),
                ["Each", "Dozen", "Case", "Pallet", "Unknown"])
    if column == "i_wholesale_cost":
        return _uniform("item", "wholesale", idx, 100, 8800)
    raise KeyError(column)


def _gen_customer(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "c_current_cdemo_sk":
        return _uniform("customer", "cdemo", idx, 1,
                        _table_rows("customer_demographics", sf))
    if column == "c_current_hdemo_sk":
        return _uniform("customer", "hdemo", idx, 1,
                        _table_rows("household_demographics", sf))
    if column == "c_customer_sk":
        return sk
    if column == "c_customer_id":
        return [f"AAAAAAAA{int(v):08d}" for v in sk]
    if column == "c_current_addr_sk":
        return _uniform("customer", "addr", idx, 1,
                        _table_rows("customer_address", sf))
    if column == "c_first_name":
        return (_uniform("customer", "first", idx, 0,
                         len(FIRST_NAMES) - 1).astype(np.int32), FIRST_NAMES)
    if column == "c_last_name":
        return (_uniform("customer", "last", idx, 0,
                         len(LAST_NAMES) - 1).astype(np.int32), LAST_NAMES)
    if column == "c_birth_year":
        return _uniform("customer", "byear", idx, 1924, 1992)
    if column == "c_birth_month":
        return _uniform("customer", "bmonth", idx, 1, 12)
    if column == "c_birth_country":
        return (_uniform("customer", "bcountry", idx, 0, 4).astype(np.int32),
                ["UNITED STATES", "CANADA", "MEXICO", "GERMANY", "JAPAN"])
    if column == "c_email_address":
        h = _hash("customer", "email", idx)
        return [f"user{int(v):016x}@example.com" for v in h]
    if column == "c_preferred_cust_flag":
        return (_uniform("customer", "pref", idx, 0, 1).astype(np.int32),
                YN)
    if column == "c_salutation":
        return (_uniform("customer", "salut", idx, 0, 5).astype(np.int32),
                ["Mr.", "Mrs.", "Ms.", "Dr.", "Sir", "Miss"])
    if column == "c_login":
        return (np.zeros(len(idx), dtype=np.int32), [""])
    if column == "c_birth_day":
        return _uniform("customer", "bday", idx, 1, 28)
    if column == "c_first_sales_date_sk":
        return _date_sk_from_offset(
            _uniform("customer", "fsale", idx, SALES_MIN, SALES_MAX))
    if column == "c_first_shipto_date_sk":
        return _gen_customer("c_first_sales_date_sk", idx, sf) \
            + _uniform("customer", "fship", idx, 1, 30)
    if column == "c_last_review_date_sk":
        return _date_sk_from_offset(
            _uniform("customer", "lastrev", idx, SALES_MIN, SALES_MAX))
    raise KeyError(column)


def _gen_customer_address(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "ca_address_sk":
        return sk
    if column == "ca_address_id":
        return [f"AAAAAAAA{int(v):08d}" for v in sk]
    if column == "ca_city":
        return (_uniform("customer_address", "city", idx, 0,
                         len(CITIES) - 1).astype(np.int32), CITIES)
    if column == "ca_county":
        return (_uniform("customer_address", "county", idx, 0,
                         len(COUNTIES) - 1).astype(np.int32), COUNTIES)
    if column == "ca_state":
        return (_uniform("customer_address", "state", idx, 0,
                         len(STATES) - 1).astype(np.int32), STATES)
    if column == "ca_zip":
        z = _uniform("customer_address", "zip", idx, 10000, 99999)
        return [f"{int(v):05d}" for v in z]
    if column == "ca_country":
        return (np.zeros(len(idx), dtype=np.int32), ["United States"])
    if column == "ca_gmt_offset":
        return -100 * _uniform("customer_address", "gmt", idx, 5, 8)
    if column == "ca_street_number":
        n = _uniform("customer_address", "stno", idx, 1, 999)
        return [str(int(v)) for v in n]
    if column == "ca_street_name":
        return (_uniform("customer_address", "stname", idx, 0,
                         len(COUNTIES) - 1).astype(np.int32), COUNTIES)
    if column == "ca_street_type":
        return (_uniform("customer_address", "sttype", idx, 0,
                         4).astype(np.int32),
                ["Street", "Ave", "Blvd", "Ct.", "Lane"])
    if column == "ca_suite_number":
        n = _uniform("customer_address", "suite", idx, 0, 99)
        return [f"Suite {int(v)}" for v in n]
    if column == "ca_location_type":
        return (_uniform("customer_address", "loctype", idx, 0,
                         2).astype(np.int32),
                ["apartment", "condo", "single family"])
    raise KeyError(column)


def _gen_store(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "s_city":
        return (_uniform("store", "city", idx, 0,
                         len(CITIES) - 1).astype(np.int32), CITIES)
    if column == "s_county":
        return (_uniform("store", "county", idx, 0,
                         len(COUNTIES) - 1).astype(np.int32), COUNTIES)
    if column == "s_zip":
        z = _uniform("store", "zip", idx, 10000, 99999)
        return [f"{int(v):05d}" for v in z]
    if column == "s_gmt_offset":
        return -100 * _uniform("store", "gmt", idx, 5, 8)
    if column == "s_store_sk":
        return sk
    if column == "s_store_id":
        return [f"AAAAAAAA{int(v):08d}" for v in sk]
    if column == "s_store_name":
        return (_uniform("store", "name", idx, 0, 9).astype(np.int32),
                ["ought", "able", "pri", "ese", "anti", "cally", "ation",
                 "eing", "n st", "bar"])
    if column == "s_number_employees":
        return _uniform("store", "employees", idx, 200, 300)
    if column == "s_floor_space":
        return _uniform("store", "floor", idx, 5_000_000, 10_000_000)
    if column == "s_market_id":
        return _uniform("store", "market", idx, 1, 10)
    if column == "s_state":
        return (_uniform("store", "state", idx, 0,
                         len(STATES) - 1).astype(np.int32), STATES)
    if column == "s_company_id":
        return np.ones(len(idx), dtype=np.int64)
    if column == "s_street_number":
        n = _uniform("store", "stno", idx, 1, 999)
        return [str(int(v)) for v in n]
    if column == "s_street_name":
        return (_uniform("store", "stname", idx, 0,
                         len(COUNTIES) - 1).astype(np.int32), COUNTIES)
    if column == "s_street_type":
        return (_uniform("store", "sttype", idx, 0, 4).astype(np.int32),
                ["Street", "Ave", "Blvd", "Ct.", "Lane"])
    if column == "s_suite_number":
        n = _uniform("store", "suite", idx, 0, 99)
        return [f"Suite {int(v)}" for v in n]
    if column == "s_company_name":
        return (np.zeros(len(idx), dtype=np.int32), ["Unknown"])
    raise KeyError(column)


def _gen_web_site(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "web_site_sk":
        return sk
    if column == "web_site_id":
        return [f"AAAAAAAA{int(v):08d}" for v in sk]
    if column == "web_name":
        return ((idx % 15).astype(np.int32),
                [f"site_{i}" for i in range(15)])
    if column == "web_company_id":
        return idx % 6 + 1
    if column == "web_company_name":
        return ((idx % 6).astype(np.int32), COMPANY_NAMES)
    raise KeyError(column)


def _gen_warehouse(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "w_warehouse_sk":
        return sk
    if column == "w_warehouse_name":
        return ((idx % 5).astype(np.int32), WAREHOUSE_NAMES)
    if column == "w_warehouse_sq_ft":
        return _uniform("warehouse", "sqft", idx, 50_000, 1_000_000)
    if column == "w_state":
        return ((idx % len(STATES)).astype(np.int32), STATES)
    if column == "w_city":
        return ((idx % len(CITIES)).astype(np.int32), CITIES)
    if column == "w_county":
        return ((idx % len(COUNTIES)).astype(np.int32), COUNTIES)
    if column == "w_country":
        return (np.zeros(len(idx), dtype=np.int32), ["United States"])
    raise KeyError(column)


def _gen_promotion(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "p_promo_sk":
        return sk
    if column == "p_promo_id":
        return [f"AAAAAAAA{int(v):08d}" for v in sk]
    if column in ("p_channel_dmail", "p_channel_email", "p_channel_tv",
                  "p_channel_event", "p_channel_catalog"):
        return (_uniform("promotion", column, idx, 0, 1).astype(np.int32), YN)
    raise KeyError(column)


def _date_sk_from_offset(off: np.ndarray) -> np.ndarray:
    """days-since-1900 offset -> d_date_sk (date_dim row i == offset i)."""
    return JULIAN_BASE + off


def _gen_store_sales(column: str, idx: np.ndarray, sf: float):
    if column == "ss_sold_time_sk":
        return _uniform("store_sales", "time", idx // LINES_PER_ORDER,
                        28800, 75600)      # store hours 8:00-21:00
    if column == "ss_cdemo_sk":
        return _uniform("store_sales", "cdemo", idx // LINES_PER_ORDER, 1,
                        _table_rows("customer_demographics", sf))
    if column == "ss_hdemo_sk":
        return _uniform("store_sales", "hdemo", idx // LINES_PER_ORDER, 1,
                        _table_rows("household_demographics", sf))
    if column == "ss_addr_sk":
        return _uniform("store_sales", "addr", idx // LINES_PER_ORDER, 1,
                        _table_rows("customer_address", sf))
    if column == "ss_ext_list_price":
        return (_gen_store_sales("ss_list_price", idx, sf)
                * _gen_store_sales("ss_quantity", idx, sf))
    if column == "ss_coupon_amt":
        return _uniform("store_sales", "coupon", idx, 0, 50000) \
            * (_uniform("store_sales", "hascoup", idx, 0, 9) == 0)
    if column == "ss_sold_date_sk":
        return _date_sk_from_offset(
            _uniform("store_sales", "sold", idx // LINES_PER_ORDER,
                     SALES_MIN, SALES_MAX))
    if column == "ss_item_sk":
        return _uniform("store_sales", "item", idx, 1, _table_rows("item", sf))
    if column == "ss_customer_sk":
        return _uniform("store_sales", "cust", idx // LINES_PER_ORDER, 1,
                        _table_rows("customer", sf))
    if column == "ss_store_sk":
        return _uniform("store_sales", "store", idx // LINES_PER_ORDER, 1,
                        _table_rows("store", sf))
    if column == "ss_promo_sk":
        return _uniform("store_sales", "promo", idx, 1,
                        _table_rows("promotion", sf))
    if column == "ss_ticket_number":
        return idx // LINES_PER_ORDER + 1
    if column == "ss_quantity":
        return _uniform("store_sales", "qty", idx, 1, 100)
    if column == "ss_wholesale_cost":
        return _uniform("store_sales", "wholesale", idx, 100, 10000)
    if column == "ss_list_price":
        w = _gen_store_sales("ss_wholesale_cost", idx, sf)
        return w + w * _uniform("store_sales", "markup", idx, 0, 200) // 100
    if column == "ss_sales_price":
        lp = _gen_store_sales("ss_list_price", idx, sf)
        return lp * _uniform("store_sales", "dscnt", idx, 20, 100) // 100
    if column == "ss_ext_sales_price":
        return (_gen_store_sales("ss_sales_price", idx, sf)
                * _gen_store_sales("ss_quantity", idx, sf))
    if column == "ss_ext_discount_amt":
        lp = _gen_store_sales("ss_list_price", idx, sf)
        sp = _gen_store_sales("ss_sales_price", idx, sf)
        return (lp - sp) * _gen_store_sales("ss_quantity", idx, sf)
    if column == "ss_net_paid":
        return _gen_store_sales("ss_ext_sales_price", idx, sf)
    if column == "ss_net_profit":
        q = _gen_store_sales("ss_quantity", idx, sf)
        w = _gen_store_sales("ss_wholesale_cost", idx, sf)
        return _gen_store_sales("ss_net_paid", idx, sf) - q * w
    if column == "ss_ext_tax":
        return _gen_store_sales("ss_ext_sales_price", idx, sf) * 9 // 100
    if column == "ss_ext_wholesale_cost":
        return (_gen_store_sales("ss_wholesale_cost", idx, sf)
                * _gen_store_sales("ss_quantity", idx, sf))
    if column == "ss_net_paid_inc_tax":
        return (_gen_store_sales("ss_net_paid", idx, sf)
                + _gen_store_sales("ss_ext_tax", idx, sf))
    raise KeyError(column)


def _gen_web_sales(column: str, idx: np.ndarray, sf: float):
    order = idx // LINES_PER_ORDER
    if column == "ws_ship_mode_sk":
        return _uniform("web_sales", "shipmode", order, 1,
                        _table_rows("ship_mode", sf))
    if column == "ws_sold_date_sk":
        return _date_sk_from_offset(
            _uniform("web_sales", "sold", order, SALES_MIN, SALES_MAX))
    if column == "ws_ship_date_sk":
        sold = _uniform("web_sales", "sold", order, SALES_MIN, SALES_MAX)
        return _date_sk_from_offset(
            sold + _uniform("web_sales", "lag", idx, 1, 120))
    if column == "ws_item_sk":
        return _uniform("web_sales", "item", idx, 1, _table_rows("item", sf))
    if column == "ws_bill_customer_sk":
        return _uniform("web_sales", "cust", order, 1,
                        _table_rows("customer", sf))
    if column == "ws_ship_addr_sk":
        return _uniform("web_sales", "addr", order, 1,
                        _table_rows("customer_address", sf))
    if column == "ws_web_site_sk":
        return _uniform("web_sales", "site", order, 1,
                        _table_rows("web_site", sf))
    if column == "ws_warehouse_sk":
        return _uniform("web_sales", "wh", idx, 1,
                        _table_rows("warehouse", sf))
    if column == "ws_promo_sk":
        return _uniform("web_sales", "promo", idx, 1,
                        _table_rows("promotion", sf))
    if column == "ws_order_number":
        return order + 1
    if column == "ws_quantity":
        return _uniform("web_sales", "qty", idx, 1, 100)
    if column == "ws_sales_price":
        return _uniform("web_sales", "price", idx, 100, 30000)
    if column == "ws_ext_sales_price":
        return (_gen_web_sales("ws_sales_price", idx, sf)
                * _gen_web_sales("ws_quantity", idx, sf))
    if column == "ws_ext_ship_cost":
        return _uniform("web_sales", "shipcost", idx, 0, 50000)
    if column == "ws_net_paid":
        return _gen_web_sales("ws_ext_sales_price", idx, sf)
    if column == "ws_net_profit":
        return (_gen_web_sales("ws_net_paid", idx, sf)
                - _uniform("web_sales", "cost", idx, 50, 40000)
                * _gen_web_sales("ws_quantity", idx, sf))
    if column == "ws_sold_time_sk":
        return _uniform("web_sales", "time", order, 0, 86399)
    if column == "ws_bill_addr_sk":
        return _uniform("web_sales", "baddr", order, 1,
                        _table_rows("customer_address", sf))
    if column == "ws_bill_cdemo_sk":
        return _uniform("web_sales", "bcdemo", order, 1,
                        _table_rows("customer_demographics", sf))
    if column == "ws_bill_hdemo_sk":
        return _uniform("web_sales", "bhdemo", order, 1,
                        _table_rows("household_demographics", sf))
    if column == "ws_ship_customer_sk":
        # usually the buyer, sometimes a gift recipient
        buyer = _gen_web_sales("ws_bill_customer_sk", idx, sf)
        other = _uniform("web_sales", "shipcust", order, 1,
                         _table_rows("customer", sf))
        same = _uniform("web_sales", "shipsame", order, 0, 9) < 7
        return np.where(same, buyer, other)
    if column == "ws_ship_cdemo_sk":
        return _uniform("web_sales", "scdemo", order, 1,
                        _table_rows("customer_demographics", sf))
    if column == "ws_ship_hdemo_sk":
        return _uniform("web_sales", "shdemo", order, 1,
                        _table_rows("household_demographics", sf))
    if column == "ws_web_page_sk":
        return _uniform("web_sales", "page", order, 1,
                        _table_rows("web_page", sf))
    if column == "ws_wholesale_cost":
        return _uniform("web_sales", "wholesale", idx, 100, 10000)
    if column == "ws_list_price":
        w = _gen_web_sales("ws_wholesale_cost", idx, sf)
        return w + w * _uniform("web_sales", "markup", idx, 0, 200) // 100
    if column == "ws_ext_list_price":
        return (_gen_web_sales("ws_list_price", idx, sf)
                * _gen_web_sales("ws_quantity", idx, sf))
    if column == "ws_ext_discount_amt":
        lp = _gen_web_sales("ws_list_price", idx, sf)
        return ((lp - _gen_web_sales("ws_sales_price", idx, sf))
                * _gen_web_sales("ws_quantity", idx, sf)).clip(0)
    if column == "ws_ext_wholesale_cost":
        return (_gen_web_sales("ws_wholesale_cost", idx, sf)
                * _gen_web_sales("ws_quantity", idx, sf))
    if column == "ws_ext_tax":
        return _gen_web_sales("ws_ext_sales_price", idx, sf) * 9 // 100
    if column == "ws_coupon_amt":
        return _uniform("web_sales", "coupon", idx, 0, 50000) \
            * (_uniform("web_sales", "hascoup", idx, 0, 9) == 0)
    if column == "ws_net_paid_inc_tax":
        return (_gen_web_sales("ws_net_paid", idx, sf)
                + _gen_web_sales("ws_ext_tax", idx, sf))
    if column == "ws_net_paid_inc_ship":
        return (_gen_web_sales("ws_net_paid", idx, sf)
                + _gen_web_sales("ws_ext_ship_cost", idx, sf))
    raise KeyError(column)


def _gen_web_returns(column: str, idx: np.ndarray, sf: float):
    n_orders = _table_rows("web_sales", sf) // LINES_PER_ORDER
    if column == "wr_order_number":
        # monotone in the row index so an order-number range is a
        # contiguous web_returns row range (the co-bucket property
        # bucket_layout depends on); strictly increasing whenever
        # n_orders >= n_returns, so returned order numbers are also
        # distinct.  The generator is self-consistent rather than
        # dsdgen-bit-exact, so redefining the draw is fair game — every
        # web_returns test is differential.
        n_returns = _table_rows("web_returns", sf)
        return (idx * max(1, n_orders)) // n_returns + 1
    if column == "wr_returned_date_sk":
        return _date_sk_from_offset(
            _uniform("web_returns", "ret", idx, SALES_MIN, SALES_MAX + 60))
    if column == "wr_item_sk":
        return _uniform("web_returns", "item", idx, 1,
                        _table_rows("item", sf))
    if column == "wr_refunded_customer_sk":
        return _uniform("web_returns", "cust", idx, 1,
                        _table_rows("customer", sf))
    if column == "wr_return_quantity":
        return _uniform("web_returns", "qty", idx, 1, 50)
    if column == "wr_return_amt":
        return _uniform("web_returns", "amt", idx, 100, 500000)
    if column == "wr_net_loss":
        return _uniform("web_returns", "loss", idx, 50, 100000)
    if column == "wr_returning_customer_sk":
        buyer = _gen_web_returns("wr_refunded_customer_sk", idx, sf)
        other = _uniform("web_returns", "rcust", idx, 1,
                         _table_rows("customer", sf))
        same = _uniform("web_returns", "rsame", idx, 0, 9) < 8
        return np.where(same, buyer, other)
    if column == "wr_refunded_addr_sk":
        return _uniform("web_returns", "faddr", idx, 1,
                        _table_rows("customer_address", sf))
    if column == "wr_returning_addr_sk":
        return _uniform("web_returns", "raddr", idx, 1,
                        _table_rows("customer_address", sf))
    if column == "wr_refunded_cdemo_sk":
        return _uniform("web_returns", "fcdemo", idx, 1,
                        _table_rows("customer_demographics", sf))
    if column == "wr_returning_cdemo_sk":
        return _uniform("web_returns", "rcdemo", idx, 1,
                        _table_rows("customer_demographics", sf))
    if column == "wr_refunded_hdemo_sk":
        return _uniform("web_returns", "fhdemo", idx, 1,
                        _table_rows("household_demographics", sf))
    if column == "wr_web_page_sk":
        return _uniform("web_returns", "page", idx, 1,
                        _table_rows("web_page", sf))
    if column == "wr_reason_sk":
        return _uniform("web_returns", "reason", idx, 1,
                        _table_rows("reason", sf))
    if column == "wr_returned_time_sk":
        return _uniform("web_returns", "time", idx, 0, 86399)
    if column == "wr_refunded_cash":
        amt = _gen_web_returns("wr_return_amt", idx, sf)
        return amt * _uniform("web_returns", "cashfrac", idx, 0, 100) // 100
    if column == "wr_reversed_charge":
        amt = _gen_web_returns("wr_return_amt", idx, sf)
        cash = _gen_web_returns("wr_refunded_cash", idx, sf)
        return (amt - cash) // 2
    if column == "wr_account_credit":
        amt = _gen_web_returns("wr_return_amt", idx, sf)
        cash = _gen_web_returns("wr_refunded_cash", idx, sf)
        rev = _gen_web_returns("wr_reversed_charge", idx, sf)
        return amt - cash - rev
    if column == "wr_fee":
        return _uniform("web_returns", "fee", idx, 50, 10000)
    if column == "wr_return_ship_cost":
        return _uniform("web_returns", "shipc", idx, 0, 25000)
    if column == "wr_return_tax":
        return _gen_web_returns("wr_return_amt", idx, sf) * 9 // 100
    if column == "wr_return_amt_inc_tax":
        return (_gen_web_returns("wr_return_amt", idx, sf)
                + _gen_web_returns("wr_return_tax", idx, sf))
    raise KeyError(column)


SM_TYPES = ["EXPRESS", "NEXT DAY", "OVERNIGHT", "REGULAR", "TWO DAY"]
SM_CODES = ["AIR", "SURFACE", "SEA", "SHIP"]
SM_CARRIERS = ["UPS", "FEDEX", "AIRBORNE", "USPS", "DHL", "TBS", "ZHOU",
               "ZOUROS", "MSC", "LATVIAN", "ALLIANCE", "ORIENTAL",
               "BARIAN", "BOXBUNDLES", "CARGO", "DIAMOND", "RUPEKSA",
               "GERMA", "HARMSTORF", "PRIVATECARRIER"]
REASONS = [f"reason {i}" for i in range(1, 36)]
BUY_POTENTIAL = ["0-500", "501-1000", "1001-5000", "5001-10000",
                 ">10000", "Unknown"]
EDUCATION = ["Primary", "Secondary", "College", "2 yr Degree",
             "4 yr Degree", "Advanced Degree", "Unknown"]
CREDIT_RATING = ["Low Risk", "Good", "High Risk", "Unknown"]
DEPARTMENTS = ["DEPARTMENT"]
CC_NAMES = ["NY Metro", "Mid Atlantic", "North Midwest", "California",
            "Pacific Northwest", "Central"]
CC_CLASSES = ["small", "medium", "large"]


def _gen_store_returns(column: str, idx: np.ndarray, sf: float):
    # each return references a deterministic store_sales row (spec: ~10%
    # of tickets are returned), so returned keys join back to real sales
    sale = _uniform("store_returns", "sale", idx, 0,
                    _table_rows("store_sales", sf) - 1)
    if column == "sr_returned_date_sk":
        sold = _gen_store_sales("ss_sold_date_sk", sale, sf)
        return sold + _uniform("store_returns", "lag", idx, 1, 60)
    if column == "sr_item_sk":
        return _gen_store_sales("ss_item_sk", sale, sf)
    if column == "sr_customer_sk":
        return _gen_store_sales("ss_customer_sk", sale, sf)
    if column == "sr_cdemo_sk":
        return _gen_store_sales("ss_cdemo_sk", sale, sf)
    if column == "sr_hdemo_sk":
        return _gen_store_sales("ss_hdemo_sk", sale, sf)
    if column == "sr_store_sk":
        return _gen_store_sales("ss_store_sk", sale, sf)
    if column == "sr_ticket_number":
        return _gen_store_sales("ss_ticket_number", sale, sf)
    if column == "sr_reason_sk":
        return _uniform("store_returns", "reason", idx, 1,
                        _table_rows("reason", sf))
    if column == "sr_return_quantity":
        return _uniform("store_returns", "qty", idx, 1, 50)
    if column == "sr_return_amt":
        return _uniform("store_returns", "amt", idx, 100, 500000)
    if column == "sr_net_loss":
        return _uniform("store_returns", "loss", idx, 50, 100000)
    raise KeyError(column)


def _gen_catalog_sales(column: str, idx: np.ndarray, sf: float):
    order = idx // LINES_PER_ORDER
    if column == "cs_sold_date_sk":
        return _date_sk_from_offset(
            _uniform("catalog_sales", "sold", order, SALES_MIN, SALES_MAX))
    if column == "cs_ship_date_sk":
        sold = _uniform("catalog_sales", "sold", order,
                        SALES_MIN, SALES_MAX)
        return _date_sk_from_offset(sold) \
            + _uniform("catalog_sales", "lag", idx, 2, 90)
    if column == "cs_bill_customer_sk":
        return _uniform("catalog_sales", "cust", order, 1,
                        _table_rows("customer", sf))
    if column == "cs_bill_cdemo_sk":
        return _uniform("catalog_sales", "cdemo", order, 1,
                        _table_rows("customer_demographics", sf))
    if column == "cs_bill_hdemo_sk":
        return _uniform("catalog_sales", "hdemo", order, 1,
                        _table_rows("household_demographics", sf))
    if column == "cs_bill_addr_sk":
        return _uniform("catalog_sales", "baddr", order, 1,
                        _table_rows("customer_address", sf))
    if column == "cs_ship_addr_sk":
        return _uniform("catalog_sales", "saddr", order, 1,
                        _table_rows("customer_address", sf))
    if column == "cs_call_center_sk":
        return _uniform("catalog_sales", "cc", order, 1,
                        _table_rows("call_center", sf))
    if column == "cs_catalog_page_sk":
        return _uniform("catalog_sales", "page", idx, 1,
                        _table_rows("catalog_page", sf))
    if column == "cs_ship_mode_sk":
        return _uniform("catalog_sales", "shipmode", order, 1,
                        _table_rows("ship_mode", sf))
    if column == "cs_warehouse_sk":
        return _uniform("catalog_sales", "wh", idx, 1,
                        _table_rows("warehouse", sf))
    if column == "cs_item_sk":
        return _uniform("catalog_sales", "item", idx, 1,
                        _table_rows("item", sf))
    if column == "cs_promo_sk":
        return _uniform("catalog_sales", "promo", idx, 1,
                        _table_rows("promotion", sf))
    if column == "cs_order_number":
        return order + 1
    if column == "cs_quantity":
        return _uniform("catalog_sales", "qty", idx, 1, 100)
    if column == "cs_wholesale_cost":
        return _uniform("catalog_sales", "wholesale", idx, 100, 10000)
    if column == "cs_list_price":
        w = _gen_catalog_sales("cs_wholesale_cost", idx, sf)
        return w + w * _uniform("catalog_sales", "markup", idx, 0, 200) // 100
    if column == "cs_sales_price":
        lp = _gen_catalog_sales("cs_list_price", idx, sf)
        return lp * _uniform("catalog_sales", "dscnt", idx, 20, 100) // 100
    if column == "cs_ext_discount_amt":
        lp = _gen_catalog_sales("cs_list_price", idx, sf)
        sp = _gen_catalog_sales("cs_sales_price", idx, sf)
        return (lp - sp) * _gen_catalog_sales("cs_quantity", idx, sf)
    if column == "cs_ext_sales_price":
        return (_gen_catalog_sales("cs_sales_price", idx, sf)
                * _gen_catalog_sales("cs_quantity", idx, sf))
    if column == "cs_ext_ship_cost":
        return _uniform("catalog_sales", "shipc", idx, 0, 50000)
    if column == "cs_net_paid":
        return _gen_catalog_sales("cs_ext_sales_price", idx, sf)
    if column == "cs_net_profit":
        q = _gen_catalog_sales("cs_quantity", idx, sf)
        w = _gen_catalog_sales("cs_wholesale_cost", idx, sf)
        return _gen_catalog_sales("cs_net_paid", idx, sf) - q * w
    if column == "cs_sold_time_sk":
        return _uniform("catalog_sales", "time", order, 0, 86399)
    if column == "cs_ship_customer_sk":
        buyer = _gen_catalog_sales("cs_bill_customer_sk", idx, sf)
        other = _uniform("catalog_sales", "shipcust", order, 1,
                         _table_rows("customer", sf))
        same = _uniform("catalog_sales", "shipsame", order, 0, 9) < 7
        return np.where(same, buyer, other)
    if column == "cs_ship_cdemo_sk":
        return _uniform("catalog_sales", "scdemo", order, 1,
                        _table_rows("customer_demographics", sf))
    if column == "cs_ship_hdemo_sk":
        return _uniform("catalog_sales", "shdemo", order, 1,
                        _table_rows("household_demographics", sf))
    if column == "cs_coupon_amt":
        return _uniform("catalog_sales", "coupon", idx, 0, 50000) \
            * (_uniform("catalog_sales", "hascoup", idx, 0, 9) == 0)
    if column == "cs_ext_list_price":
        return (_gen_catalog_sales("cs_list_price", idx, sf)
                * _gen_catalog_sales("cs_quantity", idx, sf))
    if column == "cs_ext_wholesale_cost":
        return (_gen_catalog_sales("cs_wholesale_cost", idx, sf)
                * _gen_catalog_sales("cs_quantity", idx, sf))
    if column == "cs_ext_tax":
        return _gen_catalog_sales("cs_ext_sales_price", idx, sf) * 9 // 100
    if column == "cs_net_paid_inc_tax":
        return (_gen_catalog_sales("cs_net_paid", idx, sf)
                + _gen_catalog_sales("cs_ext_tax", idx, sf))
    if column == "cs_net_paid_inc_ship":
        return (_gen_catalog_sales("cs_net_paid", idx, sf)
                + _gen_catalog_sales("cs_ext_ship_cost", idx, sf))
    if column == "cs_net_paid_inc_ship_tax":
        return (_gen_catalog_sales("cs_net_paid_inc_ship", idx, sf)
                + _gen_catalog_sales("cs_ext_tax", idx, sf))
    raise KeyError(column)


def _gen_catalog_returns(column: str, idx: np.ndarray, sf: float):
    sale = _uniform("catalog_returns", "sale", idx, 0,
                    _table_rows("catalog_sales", sf) - 1)
    if column == "cr_returned_date_sk":
        sold = _gen_catalog_sales("cs_sold_date_sk", sale, sf)
        return sold + _uniform("catalog_returns", "lag", idx, 1, 60)
    if column == "cr_item_sk":
        return _gen_catalog_sales("cs_item_sk", sale, sf)
    if column == "cr_refunded_customer_sk":
        return _gen_catalog_sales("cs_bill_customer_sk", sale, sf)
    if column == "cr_returning_customer_sk":
        # 80% returned by the buyer, else a random customer
        buyer = _gen_catalog_sales("cs_bill_customer_sk", sale, sf)
        other = _uniform("catalog_returns", "other", idx, 1,
                         _table_rows("customer", sf))
        same = _uniform("catalog_returns", "same", idx, 0, 9) < 8
        return np.where(same, buyer, other)
    if column == "cr_call_center_sk":
        return _gen_catalog_sales("cs_call_center_sk", sale, sf)
    if column == "cr_reason_sk":
        return _uniform("catalog_returns", "reason", idx, 1,
                        _table_rows("reason", sf))
    if column == "cr_order_number":
        return _gen_catalog_sales("cs_order_number", sale, sf)
    if column == "cr_return_quantity":
        return _uniform("catalog_returns", "qty", idx, 1, 50)
    if column == "cr_return_amount":
        return _uniform("catalog_returns", "amt", idx, 100, 500000)
    if column == "cr_net_loss":
        return _uniform("catalog_returns", "loss", idx, 50, 100000)
    if column == "cr_catalog_page_sk":
        return _gen_catalog_sales("cs_catalog_page_sk", sale, sf)
    if column == "cr_refunded_addr_sk":
        return _gen_catalog_sales("cs_bill_addr_sk", sale, sf)
    if column == "cr_returning_addr_sk":
        return _uniform("catalog_returns", "raddr", idx, 1,
                        _table_rows("customer_address", sf))
    if column == "cr_refunded_cash":
        amt = _gen_catalog_returns("cr_return_amount", idx, sf)
        return amt * _uniform("catalog_returns", "cashfrac", idx,
                              0, 100) // 100
    if column == "cr_reversed_charge":
        amt = _gen_catalog_returns("cr_return_amount", idx, sf)
        cash = _gen_catalog_returns("cr_refunded_cash", idx, sf)
        return (amt - cash) // 2
    if column == "cr_store_credit":
        amt = _gen_catalog_returns("cr_return_amount", idx, sf)
        cash = _gen_catalog_returns("cr_refunded_cash", idx, sf)
        rev = _gen_catalog_returns("cr_reversed_charge", idx, sf)
        return amt - cash - rev
    if column == "cr_fee":
        return _uniform("catalog_returns", "fee", idx, 50, 10000)
    if column == "cr_return_ship_cost":
        return _uniform("catalog_returns", "shipc", idx, 0, 25000)
    if column == "cr_return_tax":
        return _gen_catalog_returns("cr_return_amount", idx, sf) * 9 // 100
    if column == "cr_return_amt_inc_tax":
        return (_gen_catalog_returns("cr_return_amount", idx, sf)
                + _gen_catalog_returns("cr_return_tax", idx, sf))
    if column == "cr_warehouse_sk":
        return _gen_catalog_sales("cs_warehouse_sk", sale, sf)
    raise KeyError(column)


def _gen_inventory(column: str, idx: np.ndarray, sf: float):
    n_wh = _table_rows("warehouse", sf)
    n_item = _table_rows("item", sf)
    if column == "inv_warehouse_sk":
        return idx % n_wh + 1
    if column == "inv_item_sk":
        return (idx // n_wh) % n_item + 1
    if column == "inv_date_sk":
        week = idx // (n_wh * n_item)
        return JULIAN_BASE + (_days("1998-01-01") - EPOCH_1900) + week * 7
    if column == "inv_quantity_on_hand":
        return _uniform("inventory", "qoh", idx, 0, 1000)
    raise KeyError(column)


def _gen_catalog_page(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "cp_catalog_page_sk":
        return sk
    if column == "cp_catalog_page_id":
        return [f"AAAAAAAA{int(v):08d}" for v in sk]
    if column == "cp_department":
        return (np.zeros(len(idx), dtype=np.int32), DEPARTMENTS)
    if column == "cp_catalog_number":
        return idx // 108 + 1
    if column == "cp_catalog_page_number":
        return idx % 108 + 1
    raise KeyError(column)


def _gen_ship_mode(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "sm_ship_mode_sk":
        return sk
    if column == "sm_ship_mode_id":
        return [f"AAAAAAAA{int(v):08d}" for v in sk]
    if column == "sm_type":
        return ((idx % len(SM_TYPES)).astype(np.int32), SM_TYPES)
    if column == "sm_code":
        return ((idx // 5 % len(SM_CODES)).astype(np.int32), SM_CODES)
    if column == "sm_carrier":
        return ((idx % len(SM_CARRIERS)).astype(np.int32), SM_CARRIERS)
    raise KeyError(column)


def _gen_reason(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "r_reason_sk":
        return sk
    if column == "r_reason_id":
        return [f"AAAAAAAA{int(v):08d}" for v in sk]
    if column == "r_reason_desc":
        return ((idx % len(REASONS)).astype(np.int32), REASONS)
    raise KeyError(column)


def _gen_income_band(column: str, idx: np.ndarray, sf: float):
    if column == "ib_income_band_sk":
        return idx + 1
    if column == "ib_lower_bound":
        return idx * 10000 + 1
    if column == "ib_upper_bound":
        return (idx + 1) * 10000
    raise KeyError(column)


def _gen_household_demographics(column: str, idx: np.ndarray, sf: float):
    # cross product: income_band(20) x buy_potential(6) x dep(10) x veh(6)
    if column == "hd_demo_sk":
        return idx + 1
    if column == "hd_income_band_sk":
        return idx % 20 + 1
    if column == "hd_buy_potential":
        return ((idx // 20 % 6).astype(np.int32), BUY_POTENTIAL)
    if column == "hd_dep_count":
        return idx // 120 % 10
    if column == "hd_vehicle_count":
        return idx // 1200 % 6 - 1       # -1..4 per spec
    raise KeyError(column)


def _gen_customer_demographics(column: str, idx: np.ndarray, sf: float):
    # spec layout: cross product over gender(2) x marital(5) x
    # education(7) x purchase_estimate(20) x credit(4) x deps(7) x ...
    if column == "cd_demo_sk":
        return idx + 1
    if column == "cd_gender":
        return ((idx % 2).astype(np.int32), ["M", "F"])
    if column == "cd_marital_status":
        return ((idx // 2 % 5).astype(np.int32), ["M", "S", "D", "W", "U"])
    if column == "cd_education_status":
        return ((idx // 10 % 7).astype(np.int32), EDUCATION)
    if column == "cd_purchase_estimate":
        return (idx // 70 % 20 + 1) * 500
    if column == "cd_credit_rating":
        return ((idx // 1400 % 4).astype(np.int32), CREDIT_RATING)
    if column == "cd_dep_count":
        return idx // 5600 % 7
    if column == "cd_dep_employed_count":
        return idx // 39200 % 7
    if column == "cd_dep_college_count":
        return idx // 274400 % 7
    raise KeyError(column)


def _gen_time_dim(column: str, idx: np.ndarray, sf: float):
    if column == "t_time_sk":
        return idx
    if column == "t_time_id":
        return [f"AAAAAAAA{int(v):08d}" for v in idx]
    if column == "t_time":
        return idx
    if column == "t_hour":
        return idx // 3600
    if column == "t_minute":
        return idx // 60 % 60
    if column == "t_second":
        return idx % 60
    if column == "t_am_pm":
        return ((idx // 43200).astype(np.int32), ["AM", "PM"])
    if column == "t_shift":
        return ((idx // 28800).astype(np.int32),
                ["third", "first", "second"])
    if column == "t_meal_time":
        h = idx // 3600
        code = np.where((h >= 6) & (h <= 8), 1,
                        np.where((h >= 11) & (h <= 13), 2,
                                 np.where((h >= 17) & (h <= 19), 3, 0)))
        return (code.astype(np.int32),
                ["", "breakfast", "lunch", "dinner"])
    raise KeyError(column)


def _gen_call_center(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "cc_call_center_sk":
        return sk
    if column == "cc_call_center_id":
        return [f"AAAAAAAA{int(v):08d}" for v in sk]
    if column == "cc_name":
        return ((idx % len(CC_NAMES)).astype(np.int32), CC_NAMES)
    if column == "cc_class":
        return ((idx % len(CC_CLASSES)).astype(np.int32), CC_CLASSES)
    if column == "cc_employees":
        return _uniform("call_center", "emp", idx, 1, 7)
    if column == "cc_manager":
        return (_uniform("call_center", "mgr", idx, 0,
                         len(FIRST_NAMES) - 1).astype(np.int32), FIRST_NAMES)
    if column == "cc_county":
        return (_uniform("call_center", "county", idx, 0,
                         len(COUNTIES) - 1).astype(np.int32), COUNTIES)
    if column == "cc_state":
        return (_uniform("call_center", "state", idx, 0,
                         len(STATES) - 1).astype(np.int32), STATES)
    raise KeyError(column)


def _gen_web_page(column: str, idx: np.ndarray, sf: float):
    sk = idx + 1
    if column == "wp_web_page_sk":
        return sk
    if column == "wp_web_page_id":
        return [f"AAAAAAAA{int(v):08d}" for v in sk]
    if column == "wp_url":
        return (np.zeros(len(idx), dtype=np.int32),
                ["http://www.foo.com"])
    if column == "wp_char_count":
        return _uniform("web_page", "chars", idx, 100, 8000)
    if column == "wp_link_count":
        return _uniform("web_page", "links", idx, 2, 25)
    raise KeyError(column)


_GENERATORS = {
    "date_dim": _gen_date_dim, "item": _gen_item, "customer": _gen_customer,
    "customer_address": _gen_customer_address, "store": _gen_store,
    "web_site": _gen_web_site, "warehouse": _gen_warehouse,
    "promotion": _gen_promotion, "store_sales": _gen_store_sales,
    "web_sales": _gen_web_sales, "web_returns": _gen_web_returns,
    "store_returns": _gen_store_returns,
    "catalog_sales": _gen_catalog_sales,
    "catalog_returns": _gen_catalog_returns,
    "inventory": _gen_inventory, "catalog_page": _gen_catalog_page,
    "ship_mode": _gen_ship_mode, "reason": _gen_reason,
    "income_band": _gen_income_band,
    "household_demographics": _gen_household_demographics,
    "customer_demographics": _gen_customer_demographics,
    "time_dim": _gen_time_dim, "call_center": _gen_call_center,
    "web_page": _gen_web_page,
}


# ---------------------------------------------------------------------------
# public connector API (same shape as tpch's)
# ---------------------------------------------------------------------------

def table_row_count(table: str, sf: float) -> int:
    return _table_rows(table, sf)


def generate_column(table: str, column: str, sf: float,
                    start: int, count: int):
    idx = np.arange(start, start + count, dtype=np.int64)
    return _GENERATORS[table](column, idx, sf)


def generate_values_at(table: str, column: str, sf: float,
                       ids: np.ndarray) -> list:
    out = _GENERATORS[table](column, np.asarray(ids, dtype=np.int64), sf)
    if isinstance(out, tuple):
        codes, values = out
        return [values[int(c)] for c in codes]
    return out


def generate_dictionary_at(table: str, column: str, sf: float,
                           ids: np.ndarray):
    """As tpch.generate_dictionary_at."""
    out = _GENERATORS[table](column, np.asarray(ids, dtype=np.int64), sf)
    return out if isinstance(out, tuple) else None


def _connector_stats(handle) -> float:
    sf = dict(handle.extra).get("scaleFactor", 0.01)
    return float(table_row_count(handle.table_name, sf))


from ..sql.fragmenter import register_connector_stats as _reg_stats  # noqa: E402

_reg_stats("tpcds", _connector_stats)
