"""TPC-H connector: deterministic in-memory columnar data generator.

Plays the role of the reference's presto-tpch connector
(presto-tpch/.../TpchConnectorFactory.java:32, TpchRecordSet, TpchSplitManager):
a storage-free, deterministic data source that all conformance suites and
benchmarks run on.  Unlike the reference (which wraps io.airlift.tpch, a port
of dbgen), this generator is counter-hash based: every cell is a pure function
of (table, column, row index, scale factor), so any row range can be produced
independently — splits need no shared state, and workers can generate their
own shards directly into device memory.

Row counts match the TPC-H spec per scale factor (6M lineitem / 1.5M orders /
200k part / 800k partsupp / 150k customer / 10k supplier per SF; fixed 25
nations / 5 regions).  Value domains and formulas follow the public TPC-H
specification (retail price formula, date ranges, flag rules); text columns
use the spec's value lists.  The data is NOT bit-identical to dbgen — parity
testing is differential (TPU engine vs the numpy reference executor on the
same generated data), mirroring how the reference tests Presto vs H2
(presto-tests/.../QueryAssertions.java:52).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..common.types import (BIGINT, DATE, DOUBLE, INTEGER, Type, DecimalType,
                            VarcharType)
from ..common.block import (DictionaryBlock, FixedWidthBlock,
                            VariableWidthBlock)
from ..common.page import Page

# ---------------------------------------------------------------------------
# counter-based hashing (splitmix64), vectorized
# ---------------------------------------------------------------------------

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        z = (x + _GOLDEN).astype(np.uint64)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


_SEED_CACHE: Dict[Tuple[str, str], np.uint64] = {}


def _stream_seed(table: str, column: str) -> np.uint64:
    """Process-independent seed (builtin hash() is randomized per process,
    which would make workers generate different data for the same rows)."""
    key = (table, column)
    seed = _SEED_CACHE.get(key)
    if seed is None:
        import hashlib
        digest = hashlib.blake2b(f"{table}.{column}".encode(),
                                 digest_size=8).digest()
        seed = np.uint64(int.from_bytes(digest, "little"))
        _SEED_CACHE[key] = seed
    return seed


def _cell_hash(table: str, column: str, idx: np.ndarray) -> np.ndarray:
    """Deterministic 64-bit hash per row for a (table, column) stream."""
    seed = _stream_seed(table, column)
    with np.errstate(over="ignore"):
        return _splitmix64(idx.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + seed)


def _uniform(table, column, idx, lo, hi):
    """Uniform integer in [lo, hi] inclusive."""
    h = _cell_hash(table, column, idx)
    span = np.uint64(hi - lo + 1)
    return (h % span).astype(np.int64) + lo


# ---------------------------------------------------------------------------
# dates
# ---------------------------------------------------------------------------

def _days(datestr: str) -> int:
    return int(np.datetime64(datestr, "D").astype(np.int64))


MIN_ORDER_DATE = _days("1992-01-01")
MAX_ORDER_DATE = _days("1998-08-02") - 151
CURRENT_DATE = _days("1995-06-17")

# ---------------------------------------------------------------------------
# value lists (TPC-H spec §4.2.2.13)
# ---------------------------------------------------------------------------

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
NATIONS = [  # (name, regionkey)
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
TYPE_SYLL1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_SYLL2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_SYLL3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINER_SYLL1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_SYLL2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
RETURN_FLAGS = ["A", "N", "R"]
STATUSES = ["F", "O"]
ORDER_STATUSES = ["F", "O", "P"]
COMMENT_WORDS = [
    "blithely", "carefully", "express", "regular", "final", "ironic",
    "pending", "furiously", "quickly", "bold", "even", "special", "silent",
    "deposits", "packages", "requests", "accounts", "theodolites", "pinto",
    "beans", "foxes", "dependencies", "instructions", "platelets", "asymptotes",
]

# the spec's P_NAME word source (dbgen dists.dss "colors", 92 entries):
# part names are 5 words drawn from this list, so LIKE filters over colors
# (q9 '%green%', q20 'forest%') select at spec-like rates
P_NAME_WORDS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished",
    "chartreuse", "chiffon", "chocolate", "coral", "cornflower", "cornsilk",
    "cream", "cyan", "dark", "deep", "dim", "dodger", "drab", "firebrick",
    "floral", "forest", "frosted", "gainsboro", "ghost", "goldenrod",
    "green", "grey", "honeydew", "hot", "indian", "ivory", "khaki", "lace",
    "lavender", "lawn", "lemon", "light", "lime", "linen", "magenta",
    "maroon", "medium", "metallic", "midnight", "mint", "misty", "moccasin",
    "navajo", "navy", "olive", "orange", "orchid", "pale", "papaya",
    "peach", "peru", "pink", "plum", "powder", "puff", "purple", "red",
    "rose", "rosy", "royal", "saddle", "salmon", "sandy", "seashell",
    "sienna", "sky", "slate", "smoke", "snow", "spring", "steel", "tan",
    "thistle", "tomato", "turquoise", "violet", "wheat", "white", "yellow",
]

LINES_PER_ORDER = 4  # AVERAGE fanout: 6M lineitems / 1.5M orders per SF

# Variable lines-per-order with a closed-form row mapping: each block of 7
# consecutive orders carries exactly 28 lineitems, split 1..7 per order by
# a hash-chosen permutation (dbgen draws counts uniform 1..7 per order; the
# fixed block sum keeps idx -> orderkey a pure function, which the
# device-side generator needs).  Orders past the last full block (at most
# 6) keep the fixed fanout of 4 so total rows stay exactly 4 * orders.
_LI_PERMS = None
_LI_CUM = None


def _li_perm_tables():
    global _LI_PERMS, _LI_CUM
    if _LI_CUM is None:
        import itertools
        _LI_PERMS = np.array(list(itertools.permutations(range(1, 8))),
                             dtype=np.int64)                 # (5040, 7)
        _LI_CUM = np.concatenate(
            [np.zeros((5040, 1), dtype=np.int64),
             np.cumsum(_LI_PERMS, axis=1)], axis=1)          # (5040, 8)
    return _LI_PERMS, _LI_CUM


def _li_order_map(idx: np.ndarray, sf: float):
    """lineitem row index -> (orderkey, linenumber), vectorized."""
    _, cum = _li_perm_tables()
    n_orders = _table_rows("orders", sf)
    full = (n_orders // 7) * 28
    b = idx // 28
    r = idx % 28
    pid = (_cell_hash("lineitem", "orderblock", b)
           % np.uint64(5040)).astype(np.int64)
    crows = cum[pid]                                         # (n, 8)
    pos = (r[:, None] >= crows[:, 1:]).sum(axis=1)           # 0..6
    start = np.take_along_axis(crows, pos[:, None], axis=1)[:, 0]
    orderkey = b * 7 + pos + 1
    linenumber = r - start + 1
    tail = idx >= full
    if tail.any():
        t = idx - full
        orderkey = np.where(tail, (n_orders // 7) * 7 + t // 4 + 1,
                            orderkey)
        linenumber = np.where(tail, t % 4 + 1, linenumber)
    return orderkey, linenumber


def _table_rows(table: str, sf: float) -> int:
    base = {
        "lineitem": 6_000_000, "orders": 1_500_000, "customer": 150_000,
        "part": 200_000, "partsupp": 800_000, "supplier": 10_000,
    }
    if table == "nation":
        return 25
    if table == "region":
        return 5
    return int(base[table] * sf)


# ---------------------------------------------------------------------------
# schemas
# ---------------------------------------------------------------------------

D12_2 = DecimalType(12, 2)

SCHEMAS: Dict[str, List[Tuple[str, Type]]] = {
    "lineitem": [
        ("orderkey", BIGINT), ("partkey", BIGINT), ("suppkey", BIGINT),
        ("linenumber", INTEGER), ("quantity", D12_2),
        ("extendedprice", D12_2), ("discount", D12_2), ("tax", D12_2),
        ("returnflag", VarcharType(1)), ("linestatus", VarcharType(1)),
        ("shipdate", DATE), ("commitdate", DATE), ("receiptdate", DATE),
        ("shipinstruct", VarcharType(25)), ("shipmode", VarcharType(10)),
        ("comment", VarcharType(44)),
    ],
    "orders": [
        ("orderkey", BIGINT), ("custkey", BIGINT),
        ("orderstatus", VarcharType(1)), ("totalprice", D12_2),
        ("orderdate", DATE), ("orderpriority", VarcharType(15)),
        ("clerk", VarcharType(15)), ("shippriority", INTEGER),
        ("comment", VarcharType(79)),
    ],
    "customer": [
        ("custkey", BIGINT), ("name", VarcharType(25)),
        ("address", VarcharType(40)), ("nationkey", BIGINT),
        ("phone", VarcharType(15)), ("acctbal", D12_2),
        ("mktsegment", VarcharType(10)), ("comment", VarcharType(117)),
    ],
    "part": [
        ("partkey", BIGINT), ("name", VarcharType(55)),
        ("mfgr", VarcharType(25)), ("brand", VarcharType(10)),
        ("type", VarcharType(25)), ("size", INTEGER),
        ("container", VarcharType(10)), ("retailprice", D12_2),
        ("comment", VarcharType(23)),
    ],
    "partsupp": [
        ("partkey", BIGINT), ("suppkey", BIGINT), ("availqty", INTEGER),
        ("supplycost", D12_2), ("comment", VarcharType(199)),
    ],
    "supplier": [
        ("suppkey", BIGINT), ("name", VarcharType(25)),
        ("address", VarcharType(40)), ("nationkey", BIGINT),
        ("phone", VarcharType(15)), ("acctbal", D12_2),
        ("comment", VarcharType(101)),
    ],
    "nation": [
        ("nationkey", BIGINT), ("name", VarcharType(25)),
        ("regionkey", BIGINT), ("comment", VarcharType(152)),
    ],
    "region": [
        ("regionkey", BIGINT), ("name", VarcharType(25)),
        ("comment", VarcharType(152)),
    ],
}


# query-text column prefix per table (canonical l_quantity -> quantity)
PREFIXES: Dict[str, str] = {
    "lineitem": "l_", "orders": "o_", "customer": "c_", "part": "p_",
    "partsupp": "ps_", "supplier": "s_", "nation": "n_", "region": "r_",
}


def column_type(table: str, column: str) -> Type:
    for name, typ in SCHEMAS[table]:
        if name == column:
            return typ
    raise KeyError(f"{table}.{column}")


# ---------------------------------------------------------------------------
# column generators.  Each returns either:
#   numpy int array           (bigint/int/date/decimal-unscaled)
#   (codes, value_list)       low-cardinality varchar as dictionary
#   list[str]                 formulaic varchar
# ---------------------------------------------------------------------------

def _retail_price(partkey: np.ndarray) -> np.ndarray:
    # spec: (90000 + ((partkey/10) % 20001) + 100*(partkey % 1000)) / 100
    return (90000 + ((partkey // 10) % 20001) + 100 * (partkey % 1000))


def _order_date(orderkey: np.ndarray) -> np.ndarray:
    return _uniform("orders", "orderdate", orderkey,
                    MIN_ORDER_DATE, MAX_ORDER_DATE)


def _comment(table: str, idx: np.ndarray, nwords: int = 4) -> list:
    h = _cell_hash(table, "comment", idx)
    w = len(COMMENT_WORDS)
    parts = []
    for k in range(nwords):
        parts.append((h >> np.uint64(8 * k)) % np.uint64(w))
    arr = np.stack(parts, axis=1)
    return [" ".join(COMMENT_WORDS[int(j)] for j in row) for row in arr]


def _gen_lineitem(column: str, idx: np.ndarray, sf: float):
    # (orderkey, linenumber) only where needed — the map costs a hash +
    # permutation gather per row, pure waste for order-independent columns
    if column == "orderkey":
        return _li_order_map(idx, sf)[0]
    if column == "linenumber":
        return _li_order_map(idx, sf)[1].astype(np.int64)
    if column == "partkey":
        return _uniform("lineitem", "partkey", idx, 1, _table_rows("part", sf))
    if column == "suppkey":
        # spec-style scattering keeps part->supp association lumpy
        partkey = _gen_lineitem("partkey", idx, sf)
        s = _table_rows("supplier", sf)
        j = _uniform("lineitem", "suppj", idx, 0, 3)
        return ((partkey + j * (s // 4 + (partkey - 1) // s)) % s) + 1
    if column == "quantity":
        return _uniform("lineitem", "quantity", idx, 1, 50) * 100
    if column == "extendedprice":
        partkey = _gen_lineitem("partkey", idx, sf)
        qty = _uniform("lineitem", "quantity", idx, 1, 50)
        return qty * _retail_price(partkey)
    if column == "discount":
        return _uniform("lineitem", "discount", idx, 0, 10)
    if column == "tax":
        return _uniform("lineitem", "tax", idx, 0, 8)
    if column == "shipdate":
        od = _order_date(_li_order_map(idx, sf)[0])
        return od + _uniform("lineitem", "shipdays", idx, 1, 121)
    if column == "commitdate":
        od = _order_date(_li_order_map(idx, sf)[0])
        return od + _uniform("lineitem", "commitdays", idx, 30, 90)
    if column == "receiptdate":
        sd = _gen_lineitem("shipdate", idx, sf)
        return sd + _uniform("lineitem", "receiptdays", idx, 1, 30)
    if column == "returnflag":
        rd = _gen_lineitem("receiptdate", idx, sf)
        coin = _uniform("lineitem", "rflagcoin", idx, 0, 1)
        codes = np.where(rd <= CURRENT_DATE, coin * 2, 1)  # A/R if old, else N
        return codes.astype(np.int32), RETURN_FLAGS
    if column == "linestatus":
        sd = _gen_lineitem("shipdate", idx, sf)
        return (sd > CURRENT_DATE).astype(np.int32), STATUSES
    if column == "shipinstruct":
        return (_uniform("lineitem", "instruct", idx, 0, 3).astype(np.int32),
                INSTRUCTIONS)
    if column == "shipmode":
        return (_uniform("lineitem", "shipmode", idx, 0, 6).astype(np.int32),
                MODES)
    if column == "comment":
        return _comment("lineitem", idx, 3)
    raise KeyError(column)


def _gen_orders(column: str, idx: np.ndarray, sf: float):
    orderkey = idx + 1
    if column == "orderkey":
        return orderkey
    if column == "custkey":
        # spec excludes custkeys % 3 == 0 (a third of customers have no
        # orders): raw 1,2,3,4.. -> 1,2,4,5,7,8..
        c = _table_rows("customer", sf)
        raw = _uniform("orders", "custkey", idx, 1, c // 3 * 2)
        return raw + (raw - 1) // 2 if c >= 3 else raw
    if column == "orderstatus":
        # F if all lines shipped (order fully before cutoff), O if none, else P
        od = _order_date(orderkey)
        codes = np.where(od + 121 <= CURRENT_DATE, 0,
                         np.where(od > CURRENT_DATE, 1, 2))
        return codes.astype(np.int32), ORDER_STATUSES
    if column == "totalprice":
        # plausible magnitude; self-consistent, not dbgen-exact (see module doc)
        return _uniform("orders", "totalprice", idx, 90000, 50000000)
    if column == "orderdate":
        return _order_date(orderkey)
    if column == "orderpriority":
        return (_uniform("orders", "priority", idx, 0, 4).astype(np.int32),
                PRIORITIES)
    if column == "clerk":
        k = _uniform("orders", "clerk", idx, 1, max(1, int(1000 * sf)))
        return [f"Clerk#{int(v):09d}" for v in k]
    if column == "shippriority":
        return np.zeros(len(idx), dtype=np.int64)
    if column == "comment":
        return _comment("orders", idx, 5)
    raise KeyError(column)


def _gen_customer(column: str, idx: np.ndarray, sf: float):
    custkey = idx + 1
    if column == "custkey":
        return custkey
    if column == "name":
        return [f"Customer#{int(v):09d}" for v in custkey]
    if column == "address":
        h = _cell_hash("customer", "address", idx)
        return [f"addr-{int(v):016x}" for v in h]
    if column == "nationkey":
        return _uniform("customer", "nationkey", idx, 0, 24)
    if column == "phone":
        nk = _gen_customer("nationkey", idx, sf)
        h1 = _uniform("customer", "ph1", idx, 100, 999)
        h2 = _uniform("customer", "ph2", idx, 100, 999)
        h3 = _uniform("customer", "ph3", idx, 1000, 9999)
        return [f"{10 + int(n)}-{int(a)}-{int(b)}-{int(c)}"
                for n, a, b, c in zip(nk, h1, h2, h3)]
    if column == "acctbal":
        return _uniform("customer", "acctbal", idx, -99999, 999999)
    if column == "mktsegment":
        return (_uniform("customer", "segment", idx, 0, 4).astype(np.int32),
                SEGMENTS)
    if column == "comment":
        return _comment("customer", idx, 6)
    raise KeyError(column)


# closed part-type domains (dictionary-encoded: stable codes table-wide)
MFGRS = [f"Manufacturer#{i}" for i in range(1, 6)]
BRANDS = [f"Brand#{m}{b}" for m in range(1, 6) for b in range(1, 6)]
TYPES = [f"{a} {b} {c}" for a in TYPE_SYLL1 for b in TYPE_SYLL2
         for c in TYPE_SYLL3]
CONTAINERS = [f"{a} {b}" for a in CONTAINER_SYLL1 for b in CONTAINER_SYLL2]


def _gen_part(column: str, idx: np.ndarray, sf: float):
    partkey = idx + 1
    if column == "partkey":
        return partkey
    if column == "name":
        # 5 words from the 92-entry P_NAME list (spec 4.2.3: P_NAME is a
        # concatenation of 5 variable-length words)
        h = _cell_hash("part", "name", idx)
        w = np.uint64(len(P_NAME_WORDS))
        cols = [(h >> np.uint64(8 * k)) % w for k in range(5)]
        arr = np.stack(cols, axis=1)
        return [" ".join(P_NAME_WORDS[int(j)] for j in row) for row in arr]
    if column == "mfgr":
        m = _uniform("part", "mfgr", idx, 1, 5)
        return ((m - 1).astype(np.int32), MFGRS)
    if column == "brand":
        m = _uniform("part", "mfgr", idx, 1, 5)
        b = _uniform("part", "brand", idx, 1, 5)
        return (((m - 1) * 5 + (b - 1)).astype(np.int32), BRANDS)
    if column == "type":
        h = _cell_hash("part", "type", idx)
        a = h % 6
        b = (h >> np.uint64(8)) % 5
        c = (h >> np.uint64(16)) % 5
        return ((a * 25 + b * 5 + c).astype(np.int32), TYPES)
    if column == "size":
        return _uniform("part", "size", idx, 1, 50)
    if column == "container":
        h = _cell_hash("part", "container", idx)
        a = h % 5
        b = (h >> np.uint64(8)) % 8
        return ((a * 8 + b).astype(np.int32), CONTAINERS)
    if column == "retailprice":
        return _retail_price(partkey)
    if column == "comment":
        return _comment("part", idx, 2)
    raise KeyError(column)


def _gen_partsupp(column: str, idx: np.ndarray, sf: float):
    # 4 suppliers per part
    partkey = idx // 4 + 1
    if column == "partkey":
        return partkey
    if column == "suppkey":
        s = _table_rows("supplier", sf)
        j = idx % 4
        return ((partkey + j * (s // 4 + (partkey - 1) // s)) % s) + 1
    if column == "availqty":
        return _uniform("partsupp", "availqty", idx, 1, 9999)
    if column == "supplycost":
        return _uniform("partsupp", "supplycost", idx, 100, 100000)
    if column == "comment":
        return _comment("partsupp", idx, 6)
    raise KeyError(column)


def _gen_supplier(column: str, idx: np.ndarray, sf: float):
    suppkey = idx + 1
    if column == "suppkey":
        return suppkey
    if column == "name":
        return [f"Supplier#{int(v):09d}" for v in suppkey]
    if column == "address":
        h = _cell_hash("supplier", "address", idx)
        return [f"addr-{int(v):016x}" for v in h]
    if column == "nationkey":
        return _uniform("supplier", "nationkey", idx, 0, 24)
    if column == "phone":
        nk = _gen_supplier("nationkey", idx, sf)
        h1 = _uniform("supplier", "ph1", idx, 100, 999)
        h2 = _uniform("supplier", "ph2", idx, 100, 999)
        h3 = _uniform("supplier", "ph3", idx, 1000, 9999)
        return [f"{10 + int(n)}-{int(a)}-{int(b)}-{int(c)}"
                for n, a, b, c in zip(nk, h1, h2, h3)]
    if column == "acctbal":
        return _uniform("supplier", "acctbal", idx, -99999, 999999)
    if column == "comment":
        return _comment("supplier", idx, 5)
    raise KeyError(column)


def _gen_nation(column: str, idx: np.ndarray, sf: float):
    if column == "nationkey":
        return idx.astype(np.int64)
    if column == "name":
        return (idx.astype(np.int32), [n for n, _ in NATIONS])
    if column == "regionkey":
        return np.array([NATIONS[int(i)][1] for i in idx], dtype=np.int64)
    if column == "comment":
        return _comment("nation", idx, 4)
    raise KeyError(column)


def _gen_region(column: str, idx: np.ndarray, sf: float):
    if column == "regionkey":
        return idx.astype(np.int64)
    if column == "name":
        return (idx.astype(np.int32), REGIONS)
    if column == "comment":
        return _comment("region", idx, 4)
    raise KeyError(column)


_GENERATORS = {
    "lineitem": _gen_lineitem, "orders": _gen_orders,
    "customer": _gen_customer, "part": _gen_part,
    "partsupp": _gen_partsupp, "supplier": _gen_supplier,
    "nation": _gen_nation, "region": _gen_region,
}


# ---------------------------------------------------------------------------
# public connector API
# ---------------------------------------------------------------------------

def table_row_count(table: str, sf: float) -> int:
    return _table_rows(table, sf)


def column_stats(table: str, column: str, sf: float):
    """Analytic column statistics from the generator specs (the
    ConnectorMetadata.getTableStatistics analog; consumed by sql/stats.py).
    Values are in LOGICAL units (decimals as fractional numbers, dates as
    epoch days) to match planner constants."""
    from ..sql.stats import ColumnStats
    n = float(_table_rows(table, sf))
    orders = float(_table_rows("orders", sf))
    uniform = {
        ("lineitem", "orderkey"): (1, orders, orders),
        ("lineitem", "partkey"): (1, _table_rows("part", sf), None),
        ("lineitem", "suppkey"): (1, _table_rows("supplier", sf), None),
        ("lineitem", "linenumber"): (1, 7, 7),
        ("lineitem", "quantity"): (1.0, 50.0, 50),
        ("lineitem", "extendedprice"): (900.0, 104949.50, None),
        ("lineitem", "discount"): (0.0, 0.10, 11),
        ("lineitem", "tax"): (0.0, 0.08, 9),
        ("lineitem", "shipdate"): (MIN_ORDER_DATE + 1,
                                   MAX_ORDER_DATE + 121, None),
        ("lineitem", "commitdate"): (MIN_ORDER_DATE + 30,
                                     MAX_ORDER_DATE + 90, None),
        ("lineitem", "receiptdate"): (MIN_ORDER_DATE + 2,
                                      MAX_ORDER_DATE + 151, None),
        ("lineitem", "returnflag"): (None, None, 3),
        ("lineitem", "linestatus"): (None, None, 2),
        ("lineitem", "shipinstruct"): (None, None, 4),
        ("lineitem", "shipmode"): (None, None, 7),
        ("orders", "orderkey"): (1, n, n),
        ("orders", "custkey"): (1, _table_rows("customer", sf),
                                _table_rows("customer", sf) * 2 / 3),
        ("orders", "orderdate"): (MIN_ORDER_DATE, MAX_ORDER_DATE,
                                  MAX_ORDER_DATE - MIN_ORDER_DATE + 1),
        ("orders", "totalprice"): (900.0, 500000.0, None),
        ("orders", "orderstatus"): (None, None, 3),
        ("orders", "orderpriority"): (None, None, 5),
        ("orders", "clerk"): (None, None, max(1.0, sf * 1000)),
        ("orders", "shippriority"): (0, 0, 1),
        ("customer", "custkey"): (1, n, n),
        ("customer", "nationkey"): (0, 24, 25),
        ("customer", "acctbal"): (-999.99, 9999.99, None),
        ("customer", "mktsegment"): (None, None, 5),
        ("part", "partkey"): (1, n, n),
        ("part", "mfgr"): (None, None, 5),
        ("part", "brand"): (None, None, 25),
        ("part", "type"): (None, None, 150),
        ("part", "size"): (1, 50, 50),
        ("part", "container"): (None, None, 40),
        ("part", "retailprice"): (900.0, 2098.99, None),
        ("partsupp", "partkey"): (1, _table_rows("part", sf),
                                  _table_rows("part", sf)),
        ("partsupp", "suppkey"): (1, _table_rows("supplier", sf),
                                  _table_rows("supplier", sf)),
        ("partsupp", "availqty"): (1, 9999, 9999),
        ("partsupp", "supplycost"): (1.0, 1000.0, None),
        ("supplier", "suppkey"): (1, n, n),
        ("supplier", "nationkey"): (0, 24, 25),
        ("supplier", "acctbal"): (-999.99, 9999.99, None),
        ("nation", "nationkey"): (0, 24, 25),
        ("nation", "regionkey"): (0, 4, 5),
        ("nation", "name"): (None, None, 25),
        ("region", "regionkey"): (0, 4, 5),
        ("region", "name"): (None, None, 5),
    }
    spec = uniform.get((table, column))
    if spec is None:
        return None
    lo, hi, ndv = spec
    if ndv is None and lo is not None:
        ndv = min(n, max(1.0, float(hi) - float(lo)))
    return ColumnStats(
        low=None if lo is None else float(lo),
        high=None if hi is None else float(hi),
        ndv=None if ndv is None else float(ndv))


# string columns with open (unbounded) value domains: these are produced
# lazily on device as row-id columns and materialized on output
# (late materialization — see exec/batch.py Column.lazy)
# open-domain columns whose generated values sort identically to their row
# ids ("Supplier#000000001"-style zero-padded sequence numbers): ORDER BY on
# these late-materialized columns can sort the row ids directly
ROWID_ORDERED = {("supplier", "name"), ("customer", "name")}

# open-domain columns whose generated values are distinct per row (key-derived
# names/phones, long random text): GROUP BY may use the row id as the group
# key.  Columns drawn from small pools (orders.clerk: sf*1000 values) are NOT
# here — grouping them requires materializing a real dictionary first.
ROWID_DISTINCT = {
    ("customer", "name"), ("customer", "address"), ("customer", "phone"),
    ("customer", "comment"), ("supplier", "name"), ("supplier", "address"),
    ("supplier", "phone"), ("supplier", "comment"), ("part", "name"),
    ("part", "comment"), ("partsupp", "comment"), ("orders", "comment"),
    ("lineitem", "comment"), ("nation", "comment"), ("region", "comment"),
}

OPEN_DOMAIN = {
    ("lineitem", "comment"), ("orders", "comment"), ("orders", "clerk"),
    ("customer", "name"), ("customer", "address"), ("customer", "phone"),
    ("customer", "comment"), ("part", "name"), ("part", "comment"),
    ("partsupp", "comment"), ("supplier", "name"), ("supplier", "address"),
    ("supplier", "phone"), ("supplier", "comment"), ("nation", "comment"),
    ("region", "comment"),
}


def generate_column(table: str, column: str, sf: float,
                    start: int, count: int):
    """Raw column data for rows [start, start+count): numpy int64 array, or
    (codes:int32, values:list) dictionary pair, or list[str]."""
    idx = np.arange(start, start + count, dtype=np.int64)
    return _GENERATORS[table](column, idx, sf)


def generate_values_at(table: str, column: str, sf: float,
                       idx: np.ndarray) -> list:
    """Materialize string values for arbitrary row indices (used to realize
    late-materialized columns at output boundaries)."""
    raw = _GENERATORS[table](column, np.asarray(idx, dtype=np.int64), sf)
    if isinstance(raw, tuple):
        codes, values = raw
        return [values[c] for c in codes]
    if isinstance(raw, list):
        return raw
    return raw.tolist()


def generate_dictionary_at(table: str, column: str, sf: float,
                           idx: np.ndarray):
    """(codes, values) of a dictionary-shaped string column at arbitrary
    row indices, or None where the generator makes strings row by row:
    an output boundary ships the codes and the few values, not a Python
    string a row."""
    raw = _GENERATORS[table](column, np.asarray(idx, dtype=np.int64), sf)
    return raw if isinstance(raw, tuple) else None


def generate_block(table: str, column: str, sf: float, start: int, count: int):
    """Column data for rows [start, start+count) as a Block."""
    raw = generate_column(table, column, sf, start, count)
    typ = column_type(table, column)
    if isinstance(raw, tuple):
        codes, values = raw
        return DictionaryBlock(codes, VariableWidthBlock.from_strings(values))
    if isinstance(raw, list):
        return VariableWidthBlock.from_strings(raw)
    if typ.storage == "INT_ARRAY":
        return FixedWidthBlock(raw.astype(np.int32))
    return FixedWidthBlock(raw.astype(np.int64))


def generate_page(table: str, sf: float, start: int, count: int,
                  columns: Optional[Sequence[str]] = None) -> Page:
    cols = columns if columns is not None else [c for c, _ in SCHEMAS[table]]
    return Page([generate_block(table, c, sf, start, count) for c in cols],
                count)


# ---------------------------------------------------------------------------
# co-bucketed layout for grouped (lifespan) execution
#
# The reference bounds memory for huge joins by processing one bucket
# lifespan at a time when the joined tables are bucketed on the join key
# (Lifespan.java:30-37, GroupedExecutionTagger.java, session
# grouped_execution — SystemSessionProperties.java:105).  This generator
# gets the same property FOR FREE: orders.orderkey == row index + 1, and
# lineitem rows map to orders through fixed 7-order / 28-lineitem blocks
# (_li_order_map), so an ORDERKEY RANGE is a contiguous ROW RANGE in both
# tables — a bucket is just a pair of row-range splits, no repartitioning
# pass needed.  exec/grouped.py consumes this layout.
# ---------------------------------------------------------------------------

# tables co-partitioned on the "orderkey" domain, and the bucketing column
BUCKET_COLUMNS = {"orders": "orderkey", "lineitem": "orderkey"}


@dataclass
class TableBucket:
    """One lifespan: key range [key_lo, key_hi) and the contiguous row
    range it occupies in each co-bucketed table."""
    key_lo: int
    key_hi: int
    rows: Dict[str, Tuple[int, int]]


def bucket_layout(sf: float, n_buckets: int) -> List[TableBucket]:
    """Split the orderkey domain into up to n_buckets aligned lifespans.
    Buckets align to 7-order blocks (the lineitem row mapping's unit); the
    last bucket absorbs the fixed-fanout tail orders."""
    n_orders = _table_rows("orders", sf)
    n_lineitem = _table_rows("lineitem", sf)
    nblocks = n_orders // 7
    if nblocks == 0 or n_buckets <= 1:
        return [TableBucket(1, n_orders + 1,
                            {"orders": (0, n_orders),
                             "lineitem": (0, n_lineitem)})]
    bpb = max(1, -(-nblocks // n_buckets))      # ceil(nblocks / K)
    out: List[TableBucket] = []
    b0 = 0
    while b0 < nblocks:
        b1 = min(b0 + bpb, nblocks)
        o0, o1 = b0 * 7, b1 * 7
        l0, l1 = b0 * 28, b1 * 28
        if b1 == nblocks:           # tail orders: 4 lineitems each
            o1 = n_orders
            l1 = n_lineitem
        out.append(TableBucket(o0 + 1, o1 + 1,
                               {"orders": (o0, o1), "lineitem": (l0, l1)}))
        b0 = b1
    return out


@dataclass(frozen=True)
class TpchSplit:
    """A row-range shard of one table (reference TpchSplitManager splits by
    part index; ours are explicit ranges)."""
    table: str
    sf: float
    start: int
    end: int

    def to_dict(self):
        return {"connectorId": "tpch", "table": self.table, "sf": self.sf,
                "start": self.start, "end": self.end}

    @staticmethod
    def from_dict(d):
        return TpchSplit(d["table"], d["sf"], d["start"], d["end"])


def make_splits(table: str, sf: float, splits: int) -> List[TpchSplit]:
    total = table_row_count(table, sf)
    per = (total + splits - 1) // splits
    return [TpchSplit(table, sf, i * per, min((i + 1) * per, total))
            for i in range(splits) if i * per < total]


def split_pages(split: TpchSplit, columns: Optional[Sequence[str]] = None,
                page_rows: int = 1 << 20) -> Iterator[Page]:
    pos = split.start
    while pos < split.end:
        n = min(page_rows, split.end - pos)
        yield generate_page(split.table, split.sf, pos, n, columns)
        pos += n


# ---------------------------------------------------------------------------
# connector stats (feeds the fragmenter's join-distribution choice, the
# analog of TpchMetadata.getTableStatistics -> StatsCalculator)
# ---------------------------------------------------------------------------

def _connector_stats(handle) -> float:
    sf = dict(handle.extra).get("scaleFactor", 0.01)
    return float(table_row_count(handle.table_name, sf))


from ..sql.fragmenter import register_connector_stats as _reg_stats  # noqa: E402

_reg_stats("tpch", _connector_stats)
