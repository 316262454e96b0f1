"""The TPC-H population as this repo defines it, written out in numpy.

The plain reference's own copy of the data: every cell is a pure function
of (table, column, row index, scale factor) -- splitmix64 over a counter,
seeded per (table, column) by blake2b of "<table>.<column>" -- so the
tables can be made here without importing, or fetching anything from, the
program under test.  Only the columns the benchmark's queries touch are
written out.  Decimals are unscaled integers (two digits), dates are days
since 1970-01-01, closed string domains are codes into the lists below.
"""
import hashlib
import itertools

import numpy as np

ROWS_PER_SF = {"lineitem": 6_000_000, "orders": 1_500_000, "part": 200_000}
RETURN_FLAGS = ("A", "N", "R")
STATUSES = ("F", "O")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
TYPES = tuple(f"{a} {b} {c}"
              for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY",
                        "PROMO")
              for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED",
                        "BRUSHED")
              for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER"))
DICTIONARIES = {("lineitem", "returnflag"): RETURN_FLAGS,
                ("lineitem", "linestatus"): STATUSES,
                ("lineitem", "shipmode"): MODES,
                ("orders", "orderpriority"): PRIORITIES,
                ("part", "type"): TYPES}

BLOCK_ROWS = 28 * 75_000    # whole 28-row order blocks, ~2M rows at a time
# what a whole column is kept in (the arithmetic on a block is int64);
# every value fits: dates are days, codes and percents are small
DTYPES = {"shipdate": np.int32, "commitdate": np.int32,
          "receiptdate": np.int32, "discount": np.int8, "tax": np.int8,
          "quantity": np.int16, "returnflag": np.int8, "linestatus": np.int8,
          "shipmode": np.int8, "orderpriority": np.int8, "type": np.int16,
          "partkey": np.int32}

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def days(datestr: str) -> int:
    return int(np.datetime64(datestr, "D").astype(np.int64))


MIN_ORDER_DATE = days("1992-01-01")
MAX_ORDER_DATE = days("1998-08-02") - 151
CURRENT_DATE = days("1995-06-17")


def table_rows(table: str, sf: float) -> int:
    return int(ROWS_PER_SF[table] * sf)


def _splitmix64(x):
    with np.errstate(over="ignore"):
        z = x + _GOLDEN
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def _seed(table: str, column: str) -> np.uint64:
    digest = hashlib.blake2b(f"{table}.{column}".encode(),
                             digest_size=8).digest()
    return np.uint64(int.from_bytes(digest, "little"))


def _cell(table, column, idx):
    with np.errstate(over="ignore"):
        return _splitmix64(idx.astype(np.uint64) * _GOLDEN
                           + _seed(table, column))


def _uniform(table, column, idx, lo, hi):
    return (_cell(table, column, idx)
            % np.uint64(hi - lo + 1)).astype(np.int64) + lo


def _order_of_line(idx, sf):
    """lineitem row -> orderkey: every 7 consecutive orders share 28
    lineitems, split 1..7 by a hashed permutation; the orders past the
    last whole block of 7 keep 4 lines each."""
    perms = np.array(list(itertools.permutations(range(1, 8))), np.int64)
    ends = np.cumsum(perms, axis=1)                       # (5040, 7)
    # order within its block, for each of the 28 lines of each permutation
    pos_of = (np.arange(28)[None, :, None] >= ends[:, None, :]).sum(axis=2)
    n_orders = table_rows("orders", sf)
    full = (n_orders // 7) * 28
    block, r = idx // 28, idx % 28
    pid = (_cell("lineitem", "orderblock", block)
           % np.uint64(5040)).astype(np.int64)
    orderkey = block * 7 + pos_of[pid, r] + 1
    tail = idx >= full
    return np.where(tail, (n_orders // 7) * 7 + (idx - full) // 4 + 1,
                    orderkey)


class _Rows:
    """The columns of one range of a table's rows, each made once; a
    column is the method of its name."""

    def __init__(self, idx, sf):
        self.idx, self.sf, self.made = idx, sf, {}

    def __getitem__(self, column):
        if column not in self.made:
            self.made[column] = getattr(self, column)()
        return self.made[column]


class _Lineitem(_Rows):
    def u(self, name, lo, hi):
        return _uniform("lineitem", name, self.idx, lo, hi)

    def orderkey(self):
        return _order_of_line(self.idx, self.sf)

    def orderdate(self):
        return _uniform("orders", "orderdate", self["orderkey"],
                        MIN_ORDER_DATE, MAX_ORDER_DATE)

    def partkey(self):
        return self.u("partkey", 1, table_rows("part", self.sf))

    def quantity(self):
        return self.u("quantity", 1, 50) * 100

    def extendedprice(self):
        partkey = self["partkey"]
        retail = 90000 + ((partkey // 10) % 20001) + 100 * (partkey % 1000)
        return (self["quantity"] // 100) * retail

    def discount(self):
        return self.u("discount", 0, 10)

    def tax(self):
        return self.u("tax", 0, 8)

    def shipdate(self):
        return self["orderdate"] + self.u("shipdays", 1, 121)

    def commitdate(self):
        return self["orderdate"] + self.u("commitdays", 30, 90)

    def receiptdate(self):
        return self["shipdate"] + self.u("receiptdays", 1, 30)

    def returnflag(self):
        return np.where(self["receiptdate"] <= CURRENT_DATE,
                        self.u("rflagcoin", 0, 1) * 2, 1)

    def linestatus(self):
        return (self["shipdate"] > CURRENT_DATE).astype(np.int64)

    def shipmode(self):
        return self.u("shipmode", 0, 6)


class _Orders(_Rows):
    def orderkey(self):
        return self.idx + 1

    def orderpriority(self):
        return _uniform("orders", "priority", self.idx, 0, 4)


class _Part(_Rows):
    def partkey(self):
        return self.idx + 1

    def type(self):
        h = _cell("part", "type", self.idx)
        return ((h % np.uint64(6)) * np.uint64(25)
                + ((h >> np.uint64(8)) % np.uint64(5)) * np.uint64(5)
                + (h >> np.uint64(16)) % np.uint64(5)).astype(np.int64)


_TABLES = {"lineitem": _Lineitem, "orders": _Orders, "part": _Part}


def table(name: str, columns, sf: float, threads: int = 8) -> dict:
    """{column: whole array} of one table, made in row blocks on a few
    threads (numpy drops the interpreter lock inside its loops)."""
    from concurrent.futures import ThreadPoolExecutor
    n = table_rows(name, sf)
    out = {c: np.empty(n, DTYPES.get(c, np.int64)) for c in columns}

    def fill(start):
        idx = np.arange(start, min(n, start + BLOCK_ROWS), dtype=np.int64)
        block = _TABLES[name](idx, sf)
        for c in columns:
            out[c][start:start + len(idx)] = block[c]

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, range(0, n, BLOCK_ROWS)))
    return out


def tables(wanted: dict, sf: float) -> dict:
    """{table: [columns]} -> {table: {column: array}}."""
    return {t: table(t, sorted(set(cols)), sf) for t, cols in wanted.items()}
