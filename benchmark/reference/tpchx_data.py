"""The TPC-H tables that Q3 needs and `tpch_data` does not have: the
`customer` table and three more columns of `orders`, written out in numpy
after the same population (`tpch_data`'s counter hash and row classes; the
formulas are those of presto_tpu/connectors/device_gen.py, restated here so
that the reference imports nothing of the program under test).  A suite of
its own because `check.Reference` finds a suite's data by the first part of
a template's name.
"""
import numpy as np

from . import tpch_data as base
from .tpch_data import _Rows, _uniform

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
ROWS_PER_SF = dict(base.ROWS_PER_SF, customer=150_000)
DTYPES = dict(base.DTYPES, orderdate=np.int32, shippriority=np.int8,
              mktsegment=np.int8, custkey=np.int32)


def table_rows(table: str, sf: float) -> int:
    return int(ROWS_PER_SF[table] * sf)


class _Orders(base._Orders):
    def custkey(self):
        # two customers in three place orders (clause 4.2.3): the drawn
        # value skips every third key
        customers = table_rows("customer", self.sf)
        raw = _uniform("orders", "custkey", self.idx, 1, customers // 3 * 2)
        return raw + (raw - 1) // 2 if customers >= 3 else raw

    def orderdate(self):
        return _uniform("orders", "orderdate", self["orderkey"],
                        base.MIN_ORDER_DATE, base.MAX_ORDER_DATE)

    def shippriority(self):
        return np.zeros(len(self.idx), np.int64)


class _Customer(_Rows):
    def custkey(self):
        return self.idx + 1

    def mktsegment(self):
        return _uniform("customer", "segment", self.idx, 0, 4)


_TABLES = dict(base._TABLES, orders=_Orders, customer=_Customer)


def table(name: str, columns, sf: float, threads: int = 8) -> dict:
    """{column: whole array} of one table, as `tpch_data.table` makes it."""
    from concurrent.futures import ThreadPoolExecutor
    n = table_rows(name, sf)
    out = {c: np.empty(n, DTYPES.get(c, np.int64)) for c in columns}

    def fill(start):
        idx = np.arange(start, min(n, start + base.BLOCK_ROWS),
                        dtype=np.int64)
        block = _TABLES[name](idx, sf)
        for c in columns:
            out[c][start:start + len(idx)] = block[c]

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, range(0, n, base.BLOCK_ROWS)))
    return out


def tables(wanted: dict, sf: float) -> dict:
    """{table: [columns]} -> {table: {column: array}}."""
    return {t: table(t, sorted(set(cols)), sf) for t, cols in wanted.items()}
