"""TPC-H Q3 (clause 2.4.3), "Shipping Priority": the ten unshipped orders
of one market segment with the highest revenue.  customer joined to orders
joined to lineitem, one group an order, the first ten by revenue."""
import datetime

import numpy as np

from ..exact import day, decimal
from ..tpchx_data import SEGMENTS

LIMIT = 10


def _found(keys, wanted):
    """Positions of `wanted` in the sorted unique `keys`, and which of
    them are there at all."""
    at = np.minimum(np.searchsorted(keys, wanted), len(keys) - 1)
    return at, keys[at] == wanted


def answer(tables, params, memo, approximate=False):
    li, orders, customer = (tables[t] for t in
                            ("lineitem", "orders", "customer"))
    date = day(params["DATE"])
    # customer ⋈ orders: custkey is customer's sorted primary key
    early = np.flatnonzero(orders["orderdate"] < date)
    at, there = _found(customer["custkey"], orders["custkey"][early])
    mine = early[there & (customer["mktsegment"][at]
                          == SEGMENTS.index(params["SEGMENT"]))]
    # ⋈ lineitem: orderkey is orders' sorted primary key
    late = np.flatnonzero(li["shipdate"] > date)
    at, there = _found(orders["orderkey"][mine], li["orderkey"][late])
    late, order = late[there], mine[at[there]]
    if not len(late):
        return []
    price = li["extendedprice"][late].astype(np.int64)
    value = price * (100 - li["discount"][late].astype(np.int64))   # scale 4
    groups, code = np.unique(order, return_inverse=True)
    if approximate:     # the control: per-order sums in float32
        sums = np.zeros(len(groups), np.float32)
        np.add.at(sums, code, value.astype(np.float32))
        revenue = sums.astype(np.float64).astype(np.int64)
    else:               # exact: an order's lines sum far below 2**63
        revenue = np.zeros(len(groups), np.int64)
        np.add.at(revenue, code, value)
    odate = orders["orderdate"][groups].astype(np.int64)
    first = np.lexsort((odate, -revenue))[:LIMIT + 1]
    # the spec orders by (revenue desc, o_orderdate): a tie on both that
    # reaches the first ten rows leaves their order to the system
    tied = (np.diff(revenue[first]) == 0) & (np.diff(odate[first]) == 0)
    if tied.any():
        raise ValueError(f"q3: a tie on (revenue, o_orderdate) among the "
                         f"first {LIMIT + 1} rows for {params}")
    epoch = datetime.date(1970, 1, 1)
    return [[int(orders["orderkey"][groups[g]]), decimal(revenue[g], 4),
             (epoch + datetime.timedelta(days=int(odate[g]))).isoformat(),
             int(orders["shippriority"][groups[g]])]
            for g in first[:LIMIT]]
