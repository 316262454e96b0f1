"""TPC-H Q12 (clause 2.4.12): late lines by ship mode and order
priority; a join of lineitem with orders, one row per ship mode."""
import numpy as np

from ..exact import add_months, day
from ..tpch_data import MODES, PRIORITIES


def answer(tables, params, memo, approximate=False):
    li, orders = tables["lineitem"], tables["orders"]
    modes = [MODES.index(params["SHIPMODE1"]), MODES.index(params["SHIPMODE2"])]
    keep = (np.isin(li["shipmode"], modes)
            & (li["commitdate"] < li["receiptdate"])
            & (li["shipdate"] < li["commitdate"])
            & (li["receiptdate"] >= day(params["DATE"]))
            & (li["receiptdate"] < add_months(params["DATE"], 12)))
    at = np.searchsorted(orders["orderkey"], li["orderkey"][keep])
    if not np.array_equal(orders["orderkey"][at], li["orderkey"][keep]):
        raise ValueError("q12: a lineitem without its order")
    urgent = np.isin(orders["orderpriority"][at],
                     [PRIORITIES.index("1-URGENT"), PRIORITIES.index("2-HIGH")])
    count = lambda m: int(m.sum())  # noqa: E731 -- counts: the control has nothing to round
    mode = li["shipmode"][keep]
    return [[MODES[m], count((mode == m) & urgent), count((mode == m) & ~urgent)]
            for m in sorted(set(modes), key=lambda m: MODES[m])
            if (mode == m).any()]
