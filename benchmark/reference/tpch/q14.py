"""TPC-H Q14 (clause 2.4.14): the share of a month's revenue that came
from promoted parts; a join of lineitem with part, one decimal."""
import numpy as np

from ..exact import add_months, day, decimal, divide_half_up, total
from ..tpch_data import TYPES


def answer(tables, params, memo, approximate=False):
    li, part = tables["lineitem"], tables["part"]
    keep = ((li["shipdate"] >= day(params["DATE"]))
            & (li["shipdate"] < add_months(params["DATE"], 1)))
    at = np.searchsorted(part["partkey"], li["partkey"][keep])
    if not np.array_equal(part["partkey"][at], li["partkey"][keep]):
        raise ValueError("q14: a lineitem without its part")
    promo = np.isin(part["type"][at],
                    [i for i, t in enumerate(TYPES) if t.startswith("PROMO")])
    revenue = li["extendedprice"][keep] * (100 - li["discount"][keep].astype(np.int64))
    promoted = total(revenue[promo], approximate)
    everything = total(revenue, approximate)
    if not everything:
        return [[None]]
    # 100.00 * sum (scale 2 + 4) / sum (scale 4), rounded half up at scale 6
    return [[decimal(divide_half_up(10000 * promoted * 10 ** 4, everything), 6)]]
