"""TPC-H Q6 (clause 2.4.6): one decimal, the forecast revenue change."""
from ..exact import add_months, day, decimal, total


def answer(tables, params, memo, approximate=False):
    li = tables["lineitem"]
    lo = round(float(params["DISCOUNT"]) * 100)
    keep = ((li["shipdate"] >= day(params["DATE"]))
            & (li["shipdate"] < add_months(params["DATE"], 12))
            & (li["discount"] >= lo - 1) & (li["discount"] <= lo + 1)
            & (li["quantity"] < int(params["QUANTITY"]) * 100))
    revenue = total(li["extendedprice"][keep] * li["discount"][keep].astype("int64"),
                    approximate)
    return [[decimal(revenue, 4)]]
