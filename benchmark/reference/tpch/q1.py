"""TPC-H Q1 (clause 2.4.1): the pricing summary, one row per
(l_returnflag, l_linestatus) in that order.

Every DELTA of the spec's domain [60 .. 120] keeps the rows shipped up to
1998-12-01 - 120 days, and differs only in the two months after.  So the
rows up to that date are summed once per group, and the later ones once
per (group, ship date) -- exactly, as integers -- and an answer adds the
dates it covers."""
import numpy as np

from ..exact import day, decimal, divide_half_up, grouped_sums
from ..tpch_data import RETURN_FLAGS, STATUSES

N_GROUPS = len(RETURN_FLAGS) * len(STATUSES)
MEASURES = ("qty", "price", "disc_price", "charge", "disc", "rows")
DELTA_MAX = 120
EARLIEST = day("1998-12-01") - DELTA_MAX


def _measures(li, rows):
    """Unscaled int64 per row of `rows` (an index array)."""
    price = li["extendedprice"][rows]
    disc = li["discount"][rows].astype(np.int64)
    disc_price = price * (100 - disc)
    return {"qty": li["quantity"][rows].astype(np.int64), "price": price,
            "disc_price": disc_price,
            "charge": disc_price * (100 + li["tax"][rows].astype(np.int64)),
            "disc": disc, "rows": np.ones(len(rows), np.int64)}


def _summed(li):
    """({measure: [sum per group]} up to EARLIEST,
        {measure: (groups, dates) sums} after it)."""
    group = li["returnflag"].astype(np.int8) * len(STATUSES) + li["linestatus"]
    early = li["shipdate"] <= EARLIEST
    base = {m: [0] * N_GROUPS for m in MEASURES}
    for g in range(N_GROUPS):
        rows = np.flatnonzero(early & (group == g))
        for m, values in _measures(li, rows).items():
            base[m][g] = int(values.sum(dtype=np.int64))
    late = np.flatnonzero(~early)
    # (this repo's population ships nothing after 1998-07-03: no late rows)
    n_dates = max(0, int(li["shipdate"].max()) - EARLIEST)
    cell = group[late].astype(np.int64) * n_dates \
        + (li["shipdate"][late] - EARLIEST - 1)
    tail = {m: np.array(grouped_sums(cell, N_GROUPS * n_dates, values),
                        dtype=object).reshape(N_GROUPS, n_dates)
            for m, values in _measures(li, late).items()}
    return base, tail


def answer(tables, params, memo, approximate=False):
    delta = int(params["DELTA"])
    if delta > DELTA_MAX:
        raise ValueError(f"q1: DELTA {delta} is outside the spec's domain")
    if "q1" not in memo:
        memo["q1"] = _summed(tables["lineitem"])
    base, tail = memo["q1"]
    dates = DELTA_MAX - delta
    rows = []
    for g in range(N_GROUPS):
        if approximate:     # the control: float32 accumulators
            s = {m: int(np.sum(np.array([base[m][g], *tail[m][g, :dates]],
                                        dtype=np.float32), dtype=np.float32))
                 for m in MEASURES}
        else:
            s = {m: base[m][g] + sum(tail[m][g, :dates].tolist())
                 for m in MEASURES}
        n = s["rows"]
        if not n:
            continue
        rows.append([RETURN_FLAGS[g // len(STATUSES)],
                     STATUSES[g % len(STATUSES)],
                     decimal(s["qty"], 2), decimal(s["price"], 2),
                     decimal(s["disc_price"], 4), decimal(s["charge"], 6),
                     decimal(divide_half_up(s["qty"], n), 2),
                     decimal(divide_half_up(s["price"], n), 2),
                     decimal(divide_half_up(s["disc"], n), 2), n])
    return rows
