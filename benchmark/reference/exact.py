"""Exact decimal arithmetic for the plain reference, and its control.

Decimals are unscaled int64 per row and Python integers once summed, so
no answer of the reference is ever rounded except where SQL says so
(avg and divide round half up at the result's scale).  `approximate=True`
is the control: the same sums accumulated in float32, the step that
tempts a later change on a chip that emulates 64-bit integers.
"""
from decimal import Decimal

import numpy as np

_LOW_BITS = 24


def grouped_sums(codes, n_groups: int, values, approximate=False):
    """Sum of `values` (int64, non-negative) per group code, as Python
    integers.  Exact: np.bincount adds float64 weights, which hold every
    partial sum of the low 24 bits and of the rest apart without
    rounding (under 2**53) for tables of up to 2**29 rows."""
    values = values.astype(np.int64, copy=False)
    if approximate:
        out = np.zeros(n_groups, np.float32)
        for g in range(n_groups):
            out[g] = np.sum(values[codes == g].astype(np.float32),
                            dtype=np.float32)
        return [int(x) for x in out]
    if len(values) and (values.min() < 0 or len(values) >= 1 << 29):
        raise ValueError("grouped_sums: value or row count out of range")
    low = np.bincount(codes, values & ((1 << _LOW_BITS) - 1), n_groups)
    high = np.bincount(codes, values >> _LOW_BITS, n_groups)
    return [(int(h) << _LOW_BITS) + int(lo) for h, lo in zip(high, low)]


def total(values, approximate=False) -> int:
    if approximate:
        return int(np.sum(values.astype(np.float32), dtype=np.float32))
    return int(values.astype(np.int64, copy=False).sum(dtype=np.int64))


def divide_half_up(numerator: int, denominator: int) -> int:
    q = (abs(numerator) + abs(denominator) // 2) // abs(denominator)
    return q if (numerator >= 0) == (denominator >= 0) else -q


def decimal(unscaled: int, scale: int) -> Decimal:
    return Decimal(int(unscaled)).scaleb(-scale)


def day(datestr: str) -> int:
    return int(np.datetime64(datestr, "D").astype(np.int64))


def add_months(datestr: str, months: int) -> int:
    d = np.datetime64(datestr, "M") + np.timedelta64(months, "M")
    return int(d.astype("datetime64[D]").astype(np.int64))
