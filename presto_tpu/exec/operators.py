"""Physical operators over device Batches.

TPU-native replacements for the reference's operator set
(presto-main-base/.../operator/: HashAggregationOperator.java:56,
LookupJoinOperator.java:53, HashBuilderOperator.java:56, TopNOperator.java:32,
OrderByOperator.java:43, LimitOperator.java).  Design per SURVEY.md §7:
static shapes everywhere; selection via the batch mask; aggregation via an
open-addressing scatter table with linear probing unrolled into a fixed
number of vectorized rounds (host doubles the table if a batch exhausts the
rounds); joins via sorted-build + vectorized binary search instead of
pointer-chasing hash tables.  All functions here are jax-traceable; host
drivers sit in pipeline.py.
"""
from __future__ import annotations

import math
import threading
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .batch import Batch, Column

INT64_MIN = jnp.iinfo(jnp.int64).min
INT64_MAX = jnp.iinfo(jnp.int64).max


# ---------------------------------------------------------------------------
# hashing
# ---------------------------------------------------------------------------

def splitmix64(x):
    x = x.astype(jnp.uint64)
    x = (x + jnp.uint64(0x9E3779B97F4A7C15))
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def hash_columns(cols: List[Column], salt: int = 0):
    """Combined 64-bit hash of key columns (nulls hash distinctly)."""
    h = jnp.full(cols[0].values.shape, jnp.uint64(salt + 1), dtype=jnp.uint64)
    for c in cols:
        v = c.values
        if v.dtype == jnp.float64:
            v = jax.lax.bitcast_convert_type(v, jnp.int64)
        elif v.dtype == jnp.float32:
            v = jax.lax.bitcast_convert_type(v, jnp.int32).astype(jnp.int64)
        elif v.dtype == jnp.bool_:
            v = v.astype(jnp.int64)
        hv = splitmix64(v.astype(jnp.int64).view(jnp.uint64)
                        if hasattr(v, "view") else v)
        if c.nulls is not None:
            hv = jnp.where(c.nulls, jnp.uint64(0x9E3779B97F4A7C15), hv)
        h = splitmix64(h * jnp.uint64(31) + hv)
    return h


# ---------------------------------------------------------------------------
# filter / project
# ---------------------------------------------------------------------------

def apply_filter(batch: Batch, predicate: Column) -> Batch:
    """SQL filter: keep rows where predicate is TRUE (not false, not null)."""
    keep = predicate.values.astype(bool)
    if predicate.nulls is not None:
        keep = keep & ~predicate.nulls
    return batch.with_mask(batch.mask & keep)


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AggSpec:
    """One aggregate: function name, whether input is float, output column.
    param carries a constant argument (approx_percentile's p)."""
    name: str          # sum/count/count_star/min/max/avg/stddev*/var*/
    #                    corr/covar_pop/covar_samp/approx_percentile
    output: str
    is_float: bool = False
    param: object = None


# aggregates every execution mode supports; anything else routes through
# the scatter-hash or sort paths (run_fused / run_once gate on this)
BASIC_AGGS = {"sum", "avg", "count", "count_star", "min", "max"}
# moment-based aggregates (sum / sum-of-squares / cross-moment state)
MOMENT_AGGS = {"stddev", "stddev_pop", "stddev_samp", "variance",
               "var_pop", "var_samp"}
CORR_AGGS = {"corr", "covar_pop", "covar_samp"}
# aggregates only the sort path implements (need value-ordered segments)
SORT_ONLY_AGGS = {"approx_percentile"}
# HyperLogLog sketch aggregates (dense register arrays, scatter-max)
HLL_AGGS = {"approx_distinct"}

# Dense HLL with 2^11 registers: standard error 1.04/sqrt(2048) = 2.3%,
# the reference's default approx_distinct error bound
# (ApproximateCountDistinctAggregations.java DEFAULT_STANDARD_ERROR=0.023).
HLL_DEFAULT_BUCKETS = 2048
# reference bound on approx_distinct(x, e): lowest/highest accepted max
# standard error (HyperLogLogUtils / NumberOfBuckets limits)
HLL_MIN_STANDARD_ERROR = 0.0040625
HLL_MAX_STANDARD_ERROR = 0.26


def hll_buckets_for_error(e: float) -> int:
    """max-standard-error -> power-of-two register count m with
    1.04/sqrt(m) <= e, clamped to [2^4, 2^16] like the reference."""
    if not (HLL_MIN_STANDARD_ERROR <= e <= HLL_MAX_STANDARD_ERROR):
        raise ValueError(
            f"approx_distinct standard error {e} out of range "
            f"[{HLL_MIN_STANDARD_ERROR}, {HLL_MAX_STANDARD_ERROR}]")
    m = 16
    while 1.04 / math.sqrt(m) > e and m < (1 << 16):
        m *= 2
    return m


def _bit_length64(x):
    """Per-element bit length of a uint64 array (0 for 0)."""
    bl = jnp.zeros(x.shape, dtype=jnp.int32)
    for s in (32, 16, 8, 4, 2, 1):
        big = x >= (jnp.uint64(1) << jnp.uint64(s))
        bl = bl + jnp.where(big, s, 0)
        x = jnp.where(big, x >> jnp.uint64(s), x)
    return bl + (x > 0).astype(jnp.int32)


def _hll_bucket_rank(h, m: int):
    """uint64 hash -> (bucket index int32, rank int8).

    Bucket = low log2(m) bits; rank = leading-zero count of the remaining
    64-p bits + 1 (the HyperLogLog rho function over disjoint bit ranges)."""
    p = m.bit_length() - 1
    bucket = (h & jnp.uint64(m - 1)).astype(jnp.int32)
    rem = h >> jnp.uint64(p)
    rank = ((64 - p) - _bit_length64(rem) + 1).astype(jnp.int8)
    return bucket, rank


def _hll_alpha(m: int) -> float:
    if m == 16:
        return 0.673
    if m == 32:
        return 0.697
    if m == 64:
        return 0.709
    return 0.7213 / (1 + 1.079 / m)


def _hll_estimate(registers, m: int):
    """(G, m) int8 register array -> int64 cardinality estimates (G,).

    Flajolet et al. HyperLogLog with the small-range linear-counting
    correction, the same estimator family as the reference's airlift
    HyperLogLog (ApproximateCountDistinctAggregations.java)."""
    R = registers.reshape(-1, m).astype(jnp.float64)
    Z = jnp.sum(jnp.exp2(-R), axis=1)
    E = _hll_alpha(m) * m * m / Z
    V = jnp.sum(R == 0.0, axis=1)
    lin = m * jnp.log(m / jnp.maximum(V.astype(jnp.float64), 1.0))
    est = jnp.where((E <= 2.5 * m) & (V > 0), lin, E)
    return jnp.round(est).astype(jnp.int64)


def hll_state_bytes(specs) -> int:
    """Extra per-slot accumulator bytes for HLL register arrays."""
    return sum((s.param or HLL_DEFAULT_BUCKETS)
               for s in specs if s.name in HLL_AGGS)


def _chan_merge(na, ma, m2a, nb, mb, m2b):
    """Chan et al. parallel merge of central-moment states (n, mean, M2).

    Numerically stable (no large-magnitude cancellation), and exact at the
    boundaries: an empty side contributes nothing because its mean is 0 and
    the delta term is scaled by na*nb.  Matches the reference's
    CentralMomentsState merge (VarianceAggregation)."""
    n = na + nb
    nf = jnp.maximum(n.astype(jnp.float64), 1.0)
    naf = na.astype(jnp.float64)
    nbf = nb.astype(jnp.float64)
    delta = mb - ma
    mean = ma + delta * nbf / nf
    m2 = m2a + m2b + delta * delta * naf * nbf / nf
    return n, mean, m2


def _moment_finalize(name, mean, m2, n):
    """(value, is_null) for a variance-family aggregate from the central
    moments (mean, M2=Σ(x-mean)², count).  `mean` is unused by the formula
    but kept in the signature for symmetry with the accumulator state."""
    del mean
    nf = n.astype(jnp.float64)
    pop = name in ("stddev_pop", "var_pop")
    denom = jnp.where(pop, jnp.maximum(nf, 1.0),
                      jnp.maximum(nf - 1.0, 1.0))
    var = jnp.maximum(m2, 0.0) / denom
    if name.startswith("stddev"):
        var = jnp.sqrt(var)
    null = n < (1 if pop else 2)
    return var, null


def _corr_finalize(name, m2x, m2y, cxy, n):
    """(value, is_null) from central cross-moments: M2x=Σ(x-mx)²,
    M2y=Σ(y-my)², Cxy=Σ(x-mx)(y-my)."""
    nf = n.astype(jnp.float64)
    if name == "corr":
        den = jnp.sqrt(jnp.maximum(m2x, 0.0) * jnp.maximum(m2y, 0.0))
        null = (n < 1) | (den == 0)
        return cxy / jnp.where(den == 0, 1.0, den), null
    if name == "covar_samp":
        return cxy / jnp.maximum(nf - 1.0, 1.0), n < 2
    return cxy / jnp.maximum(nf, 1.0), n < 1


# numpy (not jnp) scalar: it embeds as a jaxpr literal, not as a captured
# device-array constant
EMPTY_SLOT = np.uint64(0xFFFFFFFFFFFFFFFF)
PROBE_ROUNDS = 16


def agg_init(num_slots: int, specs: Tuple[AggSpec, ...],
             key_names: Tuple[str, ...], key_dtypes) -> dict:
    """Fresh accumulator state (a pytree dict)."""
    state = {
        "__keyhash": jnp.full(num_slots, EMPTY_SLOT, dtype=jnp.uint64),
        "__occupied": jnp.zeros(num_slots, dtype=bool),
        "__collision": jnp.zeros((), dtype=bool),
    }
    for name, dtype in zip(key_names, key_dtypes):
        state[f"__key_{name}"] = jnp.zeros(num_slots, dtype=dtype)
        state[f"__keynull_{name}"] = jnp.zeros(num_slots, dtype=bool)
    for spec in specs:
        if spec.name in ("count", "count_star"):
            state[spec.output] = jnp.zeros(num_slots, dtype=jnp.int64)
        elif spec.name == "avg":
            dt = jnp.float64 if spec.is_float else jnp.int64
            state[spec.output + "$sum"] = jnp.zeros(num_slots, dtype=dt)
            state[spec.output + "$count"] = jnp.zeros(num_slots, dtype=jnp.int64)
        elif spec.name == "sum":
            dt = jnp.float64 if spec.is_float else jnp.int64
            state[spec.output] = jnp.zeros(num_slots, dtype=dt)
            state[spec.output + "$count"] = jnp.zeros(num_slots, dtype=jnp.int64)
        elif spec.name in ("min", "max"):
            dt = jnp.float64 if spec.is_float else jnp.int64
            init = (jnp.inf if spec.name == "min" else -jnp.inf) if spec.is_float \
                else (INT64_MAX if spec.name == "min" else INT64_MIN)
            state[spec.output] = jnp.full(num_slots, init, dtype=dt)
            state[spec.output + "$count"] = jnp.zeros(num_slots, dtype=jnp.int64)
        elif spec.name in MOMENT_AGGS:
            for suffix in ("$mean", "$m2"):
                state[spec.output + suffix] = jnp.zeros(num_slots,
                                                        dtype=jnp.float64)
            state[spec.output + "$count"] = jnp.zeros(num_slots,
                                                      dtype=jnp.int64)
        elif spec.name in CORR_AGGS:
            for suffix in ("$mx", "$my", "$m2x", "$m2y", "$cxy"):
                state[spec.output + suffix] = jnp.zeros(num_slots,
                                                        dtype=jnp.float64)
            state[spec.output + "$count"] = jnp.zeros(num_slots,
                                                      dtype=jnp.int64)
        elif spec.name in HLL_AGGS:
            m = spec.param or HLL_DEFAULT_BUCKETS
            # flat (num_slots * m) register file: one scatter-max per batch
            state[spec.output + "$hll"] = jnp.zeros(num_slots * m,
                                                    dtype=jnp.int8)
        else:
            raise NotImplementedError(f"aggregate {spec.name}")
    return state


def agg_update(state: dict, batch: Batch, key_cols: List[Column],
               agg_inputs: Dict[str, Optional[Column]],
               specs: Tuple[AggSpec, ...], num_slots: int, salt: int,
               key_names: Tuple[str, ...] = (),
               agg_inputs2: Optional[Dict[str, Column]] = None) -> dict:
    """Scatter one batch into the accumulator table.

    Open addressing, linear probing vectorized as PROBE_ROUNDS scatter rounds:
    each round, still-pending rows propose their keyhash for their current
    slot; a scatter-min picks one winner per free slot; rows whose keyhash now
    matches the slot's keyhash are placed (this includes rows whose key was
    already resident); the rest advance one slot.  Distinct keys are assumed
    to have distinct 64-bit hashes (collision probability ~G²/2⁶⁵).  Rows
    still pending after all rounds set __collision; the host re-runs the
    aggregation with a doubled table (classic table growth, amortized by the
    driver's conservative initial sizing).
    """
    mask = batch.mask
    out = dict(state)

    if key_cols:
        kh = hash_columns(key_cols, salt)
        # reserve the EMPTY sentinel
        kh = jnp.where(kh == EMPTY_SLOT, jnp.uint64(0), kh)
    else:
        kh = jnp.zeros(mask.shape, dtype=jnp.uint64)
    slot = (kh % jnp.uint64(num_slots)).astype(jnp.int32)

    table = state["__keyhash"]
    pending = mask
    placed_slot = jnp.zeros(mask.shape, dtype=jnp.int32)
    for _ in range(PROBE_ROUNDS):
        prop = jnp.where(pending, kh, EMPTY_SLOT)
        attempt = jnp.full(num_slots, EMPTY_SLOT).at[slot].min(prop)
        table = jnp.where(table == EMPTY_SLOT, attempt, table)
        win = pending & (table[slot] == kh)
        placed_slot = jnp.where(win, slot, placed_slot)
        pending = pending & ~win
        slot = jnp.where(pending, (slot + 1) % num_slots, slot)
    out["__collision"] = state["__collision"] | jnp.any(pending)
    out["__keyhash"] = table
    out["__occupied"] = table != EMPTY_SLOT
    mask = mask & ~pending          # drop unplaced rows (retry will redo all)
    # masked rows must not write anywhere: send them out of range + mode=drop
    # (a masked row scattering "current value" into a live slot would race
    # with the real write and could revert it)
    slot = jnp.where(mask, placed_slot, num_slots)

    # representative key values per slot (all rows in a slot share the key).
    # NOTE: pair by explicit key_names — jit round-trips dicts in sorted-key
    # order, so deriving the pairing from state's iteration order misaligns.
    for kname, col in zip(key_names, key_cols):
        name = f"__key_{kname}"
        out[name] = state[name].at[slot].set(col.values, mode="drop")
        if col.nulls is not None:
            out[f"__keynull_{kname}"] = state[f"__keynull_{kname}"].at[slot].set(
                col.nulls, mode="drop")

    for spec in specs:
        if spec.name == "count_star":
            out[spec.output] = state[spec.output].at[slot].add(
                mask.astype(jnp.int64), mode="drop")
            continue
        col = agg_inputs[spec.output]
        valid = mask & ~col.null_mask()
        if spec.name == "count":
            out[spec.output] = state[spec.output].at[slot].add(
                valid.astype(jnp.int64), mode="drop")
            continue
        if spec.name in MOMENT_AGGS:
            # Two scatter passes per batch: batch-local (n, mean), then
            # batch-local M2 around that mean; fold into the running state
            # with the stable Chan merge (no sum-of-squares cancellation).
            x = col.values.astype(jnp.float64)
            vslot = jnp.where(valid, slot, num_slots)
            gslot = jnp.where(valid, slot, 0)
            nb = jnp.zeros(num_slots, jnp.int64).at[vslot].add(
                jnp.ones_like(vslot, dtype=jnp.int64), mode="drop")
            sb = jnp.zeros(num_slots, jnp.float64).at[vslot].add(
                x, mode="drop")
            mb = sb / jnp.maximum(nb.astype(jnp.float64), 1.0)
            cx = jnp.where(valid, x - mb[gslot], 0.0)
            m2b = jnp.zeros(num_slots, jnp.float64).at[vslot].add(
                cx * cx, mode="drop")
            n, mean, m2 = _chan_merge(
                state[spec.output + "$count"], state[spec.output + "$mean"],
                state[spec.output + "$m2"], nb, mb, m2b)
            out[spec.output + "$count"] = n
            out[spec.output + "$mean"] = mean
            out[spec.output + "$m2"] = m2
            continue
        if spec.name in CORR_AGGS:
            c2 = agg_inputs2[spec.output]
            valid = valid & ~c2.null_mask()
            x = col.values.astype(jnp.float64)
            y = c2.values.astype(jnp.float64)
            vslot = jnp.where(valid, slot, num_slots)
            gslot = jnp.where(valid, slot, 0)
            ones = jnp.ones_like(vslot, dtype=jnp.int64)
            nb = jnp.zeros(num_slots, jnp.int64).at[vslot].add(
                ones, mode="drop")
            nbf = jnp.maximum(nb.astype(jnp.float64), 1.0)
            mxb = jnp.zeros(num_slots, jnp.float64).at[vslot].add(
                x, mode="drop") / nbf
            myb = jnp.zeros(num_slots, jnp.float64).at[vslot].add(
                y, mode="drop") / nbf
            cx = jnp.where(valid, x - mxb[gslot], 0.0)
            cy = jnp.where(valid, y - myb[gslot], 0.0)
            zeros = jnp.zeros(num_slots, jnp.float64)
            m2xb = zeros.at[vslot].add(cx * cx, mode="drop")
            m2yb = zeros.at[vslot].add(cy * cy, mode="drop")
            cxyb = zeros.at[vslot].add(cx * cy, mode="drop")
            na = state[spec.output + "$count"]
            n, mx, m2x = _chan_merge(na, state[spec.output + "$mx"],
                                     state[spec.output + "$m2x"],
                                     nb, mxb, m2xb)
            _, my, m2y = _chan_merge(na, state[spec.output + "$my"],
                                     state[spec.output + "$m2y"],
                                     nb, myb, m2yb)
            nf = jnp.maximum(n.astype(jnp.float64), 1.0)
            dx = mxb - state[spec.output + "$mx"]
            dy = myb - state[spec.output + "$my"]
            cxy = (state[spec.output + "$cxy"] + cxyb
                   + dx * dy * na.astype(jnp.float64)
                   * nb.astype(jnp.float64) / nf)
            out[spec.output + "$count"] = n
            out[spec.output + "$mx"] = mx
            out[spec.output + "$my"] = my
            out[spec.output + "$m2x"] = m2x
            out[spec.output + "$m2y"] = m2y
            out[spec.output + "$cxy"] = cxy
            continue
        if spec.name in HLL_AGGS:
            m = spec.param or HLL_DEFAULT_BUCKETS
            # salt-free value hash so register content is identical across
            # probe-salt retries and across tables merged by agg_merge
            bucket, rank = _hll_bucket_rank(hash_columns([col]), m)
            idx = jnp.where(valid, slot * m + bucket, num_slots * m)
            key = spec.output + "$hll"
            out[key] = state[key].at[idx].max(rank, mode="drop")
            continue
        v = col.values
        if spec.is_float and v.dtype != jnp.float64:
            v = v.astype(jnp.float64)
        if not spec.is_float and v.dtype != jnp.int64:
            v = v.astype(jnp.int64)
        if spec.name == "sum" or spec.name == "avg":
            key = spec.output if spec.name == "sum" else spec.output + "$sum"
            out[key] = state[key].at[slot].add(jnp.where(valid, v, 0), mode="drop")
            ckey = spec.output + ("$count" if spec.name == "sum" else "$count")
            out[ckey] = state[ckey].at[slot].add(valid.astype(jnp.int64), mode="drop")
        elif spec.name == "min":
            fill = jnp.inf if spec.is_float else INT64_MAX
            out[spec.output] = state[spec.output].at[slot].min(
                jnp.where(valid, v, fill), mode="drop")
            out[spec.output + "$count"] = state[spec.output + "$count"].at[slot].add(
                valid.astype(jnp.int64), mode="drop")
        elif spec.name == "max":
            fill = -jnp.inf if spec.is_float else INT64_MIN
            out[spec.output] = state[spec.output].at[slot].max(
                jnp.where(valid, v, fill), mode="drop")
            out[spec.output + "$count"] = state[spec.output + "$count"].at[slot].add(
                valid.astype(jnp.int64), mode="drop")
    return out


def agg_merge(a: dict, b: dict, specs: Tuple[AggSpec, ...],
              key_names: Tuple[str, ...], num_slots: int) -> dict:
    """Merge accumulator state `b` into `a` (partial->final combining).

    With probing, the same key can occupy different slots in the two tables,
    so b's occupied slots are re-inserted into a as a pseudo-batch: the slot
    arrays of b become "rows" whose values are b's accumulators.
    """
    out = dict(a)
    mask = b["__occupied"]
    kh = b["__keyhash"]
    slot = (kh % jnp.uint64(num_slots)).astype(jnp.int32)
    table = a["__keyhash"]
    pending = mask
    placed_slot = jnp.zeros(mask.shape, dtype=jnp.int32)
    for _ in range(PROBE_ROUNDS):
        prop = jnp.where(pending, kh, EMPTY_SLOT)
        attempt = jnp.full(num_slots, EMPTY_SLOT).at[slot].min(prop)
        table = jnp.where(table == EMPTY_SLOT, attempt, table)
        win = pending & (table[slot] == kh)
        placed_slot = jnp.where(win, slot, placed_slot)
        pending = pending & ~win
        slot = jnp.where(pending, (slot + 1) % num_slots, slot)
    out["__collision"] = a["__collision"] | b["__collision"] | jnp.any(pending)
    out["__keyhash"] = table
    out["__occupied"] = table != EMPTY_SLOT
    mask = mask & ~pending
    slot = jnp.where(mask, placed_slot, num_slots)

    for kname in key_names:
        out[f"__key_{kname}"] = a[f"__key_{kname}"].at[slot].set(
            b[f"__key_{kname}"], mode="drop")
        out[f"__keynull_{kname}"] = a[f"__keynull_{kname}"].at[slot].set(
            b[f"__keynull_{kname}"], mode="drop")

    def _add(key):
        out[key] = a[key].at[slot].add(
            jnp.where(mask, b[key], jnp.zeros((), b[key].dtype)), mode="drop")

    def _realign(key, dtype=jnp.float64):
        # b's per-slot values re-addressed to a's slot space; distinct keys
        # land on distinct slots, so add-into-zeros is an exact placement
        return jnp.zeros(num_slots, dtype).at[slot].add(
            jnp.where(mask, b[key], jnp.zeros((), b[key].dtype)),
            mode="drop")

    for spec in specs:
        if spec.name in MOMENT_AGGS:
            nb = _realign(spec.output + "$count", jnp.int64)
            n, mean, m2 = _chan_merge(
                a[spec.output + "$count"], a[spec.output + "$mean"],
                a[spec.output + "$m2"], nb,
                _realign(spec.output + "$mean"),
                _realign(spec.output + "$m2"))
            out[spec.output + "$count"] = n
            out[spec.output + "$mean"] = mean
            out[spec.output + "$m2"] = m2
        elif spec.name in CORR_AGGS:
            na = a[spec.output + "$count"]
            nb = _realign(spec.output + "$count", jnp.int64)
            mxb = _realign(spec.output + "$mx")
            myb = _realign(spec.output + "$my")
            n, mx, m2x = _chan_merge(na, a[spec.output + "$mx"],
                                     a[spec.output + "$m2x"], nb, mxb,
                                     _realign(spec.output + "$m2x"))
            _, my, m2y = _chan_merge(na, a[spec.output + "$my"],
                                     a[spec.output + "$m2y"], nb, myb,
                                     _realign(spec.output + "$m2y"))
            nf = jnp.maximum(n.astype(jnp.float64), 1.0)
            dx = mxb - a[spec.output + "$mx"]
            dy = myb - a[spec.output + "$my"]
            cxy = (a[spec.output + "$cxy"] + _realign(spec.output + "$cxy")
                   + dx * dy * na.astype(jnp.float64)
                   * nb.astype(jnp.float64) / nf)
            out[spec.output + "$count"] = n
            out[spec.output + "$mx"] = mx
            out[spec.output + "$my"] = my
            out[spec.output + "$m2x"] = m2x
            out[spec.output + "$m2y"] = m2y
            out[spec.output + "$cxy"] = cxy
        elif spec.name in ("count", "count_star"):
            _add(spec.output)
        elif spec.name == "avg":
            _add(spec.output + "$sum")
            _add(spec.output + "$count")
        elif spec.name == "sum":
            _add(spec.output)
            _add(spec.output + "$count")
        elif spec.name == "min":
            fill = jnp.asarray(jnp.inf if spec.is_float else INT64_MAX,
                               a[spec.output].dtype)
            out[spec.output] = a[spec.output].at[slot].min(
                jnp.where(mask, b[spec.output], fill), mode="drop")
            _add(spec.output + "$count")
        elif spec.name == "max":
            fill = jnp.asarray(-jnp.inf if spec.is_float else INT64_MIN,
                               a[spec.output].dtype)
            out[spec.output] = a[spec.output].at[slot].max(
                jnp.where(mask, b[spec.output], fill), mode="drop")
            _add(spec.output + "$count")
        elif spec.name in HLL_AGGS:
            m = spec.param or HLL_DEFAULT_BUCKETS
            key = spec.output + "$hll"
            breg = b[key].reshape(-1, m)
            rows = jnp.where(mask, slot, a["__keyhash"].shape[0])
            out[key] = a[key].reshape(-1, m).at[rows].max(
                jnp.where(mask[:, None], breg, jnp.int8(0)),
                mode="drop").reshape(-1)
    return out


# ---------------------------------------------------------------------------
# direct (small-domain) aggregation: when every group key is a closed-domain
# dictionary/bool column, the combined code IS the slot index — no hashing,
# no probing, no scatter.  Per batch this is G masked reductions, which XLA
# fuses into single passes; on TPU this is ~50x faster than the scatter
# table for the TPC-H Q1 shape (6 groups over 6M rows).
# ---------------------------------------------------------------------------

DIRECT_AGG_MAX_GROUPS = 64
# max accumulator length for span-direct (scatter-indexed) aggregation
SPAN_AGG_MAX_GROUPS = 1 << 26


def agg_direct_init(G: int, specs: Tuple[AggSpec, ...]) -> dict:
    state = {"__seen": jnp.zeros(G, dtype=jnp.int64)}
    for spec in specs:
        if spec.name in ("count", "count_star"):
            state[spec.output] = jnp.zeros(G, dtype=jnp.int64)
        elif spec.name == "avg":
            dt = jnp.float64 if spec.is_float else jnp.int64
            state[spec.output + "$sum"] = jnp.zeros(G, dtype=dt)
            state[spec.output + "$count"] = jnp.zeros(G, dtype=jnp.int64)
        elif spec.name == "sum":
            dt = jnp.float64 if spec.is_float else jnp.int64
            state[spec.output] = jnp.zeros(G, dtype=dt)
            state[spec.output + "$count"] = jnp.zeros(G, dtype=jnp.int64)
        elif spec.name in ("min", "max"):
            dt = jnp.float64 if spec.is_float else jnp.int64
            init = (jnp.inf if spec.name == "min" else -jnp.inf) \
                if spec.is_float \
                else (INT64_MAX if spec.name == "min" else INT64_MIN)
            state[spec.output] = jnp.full(G, init, dtype=dt)
            state[spec.output + "$count"] = jnp.zeros(G, dtype=jnp.int64)
        else:
            raise NotImplementedError(spec.name)
    return state


def agg_direct_update(state: dict, batch: Batch, codes,
                      agg_inputs: Dict[str, Optional[Column]],
                      specs: Tuple[AggSpec, ...], G: int) -> dict:
    """codes: combined group code per row (int, < G).  The one-hot grid
    below fuses into the surrounding program, which is why no
    hand-written grouped-sum kernel stands here: of Q1's 0.114 s pass
    over SF10's 60M rows on the v5e, each grouped-sum fusion is 2.6 ms
    (PERF.md section 5, PR 29)."""
    grid = (codes[None, :] == jnp.arange(G, dtype=codes.dtype)[:, None]) \
        & batch.mask[None, :]
    out = dict(state)
    out["__seen"] = state["__seen"] + grid.sum(axis=1)
    for spec in specs:
        if spec.name == "count_star":
            out[spec.output] = state[spec.output] + grid.sum(axis=1)
            continue
        col = agg_inputs[spec.output]
        sel = grid if col.nulls is None else grid & ~col.nulls[None, :]
        nn = sel.sum(axis=1)
        x = col.values
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.int8)
        if spec.name == "count":
            out[spec.output] = state[spec.output] + nn
        elif spec.name in ("sum", "avg"):
            dt = jnp.float64 if spec.is_float else jnp.int64
            xs = jnp.where(sel, x[None, :].astype(dt), 0).sum(axis=1)
            if spec.name == "avg":
                out[spec.output + "$sum"] = state[spec.output + "$sum"] + xs
            else:
                out[spec.output] = state[spec.output] + xs
            out[spec.output + "$count"] = \
                state[spec.output + "$count"] + nn
        elif spec.name in ("min", "max"):
            is_min = spec.name == "min"
            if spec.is_float:
                ident = jnp.array(jnp.inf if is_min else -jnp.inf,
                                  jnp.float64)
                xv = x.astype(jnp.float64)
            else:
                ident = jnp.array(INT64_MAX if is_min else INT64_MIN,
                                  jnp.int64)
                xv = x.astype(jnp.int64)
            vals = jnp.where(sel, xv[None, :], ident)
            red = vals.min(axis=1) if is_min else vals.max(axis=1)
            out[spec.output] = (jnp.minimum if is_min else jnp.maximum)(
                state[spec.output], red)
            out[spec.output + "$count"] = \
                state[spec.output + "$count"] + nn
    return out


def agg_span_init(G: int, specs: Tuple[AggSpec, ...]) -> dict:
    """State for span-direct aggregation: integer group codes in [0, G)
    index the accumulators directly (code = combined key - base) — no
    hashing, no probing, no collision retries.  The TPU-native replacement
    for the scatter hash table whenever the key span is bounded (dense PK
    group-bys like TPC-H Q3/Q18's l_orderkey).  Group keys are not stored:
    the caller reconstructs them from the slot index (see
    agg_span_finalize)."""
    state = agg_direct_init(G, specs)
    return state


def agg_span_update(state: dict, batch: Batch, codes,
                    agg_inputs: Dict[str, Optional[Column]],
                    specs: Tuple[AggSpec, ...], G: int) -> dict:
    """codes: per-row group index (int, in [0, G) for live rows); masked
    rows are routed out of range and dropped.

    All accumulator columns of one op/dtype class are packed into a single
    (N, k) -> (G, k) scatter: TPU scatters cost per-INDEX, so a scalar
    scatter wastes the lane dimension — one packed scatter of k columns
    runs ~k times faster than k scalar scatters (measured 5.5x for k=6 at
    4M rows).  NULL handling folds into the updates (add of 0 / min of
    +inf is a no-op), so every column shares one slot vector."""
    mask = batch.mask
    slot = jnp.where(mask, codes, G).astype(jnp.int32)
    out = dict(state)
    ones = mask.astype(jnp.int64)

    adds_i: List[Tuple[str, jnp.ndarray]] = [("__seen", ones)]
    adds_f: List[Tuple[str, jnp.ndarray]] = []
    mins: List[Tuple[str, jnp.ndarray]] = []
    maxs: List[Tuple[str, jnp.ndarray]] = []
    for spec in specs:
        if spec.name == "count_star":
            adds_i.append((spec.output, ones))
            continue
        col = agg_inputs[spec.output]
        valid = mask & ~col.null_mask()
        vones = valid.astype(jnp.int64)
        if spec.name == "count":
            adds_i.append((spec.output, vones))
            continue
        v = col.values
        if spec.is_float and v.dtype != jnp.float64:
            v = v.astype(jnp.float64)
        if not spec.is_float and v.dtype != jnp.int64:
            v = v.astype(jnp.int64)
        if spec.name in ("sum", "avg"):
            key = spec.output if spec.name == "sum" else spec.output + "$sum"
            (adds_f if spec.is_float else adds_i).append(
                (key, jnp.where(valid, v, jnp.zeros((), v.dtype))))
            adds_i.append((spec.output + "$count", vones))
        elif spec.name in ("min", "max"):
            is_min = spec.name == "min"
            ident = ((jnp.inf if is_min else -jnp.inf) if spec.is_float
                     else (INT64_MAX if is_min else INT64_MIN))
            upd = jnp.where(valid, v, jnp.asarray(ident, v.dtype))
            (mins if is_min else maxs).append((spec.output, upd))
            adds_i.append((spec.output + "$count", vones))

    def apply(group, op):
        if not group:
            return
        if len(group) == 1:
            key, upd = group[0]
            out[key] = getattr(state[key].at[slot], op)(upd, mode="drop")
            return
        acc = jnp.stack([state[k] for k, _ in group], axis=1)
        upd = jnp.stack([u for _, u in group], axis=1)
        acc = getattr(acc.at[slot], op)(upd, mode="drop")
        for i, (key, _) in enumerate(group):
            out[key] = acc[:, i]

    apply(adds_i, "add")
    apply(adds_f, "add")
    # min/max need dtype-uniform packing; split by dtype
    for group, op in ((mins, "min"), (maxs, "max")):
        by_dt: Dict = {}
        for key, upd in group:
            by_dt.setdefault(upd.dtype, []).append((key, upd))
        for sub in by_dt.values():
            apply(sub, op)
    return out


def agg_span_finalize(state: dict, specs: Tuple[AggSpec, ...],
                      key_names: Tuple[str, ...],
                      key_arrays: Dict[str, jnp.ndarray],
                      key_dicts: Dict[str, Tuple[str, ...]],
                      key_lazy: Optional[Dict[str, Tuple]] = None,
                      key_nulls: Optional[Dict[str, jnp.ndarray]] = None
                      ) -> Batch:
    """key_arrays: slot-index -> key value per key (reconstructed by the
    caller, e.g. base + arange(G) for a single-int-key span)."""
    fake = dict(state)
    fake["__occupied"] = state["__seen"] > 0
    G = state["__seen"].shape[0]
    for k in key_names:
        fake[f"__key_{k}"] = key_arrays[k]
        fake[f"__keynull_{k}"] = (key_nulls or {}).get(
            k, jnp.zeros(G, dtype=bool))
    return agg_finalize(fake, specs, key_names, key_dicts, key_lazy)


def _depkey_as_int64(col: Column):
    """A grouping key's values as an exact int64 representation (floats
    bitcast — the dependency check needs per-group CONSTANCY, and rows of
    one underlying source row carry bit-identical values)."""
    v = col.values
    if v.dtype == jnp.float64:
        return jax.lax.bitcast_convert_type(v, jnp.int64)
    if v.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(v, jnp.int32).astype(jnp.int64)
    if v.dtype == jnp.bool_:
        return v.astype(jnp.int64)
    return v.astype(jnp.int64)


def _depkey_restore(minv, dtype):
    if dtype == jnp.float64:
        return jax.lax.bitcast_convert_type(minv, jnp.float64)
    if dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(
            minv.astype(jnp.int32), jnp.float32)
    return minv.astype(dtype)


def depkey_init(G: int, names: Tuple[str, ...]) -> dict:
    """Accumulators verifying that grouping keys are CONSTANT within each
    anchor-key group (the runtime-span multi-key scheme: group by one
    integer anchor, prove the other keys functionally dependent)."""
    st = {}
    for k in names:
        st[f"__dep_{k}$min"] = jnp.full(G, INT64_MAX, dtype=jnp.int64)
        st[f"__dep_{k}$max"] = jnp.full(G, INT64_MIN, dtype=jnp.int64)
        st[f"__dep_{k}$nulls"] = jnp.zeros(G, dtype=jnp.int64)
    return st


def depkey_update(st: dict, batch: Batch, codes, key_cols: Dict[str, Column],
                  G: int) -> dict:
    """Constancy tracking for the dependent grouping keys in as few
    scatters as possible: min and NEGATED max share one packed min-scatter
    (max(x) == -min(-x); identities chosen so INT64_MIN never negates),
    and null counting is skipped entirely for columns with no null mask
    (lazy row-ids / dictionary codes — the common case)."""
    out = dict(st)
    if not key_cols:
        return out
    mask = batch.mask
    slot = jnp.where(mask, codes, G).astype(jnp.int32)
    names = list(key_cols)
    mins, nulls_names, nulls = [], [], []
    for k in names:
        c = key_cols[k]
        v = _depkey_as_int64(c)
        if c.nulls is None:
            valid = mask
        else:
            valid = mask & ~c.nulls
            nulls_names.append(k)
            nulls.append((mask & c.nulls).astype(jnp.int64))
        mins.append(jnp.where(valid, v, INT64_MAX))
        # negated-max lane: min over (-v) recovers max; clamp so the
        # identity never overflows on negation
        mins.append(jnp.where(valid, -jnp.maximum(v, -INT64_MAX),
                              INT64_MAX))
    acc = jnp.stack(
        [st[f"__dep_{k}$min"] for k in names]
        + [-jnp.maximum(st[f"__dep_{k}$max"], -INT64_MAX) for k in names],
        axis=1)
    # interleave is (min_0, negmax_0, min_1, negmax_1, ...) for updates but
    # (mins..., negmaxs...) for state — align both as [mins..., negmaxs...]
    upd = jnp.stack([mins[2 * i] for i in range(len(names))]
                    + [mins[2 * i + 1] for i in range(len(names))], axis=1)
    acc = acc.at[slot].min(upd, mode="drop")
    for i, k in enumerate(names):
        out[f"__dep_{k}$min"] = acc[:, i]
        out[f"__dep_{k}$max"] = -acc[:, len(names) + i]
    if nulls:
        nacc = jnp.stack([st[f"__dep_{k}$nulls"] for k in nulls_names],
                         axis=1)
        nacc = nacc.at[slot].add(jnp.stack(nulls, axis=1), mode="drop")
        for i, k in enumerate(nulls_names):
            out[f"__dep_{k}$nulls"] = nacc[:, i]
    return out


def depkey_verify(st: dict, seen, names: Tuple[str, ...]):
    """All-groups scalar: every dependent key is uniform (one non-null
    value, or all NULL) within every occupied group."""
    ok = jnp.ones((), dtype=bool)
    for k in names:
        minv = st[f"__dep_{k}$min"]
        maxv = st[f"__dep_{k}$max"]
        nc = st[f"__dep_{k}$nulls"]
        uniform = ((nc == 0) & (minv == maxv)) | (nc == seen)
        ok = ok & jnp.all(uniform | (seen == 0))
    return ok


def _decimal_avg(s, cnt, empty):
    """Presto decimal avg: round-half-away-from-zero integer division at
    the input scale (single definition shared by the hash, window, and
    sort aggregation paths)."""
    safe = jnp.where(empty, 1, cnt)
    q = jnp.sign(s) * ((jnp.abs(s) + safe // 2) // safe)
    return q.astype(jnp.int64)


def _packed_gather(columns: List[Column], perm) -> Dict[int, Column]:
    """Gather columns through one permutation with dtype-packed indexing:
    same-dtype value arrays stack into an (N, k) matrix gathered ONCE
    (TPU gathers cost per-index — k packed lanes are ~3x faster than k
    scalar gathers), null masks pack as their own bool group.  Returns
    {id(original column): gathered Column}."""
    by_dtype: Dict = {}
    for c in columns:
        by_dtype.setdefault(c.values.dtype, []).append(c)
    out_vals: Dict[int, jnp.ndarray] = {}
    for items in by_dtype.values():
        if len(items) == 1:
            out_vals[id(items[0])] = items[0].values[perm]
        else:
            stacked = jnp.stack([c.values for c in items], axis=1)[perm]
            for i, c in enumerate(items):
                out_vals[id(c)] = stacked[:, i]
    nullable = [c for c in columns if c.nulls is not None]
    out_nulls: Dict[int, jnp.ndarray] = {}
    if len(nullable) == 1:
        out_nulls[id(nullable[0])] = nullable[0].nulls[perm]
    elif nullable:
        stacked = jnp.stack([c.nulls for c in nullable], axis=1)[perm]
        for i, c in enumerate(nullable):
            out_nulls[id(c)] = stacked[:, i]
    return {id(c): Column(out_vals[id(c)], out_nulls.get(id(c)),
                          c.dictionary, c.lazy) for c in columns}


# ---------------------------------------------------------------------------
# streaming quantile summary for global approx_percentile
#
# The reference streams t-digest state
# (ApproximateLongPercentileAggregations.java); the XLA-friendly mergeable
# summary here is the classic equal-weight quantile summary: each input
# batch is reduced to its m equi-spaced order statistics plus its row
# count (one device sort per batch, static shapes), and the final
# percentile is the weighted nearest-rank over the union of all batch
# summaries — each summary point stands for count/m rows.  Rank error is
# bounded by the within-batch summarization only: <= 1/(2m) of each
# batch's weight, so <= 1/(2m) overall (m=8192 -> 0.006% rank error);
# the final union step is exact, so error does NOT grow with batch count.
# Summaries from disjoint spill buckets merge by concatenation, the same
# property the reference gets from t-digest merge.
# ---------------------------------------------------------------------------

PERCENTILE_SKETCH_POINTS = 8192


def percentile_batch_summary(values, alive, m: int = PERCENTILE_SKETCH_POINTS):
    """(values, alive mask) -> (points: (m,) float64, count: int64).
    Points are the m equi-spaced order statistics of the alive values
    (all-NaN when count == 0).  Jit-safe, static shapes."""
    v = values.astype(jnp.float64)
    # alive rows first, ordered by value (flag sort keeps NaN payloads of
    # dead lanes out of the prefix)
    perm = jnp.lexsort((v, ~alive))
    vs = v[perm]
    cnt = jnp.sum(alive.astype(jnp.int64))
    j = jnp.arange(m)
    # equi-spaced ranks over [0, cnt-1]; cnt==0 -> gather index 0, masked
    # by the NaN fill below
    pos = jnp.floor(j * jnp.maximum(cnt - 1, 0) / (m - 1) + 0.5) \
        .astype(jnp.int32)
    pts = vs[jnp.clip(pos, 0, vs.shape[0] - 1)]
    pts = jnp.where(cnt > 0, pts, jnp.nan)
    return pts, cnt


def percentile_union_value(points, counts, p: float):
    """(B, m) batch summary points + (B,) counts -> (value, is_null).
    Weighted nearest-rank over the union: point i of batch b represents
    counts[b]/m rows.  Exact given the summaries."""
    B, m = points.shape
    w = jnp.repeat(counts.astype(jnp.float64) / m, m)     # (B*m,)
    flat = points.reshape(-1)
    valid = ~jnp.isnan(flat)
    w = jnp.where(valid, w, 0.0)
    order = jnp.lexsort((flat, ~valid))
    fv, fw = flat[order], w[order]
    cum = jnp.cumsum(fw)
    total = jnp.sum(counts)
    # nearest-rank in row space (same rounding as the sort path's
    # floor(p*(cnt-1)+0.5)): the answer is the first summary point whose
    # cumulative weight exceeds the target row index
    target = jnp.floor(p * jnp.maximum(total - 1, 0).astype(jnp.float64)
                       + 0.5)
    idx = jnp.searchsorted(cum, target, side="right")
    val = fv[jnp.clip(idx, 0, fv.shape[0] - 1)]
    return val, total == 0


def sort_group_aggregate(batch: Batch, key_names: Tuple[str, ...],
                         agg_inputs: Dict[str, Optional[Column]],
                         specs: Tuple[AggSpec, ...],
                         agg_inputs2: Optional[Dict[str, Column]] = None
                         ) -> Batch:
    """Grouped aggregation by SORT + segmented scans — argsort, gathers,
    cumsums and associative scans only, NO scatters.  On TPU a scatter
    costs ~100ms per million rows while sorts and scans stream at memory
    bandwidth, so this is the high-cardinality replacement for the
    scatter hash table (the reference's HashAggregationOperator falls
    back to no such trick — this is the TPU-native formulation).

    Groups by the combined 64-bit key hash (distinct keys assumed to have
    distinct hashes — the same assumption the scatter table makes).
    Output: capacity == input capacity, one live row per group at its
    segment-start position."""
    if key_names:
        kh = _orderable_hash(hash_columns(
            [batch.columns[k] for k in key_names]))
    else:
        # global aggregation: every live row in one segment
        kh = jnp.zeros(batch.mask.shape, dtype=jnp.int64)
    kh = jnp.where(batch.mask, kh, INT64_MAX)
    perm = jnp.argsort(kh).astype(jnp.int32)
    khs = kh[perm]
    n = khs.shape[0]
    live = khs != INT64_MAX
    is_start = live & jnp.concatenate(
        [jnp.ones(1, dtype=bool), khs[1:] != khs[:-1]])
    if not key_names:
        # SQL: a global aggregate yields one row even over empty input
        # (the dead row-0 segment has zero contributions -> NULL/0 row)
        is_start = is_start.at[0].set(True)
    # int32 index math: int64-indexed gathers are ~8x slower on TPU and
    # n is far below 2^31 (SORT_AGG_MAX_BYTES bound)
    idx = jnp.arange(n, dtype=jnp.int32)
    # exclusive end of each segment = next segment start (suffix-min)
    nxt = jnp.flip(jax.lax.cummin(jnp.flip(
        jnp.where(is_start, idx, n))))
    seg_end = jnp.concatenate([nxt[1:], jnp.full(1, n, dtype=jnp.int32)])
    seg_end = jnp.where(live, seg_end, idx + 1)
    s_lo = idx
    s_hi = jnp.clip(seg_end, 0, n).astype(jnp.int32)
    # per-row segment START (for whole-group values at interior rows)
    seg_start_row = jax.lax.cummax(jnp.where(is_start, idx, 0)) \
        .astype(jnp.int32)

    # -- packed gathers: the permutation gather is the dominant cost here
    # (TPU gathers pay per-index; one (N, k) gather of k same-dtype
    # columns runs ~3x faster than k scalar gathers), so key and input
    # columns are stacked by dtype and gathered once per dtype
    gather_cols: Dict[int, Column] = {}
    for k in key_names:
        gather_cols[id(batch.columns[k])] = batch.columns[k]
    for spec in specs:
        if spec.name not in ("count_star", "approx_percentile"):
            c = agg_inputs[spec.output]
            gather_cols[id(c)] = c
    if agg_inputs2:
        for c in agg_inputs2.values():
            gather_cols[id(c)] = c
    gathered = _packed_gather(list(gather_cols.values()), perm)

    # -- packed segment counts/sums: every spec needs its segment count,
    # sum/avg need a value sum — ONE stacked cumsum per dtype class
    # replaces a cumsum per spec
    i64_items: List[jnp.ndarray] = []
    f64_items: List[jnp.ndarray] = []
    plan = []           # (spec, contrib, x, cnt_idx, sum_idx, is_f64)
    for spec in specs:
        if spec.name in ("count_star", "approx_percentile"):
            contrib, x = live, None
        else:
            c = gathered[id(agg_inputs[spec.output])]
            contrib = live & ~c.null_mask()
            x = c.values
        cnt_idx = len(i64_items)
        i64_items.append(contrib.astype(jnp.int64))
        sum_idx = None
        is_f64 = False
        if spec.name in ("sum", "avg"):
            dt = jnp.float64 if spec.is_float else jnp.int64
            xv = jnp.where(contrib, x, 0).astype(dt)
            is_f64 = spec.is_float
            if is_f64:
                sum_idx = len(f64_items)
                f64_items.append(xv)
            else:
                sum_idx = len(i64_items)
                i64_items.append(xv)
        plan.append((spec, contrib, x, cnt_idx, sum_idx, is_f64))

    def _seg(items, dt):
        if not items:
            return None
        m = jnp.stack(items)                              # (k, N)
        p = jnp.concatenate([jnp.zeros((len(items), 1), dtype=dt),
                             jnp.cumsum(m, axis=1)], axis=1)
        return p[:, s_hi] - p[:, s_lo]                    # (k, N)

    seg_i = _seg(i64_items, jnp.int64)
    seg_f = _seg(f64_items, jnp.float64)

    cols: Dict[str, Column] = {}
    for k in key_names:
        cols[k] = gathered[id(batch.columns[k])]
    for spec, contrib, x, cnt_idx, sum_idx, is_f64 in plan:
        cnt = seg_i[cnt_idx]
        if spec.name in ("count", "count_star"):
            cols[spec.output] = Column(cnt, None)
            continue
        empty = cnt == 0
        if spec.name in ("sum", "avg"):
            s = (seg_f if is_f64 else seg_i)[sum_idx]
            if spec.name == "sum":
                cols[spec.output] = Column(s, empty)
            else:
                if spec.is_float:
                    safe = jnp.where(empty, 1, cnt)
                    cols[spec.output] = Column(s / safe, empty)
                else:
                    cols[spec.output] = Column(_decimal_avg(s, cnt, empty),
                                               empty)
        elif spec.name in ("min", "max"):
            is_min = spec.name == "min"
            if spec.is_float:
                ident = jnp.array(jnp.inf if is_min else -jnp.inf,
                                  jnp.float64)
                xv = x.astype(jnp.float64)
            else:
                ident = jnp.array(INT64_MAX if is_min else INT64_MIN,
                                  jnp.int64)
                xv = x.astype(jnp.int64)
            xv = jnp.where(contrib, xv, ident)

            def comb(a, b, _min=is_min):
                fa, va = a
                fb, vb = b
                m = jnp.minimum(va, vb) if _min else jnp.maximum(va, vb)
                return (fa | fb, jnp.where(fb, vb, m))

            _, run = jax.lax.associative_scan(comb, (is_start, xv))
            vals = run[jnp.clip(s_hi - 1, 0, n - 1)]
            cols[spec.output] = Column(vals, empty)
        elif spec.name in MOMENT_AGGS:
            # numerically stable two-pass: the group mean comes from the
            # first prefix sum IN THE SAME program, then the second pass
            # accumulates centered squares (the reference's
            # VarianceAggregation keeps central moments for the same
            # reason)
            xf = jnp.where(contrib, x.astype(jnp.float64), 0.0)
            ps = jnp.concatenate([jnp.zeros(1), jnp.cumsum(xf)])
            c0m = jnp.concatenate([jnp.zeros(1, dtype=jnp.int64),
                                   jnp.cumsum(contrib.astype(jnp.int64))])
            g_sum = ps[s_hi] - ps[seg_start_row]     # whole-group, per row
            g_cnt = c0m[s_hi] - c0m[seg_start_row]
            mean_row = g_sum / jnp.maximum(g_cnt, 1)
            d = jnp.where(contrib, x.astype(jnp.float64) - mean_row, 0.0)
            ps2 = jnp.concatenate([jnp.zeros(1), jnp.cumsum(d * d)])
            m2 = ps2[s_hi] - ps2[s_lo]
            pop = spec.name in ("stddev_pop", "var_pop")
            denom = jnp.maximum(cnt if pop else cnt - 1, 1) \
                .astype(jnp.float64)
            v = m2 / denom
            if spec.name.startswith("stddev"):
                v = jnp.sqrt(v)
            null = cnt < (1 if pop else 2)
            cols[spec.output] = Column(v, null)
        elif spec.name in CORR_AGGS:
            c2 = gathered[id(agg_inputs2[spec.output])]
            contrib2 = contrib & ~c2.null_mask()
            c0 = jnp.concatenate([jnp.zeros(1, dtype=jnp.int64),
                                  jnp.cumsum(contrib2.astype(jnp.int64))])
            n2 = c0[s_hi] - c0[s_lo]
            xf = jnp.where(contrib2, x.astype(jnp.float64), 0.0)
            yf = jnp.where(contrib2, c2.values.astype(jnp.float64), 0.0)
            # two-pass centered cross-moments (same stability rationale as
            # the MOMENT branch); stacked cumsums keep the HLO op count low
            stack1 = jnp.stack([xf, yf])
            p1 = jnp.concatenate(
                [jnp.zeros((2, 1)), jnp.cumsum(stack1, axis=1)], axis=1)
            g_cnt = jnp.maximum(c0[s_hi] - c0[seg_start_row], 1)
            mean_x = (p1[0, s_hi] - p1[0, seg_start_row]) / g_cnt
            mean_y = (p1[1, s_hi] - p1[1, seg_start_row]) / g_cnt
            dx = jnp.where(contrib2, x.astype(jnp.float64) - mean_x, 0.0)
            dy = jnp.where(contrib2,
                           c2.values.astype(jnp.float64) - mean_y, 0.0)
            stack2 = jnp.stack([dx * dx, dy * dy, dx * dy])
            p2 = jnp.concatenate(
                [jnp.zeros((3, 1)), jnp.cumsum(stack2, axis=1)], axis=1)
            seg = p2[:, s_hi] - p2[:, s_lo]
            v, null = _corr_finalize(spec.name, seg[0], seg[1], seg[2], n2)
            cols[spec.output] = Column(v, null)
        elif spec.name == "approx_percentile":
            # value-ordered secondary sort: NULL/dead rows sort last
            # within their key-hash segment, then the nearest-rank element
            # is one gather at fs + round(p * (cnt-1))
            p = float(spec.param if spec.param is not None else 0.5)
            xc = agg_inputs[spec.output]
            vx = xc.values
            alive = batch.mask & ~xc.null_mask()
            # dead/NULL rows ordered by an explicit flag (not an in-band
            # value sentinel, which legitimate inf/INT64_MAX values or
            # NaN would interleave with)
            perm_p = jnp.lexsort((vx, ~alive, kh)).astype(jnp.int32)
            vx_sorted = vx[perm_p]
            alive_p = alive[perm_p]
            a0 = jnp.concatenate([jnp.zeros(1, dtype=jnp.int64),
                                  jnp.cumsum(alive_p.astype(jnp.int64))])
            cntp = a0[s_hi] - a0[s_lo]
            pos = s_lo + jnp.floor(
                p * jnp.maximum(cntp - 1, 0) + 0.5).astype(jnp.int32)
            vals = vx_sorted[jnp.clip(pos, 0, n - 1)]
            cols[spec.output] = Column(vals, cntp == 0, xc.dictionary,
                                       xc.lazy)
        else:
            raise NotImplementedError(spec.name)
    return Batch(cols, is_start)


def agg_direct_finalize(state: dict, specs: Tuple[AggSpec, ...],
                        key_names: Tuple[str, ...],
                        key_doms: Tuple[int, ...],
                        key_dtypes,
                        key_dicts: Dict[str, Tuple[str, ...]],
                        force_row: bool = False) -> Batch:
    """Decode slot index -> key codes, then reuse agg_finalize.
    force_row: a global aggregation yields one row even over no input."""
    G = 1
    for d in key_doms:
        G *= d
    fake = dict(state)
    fake["__occupied"] = (state["__seen"] > 0) | force_row
    slot = jnp.arange(G, dtype=jnp.int64)
    stride = G
    for k, dom, dt in zip(key_names, key_doms, key_dtypes):
        stride //= dom
        code = (slot // stride) % dom
        fake[f"__key_{k}"] = code.astype(dt)
        fake[f"__keynull_{k}"] = jnp.zeros(G, dtype=bool)
    return agg_finalize(fake, specs, key_names, key_dicts)


def agg_finalize(state: dict, specs: Tuple[AggSpec, ...],
                 key_names: Tuple[str, ...],
                 key_dicts: Dict[str, Tuple[str, ...]],
                 key_lazy: Optional[Dict[str, Tuple]] = None) -> Batch:
    """Accumulator table -> output Batch (capacity == num_slots, mask ==
    occupied).  Runs under jit; host later compacts via batch_to_page.

    key_lazy carries late-materialization tags for open-domain string keys:
    such keys group by row identity (their values are source row ids), which
    is exact whenever a unique key is also in the grouping set (the TPC-H
    Q10 shape: c_custkey determines c_address/c_comment)."""
    occupied = state["__occupied"]
    cols: Dict[str, Column] = {}
    for name in key_names:
        cols[name] = Column(state[f"__key_{name}"],
                            state.get(f"__keynull_{name}"),
                            key_dicts.get(name),
                            (key_lazy or {}).get(name))
    for spec in specs:
        if spec.name in ("count", "count_star"):
            cols[spec.output] = Column(state[spec.output], None)
        elif spec.name == "sum":
            # SQL: sum of zero non-null inputs is NULL
            empty = state[spec.output + "$count"] == 0
            cols[spec.output] = Column(state[spec.output], empty)
        elif spec.name == "avg":
            s = state[spec.output + "$sum"]
            c = state[spec.output + "$count"]
            empty = c == 0
            safe_c = jnp.where(empty, 1, c)
            if spec.is_float:
                cols[spec.output] = Column(s / safe_c, empty)
            else:
                cols[spec.output] = Column(_decimal_avg(s, c, empty), empty)
        elif spec.name in ("min", "max"):
            empty = state[spec.output + "$count"] == 0
            cols[spec.output] = Column(state[spec.output], empty)
        elif spec.name in MOMENT_AGGS:
            v, null = _moment_finalize(
                spec.name, state[spec.output + "$mean"],
                state[spec.output + "$m2"],
                state[spec.output + "$count"])
            cols[spec.output] = Column(v, null)
        elif spec.name in CORR_AGGS:
            v, null = _corr_finalize(
                spec.name, state[spec.output + "$m2x"],
                state[spec.output + "$m2y"], state[spec.output + "$cxy"],
                state[spec.output + "$count"])
            cols[spec.output] = Column(v, null)
        elif spec.name in HLL_AGGS:
            m = spec.param or HLL_DEFAULT_BUCKETS
            # approx_distinct is never NULL: 0 over empty/all-null input
            cols[spec.output] = Column(
                _hll_estimate(state[spec.output + "$hll"], m), None)
    return Batch(cols, occupied)


# ---------------------------------------------------------------------------
# join: sorted build + vectorized binary search probe
# ---------------------------------------------------------------------------

def _orderable_hash(kh):
    """uint64 hash -> order-preserving int64 (searchsorted on uint64 may go
    through float64 and lose low bits; int64 compares exactly)."""
    return (kh ^ jnp.uint64(0x8000000000000000)).astype(jnp.int64)


@dataclass
class BuildTable:
    """Materialized, hash-sorted build side (pytree)."""
    keyhash_sorted: jnp.ndarray      # order-preserving int64, padding = max
    perm: jnp.ndarray                # sort permutation (int32)
    columns: Dict[str, Column]       # original (unsorted) build columns
    valid_count: jnp.ndarray         # scalar int32
    run_len: jnp.ndarray             # per-position equal-key run length

    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        return ((self.keyhash_sorted, self.perm,
                 tuple(self.columns[n] for n in names), self.valid_count,
                 self.run_len),
                names)

    @classmethod
    def tree_unflatten(cls, names, children):
        kh, perm, cols, vc, rl = children
        return cls(kh, perm, dict(zip(names, cols)), vc, rl)


jax.tree_util.register_pytree_node_class(BuildTable)


def build_table(batch: Batch, key_names: List[str], salt: int = 0) -> BuildTable:
    """Sort the build side by key hash (padding rows sort to the end).

    Also precomputes per-position run lengths so the probe can derive match
    counts from ONE searchsorted (searchsorted is the most expensive
    primitive in the probe on TPU; see probe_join).  All index arrays are
    int32: int64-indexed gathers are ~8x slower on TPU."""
    key_cols = [batch.columns[k] for k in key_names]
    kh = _orderable_hash(hash_columns(key_cols, salt))
    kh = jnp.where(batch.mask, kh, jnp.iinfo(jnp.int64).max)
    perm = jnp.argsort(kh).astype(jnp.int32)
    kh_sorted = kh[perm]
    n = kh_sorted.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones(1, dtype=bool),
                                kh_sorted[1:] != kh_sorted[:-1]])
    run_start = jax.lax.cummax(jnp.where(is_start, pos, 0))
    run_len = _run_end(is_start, n) - run_start
    return BuildTable(kh_sorted, perm, dict(batch.columns),
                      jnp.sum(batch.mask).astype(jnp.int32),
                      run_len)


def _run_end(is_start, n):
    """Per-position exclusive end of the containing equal-key run: the next
    run's start, filled backwards (reverse cummin of start positions)."""
    pos = jnp.arange(n, dtype=jnp.int32)
    starts_rev = jnp.where(is_start, pos, n)[::-1]
    return jnp.concatenate(
        [jax.lax.cummin(starts_rev)[::-1][1:],
         jnp.full(1, n, dtype=jnp.int32)])


def probe_join(batch: Batch, table: BuildTable, probe_keys: List[str],
               build_output: List[str], out_capacity: int,
               salt: int = 0, join_type: str = "INNER", filter_fn=None,
               matched=None):
    """Equi-join probe: returns (joined Batch, overflow flag, total).

    Output columns = all probe columns + build_output (renamed by caller).
    INNER: one output row per (probe row, matching build row) passing the
    optional non-equi `filter_fn` (a Batch -> Column predicate over the
    expanded rows).
    LEFT: probe rows with NO surviving match (the filter applies to pairs
    BEFORE null-extension, per SQL ON semantics) produce one row with nulls
    on the build side; output capacity is out_capacity + batch.capacity.
    """
    # ONE searchsorted (the dominant primitive cost on TPU): the left
    # insertion point plus the build side's precomputed run lengths give
    # the match count; int32 index math keeps gathers ~8x faster than
    # int64-indexed ones.
    kh = _orderable_hash(hash_columns(
        [batch.columns[k] for k in probe_keys], salt))
    nb = table.perm.shape[0]
    # scan_unrolled: ~2x the default scan method's throughput on TPU
    lo = jnp.searchsorted(table.keyhash_sorted, kh, side="left",
                          method="scan_unrolled").astype(jnp.int32)
    lo_c = jnp.clip(lo, 0, nb - 1)
    hit = table.keyhash_sorted[lo_c] == kh
    # SQL equi-join: a NULL key never matches (exec/reference.py:452-457)
    for k in probe_keys:
        nn = batch.columns[k].nulls
        if nn is not None:
            hit = hit & ~nn
    counts = jnp.where(batch.mask & hit, table.run_len[lo_c], 0)
    offsets = jnp.cumsum(counts.astype(jnp.int64))
    total = offsets[-1]
    overflow = total > out_capacity
    starts = (offsets - counts).astype(jnp.int32)

    j = jnp.arange(out_capacity, dtype=jnp.int32)
    # which probe row does output j belong to?  scatter each row's index at
    # its start slot, then forward-fill (cummax) — replaces a searchsorted
    # of out_capacity lookups, the old hot spot
    rows32 = jnp.arange(batch.capacity, dtype=jnp.int32)
    rowmark = jnp.zeros(out_capacity, dtype=jnp.int32).at[
        jnp.where(counts > 0, starts, out_capacity)
    ].max(rows32, mode="drop")
    row = jax.lax.cummax(rowmark)
    k = j - starts[row]                      # match ordinal within the row
    build_pos = jnp.clip(lo[row] + k, 0, nb - 1)
    build_idx = table.perm[build_pos]
    out_mask = j < total

    out_cols: Dict[str, Column] = {}
    pg = _packed_gather(list(batch.columns.values()), row)
    for name, col in batch.columns.items():
        out_cols[name] = pg[id(col)]
    bg = _packed_gather([table.columns[n] for n in build_output], build_idx)
    for name in build_output:
        out_cols[name] = bg[id(table.columns[name])]
    pairs = Batch(out_cols, out_mask)
    if filter_fn is not None:
        pred = filter_fn(pairs)
        keep = pred.values.astype(bool)
        if pred.nulls is not None:
            keep = keep & ~pred.nulls
        pairs = pairs.with_mask(pairs.mask & keep)
    if matched is not None:
        # FULL: record which build rows found a surviving match
        matched = matched.at[build_idx].max(pairs.mask, mode="drop")
    if join_type == "INNER":
        return pairs, overflow, total, matched

    # LEFT/FULL: append one null-extended row per probe row without a
    # surviving match (extra region of batch.capacity rows)
    has_match = jnp.zeros(batch.capacity, dtype=bool).at[row].max(
        pairs.mask, mode="drop")
    extra_mask = batch.mask & ~has_match
    final_cols: Dict[str, Column] = {}
    for name, col in batch.columns.items():
        pc = pairs.columns[name]
        values = jnp.concatenate([pc.values, col.values])
        nulls = None
        if pc.nulls is not None or col.nulls is not None:
            nulls = jnp.concatenate([pc.null_mask(), col.null_mask()])
        final_cols[name] = Column(values, nulls, col.dictionary, col.lazy)
    for name in build_output:
        pc = pairs.columns[name]
        src = table.columns[name]
        pad = jnp.zeros(batch.capacity, dtype=pc.values.dtype)
        values = jnp.concatenate([pc.values, pad])
        nulls = jnp.concatenate([pc.null_mask(),
                                 jnp.ones(batch.capacity, dtype=bool)])
        final_cols[name] = Column(values, nulls, src.dictionary, src.lazy)
    final_mask = jnp.concatenate([pairs.mask, extra_mask])
    # the returned count is the LIVE row total of the emitted batch (pairs
    # + null-extended rows) so callers can right-size compaction; overflow
    # is still judged against the pair region alone
    return (Batch(final_cols, final_mask), overflow,
            total + jnp.sum(extra_mask), matched)


# a gather's cost an index grows with the table it reads (on the v5e 7 ns
# into 64K entries, 13-27 ns into 16M: PERF.md, PR 34), so a lookup whose
# live indices all lie within this many entries of each other reads them
# from a window sliced out of the table
GATHER_WINDOW = 1 << 16
# ... and one whose live indices, LOOKUP_BLOCK rows at a time, lie in two
# neighbouring LOOKUP_TILE-entry tiles of that window reads no entry by
# index: each block's two tiles are picked out by a one-hot int8 product on
# the matrix unit and each row selects its entry by a compare and a select
# (on the v5e a 64K-row chunk's lookup takes 36 us against the window's
# 564: PERF.md §6).  A block reads LOOKUP_SPAN entries
LOOKUP_BLOCK = 256
LOOKUP_TILE = 128
LOOKUP_SPAN = 2 * LOOKUP_TILE
# the ways a `gather_near` can go, as `gather_near_path` reports them
LOOKUP_GATHER, LOOKUP_WINDOW, LOOKUP_BLOCKED = 0, 1, 2

_LOOKUPS = threading.local()


@contextmanager
def lookup_paths():
    """The way each `gather_near` traced inside the block went, as traced
    int32 scalars (LOOKUP_*) in trace order: a program returns them with
    its outputs, so the host reads them in a fetch it makes anyway."""
    outer = getattr(_LOOKUPS, "paths", None)
    _LOOKUPS.paths = []
    try:
        yield _LOOKUPS.paths
    finally:
        _LOOKUPS.paths = outer


def gather_near(table, idx, live):
    """`table[idx]` (an int32 table, the indices already clipped to it) by
    the cheapest way the live rows' indices allow: a batch of a fact table
    scanned in its key's order, as `lineitem` is in `l_orderkey`'s, probes
    a stretch of the key space no longer than itself, and each
    LOOKUP_BLOCK rows of it a stretch shorter still.  Which it is, the
    indices decide, a batch at a time (`gather_near_path`); rows that are
    not live read garbage."""
    values, path = gather_near_path(table, idx, live)
    paths = getattr(_LOOKUPS, "paths", None)
    if paths is not None:
        paths.append(path)
    return values


def gather_near_path(table, idx, live):
    """(`gather_near`'s values, the LOOKUP_* way it took): the block-local
    read where every block's live indices lie in the two tiles that begin
    at the tile of its least one, else the window where all of them lie in
    GATHER_WINDOW entries, else the gather."""
    size = table.shape[0]
    if size <= 2 * GATHER_WINDOW:
        return table[idx], jnp.int32(LOOKUP_GATHER)
    lo = jnp.min(jnp.where(live, idx, size - 1))
    hi = jnp.max(jnp.where(live, idx, 0))
    start = jnp.minimum(lo, size - GATHER_WINDOW)

    def window():
        return jax.lax.dynamic_slice(table, (start,), (GATHER_WINDOW,))

    def near():
        return window()[jnp.clip(idx - start, 0, GATHER_WINDOW - 1)]
    ways = [lambda: table[idx], near]
    path = jnp.where(hi - start < GATHER_WINDOW, LOOKUP_WINDOW, LOOKUP_GATHER)
    if idx.shape[0] % LOOKUP_BLOCK == 0:
        blocks = idx.reshape(-1, LOOKUP_BLOCK)
        held = live.reshape(blocks.shape)
        lo_b = jnp.min(jnp.where(held, blocks, size - 1), axis=1)
        hi_b = jnp.max(jnp.where(held, blocks, 0), axis=1)
        # the window's tile of each block's least live index (a block with
        # no live row reads the last two and fits)
        tile = jnp.clip((lo_b - start) // LOOKUP_TILE, 0,
                        GATHER_WINDOW // LOOKUP_TILE - 2)
        base = start + tile * LOOKUP_TILE
        ways.append(lambda: _block_local(window(), tile,
                                         blocks - base[:, None]))
        path = jnp.where(jnp.all(hi_b - base < LOOKUP_SPAN), LOOKUP_BLOCKED,
                         path)
    path = path.astype(jnp.int32)
    return jax.lax.switch(path, ways), path


def _block_local(window, tile, rel):
    """Row r of block b reads entry `rel[b, r]` of the LOOKUP_SPAN entries
    that begin at tile `tile[b]` of the window.  Exact: the product sums
    one byte and zeros, the select one entry and zeros."""
    nt = GATHER_WINDOW // LOOKUP_TILE
    pairs = jnp.concatenate(
        [window[:-LOOKUP_TILE].reshape(nt - 1, LOOKUP_TILE),
         window[LOOKUP_TILE:].reshape(nt - 1, LOOKUP_TILE)], axis=1)
    onehot = (tile[:, None] == jnp.arange(nt - 1, dtype=tile.dtype)
              ).astype(jnp.int8)
    stretch = _from_bytes(jax.lax.dot(onehot, _to_bytes(pairs),
                                      preferred_element_type=jnp.int32))
    # [block, entry, row]: the rows on the vector's lanes, the reduction
    # over the entries elementwise across registers
    pick = rel[:, None, :] == jnp.arange(LOOKUP_SPAN, dtype=rel.dtype
                                         )[None, :, None]
    return jnp.sum(jnp.where(pick, stretch[:, :, None], 0), axis=1,
                   dtype=window.dtype).reshape(-1)


def _to_bytes(v):
    """int32 [m, k] -> int8 [m, 4k]: each entry's four bytes, less 128."""
    u = jax.lax.bitcast_convert_type(v, jnp.uint32)
    parts = [((u >> (8 * j)) & 0xFF).astype(jnp.int32) - 128
             for j in range(4)]
    return jnp.stack(parts, axis=-1).astype(jnp.int8).reshape(
        v.shape[0], 4 * v.shape[1])


def _from_bytes(r):
    """`_to_bytes`' inverse, from the int32 sums of its bytes."""
    b = (r + 128).astype(jnp.uint32).reshape(r.shape[0], -1, 4)
    u = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)
    return jax.lax.bitcast_convert_type(u, jnp.int32)


def direct_lookup(batch: Batch, dt, probe_key: str):
    """(hit, build_row_index) for a direct-address table lookup —
    THE single definition of the slot math shared by the fused chain
    (fused.probe_direct), the streaming direct join, and the direct semi
    marker.  Misses return index 0 (in-bounds garbage; callers mask/null
    those rows); NULL probe keys never match; of a row that is not live
    both are garbage."""
    col = batch.columns[probe_key]
    v = col.values.astype(jnp.int64)
    size = dt.slots.shape[0]
    k = v - dt.base
    inb = (k >= 0) & (k < size)
    slot = gather_near(dt.slots, jnp.clip(k, 0, size - 1).astype(jnp.int32),
                       batch.mask & inb)
    hit = inb & (slot >= 0)
    if col.nulls is not None:
        hit = hit & ~col.nulls
    return hit, jnp.where(hit, slot, 0)


def probe_join_direct(batch: Batch, dt, probe_key: str,
                      build_output: List[str], join_type: str = "INNER",
                      filter_fn=None, matched=None):
    """Fanout-1 equi-join probe against a direct-address table
    (fused.DirectTable): ONE int32 gather instead of a searchsorted, and —
    because each probe row yields at most one output row — the output
    capacity equals the probe capacity, so there is no overflow flag, no
    live-count compaction, and ZERO host syncs per batch.  Mirrors
    probe_join's semantics: the ON-filter applies to pairs BEFORE
    null-extension; `matched` (FULL joins) records surviving build rows."""
    hit, bidx = direct_lookup(batch, dt, probe_key)
    hit = hit & batch.mask
    bidx = jnp.where(hit, bidx, 0)

    out_cols: Dict[str, Column] = dict(batch.columns)
    bg = _packed_gather([dt.columns[n] for n in build_output], bidx)
    for name in build_output:
        out_cols[name] = bg[id(dt.columns[name])]
    pairs = Batch(out_cols, hit)
    if filter_fn is not None:
        pred = filter_fn(pairs)
        keep = pred.values.astype(bool)
        if pred.nulls is not None:
            keep = keep & ~pred.nulls
        hit = hit & keep
        pairs = pairs.with_mask(hit)
    if matched is not None:
        nbuild = matched.shape[0]
        vslot = jnp.where(hit, bidx, nbuild)
        matched = matched.at[vslot].max(hit, mode="drop")
    if join_type == "INNER":
        return pairs, matched
    # LEFT/FULL: rows without a surviving match keep their probe columns
    # and read NULL on the build side (in-place, no extra row region —
    # fanout is 1, so the null-extended row IS the probe row)
    final_cols = dict(batch.columns)
    for name in build_output:
        c = pairs.columns[name]
        nulls = ~hit if c.nulls is None else (~hit | c.nulls)
        final_cols[name] = Column(c.values, nulls, c.dictionary, c.lazy)
    return Batch(final_cols, batch.mask), matched


def semi_join_mark_direct(batch: Batch, dt, probe_key: str,
                          build_has_null=False) -> Column:
    """semi_join_mark against a direct-address table: one int32 gather per
    probe batch, same three-valued semantics."""
    hit, _ = direct_lookup(batch, dt, probe_key)
    probe_null = batch.columns[probe_key].nulls
    if probe_null is None and isinstance(build_has_null, bool) \
            and not build_has_null:
        return Column(hit, None)
    nulls = ~hit & build_has_null
    if probe_null is not None:
        nulls = nulls | probe_null
    return Column(hit, nulls)


def semi_join_mark(batch: Batch, table: BuildTable, probe_keys: List[str],
                   salt: int = 0, build_has_null=False) -> Column:
    """SemiJoin marker with SQL three-valued semantics (reference
    HashSemiJoinOperator): TRUE on a match, FALSE on a definite miss, NULL
    when the probe key is NULL or when there is no match but the build side
    contained a NULL key (x IN (..., NULL) is UNKNOWN, never FALSE).
    Callers exclude NULL build keys before building and pass
    `build_has_null` (python bool or traced scalar) to report them."""
    kh = _orderable_hash(hash_columns(
        [batch.columns[k] for k in probe_keys], salt))
    lo = jnp.clip(jnp.searchsorted(table.keyhash_sorted, kh, side="left",
                                   method="scan_unrolled")
                  .astype(jnp.int32), 0, table.perm.shape[0] - 1)
    hit = table.keyhash_sorted[lo] == kh
    probe_null = None
    for k in probe_keys:
        nn = batch.columns[k].nulls
        if nn is not None:
            hit = hit & ~nn
            probe_null = nn if probe_null is None else probe_null | nn
    if probe_null is None and isinstance(build_has_null, bool) \
            and not build_has_null:
        return Column(hit, None)
    nulls = ~hit & build_has_null
    if probe_null is not None:
        nulls = nulls | probe_null
    return Column(hit, nulls)


# ---------------------------------------------------------------------------
# sort / topn / limit
# ---------------------------------------------------------------------------

def sort_indices(batch: Batch, keys: List[Tuple[str, str]]):
    """Stable sort permutation honoring sort orders; padding rows last.
    keys: [(column, ASC_NULLS_FIRST|...)]."""
    return jnp.lexsort(_sort_keys(batch, keys))


def _sort_keys(batch: Batch, keys: List[Tuple[str, str]]) -> tuple:
    """The arrays whose ascending lexicographic order, LAST one first, is
    the order `keys` ask for, padding rows after everything."""
    arrays = []
    # lexsort: last key is primary -> reverse
    for name, order in reversed(keys):
        col = batch.columns[name]
        v = col.values
        desc = order.startswith("DESC")
        if col.lazy is not None:
            from ..connectors import catalog as _catalog
            _, table, column, _sf = col.lazy
            if (table, column) not in _catalog.ROWID_ORDERED:
                raise NotImplementedError(
                    "ORDER BY on a late-materialized string column")
            # values are row ids; generator guarantees id order == lex order
        if col.dictionary is not None:
            # codes -> lexical ranks (host-precomputed, static)
            rank = np.argsort(np.argsort(np.array(col.dictionary)))
            v = jnp.asarray(rank.astype(np.int64))[v]
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int8)
        if jnp.issubdtype(v.dtype, jnp.floating):
            v = jnp.where(jnp.isnan(v), jnp.inf, v)  # NaN sorts as largest (Presto)
            key = -v if desc else v
            nullv = jnp.inf
        else:
            # Narrow ints promote to int64 when a sentinel or negation
            # could wrap: the INT64_MAX null sentinel would truncate to -1
            # in an int32 key (q14_1 NULLS LAST bug), and DESC negates the
            # key, where -INT_MIN wraps to itself at the narrow width.
            # ASC non-null keys keep their width (nothing can wrap).
            if (col.nulls is not None or desc) and v.dtype != jnp.int64:
                v = v.astype(jnp.int64)
            key = -v if desc else v
            nullv = INT64_MAX
        if col.nulls is not None:
            nulls_first = order.endswith("NULLS_FIRST")
            key = jnp.where(col.nulls, (-nullv if nulls_first else nullv), key)
        arrays.append(key)
    # padding sorts after everything
    pad_key = (~batch.mask).astype(jnp.int8)
    return tuple(arrays) + (pad_key,)


# up to this many rows a TopN picks them one after another, n passes of
# reductions over the batch, where a whole sort of it would be compiled
# (97 s on the v5e for 256K rows, PERF.md PR 34) and run for ten rows
TOPN_SELECT_MAX = 64


def _first_indices(batch: Batch, keys: List[Tuple[str, str]], n: int):
    """The first n rows of `sort_indices`' order without sorting: n times
    the least remaining row under the same keys, ties to the earlier row
    as the stable sort leaves them."""
    arrays = _sort_keys(batch, keys)[::-1]      # primary key first
    cap = batch.capacity
    pos = jnp.arange(cap, dtype=jnp.int32)

    def pick(i, carry):
        taken, out = carry
        cand = ~taken
        for a in arrays:
            best = jnp.min(jnp.where(cand, a, jnp.asarray(
                jnp.inf if jnp.issubdtype(a.dtype, jnp.floating)
                else jnp.iinfo(a.dtype).max, a.dtype)))
            cand = cand & (a == best)
        at = jnp.min(jnp.where(cand, pos, cap - 1))
        return taken.at[at].set(True), out.at[i].set(at)
    _, out = jax.lax.fori_loop(
        0, n, pick, (jnp.zeros(cap, dtype=bool),
                     jnp.zeros(n, dtype=jnp.int32)))
    return out


def topn(batch: Batch, keys: List[Tuple[str, str]], n: int) -> Batch:
    """Take first n rows by sort order; result capacity = n."""
    if n <= TOPN_SELECT_MAX and n <= batch.capacity:
        perm = _first_indices(batch, keys, n)
    else:
        perm = sort_indices(batch, keys)[:n]
    cols = {name: c.gather(perm) for name, c in batch.columns.items()}
    return Batch(cols, batch.mask[perm])


def sort_batch(batch: Batch, keys: List[Tuple[str, str]]) -> Batch:
    perm = sort_indices(batch, keys)
    cols = {name: c.gather(perm) for name, c in batch.columns.items()}
    return Batch(cols, batch.mask[perm])


# ---------------------------------------------------------------------------
# window functions
# (reference: presto-main-base/.../operator/WindowOperator.java:69; default
#  frame RANGE UNBOUNDED PRECEDING .. CURRENT ROW, i.e. running aggregates
#  include the current row's full peer group)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class WindowSpec:
    """One window function over the node's shared (partition, order) spec.

    frame: None = default (RANGE UNBOUNDED PRECEDING .. CURRENT ROW) or a
    normalized tuple (type, start_kind, start_off, end_kind, end_off) per
    the reference WindowFrame (presto-main-base/.../operator/window/).
    extra: constant arguments (lag/lead offset + default, nth_value n,
    ntile n)."""
    name: str
    output: str
    arg: Optional[str] = None   # input column (None for ranking / count(*))
    is_float: bool = False      # float accumulation (vs int64 / decimal)
    frame: Optional[tuple] = None
    extra: tuple = ()


def _row_change(col: Column) -> jnp.ndarray:
    """[i] = row i differs from row i-1 (null-aware: two NULLs are equal,
    NaN equals NaN — grouping semantics, not comparison semantics)."""
    v = col.values
    a, b = v[1:], v[:-1]
    if jnp.issubdtype(v.dtype, jnp.floating):
        eq = (a == b) | (jnp.isnan(a) & jnp.isnan(b))
    else:
        eq = a == b
    if col.nulls is not None:
        na, nb = col.nulls[1:], col.nulls[:-1]
        eq = jnp.where(na | nb, na & nb, eq)
    return jnp.concatenate([jnp.ones(1, dtype=bool), ~eq])


def _range_reduce(x, fs, fe, is_min: bool, ident):
    """Per-row min/max of x over index range [fs, fe] (sparse doubling
    table: log2(n) precomputed levels, two gathers per query row).  Empty
    ranges (fe < fs) return ident."""
    n = x.shape[0]
    levels = [x]
    size = 1
    while size < n:
        cur = levels[-1]
        pad = jnp.full((size,), ident, x.dtype)
        shifted = jnp.concatenate([cur[size:], pad])
        levels.append(jnp.minimum(cur, shifted) if is_min
                      else jnp.maximum(cur, shifted))
        size <<= 1
    stacked = jnp.stack(levels)                         # (L, n)
    length = jnp.maximum(fe - fs + 1, 1)
    j = (63 - jax.lax.clz(length.astype(jnp.uint64))).astype(jnp.int32)
    fs_c = jnp.clip(fs, 0, n - 1).astype(jnp.int32)
    hi = jnp.clip(fe - (jnp.int64(1) << j.astype(jnp.int64)) + 1,
                  0, n - 1).astype(jnp.int32)
    a = stacked[j, fs_c]
    b = stacked[j, hi]
    r = jnp.minimum(a, b) if is_min else jnp.maximum(a, b)
    return jnp.where(fe < fs, ident, r)


def window_batch(batch: Batch, partition_names: Tuple[str, ...],
                 orderings: Tuple[Tuple[str, str], ...],
                 specs: Tuple[WindowSpec, ...]) -> Batch:
    """Evaluate all window functions sharing one (partition, order) spec.

    Sorts the whole batch by (partition keys, order keys) — padding rows
    last, forming their own segment — then computes every function with
    segmented prefix scans / sparse-table range reductions: no
    per-partition loop, so partition count and sizes stay out of the
    compiled shape.  Frames per reference WindowOperator.java:69 +
    operator/window/: ROWS with offsets, RANGE with
    unbounded/current-row bounds.  Output row order is the sorted order
    (SQL does not guarantee WindowNode output order)."""
    sort_keys = [(p, "ASC_NULLS_FIRST") for p in partition_names] + list(orderings)
    perm = sort_indices(batch, sort_keys)   # [] keys still sorts padding last
    cols = {n: c.gather(perm) for n, c in batch.columns.items()}
    mask = batch.mask[perm]

    n = batch.capacity
    idx = jnp.arange(n, dtype=jnp.int64)

    part_start = jnp.zeros(n, dtype=bool).at[0].set(True)
    # the valid->padding transition starts a segment so padding never joins
    # (or extends the frame of) the last real partition
    part_start = part_start | jnp.concatenate(
        [jnp.zeros(1, dtype=bool), mask[1:] != mask[:-1]])
    for p in partition_names:
        part_start = part_start | _row_change(cols[p])
    peer_start = part_start
    for o, _ in orderings:
        peer_start = peer_start | _row_change(cols[o])

    seg_start = jax.lax.cummax(jnp.where(part_start, idx, 0))
    peer_start_idx = jax.lax.cummax(jnp.where(peer_start, idx, 0))
    # frame end = last row of the current peer group: one before the next
    # peer-group start (suffix-min of start indices, shifted left)
    at_or_after = jnp.flip(jax.lax.cummin(jnp.flip(
        jnp.where(peer_start, idx, n))))
    peer_end = jnp.concatenate(
        [at_or_after[1:], jnp.full(1, n, dtype=jnp.int64)]) - 1
    at_or_after_p = jnp.flip(jax.lax.cummin(jnp.flip(
        jnp.where(part_start, idx, n))))
    seg_end = jnp.concatenate(
        [at_or_after_p[1:], jnp.full(1, n, dtype=jnp.int64)]) - 1

    def frame_bounds(spec: WindowSpec):
        """(fs, fe) row index bounds of the spec's frame, clamped to the
        partition; empty frames have fe < fs."""
        f = spec.frame
        if f is None:
            return seg_start, peer_end
        ftype, sk, so, ek, eo = f
        if ftype == "RANGE":
            fs = {"UNBOUNDED_PRECEDING": seg_start,
                  "CURRENT": peer_start_idx}.get(sk)
            fe = {"CURRENT": peer_end,
                  "UNBOUNDED_FOLLOWING": seg_end}.get(ek)
            if fs is None or fe is None:
                raise NotImplementedError(
                    "RANGE frame bounds with offsets")
            return fs, fe
        fs = {"UNBOUNDED_PRECEDING": seg_start, "CURRENT": idx,
              "PRECEDING": idx - (so or 0),
              "FOLLOWING": idx + (so or 0),
              "UNBOUNDED_FOLLOWING": seg_end + 1}[sk]
        fe = {"UNBOUNDED_FOLLOWING": seg_end, "CURRENT": idx,
              "PRECEDING": idx - (eo or 0),
              "FOLLOWING": idx + (eo or 0),
              "UNBOUNDED_PRECEDING": seg_start - 1}[ek]
        return jnp.maximum(fs, seg_start), jnp.minimum(fe, seg_end)

    out = dict(cols)
    for spec in specs:
        if spec.name == "row_number":
            out[spec.output] = Column(idx - seg_start + 1, None)
            continue
        if spec.name == "rank":
            out[spec.output] = Column(peer_start_idx - seg_start + 1, None)
            continue
        if spec.name == "dense_rank":
            cp = jnp.cumsum(peer_start.astype(jnp.int64))
            out[spec.output] = Column(cp - cp[seg_start] + 1, None)
            continue
        if spec.name == "percent_rank":
            size = seg_end - seg_start + 1
            rank = peer_start_idx - seg_start + 1
            denom = jnp.maximum(size - 1, 1)
            v = (rank - 1).astype(jnp.float64) / denom
            out[spec.output] = Column(jnp.where(size <= 1, 0.0, v), None)
            continue
        if spec.name == "cume_dist":
            size = seg_end - seg_start + 1
            thru = peer_end - seg_start + 1
            out[spec.output] = Column(
                thru.astype(jnp.float64) / jnp.maximum(size, 1), None)
            continue
        if spec.name == "ntile":
            nt = jnp.int64(spec.extra[0])
            size = seg_end - seg_start + 1
            rn = idx - seg_start
            q, r = size // nt, size % nt
            big = r * (q + 1)
            bucket = jnp.where(
                rn < big, rn // jnp.maximum(q + 1, 1),
                r + (rn - big) // jnp.maximum(q, 1))
            out[spec.output] = Column(bucket + 1, None)
            continue

        if spec.name in ("lag", "lead", "first_value", "last_value",
                         "nth_value"):
            col = cols[spec.arg]
            fs, fe = frame_bounds(spec)
            if spec.name in ("lag", "lead"):
                off = jnp.int64(spec.extra[0] if spec.extra else 1)
                src = idx - off if spec.name == "lag" else idx + off
                valid = (src >= seg_start) & (src <= seg_end) & mask
            elif spec.name == "first_value":
                src = fs
                valid = (fe >= fs) & mask
            elif spec.name == "last_value":
                src = fe
                valid = (fe >= fs) & mask
            else:   # nth_value(x, k)
                k = jnp.int64(spec.extra[0] if spec.extra else 1)
                src = fs + k - 1
                valid = (src >= fs) & (src <= fe) & mask
            src_c = jnp.clip(src, 0, n - 1)
            vals = col.values[src_c]
            nulls = col.null_mask()[src_c] | ~valid
            default = spec.extra[1] if (spec.name in ("lag", "lead")
                                        and len(spec.extra) > 1) else None
            if default is not None:
                if col.dictionary is not None or col.lazy is not None:
                    raise NotImplementedError(
                        "lag/lead default over string columns")
                vals = jnp.where(valid, vals,
                                 jnp.asarray(default, vals.dtype))
                nulls = jnp.where(valid, col.null_mask()[src_c], False)
            out[spec.output] = Column(vals, nulls, col.dictionary,
                                      col.lazy)
            continue

        # frame aggregates
        fs, fe = frame_bounds(spec)
        empty = fe < fs
        fs_c = jnp.clip(fs, 0, n - 1)
        fe_c = jnp.clip(fe, 0, n - 1)
        if spec.name == "count_star":
            contrib = mask
            x = contrib.astype(jnp.int64)
        else:
            c = cols[spec.arg]
            contrib = mask if c.nulls is None else (mask & ~c.nulls)
            x = c.values
        cnt0 = jnp.concatenate([jnp.zeros(1, dtype=jnp.int64),
                                jnp.cumsum(contrib.astype(jnp.int64))])
        frame_cnt = jnp.where(empty, 0, cnt0[fe_c + 1] - cnt0[fs_c])
        if spec.name in ("count", "count_star"):
            out[spec.output] = Column(frame_cnt, None)
        elif spec.name in ("sum", "avg"):
            dt = jnp.float64 if spec.is_float else jnp.int64
            xv = jnp.where(contrib, x, 0).astype(dt)
            ps0 = jnp.concatenate([jnp.zeros(1, dtype=dt), jnp.cumsum(xv)])
            frame_sum = jnp.where(empty, jnp.zeros((), dt),
                                  ps0[fe_c + 1] - ps0[fs_c])
            isempty = frame_cnt == 0     # SQL: aggregate of no rows is NULL
            safe = jnp.where(isempty, 1, frame_cnt)
            if spec.name == "sum":
                out[spec.output] = Column(frame_sum, isempty)
            elif spec.is_float:
                out[spec.output] = Column(frame_sum / safe, isempty)
            else:
                out[spec.output] = Column(
                    _decimal_avg(frame_sum, frame_cnt, isempty), isempty)
        elif spec.name in ("min", "max"):
            is_min = spec.name == "min"
            was_bool = x.dtype == jnp.bool_
            col = cols[spec.arg]
            # string columns: dictionary codes compare by LEXICAL rank, not
            # code value; min/max over lazy row ids is valid only for
            # ROWID_ORDERED columns (the compiler encodes others first)
            code_of_rank = None
            if col.dictionary is not None:
                d = np.array(col.dictionary)
                rank_of_code = np.argsort(np.argsort(d)).astype(np.int64)
                code_of_rank = jnp.asarray(np.argsort(rank_of_code))
                x = jnp.asarray(rank_of_code)[x]
            if was_bool:
                x = x.astype(jnp.int8)
            if jnp.issubdtype(x.dtype, jnp.floating):
                ident = jnp.array(jnp.inf if is_min else -jnp.inf, x.dtype)
            else:
                ident = jnp.array(jnp.iinfo(x.dtype).max if is_min
                                  else jnp.iinfo(x.dtype).min, x.dtype)
            xv = jnp.where(contrib, x, ident)
            vals = _range_reduce(xv, fs, fe, is_min, ident)
            isempty = frame_cnt == 0
            if was_bool:
                vals = vals.astype(jnp.bool_)
            if col.dictionary is not None:
                # rank -> code; empty frames hold the identity sentinel,
                # clamp before the gather (result is NULL there anyway)
                vals = code_of_rank[jnp.where(isempty, 0, vals)]
                out[spec.output] = Column(vals, isempty, col.dictionary)
            elif col.lazy is not None:
                vals = jnp.where(isempty, 0, vals)
                out[spec.output] = Column(vals, isempty, None, col.lazy)
            else:
                out[spec.output] = Column(vals, isempty)
        else:
            raise NotImplementedError(f"window function {spec.name}")
    return Batch(out, mask)


def limit(batch: Batch, n: int, already_consumed) -> Tuple[Batch, jnp.ndarray]:
    """Keep first n valid rows across batches; returns new consumed count."""
    rank = jnp.cumsum(batch.mask) + already_consumed  # 1-based rank
    keep = batch.mask & (rank <= n)
    return batch.with_mask(keep), already_consumed + jnp.sum(batch.mask.astype(jnp.int64))


def distinct(batch: Batch, key_names: List[str], state_kh, salt: int = 0):
    """Streaming DISTINCT via seen-hash table (exact up to 64-bit hash).
    state_kh: sorted uint64 array of seen hashes (padded with max)."""
    raise NotImplementedError("distinct handled via grouped agg for now")


# ---------------------------------------------------------------------------
# compaction: gather valid rows to the front (host boundary / exchange prep)
# ---------------------------------------------------------------------------

def _pack_lanes(leaves, n: int):
    """Every leaf (n leading rows, any trailing shape, any dtype) as rows
    of ONE uint32 matrix [lanes, n], and what `_unpack_lanes` needs to
    undo it: 8-byte values take two lanes, narrower ones one."""
    rows, layout = [], []
    for a in leaves:
        flat = a.reshape(n, -1).T                       # [k, n]
        size = a.dtype.itemsize
        if a.dtype == jnp.bool_:
            lanes = flat.astype(jnp.uint32)
        elif size == 8:
            pair = jax.lax.bitcast_convert_type(flat, jnp.uint32)
            lanes = jnp.moveaxis(pair, -1, 1).reshape(-1, n)    # [2k, n]
        elif size == 4:
            lanes = jax.lax.bitcast_convert_type(flat, jnp.uint32)
        else:
            unsigned = {1: jnp.uint8, 2: jnp.uint16}[size]
            lanes = jax.lax.bitcast_convert_type(
                flat, unsigned).astype(jnp.uint32)
        rows.append(lanes)
        layout.append((a.shape, a.dtype, lanes.shape[0]))
    return jnp.concatenate(rows, axis=0), layout


def _unpack_lanes(packed, layout, n: int):
    out, at = [], 0
    for shape, dtype, lanes in layout:
        rows = packed[at:at + lanes]
        at += lanes
        size = jnp.dtype(dtype).itemsize
        if dtype == jnp.bool_:
            flat = rows != 0
        elif size == 8:
            flat = jax.lax.bitcast_convert_type(
                jnp.moveaxis(rows.reshape(-1, 2, n), 1, -1), dtype)
        elif size == 4:
            flat = jax.lax.bitcast_convert_type(rows, dtype)
        else:
            unsigned = {1: jnp.uint8, 2: jnp.uint16}[size]
            flat = jax.lax.bitcast_convert_type(rows.astype(unsigned), dtype)
        out.append(flat.T.reshape(shape))
    return out


def compact_front(batch: Batch) -> Batch:
    """Move live rows to a contiguous prefix (stable) WITHOUT a scatter or
    a gather: a row with d dead rows before it moves d places left, one
    binary digit of d a round, least significant first -- log2(capacity)
    rounds of a static shift and a select.  Two live rows never meet on
    the way (the row behind has at least as many dead rows before it,
    and they differ in position by more than in d), and order is kept.
    On the TPU a scatter or gather costs 50-80 ns an index (a 64K-row
    chunk: 3-5 ms an array, measured in PR 32); this costs microseconds.
    Every column rides as lanes of ONE uint32 matrix, with each row's
    remaining shift as one more lane (-1: no live row here), so a round
    is one shift and one select whatever the columns are: a few device
    ops a round, not a few an array.  Dead rows hold garbage."""
    n = batch.capacity
    live = batch.mask
    at = jnp.arange(n, dtype=jnp.int32)
    shift = jnp.where(live, at - (jnp.cumsum(live, dtype=jnp.int32) - 1), -1)
    leaves, tree = jax.tree_util.tree_flatten(batch.columns)
    packed, layout = _pack_lanes(leaves, n)
    state = jnp.concatenate(
        [packed, jax.lax.bitcast_convert_type(shift, jnp.uint32)[None]])
    dead = jnp.uint32(0xFFFFFFFF)           # the shift lane of an empty slot
    step = 1
    while step < n:
        moving = state[-1]
        goes = (moving != dead) & ((moving & jnp.uint32(step)) != 0)
        comes = jnp.roll(goes, -step) & (at < n - step)
        state = jnp.where(comes, jnp.roll(state, -step, axis=1),
                          state.at[-1].set(jnp.where(goes, dead, moving)))
        step <<= 1
    columns = jax.tree_util.tree_unflatten(
        tree, _unpack_lanes(state[:-1], layout, n))
    return Batch(columns, state[-1] != dead)


def compact(batch: Batch, out_capacity: Optional[int] = None) -> Batch:
    """Move live rows to a contiguous prefix (stable).  cumsum + scatter
    rather than argsort: sort kernels cost tens of seconds of XLA compile
    time per shape on TPU, while scatter compiles in ~1s."""
    cap = out_capacity or batch.capacity
    pos = jnp.cumsum(batch.mask) - 1
    idx = jnp.where(batch.mask, pos, cap).astype(jnp.int32)

    def scat(v):
        out = jnp.zeros((cap,) + v.shape[1:], v.dtype)
        return out.at[idx].set(v, mode="drop")

    cols = {name: Column(scat(c.values),
                         None if c.nulls is None else scat(c.nulls),
                         c.dictionary, c.lazy,
                         None if c.lengths is None else scat(c.lengths))
            for name, c in batch.columns.items()}
    mask = jnp.zeros(cap, dtype=bool).at[idx].set(batch.mask, mode="drop")
    return Batch(cols, mask)
