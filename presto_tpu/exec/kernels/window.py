"""Prefix-sum window aggregation inside a Pallas kernel.

The XLA window path (operators.window_batch) computes every running
aggregate / ranking function with whole-array cumulative scans; each
scan materializes its intermediate in HBM.  This kernel keeps the
sorted run VMEM-resident and evaluates ALL window outputs that share
one (partition, order) spec in a single launch, using the same
work-efficient pairing scan the scan kernel's compaction uses
(generalized to max/min/add so segment starts, peer ends and running
sums are in-kernel scans):

  sort      stays OUTSIDE the kernel: ops.sort_indices is the single
            definition of order semantics (dictionary ranks, NULL
            sentinels, padding-last), shared with the XLA path so the
            two paths see the SAME permutation.
  segments  partition / peer boundaries from null-aware change flags
            over the sorted key columns (operators._row_change twin),
            plus the live->padding mask transition, exactly as in
            window_batch; segment starts/ends come from inclusive
            max/min scans over flagged indices.
  frames    the default frame (RANGE UNBOUNDED PRECEDING .. CURRENT
            ROW) = [segment start, peer-group end]; running
            SUM/COUNT/AVG read two points of an inclusive prefix sum.

Parity contract: the pairing scans are exact for the integer max/min/
add operators regardless of association, the frame-aggregate identity
cnt0[fe+1] - cnt0[fs] == incl[fe] - incl[fs] + contrib[fs] is exact
int64 arithmetic, and padding lanes (appended after the sorted dead
rows to reach the scan's power-of-two width) start their own segment
at the mask transition exactly like window_batch's padding rows -- so
live-row outputs are bit-identical to the XLA path and the numpy
oracle.  Float sum/avg would re-associate the reduction tree, so they
decline instead (WindowFunctionShape); TPC-H decimals are unscaled
int64 on device and stay exact, including _decimal_avg rounding.

Gates (kernelDeclined reasons, scan_kernel.KERNEL_DECLINE_REASONS):
  WindowFunctionShape  function outside {row_number, rank, dense_rank,
                       count, count_star, sum, avg}, an explicit
                       frame, constant extras, or float accumulation
  WindowKeyShape       a late-materialized (lazy) partition/order/arg
                       column -- peer detection must not reorder the
                       row-id indirection
  WindowInputSize      padded operand bytes over
                       KERNEL_WINDOW_MAX_BYTES (the whole sorted run
                       must sit in VMEM at once)
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .. import operators as ops
from ..batch import Batch, Column
from . import shim
from ...utils.runtime_stats import jit_as
from .scan_kernel import KERNEL_METRICS

# the whole sorted run (mask + key/arg columns + per-spec outputs) is
# VMEM-resident for the launch; bigger inputs decline and run the XLA
# scans, which stream through HBM
KERNEL_WINDOW_MAX_BYTES = 1 << 23

_SUPPORTED = ("row_number", "rank", "dense_rank", "count", "count_star",
              "sum", "avg")

# compiled launchers keyed by the static shape (spec tuple, key layout,
# padded width) -- the window twin of the scan kernel's runner cache
_RUNNER_CACHE: Dict[tuple, object] = {}


def _exclusive_scan(x, op, ident):
    """scan_kernel._blelloch_exclusive generalized to any associative
    `op` with identity `ident` (max/min/add over a power-of-two
    vector).  Integer ops are exact under any pairing, so the result
    matches lax.cummax/cummin/jnp.cumsum bit-for-bit."""
    cur = x
    levels = []
    while cur.shape[0] > 1:
        levels.append(cur)
        pairs = cur.reshape(-1, 2)
        cur = op(pairs[:, 0], pairs[:, 1])
    pref = jnp.full_like(cur, ident)
    for lvl in reversed(levels):
        pairs = lvl.reshape(-1, 2)
        left = pref
        right = op(pref, pairs[:, 0])
        pref = jnp.stack([left, right], axis=1).reshape(-1)
    return pref


def _inclusive_scan(x, op, ident):
    return op(_exclusive_scan(x, op, ident), x)


def _change(v, nulls):
    """operators._row_change over raw (values, nulls) arrays: [i] = row
    i differs from row i-1, null-aware (two NULLs equal, NaN equals
    NaN -- grouping semantics)."""
    a, b = v[1:], v[:-1]
    if jnp.issubdtype(v.dtype, jnp.floating):
        eq = (a == b) | (jnp.isnan(a) & jnp.isnan(b))
    else:
        eq = a == b
    if nulls is not None:
        na, nb = nulls[1:], nulls[:-1]
        eq = jnp.where(na | nb, na & nb, eq)
    return jnp.concatenate([jnp.ones(1, dtype=bool), ~eq])


def _build_runner(partition_names, orderings, specs, layout, N):
    """Jitted whole-array Pallas launch for one static window shape.
    `layout` lists the kernel's column operands as (name, has_nulls) in
    input order; every operand is a padded (N,) array."""
    n_specs = len(specs)

    def kernel(*refs):
        mask = refs[0][...]
        arrays = {}
        r = 1
        for name, has_nulls in layout:
            v = refs[r][...]
            r += 1
            nl = None
            if has_nulls:
                nl = refs[r][...]
                r += 1
            arrays[name] = (v, nl)
        out_val_refs = refs[r:r + n_specs]
        out_null_refs = refs[r + n_specs:]

        idx = jnp.arange(N, dtype=jnp.int64)
        # the valid->padding transition starts a segment so padding
        # never joins (or extends the frame of) the last real partition
        part_start = (idx == 0) | jnp.concatenate(
            [jnp.zeros(1, dtype=bool), mask[1:] != mask[:-1]])
        for p in partition_names:
            part_start = part_start | _change(*arrays[p])
        peer_start = part_start
        for o, _ in orderings:
            peer_start = peer_start | _change(*arrays[o])

        seg_start = _inclusive_scan(jnp.where(part_start, idx, 0),
                                    jnp.maximum, 0)
        peer_start_idx = _inclusive_scan(jnp.where(peer_start, idx, 0),
                                         jnp.maximum, 0)
        at_or_after = jnp.flip(_inclusive_scan(
            jnp.flip(jnp.where(peer_start, idx, N)), jnp.minimum, N))
        peer_end = jnp.concatenate(
            [at_or_after[1:], jnp.full(1, N, dtype=jnp.int64)]) - 1

        # default frame: RANGE UNBOUNDED PRECEDING .. CURRENT ROW
        fs, fe = seg_start, peer_end
        empty = fe < fs
        fs_c = jnp.clip(fs, 0, N - 1)
        fe_c = jnp.clip(fe, 0, N - 1)

        for j, spec in enumerate(specs):
            nulls = None
            if spec.name == "row_number":
                vals = idx - seg_start + 1
            elif spec.name == "rank":
                vals = peer_start_idx - seg_start + 1
            elif spec.name == "dense_rank":
                cp = _inclusive_scan(peer_start.astype(jnp.int64),
                                     jnp.add, 0)
                vals = cp - cp[seg_start] + 1
            else:
                if spec.name == "count_star":
                    contrib = mask
                    x = contrib.astype(jnp.int64)
                else:
                    x, xn = arrays[spec.arg]
                    contrib = mask if xn is None else (mask & ~xn)
                # cnt0[fe+1] - cnt0[fs] over the concat([0], cumsum)
                # prefix == incl[fe] - incl[fs] + contrib[fs]: exact
                # int64, no length-(N+1) array in VMEM
                ci = contrib.astype(jnp.int64)
                cnt_incl = _inclusive_scan(ci, jnp.add, 0)
                frame_cnt = jnp.where(
                    empty, 0,
                    cnt_incl[fe_c] - cnt_incl[fs_c] + ci[fs_c])
                if spec.name in ("count", "count_star"):
                    vals = frame_cnt
                else:                            # sum / avg (integer)
                    xv = jnp.where(contrib, x, 0).astype(jnp.int64)
                    sum_incl = _inclusive_scan(xv, jnp.add, 0)
                    frame_sum = jnp.where(
                        empty, 0,
                        sum_incl[fe_c] - sum_incl[fs_c] + xv[fs_c])
                    isempty = frame_cnt == 0
                    if spec.name == "sum":
                        vals = frame_sum
                    else:
                        vals = ops._decimal_avg(frame_sum, frame_cnt,
                                                isempty)
                    nulls = isempty
            out_val_refs[j][...] = vals.astype(jnp.int64)
            out_null_refs[j][...] = (nulls if nulls is not None
                                     else jnp.zeros(N, dtype=bool))

    out_shape = ([jax.ShapeDtypeStruct((N,), jnp.int64)
                  for _ in range(n_specs)]
                 + [jax.ShapeDtypeStruct((N,), bool)
                    for _ in range(n_specs)])

    @jit_as("pallas_window")
    def launch(flat):
        return shim.pallas_call(kernel, out_shape=out_shape)(*flat)

    return launch


def try_window_kernel(batch: Batch, partition_names, orderings, specs, *,
                      declined, runtime_stats=None):
    """Evaluate a WindowNode's shared-spec functions through the Pallas
    prefix-scan kernel when eligible.  Returns the output Batch (sorted
    row order, same contract as ops.window_batch) or None after
    metering one kernelDeclined{reason} -- the XLA path takes over."""
    for spec in specs:
        if (spec.name not in _SUPPORTED or spec.frame is not None
                or spec.extra):
            declined("WindowFunctionShape")
            return None
        if spec.name in ("sum", "avg") and spec.is_float:
            # float cumsum re-associates the reduction tree; declining
            # preserves the bit-identity contract
            declined("WindowFunctionShape")
            return None
    if jax.default_backend() not in ("cpu", "tpu"):
        declined("Backend")
        return None
    needed = []
    for nm in (tuple(partition_names) + tuple(o for o, _ in orderings)
               + tuple(s.arg for s in specs if s.arg)):
        if nm not in needed:
            needed.append(nm)
    for nm in needed:
        if batch.columns[nm].lazy is not None:
            declined("WindowKeyShape")
            return None

    n = batch.capacity
    N = 1 << max(0, int(n - 1).bit_length())
    layout = []
    nbytes = N                                    # mask
    for nm in needed:
        c = batch.columns[nm]
        has_nulls = c.nulls is not None
        layout.append((nm, has_nulls))
        nbytes += N * (c.values.dtype.itemsize + (1 if has_nulls else 0))
    nbytes += N * 9 * max(1, len(specs))          # int64+bool outputs
    if nbytes > KERNEL_WINDOW_MAX_BYTES:
        declined("WindowInputSize")
        return None

    # the sort and gather are shared with the XLA path: one definition
    # of order semantics, one permutation
    sort_keys = [(p, "ASC_NULLS_FIRST") for p in partition_names] \
        + list(orderings)
    perm = ops.sort_indices(batch, sort_keys)
    cols = {nm: c.gather(perm) for nm, c in batch.columns.items()}
    mask = batch.mask[perm]

    pad = N - n

    def p1(a):
        return jnp.pad(a, (0, pad)) if pad else a

    flat = [p1(mask)]
    for nm, has_nulls in layout:
        c = cols[nm]
        flat.append(p1(c.values))
        if has_nulls:
            flat.append(p1(c.nulls))

    key = (tuple(partition_names), tuple(orderings), tuple(specs),
           tuple((nm, str(cols[nm].values.dtype), hn)
                 for nm, hn in layout), N)
    runner = _RUNNER_CACHE.get(key)
    if runner is None:
        runner = _build_runner(tuple(partition_names), tuple(orderings),
                               tuple(specs), tuple(layout), N)
        _RUNNER_CACHE[key] = runner
    outs = runner(tuple(flat))

    n_specs = len(specs)
    out = dict(cols)
    for j, spec in enumerate(specs):
        vals = outs[j][:n]
        if spec.name in ("sum", "avg"):
            out[spec.output] = Column(vals, outs[n_specs + j][:n])
        else:
            out[spec.output] = Column(vals, None)
    KERNEL_METRICS.record_window_run()
    if runtime_stats is not None:
        runtime_stats.add("kernelWindowPrograms", 1)
    return Batch(out, mask)
