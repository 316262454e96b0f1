"""Grouped aggregation inside the Pallas scan kernel.

PR 10's kernel only ran direct-mode shapes (G<=64 one-hot accumulator
grids); every grouped-by-key plan fell back to the XLA chain.  This
module keeps the whole decode -> predicate -> Blelloch-compact pipeline
of scan_kernel.py and swaps the aggregation tail for one of two
slot-addressing modes, mirroring the XLA chain's own span/hash split:

  span   closed dictionary/bool key domains whose stride product fits
         the VMEM accumulator gate (KERNEL_SPAN_MAX_GROUPS): the
         combined stride code IS the slot index, and because
         operators.agg_span_init is agg_direct_init (same state
         template and int64/float64 dtype split), the direct runner's
         stacked-accumulator kernel is reused verbatim with
         ops.agg_span_update as the subtile update -- a packed scatter
         instead of the G x rows one-hot grid.  Finalize reconstructs
         the key values from the slot index exactly like the XLA
         static-span path, so results stay bit-identical (integers) /
         last-ulp (float sums).

  hash   everything else (open integer domains, multi-key mixes, lazy
         row-id keys): operators.agg_update's open-addressing scatter
         table runs IN-KERNEL over compacted subtiles with salt 0.  The
         per-slot state (keyhash / occupied / key values / accumulator
         columns) lives across grid steps in the kernel's output
         blocks, initialized from the agg_init template on step 0, and
         feeds ops.agg_finalize unchanged.  The table is sized from the
         optimizer's group estimate (the pipeline's initial_slots) and
         capped at KERNEL_HASH_MAX_SLOTS; an estimate over the cap, a
         failed memory reservation, or a runtime probe overflow
         (__collision) declines with AggGroupCardinality and the XLA
         chain -- with its doubling collision retry -- takes over.

Both modes share the direct kernel's grid construction (zone-map-pruned
pow2 blocks, padded tails) and DMA staging knob (`scan.kernel-dma`),
and emit the same device-side per-step row counters.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import operators as ops
from ..batch import Batch, Column
from . import shim
from ...utils.runtime_stats import host_get, jit_as
from .scan_kernel import (GROUPED_SUBTILE_ROWS, KERNEL_HASH_MAX_SLOTS,
                          _chunk_block, _whole_1d, _whole_2d,
                          agg_compaction_entries, aligned_grid,
                          block_rows_for, build_direct_runner,
                          chain_eligible, compact_columns,
                          decode_columns, dma_scratch_shapes,
                          encoded_in_specs, gather_encoded_arrays,
                          meter_kernel_run, run_chain_steps,
                          staged_indices, subtile_agg_inputs,
                          _stage_slabs)


def build_hash_runner(chain, kinds: Dict[str, str], n_params: int, *,
                      specs, key_names, key_dtypes, num_slots, salt=0,
                      agg_exprs, lowering, dma: str = "single",
                      join_plan=None):
    """Jitted Pallas launcher for the hashed grouped mode: the
    open-addressing accumulator table of ops.agg_init/agg_update lives
    in the kernel's per-entry output blocks (grid steps accumulate into
    block 0), updated subtile-by-subtile over the compacted rows with
    the SAME probe/scatter code the XLA chain runs -- the kernel cannot
    drift from the engine's slot semantics.  Returns (launcher,
    entry_names).  `join_plan` lowers fanout-1 join/semi probe steps
    in-kernel exactly as in build_direct_runner (kernels/join.py)."""
    from .join import join_appliers
    n_join = len(join_plan.arrays) if join_plan is not None else 0
    meta = chain.scan_meta
    br = block_rows_for(chain.leaf_cap(()))
    steps = chain.steps
    n_steps = len(steps)
    dicts = meta["dicts"]
    colmap = meta["colmap"]
    names = tuple(colmap)
    staged = staged_indices(names, kinds) if dma == "double" else ()
    n_staged = len(staged)

    template = ops.agg_init(num_slots, specs, key_names, key_dtypes)
    entry_names = tuple(template)
    n_entries = len(entry_names)
    # every agg_init entry is a UNIFORM fill (zeros / EMPTY_SLOT /
    # +-int64 extrema), so the kernel recreates the template in its
    # step-0 output init from host scalar fills -- pallas_call rejects
    # device arrays captured as tracing constants
    t_host = host_get(template, "kernel_state_template")
    fills = {name: np.asarray(v).flat[0] for name, v in t_host.items()}
    entry_dtypes = {name: np.asarray(v).dtype for name, v in t_host.items()}

    def kernel(bidx_ref, lo_ref, hi_ref, *refs):
        if n_staged:
            scratch = refs[-(n_staged + 1):-1]
            sem = refs[-1]
            refs = refs[:-(n_staged + 1)]
        col_refs = refs[:len(refs) - n_entries - 1 - n_params - n_join]
        join_refs = refs[len(col_refs):len(col_refs) + n_join]
        param_refs = refs[len(col_refs) + n_join:
                          len(col_refs) + n_join + n_params]
        state_refs = refs[-(n_entries + 1):-1]
        counts_ref = refs[-1]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init_outputs():
            for name, ref in zip(entry_names, state_refs):
                ref[...] = jnp.full(ref.shape, fills[name],
                                    dtype=entry_dtypes[name])
            counts_ref[...] = jnp.zeros((1, 1 + n_steps), dtype=jnp.int64)

        slabs = (_stage_slabs(col_refs, staged, scratch, sem, bidx_ref,
                              br) if n_staged else {})
        pos = bidx_ref[i].astype(jnp.int64) * br
        idx0 = jnp.arange(br, dtype=jnp.int64)
        live = (idx0 >= lo_ref[i].astype(jnp.int64)) \
            & (idx0 < hi_ref[i].astype(jnp.int64))

        cols = decode_columns(names, kinds, dicts, col_refs, slabs,
                              pos, idx0, live)
        params_k = tuple(p[...][0] for p in param_refs)
        appliers = (join_appliers(join_plan,
                                  [r[...] for r in join_refs])
                    if n_join else None)
        batch, counts = run_chain_steps(Batch(cols, live), live, steps,
                                        lowering, params_k, n_params,
                                        appliers)

        # compact the group-key columns alongside the aggregate inputs:
        # the hash update probes on VALUES, so the keys ride the same
        # prefix-sum scatter
        named = agg_compaction_entries(specs, agg_exprs(batch))
        key_has_nulls = {}
        for k in key_names:
            col = batch.columns[k]
            named.append(("kv:" + k, col.values))
            key_has_nulls[k] = col.nulls is not None
            if col.nulls is not None:
                named.append(("kn:" + k, col.nulls))
        total, compacted = compact_columns(batch.mask, br, named)

        state = {}
        for name, ref in zip(entry_names, state_refs):
            v = ref[...]
            state[name] = v[0] if name == "__collision" else v

        ts = min(br, GROUPED_SUBTILE_ROWS)
        n_sub = (total + ts - 1) // ts
        sub_idx = jnp.arange(ts, dtype=jnp.int32)

        def sub(j, st):
            off = j * ts
            m = (off + sub_idx) < total
            key_cols: List[Column] = []
            for k in key_names:
                sv = jax.lax.dynamic_slice(
                    compacted["kv:" + k], (off,), (ts,))
                sn = (jax.lax.dynamic_slice(
                    compacted["kn:" + k], (off,), (ts,))
                    if key_has_nulls[k] else None)
                key_cols.append(Column(sv, sn))
            sa = subtile_agg_inputs(compacted, specs, off, ts)
            return ops.agg_update(st, Batch({}, m), key_cols, sa, specs,
                                  num_slots, salt, key_names, None)
        state = jax.lax.fori_loop(0, n_sub, sub, state)
        for name, ref in zip(entry_names, state_refs):
            v = state[name]
            ref[...] = v.reshape(1) if name == "__collision" else v
        counts_ref[...] = counts_ref[...] + jnp.stack(counts).astype(
            jnp.int64)[None, :]

    @jit_as("pallas_grouped_agg")
    def run(bidx, lo, hi, arrays, jarrays, params):
        flat = list(arrays)
        in_specs = encoded_in_specs(names, kinds, flat, br, staged)
        for a in jarrays:
            flat.append(a)
            in_specs.append(pl.BlockSpec(a.shape, _whole_1d))
        for p in params:
            flat.append(jnp.asarray(p).reshape(1))
            in_specs.append(pl.BlockSpec((1,), _whole_1d))
        out_shape = []
        out_specs = []
        for name in entry_names:
            shape = (1,) if name == "__collision" else (num_slots,)
            out_shape.append(
                jax.ShapeDtypeStruct(shape, template[name].dtype))
            out_specs.append(pl.BlockSpec(shape, _whole_1d))
        out_shape.append(
            jax.ShapeDtypeStruct((1, 1 + n_steps), jnp.int64))
        out_specs.append(pl.BlockSpec((1, 1 + n_steps), _whole_2d))
        scratch_shapes = (dma_scratch_shapes(staged, flat, br)
                          if n_staged else [])
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bidx.shape[0],),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=tuple(scratch_shapes),
        )
        return shim.pallas_call(kernel, grid_spec=grid_spec,
                                out_shape=out_shape)(bidx, lo, hi, *flat)

    return run, entry_names


def try_grouped_scan_kernel(chain, aux, *, specs, key_names, key_dtypes,
                            key_dicts, key_lazy, span_info, est_slots,
                            agg_exprs, lowering, cache, declined, pool,
                            state_bytes, runtime_stats=None,
                            dma: str = "single", expands=()):
    """Run a grouped (G > 64) aggregation chain through the Pallas
    kernel when eligible: span mode when `span_info` (the caller's
    _direct_mode_info at gmax=KERNEL_SPAN_MAX_GROUPS) is set, hashed
    open addressing otherwise.  Returns (finalized Batch,
    int64[1 + n_steps] row counters, grid length), or None after
    metering one kernelDeclined{reason} -- the XLA span/sort/hash paths
    take over.  The AggGroupCardinality capacity gate covers: a group
    estimate over KERNEL_HASH_MAX_SLOTS, a failed accumulator memory
    reservation, and a runtime probe overflow (each of which means the
    group population is too large for a VMEM-resident table).

    Chains with fanout-1 join/semi steps (Q3/Q18 shapes) lower their
    probes in-kernel; `expands` is prep()'s per-join fanout tuple, and
    the build operand bytes are charged to `pool` non-revocably for
    each launch (kernels/join.py)."""
    from .join import (KERNEL_JOIN_MAX_BUILD_BYTES, plan_join_layout,
                       reserve_build_operands)
    elig = chain_eligible(chain, aux, declined, allow_joins=True)
    if elig is None:
        return None
    cached, colmap = elig
    jplan = plan_join_layout(chain.steps, aux, expands, declined,
                             max_bytes=KERNEL_JOIN_MAX_BUILD_BYTES)
    if jplan is None:
        return None
    names = tuple(colmap)
    br = block_rows_for(chain.leaf_cap(()))
    n_steps = len(chain.steps)
    params_fp = chain.compiler.ctx.params_fingerprint
    grid = aligned_grid(chain.scan_meta, br, params_fp)
    params = tuple(aux[-1]) if chain.has_params else ()
    kinds = {name: cached[colmap[name]].kind for name in colmap}
    n_staged = (len(staged_indices(names, kinds))
                if dma == "double" else 0)

    if span_info is not None:
        doms, G, strides, kdts, kdicts = span_info
        reserve = G * 24 * max(1, len(specs))
        if not pool.try_reserve(reserve):
            declined("AggGroupCardinality")
            return None
        try:
            if not grid:
                state = ops.agg_span_init(G, specs)
                kcounts = jnp.zeros(1 + n_steps, dtype=jnp.int64)
                n_blocks = 0
            else:
                max_block = max(b for b, _lo, _hi in grid)
                flat_arrays = gather_encoded_arrays(
                    cached, colmap, names, (max_block + 1) * br, cache)
                key = ("pallas_span", G, strides, len(params), dma,
                       jplan.sig)
                runner = cache.get(key)
                if runner is None:
                    runner = build_direct_runner(
                        chain, kinds, len(params), specs=specs,
                        key_names=key_names, strides=strides, G=G,
                        agg_exprs=agg_exprs, lowering=lowering, dma=dma,
                        update_fn=ops.agg_span_update,
                        subtile=GROUPED_SUBTILE_ROWS,
                        join_plan=jplan if jplan.steps else None)
                    cache[key] = runner
                bidx = jnp.asarray([b for b, _, _ in grid],
                                   dtype=jnp.int32)
                lo = jnp.asarray([l for _, l, _ in grid],
                                 dtype=jnp.int32)
                hi = jnp.asarray([h for _, _, h in grid],
                                 dtype=jnp.int32)
                if not reserve_build_operands(pool, jplan.nbytes):
                    declined("JoinBuildSize")
                    return None
                try:
                    acc_i, acc_f, kc = runner.fn(
                        bidx, lo, hi, flat_arrays, jplan.arrays, params,
                        runner.init_i, runner.init_f)
                finally:
                    if jplan.nbytes:
                        pool.free(jplan.nbytes)
                state = {k: acc_i[j]
                         for j, k in enumerate(runner.int_names)}
                state.update({k: acc_f[j]
                              for j, k in enumerate(runner.flt_names)})
                kcounts = kc[0]
                n_blocks = len(grid)
            slot = jnp.arange(G, dtype=jnp.int64)
            key_arrays = {}
            stride = G
            for k, dom, dt in zip(key_names, doms, kdts):
                stride //= dom
                key_arrays[k] = ((slot // stride) % dom).astype(dt)
            out = ops.agg_span_finalize(state, specs, key_names,
                                        key_arrays, kdicts, key_lazy)
        finally:
            pool.free(reserve)
        meter_kernel_run(runtime_stats, n_blocks, n_staged, dma)
        return out, kcounts, n_blocks

    # ---- hashed open-addressing mode ----
    # the caller's est_slots carries ~2x probing headroom over the
    # optimizer's group estimate, so only an estimate beyond 2x the cap
    # means the group population itself cannot fit the VMEM table; a
    # merely pessimistic estimate is clamped and the runtime __collision
    # probe below stays the ground truth
    if est_slots > 2 * KERNEL_HASH_MAX_SLOTS:
        declined("AggGroupCardinality")
        return None
    if not grid:
        state = ops.agg_init(num_slots := min(max(int(est_slots), 1024),
                                              KERNEL_HASH_MAX_SLOTS),
                             specs, key_names, key_dtypes)
        out = ops.agg_finalize(state, specs, key_names, key_dicts,
                               key_lazy)
        meter_kernel_run(runtime_stats, 0, n_staged, dma)
        return out, jnp.zeros(1 + n_steps, dtype=jnp.int64), 0
    max_block = max(b for b, _lo, _hi in grid)
    flat_arrays = gather_encoded_arrays(
        cached, colmap, names, (max_block + 1) * br, cache)
    bidx = jnp.asarray([b for b, _, _ in grid], dtype=jnp.int32)
    lo = jnp.asarray([l for _, l, _ in grid], dtype=jnp.int32)
    hi = jnp.asarray([h for _, _, h in grid], dtype=jnp.int32)
    # mirror the XLA hash path's collision discipline (doubling + fresh
    # salt per attempt), bounded by the VMEM slot cap instead of the
    # retry budget: past the cap the shape genuinely doesn't fit and the
    # XLA chain — which can keep doubling in HBM — takes over
    num_slots = min(max(int(est_slots), 1024), KERNEL_HASH_MAX_SLOTS)
    salt = 0
    while True:
        reserve = state_bytes(num_slots, key_names, specs)
        if not pool.try_reserve(reserve):
            declined("AggGroupCardinality")
            return None
        try:
            key = ("pallas_hash", num_slots, salt, tuple(key_names),
                   tuple(str(d) for d in key_dtypes), len(params), dma,
                   jplan.sig)
            hit = cache.get(key)
            if hit is None:
                hit = build_hash_runner(
                    chain, kinds, len(params), specs=specs,
                    key_names=key_names, key_dtypes=key_dtypes,
                    num_slots=num_slots, salt=salt, agg_exprs=agg_exprs,
                    lowering=lowering, dma=dma,
                    join_plan=jplan if jplan.steps else None)
                cache[key] = hit
            run, entry_names = hit
            if not reserve_build_operands(pool, jplan.nbytes):
                declined("JoinBuildSize")
                return None
            try:
                outs = run(bidx, lo, hi, flat_arrays, jplan.arrays,
                           params)
            finally:
                if jplan.nbytes:
                    pool.free(jplan.nbytes)
            state = {}
            for name, v in zip(entry_names, outs[:-1]):
                state[name] = v[0] if name == "__collision" else v
            if not bool(host_get(state["__collision"],
                                 "agg_hash_collision")):
                out = ops.agg_finalize(state, specs, key_names,
                                       key_dicts, key_lazy)
                meter_kernel_run(runtime_stats, len(grid), n_staged, dma)
                return out, outs[-1][0], len(grid)
        finally:
            pool.free(reserve)
        if num_slots >= KERNEL_HASH_MAX_SLOTS:
            # probe overflow at the cap: the real group population
            # outgrew the VMEM-resident table
            declined("AggGroupCardinality")
            return None
        num_slots = min(2 * num_slots, KERNEL_HASH_MAX_SLOTS)
        salt += 1
