"""Pallas fused scan kernel: decode -> filter -> prefix-sum compact ->
partial aggregation in one VMEM-resident grid pass.

The XLA fused chain (exec/fused.py) already collapses scan -> filter ->
project -> partial-agg into one program, but its aggregation update
reads the FULL chunk tile: a selective predicate (TPC-H Q6 keeps ~2% of
rows) still pays the G x cap one-hot grid over every padded row.  This
kernel is the hand-written hot path the ROADMAP's HBM-gap item calls
for:

  grid      one step per SURVIVING block-aligned chunk.  The kernel
            re-grids the scan's split ranges onto its OWN power-of-two
            block size (block_rows_for: the pow2 ceiling of the chain's
            chunk capacity — aggregation is order-insensitive, so any
            partition of the same row set is legal); each grid entry
            carries its block index plus a [lo, hi) live row range as
            scalar-prefetch operands, which also masks short/misaligned
            chunk tails (the launcher zero-pads encoded arrays up to the
            grid, so tail shape never declines the kernel).  Zone-map
            pruning runs over THIS grid, so pruned blocks never issue
            DMAs -- they are simply not in the grid.
  decode    ResidentColumn blocks stream out of HBM in ENCODED form.
            `dma = single` uses Pallas block specs (the implicit
            double-buffering Pallas applies across grid steps);
            `dma = double` stages the per-row slabs MANUALLY: block k+1's
            encoded slabs start their pltpu.make_async_copy into the
            alternate VMEM buffer while block k decodes/aggregates
            (_stage_slabs).  Dict gather / RLE binary search then runs
            in vector registers -- late materialization with the same
            semantics as ResidentColumn.slice_decode.
  filter    the chain's own predicate/project expressions, lowered by
            the SAME exec/lowering.Lowering the XLA chain uses -- the
            kernel cannot drift from the engine semantics.  Bound
            parameters (the serving tier parameterizes plan literals)
            ride as traced scalar inputs, so re-executions with
            different constants reuse the compiled kernel.
  compact   a work-efficient Blelloch exclusive prefix sum over the
            selection mask drives an in-VMEM scatter compaction (no XLA
            gather round-trip), after which the aggregation update only
            touches ceil(live/SUBTILE) subtiles instead of the full tile
  agg       operators.agg_direct_update (one-hot grid, G<=64) or
            operators.agg_span_update (packed scatter, grouped span
            mode -- kernels/grouped.py) over compacted subtiles; the
            packed int64/float64 accumulators live in the kernel's
            output block across grid steps and feed the operators
            finalize path unchanged.  Hashed grouped shapes build their
            own kernel in kernels/grouped.py from these helpers.

Device-side row counters (scan live rows + live rows after every chain
step) accumulate in an output block exactly like the XLA chain's
with_counts path, so EXPLAIN ANALYZE / QueryInfo operator stats stay
accurate on the kernel path.

Parity contract (tests/test_scan_kernel.py): integer accumulators
(sums over int64/decimal/date/bool, count, min, max) and the row
counters are BIT-FOR-BIT identical to the XLA chain -- integer adds
and min/max are associative, so compaction and re-gridding cannot
change them.  float64 sum/avg may differ in the last ulp (different
reduction tree pairings); TPC-H decimals are unscaled int64 on device,
so the Q1/Q6 money aggregates are exact.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import operators as ops
from ..batch import Batch, Column
from . import shim
from ...utils.runtime_stats import jit_as

# Eligibility refusals, surfaced as kernelDeclined{reason} RuntimeStats
# counters (exec/pipeline.py _kernel_declined) -- the kernel twin of the
# fusionDeclined{...} family.  "AggFunctionShape" is recorded by the
# pipeline itself, "Disabled"/"CompilerRefused"/"Backend"(auto) by
# kernel_gate below; the rest are produced here / in kernels/grouped.py.
# ("ChunkAlignment" was held at 0 for one release after tail padding
# landed and is now retired — the launcher pads/lane-masks every tail,
# so the decline cannot occur.)
KERNEL_DECLINE_REASONS = (
    "Disabled",              # scan.kernel = xla
    "AggFunctionShape",      # non-BASIC aggregate functions (moment/corr/
    #                          percentile/HLL state has no kernel stacks)
    "AggGroupCardinality",   # group count beyond the VMEM accumulator
    #                          gates (span > KERNEL_SPAN_MAX_GROUPS and
    #                          hash estimate/collision > KERNEL_HASH_MAX_SLOTS)
    "CompilerRefused",       # scan.kernel = auto and the chip's compiler
    #                          refuses a kernel family the chain needs
    #                          (KERNEL_FAMILY_COMPILES)
    "Backend",               # auto off-TPU (interpret-mode emulation is
    #                          never a win), or a platform that is
    #                          neither tpu nor cpu-interpret
    "PlanShape",             # chain has uid steps (position-keyed unique
    #                          ids need the XLA chain's expansion layout)
    "ColumnsNotResident",    # a scanned column is not HBM-resident encoded
    "JoinShape",             # fanout-k expansion join, residual ON filter,
    #                          or a non-INNER/LEFT fused join form
    #                          (kernels/join.py plan_join_layout)
    "JoinBuildSize",         # build-table operand bytes over
    #                          KERNEL_JOIN_MAX_BUILD_BYTES, or the
    #                          MemoryContext reservation failed
    "WindowFunctionShape",   # window function / frame / float accumulation
    #                          outside the prefix-sum kernel's repertoire
    #                          (kernels/window.py)
    "WindowKeyShape",        # late-materialized (lazy) partition/order/arg
    #                          column: peer detection needs decoded values
    "WindowInputSize",       # padded sort run over KERNEL_WINDOW_MAX_BYTES
    #                          (whole input must sit in VMEM at once)
)

# Whether the chip's compiler (Mosaic: JAX 0.9.0, libtpu 0.0.34, v5e)
# accepts each kernel family's launcher.  Static and the same on every
# backend: tests/test_chip_compile.py lowers each family for a described
# v5e and fails when this table and the compiler disagree, so the PR that
# makes a family compile flips its entry there and here.  All five are
# refused today: the kernels compute in 64-bit lanes over 1-D blocks,
# which Mosaic has no tiling for (first error lines in ROADMAP.md).
KERNEL_FAMILY_COMPILES = {
    "direct": False,   # build_direct_runner, one-hot grid (G <= 64)
    "span": False,     # build_direct_runner, packed-scatter span slots
    "hash": False,     # grouped.build_hash_runner, open addressing
    "join": False,     # join.join_appliers probes lowered into the above
    "window": False,   # window._build_runner, prefix-scan window functions
}


def kernel_gate(mode: str, *families: str) -> Optional[str]:
    """The one scan.kernel decision, made before any kernel is built: the
    kernelDeclined reason under which `mode` keeps a scan needing these
    kernel `families` on the XLA chain, or None when the kernel path is
    taken.  "pallas" is an explicit pin and never declines here -- a
    lowering the compiler refuses then fails the query with the
    compiler's message."""
    if mode == "xla":
        return "Disabled"
    if mode == "auto":
        if not all(KERNEL_FAMILY_COMPILES[f] for f in families):
            return "CompilerRefused"
        if jax.default_backend() != "tpu":
            return "Backend"
    return None


def chain_families(base: str, steps) -> Tuple[str, ...]:
    """Kernel families a fused chain needs: its aggregation tail `base`
    plus the in-kernel probe when the chain carries join/semi steps."""
    if any(s[0] in ("join", "semi") for s in steps):
        return (base, "join")
    return (base,)


# compacted rows are aggregated in subtiles of this many rows: the
# G x SUBTILE one-hot grid stays small while a selective filter skips
# most subtiles entirely (n_sub = ceil(live/SUBTILE) loop trips)
SUBTILE_ROWS = 2048
# grouped modes scatter instead of building the one-hot grid, so their
# subtiles can be wider (fewer fori_loop trips over the probe rounds)
GROUPED_SUBTILE_ROWS = 8192

# VMEM accumulator gates for the grouped modes (kernels/grouped.py).
# span: G * ~(1 + n_specs * 2) int64/float64 rows must sit in VMEM next
# to the decoded block; 32K groups * ~10 accumulator rows * 8B = 2.5MB.
# hash: the open-addressing table carries keyhash/occupied/key values/
# per-spec accumulators per slot; 64K slots * ~15 arrays * 8B = 7.5MB.
# Both leave headroom under a 16MB VMEM core budget at 64K-row blocks;
# truly huge G declines with AggGroupCardinality and runs the XLA chain.
KERNEL_SPAN_MAX_GROUPS = 1 << 15
KERNEL_HASH_MAX_SLOTS = 1 << 16

# scan.kernel-dma knob values (ExecutionConfig.scan_kernel_dma)
DMA_MODES = ("single", "double")


class KernelMetrics:
    """Process-lifetime roll-up of the per-query kernel counters, so the
    telemetry scraper (telemetry/otlp.py scrape_metric_points) and
    /v1/metrics can export kernel engagement without a live query: every
    kernelDeclined{reason} tick and meter_kernel_run call lands here too."""

    def __init__(self):
        import threading
        self._lock = threading.Lock()
        self.declined: Dict[str, int] = {}
        self.scan_programs = 0
        self.window_programs = 0
        self.dma_staged_blocks = 0
        self.dma_prefetched_blocks = 0

    def record_declined(self, reason: str) -> None:
        with self._lock:
            self.declined[reason] = self.declined.get(reason, 0) + 1

    def record_run(self, n_staged_copies: int, n_prefetched: int) -> None:
        with self._lock:
            self.scan_programs += 1
            self.dma_staged_blocks += n_staged_copies
            self.dma_prefetched_blocks += n_prefetched

    def record_window_run(self) -> None:
        with self._lock:
            self.window_programs += 1

    def snapshot(self) -> dict:
        with self._lock:
            staged = self.dma_staged_blocks
            return {
                "declined": dict(self.declined),
                "scan_programs": self.scan_programs,
                "window_programs": self.window_programs,
                "dma_staged_blocks": staged,
                "dma_prefetched_blocks": self.dma_prefetched_blocks,
                "dma_overlap_fraction": (
                    self.dma_prefetched_blocks / staged if staged else 0.0),
            }


KERNEL_METRICS = KernelMetrics()


def _blelloch_exclusive(x):
    """Work-efficient (Blelloch) exclusive prefix sum of a power-of-two
    length vector, expressed with reshapes so both the up-sweep and the
    down-sweep are dense vector ops (no scatter): pairing adjacent
    elements halves the vector per level, then each level's prefix
    splits back into (left, left + pair_first)."""
    cur = x
    levels = []
    while cur.shape[0] > 1:
        levels.append(cur)
        pairs = cur.reshape(-1, 2)
        cur = pairs[:, 0] + pairs[:, 1]
    pref = jnp.zeros_like(cur)
    for lvl in reversed(levels):
        pairs = lvl.reshape(-1, 2)
        left = pref
        right = pref + pairs[:, 0]
        pref = jnp.stack([left, right], axis=1).reshape(-1)
    return pref


def _bisect_right(a, v):
    """searchsorted(a, v, side="right") as a fixed-trip vectorized
    binary search -- jnp.searchsorted does not lower inside Pallas TPU
    kernels, and the loop is exact integer arithmetic so interpret and
    compiled runs agree with the XLA chain's searchsorted decode."""
    size = a.shape[0]
    steps = max(1, int(math.ceil(math.log2(size + 1))) + 1)
    lo = jnp.zeros(v.shape, dtype=jnp.int64)
    hi = jnp.full(v.shape, size, dtype=jnp.int64)
    for _ in range(steps):
        cont = lo < hi
        mid = (lo + hi) // 2
        le = a[jnp.clip(mid, 0, size - 1)] <= v
        lo = jnp.where(cont & le, mid + 1, lo)
        hi = jnp.where(cont & ~le, mid, hi)
    return lo


class _Runner(NamedTuple):
    fn: Callable                 # jitted launcher
    init_i: object               # (Ni, G) int64 accumulator init rows
    init_f: object               # (max(Nf,1), G) float64 init rows
    int_names: Tuple[str, ...]   # acc_i row -> agg state key
    flt_names: Tuple[str, ...]   # acc_f row -> agg state key


def _chunk_block(i, bidx, lo, hi):
    return (bidx[i],)


def _whole_1d(i, bidx, lo, hi):
    return (0,)


def _whole_2d(i, bidx, lo, hi):
    return (0, 0)


def _merged_ranges(splits) -> List[Tuple[int, int]]:
    """The scan's owned row ranges, sorted and coalesced."""
    out: List[List[int]] = []
    for s, e in sorted((int(sp.start), int(sp.end)) for sp in splits):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _block_pruned(zone_maps, pushdown, params, pos: int,
                  count: int) -> bool:
    """storage/pushdown.prune_chunks' conservative unsatisfiability
    test for ONE aligned block (the kernel grid differs from the
    chain's split-relative chunk grid, so pruning re-runs here; the
    chain already metered ITS grid in chunks_for)."""
    from ...storage.pushdown import (entry_unsatisfiable,
                                     resolve_entry_value)
    for e in pushdown:
        zm = zone_maps.get(e["column"])
        if zm is None:
            continue
        value = resolve_entry_value(e["value"], params)
        if value is None:
            continue
        bounds = zm.chunk_bounds(pos, count)
        if bounds is None:
            continue
        if entry_unsatisfiable(e["op"], value, *bounds):
            return True
    return False


def block_rows_for(cap: int) -> int:
    """The kernel's block size for a chain with chunk capacity `cap`:
    the power-of-two ceiling.  The Blelloch scan pairs elements level by
    level, so tiles must be pow2; re-gridding is legal because
    aggregation is order-insensitive, and rows between a split end and
    the block end are lane-masked via the [lo, hi) scalar-prefetch range
    (the launcher zero-pads encoded arrays to the grid, so a short last
    chunk never declines the kernel)."""
    return 1 << max(0, int(cap - 1).bit_length())


def aligned_grid(meta: dict, block_rows: int,
                 params) -> List[Tuple[int, int, int]]:
    """(block index, lo, hi) grid entries tiling the scan's split
    ranges with block_rows-aligned blocks; [lo, hi) is the
    block-relative live row range.  A block straddling two disjoint
    owned ranges yields two entries (grid steps accumulate, so
    revisiting a block is sound).  Zone-map-pruned entries are dropped
    HERE -- they never reach the grid, so their HBM blocks are never
    DMA'd."""
    zone_maps = meta.get("zone_maps") or {}
    pushdown = meta.get("pushdown") or []
    entries: List[Tuple[int, int, int]] = []
    for s, e in _merged_ranges(meta["splits"]):
        for b in range(s // block_rows, (e - 1) // block_rows + 1):
            lo = max(s, b * block_rows) - b * block_rows
            hi = min(e, (b + 1) * block_rows) - b * block_rows
            if zone_maps and pushdown and _block_pruned(
                    zone_maps, pushdown, params,
                    b * block_rows + lo, hi - lo):
                continue
            entries.append((b, lo, hi))
    return entries


# ---------------------------------------------------------------------------
# shared kernel-body helpers (direct + grouped runners)
# ---------------------------------------------------------------------------

def staged_indices(names, kinds) -> Tuple[int, ...]:
    """Flat input indices of the PER-ROW encoded arrays (plain data,
    dict codes) -- the arrays whose blocks stream per grid step and are
    therefore candidates for manual double-buffered DMA staging.  Whole
    arrays (dict values, RLE runs) are VMEM-resident block specs in
    both modes."""
    idx, r = [], 0
    for name in names:
        kind = kinds[name]
        if kind == "plain":
            idx.append(r)
            r += 1
        elif kind == "dict":
            idx.append(r)
            r += 2
        else:                                        # rle: whole arrays
            r += 2
    return tuple(idx)


def _stage_slabs(col_refs, staged, scratch, sem, bidx_ref, block_rows):
    """Manual double-buffered DMA staging of the current grid block's
    per-row slabs: start block k+1's HBM->VMEM copies into the alternate
    buffer BEFORE waiting on block k's own, so the next block's copy
    overlaps this block's decode/aggregate compute (the pallas guide's
    double-buffering pattern, driven by the scalar-prefetch block index
    array).  Returns {flat input index: slab} for the current step."""
    i = pl.program_id(0)
    n = pl.num_programs(0)

    def copy(slot, step, j):
        ref = col_refs[staged[j]]
        return pltpu.make_async_copy(
            ref.at[pl.ds(bidx_ref[step] * block_rows, block_rows)],
            scratch[j].at[slot], sem.at[slot, j])

    @pl.when(i == 0)
    def _warm_up():
        for j in range(len(staged)):
            copy(0, 0, j).start()

    @pl.when(i + 1 < n)
    def _prefetch_next():
        for j in range(len(staged)):
            copy((i + 1) % 2, i + 1, j).start()

    slot = i % 2
    slabs = {}
    for j in range(len(staged)):
        copy(slot, i, j).wait()
        slabs[staged[j]] = scratch[j][slot]
    return slabs


def decode_columns(names, kinds, dicts, col_refs, slabs, pos, idx0,
                   live) -> Dict[str, Column]:
    """ResidentColumn.slice_decode semantics over the block's VMEM
    slabs: plain read, dict gather, RLE binary search, then the scan's
    dead-row zeroing.  `slabs` overrides col_refs for manually staged
    per-row arrays (dma = double); empty in single mode."""
    def read(r):
        return slabs[r] if r in slabs else col_refs[r][...]

    cols: Dict[str, Column] = {}
    r = 0
    for name in names:
        kind = kinds[name]
        if kind == "plain":
            v = read(r)
            r += 1
        elif kind == "dict":
            codes = read(r)
            values = col_refs[r + 1][...]
            r += 2
            v = values[codes.astype(jnp.int32)]
        else:                                    # rle
            run_values = col_refs[r][...]
            run_starts = col_refs[r + 1][...]
            r += 2
            ri = _bisect_right(run_starts, pos + idx0) - 1
            ri = jnp.clip(ri, 0, run_values.shape[0] - 1)
            v = run_values[ri]
        v = jnp.where(live, v, jnp.zeros((), v.dtype))
        cols[name] = Column(v, None, dicts.get(name))
    return cols


def run_chain_steps(batch: Batch, live, steps, lowering, params_k,
                    n_params, appliers=None):
    """The chain's own filter/project/rename steps, lowered by the
    engine's Lowering (shared with the XLA chain), with the same
    per-step live-row counters chain.make(with_counts=True) emits.
    The bound-parameter vector rides along for step expressions exactly
    as in FusedChain.make's _pb (aggregation input expressions see a
    param-less batch on both paths).  `appliers` maps a step index to an
    in-kernel replacement closure (the join/semi probe appliers from
    kernels/join.py, which read the VMEM-resident build operands
    directly) -- every other step kind still lowers here."""
    def _pb(b):
        return b.with_params(params_k) if n_params else b

    counts = [jnp.sum(live)]
    for si, step in enumerate(steps):
        kind = step[0]
        if appliers is not None and si in appliers:
            batch = appliers[si](batch)
        elif kind == "filter":
            batch = ops.apply_filter(
                batch, lowering.eval(step[1], _pb(batch)))
        elif kind == "project":
            pb = _pb(batch)
            batch = Batch({v2.name: lowering.eval(e, pb)
                           for v2, e in step[1]}, batch.mask)
        else:                                    # rename
            batch = Batch({o: batch.columns[src]
                           for o, src in step[1]}, batch.mask)
        counts.append(jnp.sum(batch.mask))
    return batch, counts


def compact_columns(mask, cap, named):
    """Prefix-sum compaction: exclusive Blelloch scan of the mask gives
    each live row its packed slot; dead rows scatter to index cap and
    drop.  `named` is a list of (key, 1-D array) pairs; returns (live
    total, {key: compacted array}).  Downstream aggregation then loops
    over live subtiles only."""
    pref = _blelloch_exclusive(mask.astype(jnp.int32))
    total = pref[cap - 1] + mask[cap - 1].astype(jnp.int32)
    dest = jnp.where(mask, pref, cap)
    out = {k: jnp.zeros(cap, dtype=a.dtype).at[dest].set(a, mode="drop")
           for k, a in named}
    return total, out


def agg_compaction_entries(specs, agg_cols):
    """(key, array) compaction entries for the aggregate input columns
    ("v:" values / "n:" nulls per spec output; count_star has none)."""
    named = []
    for spec in specs:
        col = agg_cols.get(spec.output)
        if col is None:                          # count_star
            continue
        named.append(("v:" + spec.output, col.values))
        if col.nulls is not None:
            named.append(("n:" + spec.output, col.nulls))
    return named


def subtile_agg_inputs(compacted, specs, off, ts):
    """Slice one subtile's aggregate inputs out of the compacted
    columns (dynamic_slice keeps the loop body shape-static)."""
    sa: Dict[str, Optional[Column]] = {}
    for spec in specs:
        cv = compacted.get("v:" + spec.output)
        if cv is None:
            sa[spec.output] = None
            continue
        sv = jax.lax.dynamic_slice(cv, (off,), (ts,))
        cn = compacted.get("n:" + spec.output)
        sn = (jax.lax.dynamic_slice(cn, (off,), (ts,))
              if cn is not None else None)
        sa[spec.output] = Column(sv, sn)
    return sa


def encoded_in_specs(names, kinds, flat, block_rows, staged):
    """BlockSpecs for the flat encoded-array inputs, in staged_indices
    order.  Per-row arrays stream per grid block (single mode) or sit in
    ANY memory space awaiting the kernel's manual DMA (double mode);
    whole arrays are always whole VMEM blocks."""
    row_spec = (pl.BlockSpec(memory_space=pltpu.ANY) if staged
                else pl.BlockSpec((block_rows,), _chunk_block))
    in_specs: List = []
    r = 0
    for name in names:
        kind = kinds[name]
        if kind == "plain":
            in_specs.append(row_spec)
            r += 1
        elif kind == "dict":
            in_specs += [row_spec,
                         pl.BlockSpec(flat[r + 1].shape, _whole_1d)]
            r += 2
        else:                                    # rle
            in_specs += [pl.BlockSpec(flat[r].shape, _whole_1d),
                         pl.BlockSpec(flat[r + 1].shape, _whole_1d)]
            r += 2
    return in_specs


def dma_scratch_shapes(staged, flat, block_rows):
    """Double-buffer VMEM scratch (2 slots per staged array) plus one
    (2, n_staged) DMA semaphore array for _stage_slabs."""
    shapes = [pltpu.VMEM((2, block_rows), flat[r].dtype) for r in staged]
    shapes.append(pltpu.SemaphoreType.DMA((2, len(staged))))
    return shapes


def chain_eligible(chain, aux, declined, allow_joins: bool = False):
    """Gates shared by every kernel mode: backend, chain step shapes,
    HBM residency.  Returns (cached, colmap) or None after metering one
    decline.  `allow_joins` admits join/semi probe steps (the caller
    must then lower them via kernels/join.py plan_join_layout, which
    applies its own Join* gates); uid steps always decline -- their
    position-keyed ids need the XLA chain's expansion layout."""
    allowed = (("filter", "project", "rename", "join", "semi")
               if allow_joins else ("filter", "project", "rename"))
    if jax.default_backend() not in ("cpu", "tpu"):
        declined("Backend")
        return None
    if any(s[0] not in allowed for s in chain.steps):
        declined("PlanShape")
        return None
    cached = aux[0] or {}
    colmap = chain.scan_meta.get("colmap") or {}
    if not colmap or any(colmap[n] not in cached for n in colmap):
        declined("ColumnsNotResident")
        return None
    if any(cached[colmap[n]].base is not None for n in colmap):
        # a mesh shard: the kernels index the arrays by table position
        declined("ColumnsNotResident")
        return None
    return cached, colmap


def gather_encoded_arrays(cached, colmap, names, need, cache):
    """The flat encoded-array inputs in staged_indices order, with
    per-row arrays zero-padded up to `need` rows (the grid's last block
    end) when the store's build-time capacity falls short -- padded
    lanes are dead by the [lo, hi) mask, so a short tail never declines.
    Pads are cached per (column, need) and invalidated when the store
    regenerates the underlying array (LRU eviction)."""
    flat: List = []
    for name in names:
        rc = cached[colmap[name]]
        arrs = tuple(rc.arrays)
        if rc.kind in ("plain", "dict") and arrs[0].shape[0] < need:
            ck = ("kernel_pad", colmap[name], need)
            hit = cache.get(ck)
            if hit is None or hit[0] is not arrs[0]:
                hit = (arrs[0],
                       jnp.pad(arrs[0], (0, need - arrs[0].shape[0])))
                cache[ck] = hit
            arrs = (hit[1],) + arrs[1:]
        flat += list(arrs)
    return tuple(flat)


def meter_kernel_run(runtime_stats, n_blocks, n_staged, dma) -> None:
    """One kernelScanPrograms tick per launched kernel; in double-DMA
    mode also the structural overlap fraction: every staged slab copy
    after the first block's was issued while the PREVIOUS block
    computed, so prefetched/staged = (n_blocks-1)/n_blocks of the DMA
    traffic overlapped compute.  (A wall-clock overlap measure needs the
    real-TPU re-run the ROADMAP tracks; the structural fraction is
    deterministic, so tests and dashboards can pin it.)"""
    staged_copies = prefetched = 0
    if dma == "double" and n_staged and n_blocks:
        staged_copies = n_blocks * n_staged
        prefetched = (n_blocks - 1) * n_staged
    KERNEL_METRICS.record_run(staged_copies, prefetched)
    if runtime_stats is None:
        return
    runtime_stats.add("kernelScanPrograms", 1)
    if staged_copies:
        runtime_stats.add("kernelDmaStagedBlocks", staged_copies)
        runtime_stats.add("kernelDmaPrefetchedBlocks", prefetched)
        runtime_stats.add("kernelDmaOverlapFraction",
                          prefetched / staged_copies)


# ---------------------------------------------------------------------------
# direct / span runner (stacked int64+float64 accumulator outputs)
# ---------------------------------------------------------------------------

def build_direct_runner(chain, kinds: Dict[str, str], n_params: int, *,
                        specs, key_names, strides, G, agg_exprs,
                        lowering, dma: str = "single",
                        update_fn=None, subtile: int = None,
                        join_plan=None) -> _Runner:
    """Compile the chain's static shape (column encodings, steps, agg
    specs) into a jitted Pallas launcher.  `kinds` maps each scan
    output name to its ResidentColumn encoding; `n_params` is the
    length of the chain's bound-parameter vector.  The launcher
    re-traces when the surviving-grid length changes (param pruning);
    everything else is baked in, mirroring the fused_cache programs of
    the XLA path.

    agg_span_init IS agg_direct_init (same state template and dtype
    split), so the SAME stacked-accumulator kernel serves both the
    direct mode (update_fn = ops.agg_direct_update, one-hot grid,
    G<=64) and the grouped span mode (update_fn = ops.agg_span_update,
    packed scatter, G up to KERNEL_SPAN_MAX_GROUPS).

    `join_plan` (kernels/join.py JoinPlan) lowers the chain's fanout-1
    join/semi probe steps into the kernel body: its flat build operands
    ride as whole-1D VMEM inputs between the encoded columns and the
    bound parameters, and run_chain_steps swaps the matching steps for
    the plan's probe appliers."""
    from .join import join_appliers
    update_fn = update_fn or ops.agg_direct_update
    ts_rows = subtile or SUBTILE_ROWS
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

    template = ops.agg_direct_init(G, specs)
    int_names = tuple(k for k, v in template.items()
                      if v.dtype == jnp.int64)
    flt_names = tuple(k for k, v in template.items()
                      if v.dtype == jnp.float64)
    assert len(int_names) + len(flt_names) == len(template)
    n_i = len(int_names)
    n_f = len(flt_names)
    init_i = jnp.stack([template[k] for k in int_names])
    init_f = (jnp.stack([template[k] for k in flt_names]) if n_f
              else jnp.zeros((1, G), dtype=jnp.float64))

    def kernel(bidx_ref, lo_ref, hi_ref, *refs):
        if n_staged:
            scratch = refs[-(n_staged + 1):-1]
            sem = refs[-1]
            refs = refs[:-(n_staged + 1)]
        col_refs = refs[:len(refs) - 5 - n_params - n_join]
        join_refs = refs[len(col_refs):len(col_refs) + n_join]
        param_refs = refs[len(col_refs) + n_join:
                          len(col_refs) + n_join + n_params]
        init_i_ref, init_f_ref = refs[-5:-3]
        acc_i_ref, acc_f_ref, counts_ref = refs[-3:]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init_outputs():
            acc_i_ref[...] = init_i_ref[...]
            acc_f_ref[...] = init_f_ref[...]
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

        codes = None
        for k, stride in zip(key_names, strides):
            c = batch.columns[k].values.astype(jnp.int64)
            codes = c * stride if codes is None else codes + c * stride
        if codes is None:
            codes = jnp.zeros(br, dtype=jnp.int64)
        agg_cols = agg_exprs(batch)
        total, compacted = compact_columns(
            batch.mask, br,
            [("codes", codes)] + agg_compaction_entries(specs, agg_cols))

        ts = min(br, ts_rows)
        n_sub = (total + ts - 1) // ts
        acc_i = acc_i_ref[...]
        acc_f = acc_f_ref[...]
        state = {k: acc_i[j] for j, k in enumerate(int_names)}
        state.update({k: acc_f[j] for j, k in enumerate(flt_names)})
        sub_idx = jnp.arange(ts, dtype=jnp.int32)

        def sub(j, st):
            off = j * ts
            m = (off + sub_idx) < total
            sc = jax.lax.dynamic_slice(compacted["codes"], (off,), (ts,))
            sa = subtile_agg_inputs(compacted, specs, off, ts)
            return update_fn(st, Batch({}, m), sc, sa, specs, G)
        state = jax.lax.fori_loop(0, n_sub, sub, state)
        acc_i_ref[...] = jnp.stack([state[k] for k in int_names])
        if n_f:
            acc_f_ref[...] = jnp.stack([state[k] for k in flt_names])
        counts_ref[...] = counts_ref[...] + jnp.stack(counts).astype(
            jnp.int64)[None, :]

    @jit_as("pallas_scan_agg")
    def run(bidx, lo, hi, arrays, jarrays, params, init_i_arg,
            init_f_arg):
        flat = list(arrays)
        in_specs = encoded_in_specs(names, kinds, flat, br, staged)
        for a in jarrays:
            flat.append(a)
            in_specs.append(pl.BlockSpec(a.shape, _whole_1d))
        for p in params:
            flat.append(jnp.asarray(p).reshape(1))
            in_specs.append(pl.BlockSpec((1,), _whole_1d))
        flat += [init_i_arg, init_f_arg]
        in_specs += [pl.BlockSpec(init_i_arg.shape, _whole_2d),
                     pl.BlockSpec(init_f_arg.shape, _whole_2d)]
        out_shape = [
            jax.ShapeDtypeStruct((n_i, G), jnp.int64),
            jax.ShapeDtypeStruct((max(n_f, 1), G), jnp.float64),
            jax.ShapeDtypeStruct((1, 1 + n_steps), jnp.int64),
        ]
        out_specs = [
            pl.BlockSpec((n_i, G), _whole_2d),
            pl.BlockSpec((max(n_f, 1), G), _whole_2d),
            pl.BlockSpec((1, 1 + n_steps), _whole_2d),
        ]
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

    return _Runner(run, init_i, init_f, int_names, flt_names)


def try_direct_scan_kernel(chain, aux, *, specs, key_names, strides, G,
                           agg_exprs, lowering, cache, declined,
                           runtime_stats=None, dma: str = "single",
                           expands=(), pool=None):
    """Run the fused scan chain through the Pallas kernel when eligible.

    Returns (agg_direct state dict, int64[1 + n_steps] row counters,
    grid length) on success -- the caller feeds them to
    agg_direct_finalize and the operator-stats spine exactly like the
    XLA direct path -- or None after recording one
    kernelDeclined{reason} counter.

    Chains with fanout-1 join/semi steps lower their probes in-kernel
    (kernels/join.py); `expands` is prep()'s per-join fanout tuple and
    `pool` the owning operator's MemoryContext, charged the build
    operand bytes non-revocably for the launch's duration."""
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
    br = block_rows_for(chain.leaf_cap(()))
    params_fp = chain.compiler.ctx.params_fingerprint
    grid = aligned_grid(chain.scan_meta, br, params_fp)
    if not grid:
        # everything pruned: the XLA chain keeps one chunk for its
        # compiled fori_loop, but the kernel can simply return its init
        # state (the residual filter would kill every row anyway)
        template = ops.agg_direct_init(G, specs)
        return (template,
                jnp.zeros(1 + len(chain.steps), dtype=jnp.int64), 0)
    names = tuple(colmap)
    max_block = max(b for b, _lo, _hi in grid)
    flat_arrays = gather_encoded_arrays(cached, colmap, names,
                                        (max_block + 1) * br, cache)

    params = tuple(aux[-1]) if chain.has_params else ()
    key = ("pallas_direct", G, strides, len(params), dma, jplan.sig)
    runner = cache.get(key)
    if runner is None:
        kinds = {name: cached[colmap[name]].kind for name in colmap}
        runner = build_direct_runner(
            chain, kinds, len(params), specs=specs, key_names=key_names,
            strides=strides, G=G, agg_exprs=agg_exprs, lowering=lowering,
            dma=dma, join_plan=jplan if jplan.steps else None)
        cache[key] = runner
    if not reserve_build_operands(pool, jplan.nbytes):
        declined("JoinBuildSize")
        return None
    bidx = jnp.asarray([b for b, _lo, _hi in grid], dtype=jnp.int32)
    lo = jnp.asarray([lo_ for _b, lo_, _hi in grid], dtype=jnp.int32)
    hi = jnp.asarray([hi_ for _b, _lo, hi_ in grid], dtype=jnp.int32)
    try:
        acc_i, acc_f, kcounts = runner.fn(bidx, lo, hi, flat_arrays,
                                          jplan.arrays, params,
                                          runner.init_i, runner.init_f)
    finally:
        if pool is not None and jplan.nbytes:
            pool.free(jplan.nbytes)
    state = {k: acc_i[j] for j, k in enumerate(runner.int_names)}
    state.update({k: acc_f[j] for j, k in enumerate(runner.flt_names)})
    kinds = {name: cached[colmap[name]].kind for name in colmap}
    meter_kernel_run(runtime_stats, len(grid),
                     len(staged_indices(names, kinds)), dma)
    return state, kcounts[0], len(grid)
