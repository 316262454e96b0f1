"""Micro-batched execution of one compiled point-query template.

The serving tier's bound-parameter design makes concurrent EXECUTE..USING
requests against the same canonical plan differ ONLY in the parameter
vector riding the jitted program as a traced argument
(`Batch.with_params`, exec/pipeline.py).  A BatchedTemplateRunner
exploits that: it vmaps the template's fused scan→chain→agg-update loop
over a leading batch axis of stacked parameter vectors, so N in-flight
queries cost ONE device launch instead of N.  Per-lane aggregation
states are then demultiplexed and finalized independently, so each
query still gets its own result pages, stats, and history record.

Eligibility is deliberately the same envelope as the fused XLA
direct-mode aggregation path (one-hot grid, BASIC_AGGS, closed small key
domains) — the batched program replays exactly the per-lane computation
the sequential fused path would run, chunk loop and all, which is what
makes the bit-identical-results guarantee of the batching layer hold.
Anything outside that envelope (hash-table aggs, sort paths,
parameterized build sides or pushdown pruning whose CHUNK LIST depends
on the bound constants) declines batching and the queries run
sequentially as before.

Batch widths are padded to powers of two (padding lanes replicate lane
0's parameters and are discarded at demux) so the per-width retrace
count stays logarithmic in the configured max batch size.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..common.types import DoubleType, RealType
from ..spi import plan as P
from ..exec import operators as ops
from ..exec.batch import Batch, batch_to_page
from ..exec.fused import assemble_chain
from ..exec.lowering import canonical_name
from ..exec.pipeline import _direct_mode_info, _rewrite_agg_masks
from ..utils.runtime_stats import named_jit


class BatchedTemplateRunner:
    """One compiled template's vmapped executor.  Built (once, cached on
    the owning PlanCompiler) from a checked-out canonical-cache entry;
    `run` takes per-lane device parameter tuples and returns one host
    Page per lane."""

    def __init__(self, compiler, output, chain, aux_base, expands,
                 leaf_cap, specs, input_exprs, key_names, info, projects):
        self.compiler = compiler
        self.output = output
        self.chain = chain
        self.aux_base = aux_base        # prep aux WITHOUT the params slot
        self.expands = expands
        self.leaf_cap = leaf_cap
        self.specs = specs
        self.input_exprs = input_exprs
        self.key_names = key_names
        self.doms, self.G, self.strides, self.kdts, self.kdicts = info
        self.projects = projects        # ProjectNodes root->down above agg
        self.low = compiler.lowering
        self._run_jit = named_jit("serve_batched_scan_agg", self._run_all)

    # -- the single-launch program ---------------------------------------

    def _run_all(self, pos_arr, cnt_arr, aux_base, stacked):
        """vmap over stacked parameter vectors of the SAME fori_loop the
        sequential fused direct path runs (exec/pipeline.py `loop`): each
        lane's update sequence — chunk order, one-hot grid, masked
        reductions — is identical to its solo execution, so per-lane
        results are bit-identical to unbatched runs.  Finalize and the
        scalar projections above the aggregation run INSIDE the vmapped
        program (elementwise, so vmap changes nothing bitwise): demux is
        then a per-lane slice of one small stacked result instead of a
        per-lane eager finalize chain."""
        chain, expands, leaf_cap = self.chain, self.expands, self.leaf_cap
        specs, G, strides = self.specs, self.G, self.strides
        key_names, low = self.key_names, self.low
        input_exprs = self.input_exprs
        inner = [v.name for v in self.output.source.output_variables]
        outer = [v.name for v in self.output.outputs]

        def per_lane(params):
            aux = aux_base + (params,)

            def body(i, st):
                b = chain.make(pos_arr[i], cnt_arr[i], aux, expands,
                               leaf_cap)
                codes = None
                for k, stride in zip(key_names, strides):
                    c = b.columns[k].values.astype(jnp.int64)
                    codes = (c * stride if codes is None
                             else codes + c * stride)
                if codes is None:       # global aggregation: one group
                    codes = jnp.zeros(b.capacity, dtype=jnp.int64)
                pb = b.with_params(params)
                agg_cols = {out: (low.eval(e, pb) if e is not None
                                  else None)
                            for out, e in input_exprs.items()}
                return ops.agg_direct_update(st, b, codes, agg_cols,
                                             specs, G)
            state = jax.lax.fori_loop(0, pos_arr.shape[0], body,
                                      ops.agg_direct_init(G, specs))
            out = ops.agg_direct_finalize(
                state, specs, key_names, self.doms, self.kdts,
                self.kdicts, force_row=not key_names)
            for node in reversed(self.projects):
                pb = out.with_params(params)
                cols = {v.name: low.eval(e, pb)
                        for v, e in node.assignments.items()}
                out = Batch(cols, out.mask)
            return Batch({o: out.columns[i_]
                          for i_, o in zip(inner, outer)}, out.mask)
        return jax.vmap(per_lane)(stacked)

    # -- execution --------------------------------------------------------

    def run(self, dev_list: List[Tuple]) -> Tuple[List, int, int]:
        """dev_list: per-lane tuples of device parameter scalars (one per
        slot, `sql.canonical.device_params` order).  Returns (pages,
        launch_nanos, demux_nanos) with one Page per input lane."""
        n = len(dev_list)
        width = 1 << max(0, n - 1).bit_length()
        lanes = list(dev_list) + [dev_list[0]] * (width - n)
        stacked = tuple(jnp.stack([lane[s] for lane in lanes])
                        for s in range(len(dev_list[0])))
        # ONE chunk list for every lane: suppress ["param", i] zone-map
        # markers (they resolve per-binding) and prune by plan constants
        # and dynamic-filter summaries only.  Chunks a per-lane prune
        # would have skipped contribute the aggregation identity (their
        # rows are filter-masked), so lane results stay bit-identical to
        # solo runs over the pruned list.
        ctx = self.compiler.ctx
        saved_fp = ctx.params_fingerprint
        ctx.params_fingerprint = None
        try:
            chunks = self.chain.chunks_for(self.expands)
        finally:
            ctx.params_fingerprint = saved_fp
        pos_arr = jnp.asarray([c0 for c0, _ in chunks], dtype=jnp.int64)
        cnt_arr = jnp.asarray([c1 for _, c1 in chunks], dtype=jnp.int64)
        t0 = time.perf_counter_ns()  # lint: allow-wall-clock
        stacked_out = self._run_jit(pos_arr, cnt_arr, self.aux_base,
                                    stacked)
        launch = time.perf_counter_ns() - t0  # lint: allow-wall-clock

        t1 = time.perf_counter_ns()  # lint: allow-wall-clock
        outer = [v.name for v in self.output.outputs]
        types = [v.type for v in self.output.outputs]
        pages = []
        for i in range(n):
            lane = jax.tree_util.tree_map(lambda a, _i=i: a[_i],
                                          stacked_out)
            pages.append(batch_to_page(lane, outer, types))
        demux = time.perf_counter_ns() - t1  # lint: allow-wall-clock
        return pages, launch, demux


def _eligible(compiler, output) -> Optional[BatchedTemplateRunner]:
    ctx = compiler.ctx
    cfg = ctx.config
    # the sequential execution these lanes must match bit-for-bit is the
    # fused XLA direct path; decline whenever that path would not run
    if not cfg.fuse_pipelines or ctx.stats is not None:
        return None
    if ctx.memory is not None and ctx.memory.limited:
        return None
    if ctx.params is None:
        return None
    projects = []
    node = output.source
    while isinstance(node, P.ProjectNode):
        projects.append(node)
        node = node.source
    if not isinstance(node, P.AggregationNode):
        return None
    agg = _rewrite_agg_masks(node)
    if any(a.distinct for a in agg.aggregations.values()):
        return None
    specs = []
    input_exprs: Dict[str, object] = {}
    for v, a in agg.aggregations.items():
        fname = canonical_name(a.call.display_name)
        args = a.call.arguments
        if fname == "count" and not args:
            fname = "count_star"
        if fname not in ops.BASIC_AGGS:
            return None
        is_float = isinstance(v.type, (DoubleType, RealType))
        specs.append(ops.AggSpec(fname, v.name, is_float, None))
        input_exprs[v.name] = args[0] if args else None
    specs = tuple(specs)
    key_names = tuple(v.name for v in agg.grouping_keys)

    chain = assemble_chain(compiler, agg.source)
    if chain is None or not chain.chunks:
        return None
    if not chain.has_params:
        return None                 # nothing varies between lanes
    if chain.build_params:
        # the build tables would be a function of the bound constants —
        # not lane-shareable.  params_pushdown is fine: run() prunes the
        # shared chunk list by plan constants only, and the lanes' own
        # filters mask the rows a per-lane prune would have skipped.
        return None
    try:
        prep_res = chain.prep()
    except Exception:   # noqa: BLE001 — decline, never fail the query
        return None
    if prep_res is None:
        return None
    aux, expands, _deferred = prep_res
    aux = aux[:-1] + (ctx.params,)
    leaf_cap = chain.leaf_cap(expands)
    try:
        probe = chain.shape_probe(aux, expands, leaf_cap)
    except Exception:   # noqa: BLE001
        return None
    key_cols = [probe.columns.get(k) for k in key_names]
    if any(c is None for c in key_cols):
        return None
    info = _direct_mode_info(key_names, key_cols)
    if info is None:
        return None
    return BatchedTemplateRunner(compiler, output, chain, aux[:-1],
                                 expands, leaf_cap, specs, input_exprs,
                                 key_names, info, projects)


def batched_runner_for(compiler, output) -> Optional[BatchedTemplateRunner]:
    """Get-or-build the template's batched runner, cached on the owning
    PlanCompiler (the attribute rides the compiler through the PlanCache
    pool's checkin/checkout; a rebuilt compiler re-derives it once).
    Returns None — and remembers the refusal — when the template is
    outside the batchable envelope."""
    cached = getattr(compiler, "_batched_runner", None)
    if cached is not None:
        return cached or None       # False == remembered refusal
    runner = _eligible(compiler, output)
    compiler._batched_runner = runner if runner is not None else False
    return runner


def disable_for(compiler) -> None:
    """A batched drain failed at runtime: pin this compiler's template to
    the sequential path (callers already re-ran the lanes solo)."""
    compiler._batched_runner = False
