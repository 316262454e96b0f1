"""Grouped (lifespan) execution: run a fused join+aggregation pipeline
bucket-by-bucket so peak HBM is ~1/K of the whole-table footprint.

The reference mechanism: when the tables under a join are bucketed on the
join key, a stage executes one bucket Lifespan at a time instead of
building the whole hash table at once (Lifespan.java:30-37,
GroupedExecutionTagger.java, session grouped_execution —
SystemSessionProperties.java:105); this is how Presto bounds memory for
huge joins without spilling.  TPU-first re-design:

  * Buckets come from the connector's co-bucketed layout
    (connectors/catalog.py bucket_layout): a key range maps to contiguous
    ROW RANGES in every co-bucketed table, so "repartitioning" is just
    split arithmetic — no shuffle pass, no partitioned spill files.
  * One bucket = one XLA program invocation.  All buckets share the SAME
    jitted program (pos/cnt arrays, build tables, and the key base are
    dynamic arguments; equal-sized buckets keep every shape static), so
    the host loop over K lifespans costs K dispatches, not K compiles.
  * Per-bucket aggregation is SORT-based (operators.sort_group_aggregate
    over the bucket's stacked chain output): measured fastest on chip
    against both the scatter table (~100ms per scattered million rows on
    TPU) and a streaming pre-grouped formulation whose extra segment
    gathers outweighed the argsort it avoided.  It is also fully general
    over grouping keys — no functional-dependency requirement.

Correctness argument: the anchor group key IS the bucket key, so every
output group lives in exactly one bucket; bucketed builds are restricted
to the bucket's key range, which drops only build rows that could never
match a probe row of this bucket; non-bucketed builds are replicated
across buckets (the reference broadcasts un-bucketed join sides under
grouped execution the same way).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..connectors import catalog
from ..spi import plan as P
from ..spi.expr import VariableReferenceExpression
from . import operators as ops
from .batch import Batch
from ..utils.runtime_stats import host_get, jit_as

# keyspace span above which auto mode engages, and the per-bucket span it
# targets (accumulator footprint and build-table size scale with the span)
AUTO_SPAN_THRESHOLD = 1 << 24
TARGET_BUCKET_SPAN = 1 << 22


def _resolve_to_scan(node: P.PlanNode, var_name: str):
    """Walk pass-through nodes to the TableScan column `var_name` reads, or
    None when the variable is computed (the PrestoToVeloxQueryPlan-style
    identity-lineage check a bucketing decision needs)."""
    while True:
        if isinstance(node, P.ProjectNode):
            expr = next((e for v, e in node.assignments.items()
                         if v.name == var_name), None)
            if not isinstance(expr, VariableReferenceExpression):
                return None
            var_name = expr.name
            node = node.source
        elif isinstance(node, P.FilterNode):
            node = node.source
        elif isinstance(node, P.ExchangeNode) and not node.inputs \
                and len(node.exchange_sources) == 1:
            src = node.exchange_sources[0]
            outer = [v.name for v in node.partitioning_scheme.output_layout]
            inner = [v.name for v in src.output_variables]
            try:
                var_name = inner[outer.index(var_name)]
            except ValueError:
                return None
            node = src
        elif isinstance(node, P.JoinNode):
            left_names = {v.name for v in node.left.output_variables}
            node = node.left if var_name in left_names else node.right
        elif isinstance(node, P.SemiJoinNode):
            if var_name == node.semi_join_output.name:
                return None
            node = node.source
        elif isinstance(node, P.TableScanNode):
            for v, col in node.assignments.items():
                if v.name == var_name:
                    return node, col.name
            return None
        else:
            return None


def _materialize_bucket_build(compiler, jn, scan_node, btable: str,
                              rows: Tuple[int, int]):
    """Materialize a join's build subtree restricted to one bucket's row
    range of its bucketed scan, through the FUSED path.

    The compiler memoizes BatchSources per node id, so the scan's cached
    source (which baked the previous bucket's splits into its fused_scan
    metadata) is evicted around the materialization and restored after —
    other consumers of the same node id keep their view, and the jitted
    fmat program is reused across buckets (its chunk arrays are dynamic
    arguments)."""
    from .fused import _empty_build_batch, fused_materialize
    cid = scan_node.table.connector_id
    sf = dict(scan_node.table.extra).get("scaleFactor", 0.01)
    ctx = compiler.ctx
    saved_split = ctx.splits.get(scan_node.id)
    saved_src = compiler._sources.pop(scan_node.id, None)
    ctx.splits[scan_node.id] = [catalog.TableSplit(
        cid, btable, sf, rows[0], rows[1])]
    try:
        b = fused_materialize(compiler, jn.right)
    finally:
        if saved_split is None:
            ctx.splits.pop(scan_node.id, None)
        else:
            ctx.splits[scan_node.id] = saved_split
        if saved_src is None:
            compiler._sources.pop(scan_node.id, None)
        else:
            compiler._sources[scan_node.id] = saved_src
    if b is None:
        b = _empty_build_batch(jn.right)
    return b


def _full_coverage(splits, table: str, sf: float, cid: str) -> bool:
    """Whether the scan's splits cover the whole table contiguously (a
    distributed task owning a split subset must not re-bucket it)."""
    total = catalog.table_row_count(table, sf, cid)
    ranges = sorted((s.start, s.end) for s in splits)
    pos = 0
    for lo, hi in ranges:
        if lo != pos:
            return False
        pos = hi
    return pos == total


class GroupedRunner:
    """Compiled per-bucket programs + layout; .run() yields one finalized
    aggregation batch per lifespan.  Built once per plan compile and
    reused across re-executions (jitted programs are instance state)."""

    def __init__(self, compiler, chain, layout, anchor, dep_names,
                 key_names, specs, agg_exprs_fn, G, expands, shared_aux,
                 per_bucket_builds, key_dtypes, key_dicts, probe_table):
        self.compiler = compiler
        self.chain = chain
        self.layout = layout
        self.anchor = anchor
        self.dep_names = dep_names
        self.key_names = key_names
        self.specs = specs
        self.agg_exprs_fn = agg_exprs_fn
        self.G = G
        self.expands = expands
        self.shared_aux = shared_aux          # None entries = per-bucket
        self.per_bucket_builds = per_bucket_builds
        self.key_dtypes = key_dtypes
        self.key_dicts = key_dicts
        self.probe_table = probe_table
        self.leaf_cap = chain.leaf_cap(expands)
        # parameter fingerprint the shared aux / bucket-0 probe / fanout
        # reservations were built under; the caller rebuilds the runner
        # when a parameterized BUILD subtree sees a different fingerprint
        self.params_fp = compiler.ctx.params_fingerprint
        self._sort_progs: Dict[int, callable] = {}
        # bucket-0 (aux, dup flags) built during eligibility; consumed by
        # the first run() so the build work is not repeated
        self._aux0 = None

    # -- per-bucket pieces -------------------------------------------------

    def _bucket_chunks(self, rows: Tuple[int, int]):
        p, end = rows
        out = []
        while p < end:
            n = min(self.leaf_cap, end - p)
            out.append((p, n))
            p += n
        return out

    def _bucket_aux(self, bucket):
        """aux tuple for this bucket: shared entries + freshly materialized
        bucketed build tables (restricted to the bucket's row range, via
        _materialize_bucket_build).  A build whose reserved fanout is 1
        becomes a direct-address table keyed off the bucket's key base;
        a fanout-k build becomes a hash-sorted table probed with the
        k-way expansion the shared program reserved at prep time."""
        from .fused import DirectTable, _direct_builder, _drop_null_keys, \
            _max_run
        aux = list(self.shared_aux)
        # per-build overflow flags (device bools): key duplicated in a
        # fanout-1 build, or multiplicity > k in a fanout-k build
        dups: List = []
        for (ai, jn, scan_node, btable, bkey, k) in self.per_bucket_builds:
            b = _materialize_bucket_build(self.compiler, jn, scan_node,
                                          btable, bucket.rows[btable])
            b = _drop_null_keys(b, (bkey,))
            if k == 1:
                col = b.columns[bkey]
                slots, dup = _direct_builder(self.G)(
                    col.values, b.mask, jnp.int64(bucket.key_lo))
                dups.append(dup)
                aux[ai] = DirectTable(slots, jnp.int64(bucket.key_lo),
                                      dict(b.columns))
            else:
                from .pipeline import _jits
                tbl = _jits()[1](b, (bkey,))
                dups.append(_max_run(tbl) > k)
                aux[ai] = tbl
        return tuple(aux), dups

    def _get_sort_prog(self, S: int):
        prog = self._sort_progs.get(S)
        if prog is None:
            chain, expands, leaf_cap = self.chain, self.expands, self.leaf_cap
            key_names, specs = self.key_names, self.specs
            agg_exprs = self.agg_exprs_fn

            @jit_as("grouped_sort_agg")
            def prog(pos_arr, cnt_arr, aux):
                def step(pc):
                    b = chain.make(pc[0], pc[1], aux, expands, leaf_cap)
                    cols = {k: b.columns[k] for k in key_names}
                    for out, col in agg_exprs(b).items():
                        if col is not None:
                            cols["$in_" + out] = col
                    return Batch(cols, b.mask)
                stacked = jax.lax.map(step, (pos_arr, cnt_arr))
                flat = jax.tree_util.tree_map(
                    lambda a: a.reshape((-1,) + a.shape[2:]), stacked)
                inputs = {s.output: flat.columns.get("$in_" + s.output)
                          for s in specs}
                return ops.sort_group_aggregate(
                    Batch({k: flat.columns[k] for k in key_names},
                          flat.mask), key_names, inputs, specs, {})
            self._sort_progs[S] = prog
        return prog

    # -- driver ------------------------------------------------------------

    @staticmethod
    def _check_dups(dup_flags) -> None:
        if dup_flags and any(bool(d) for d in host_get(
                dup_flags, "grouped_build_dups")):
            # a bucketed build's key multiplicity exceeds what the shared
            # program reserved for this bucket (duplicates against a
            # direct table, or a run longer than the fanout-k expansion):
            # the probe would keep an arbitrary subset of matches, and
            # earlier lifespans already streamed downstream, so the only
            # correct move is to fail loudly (the single-lifespan path
            # handles any fanout via replicated builds)
            raise NotImplementedError(
                "grouped execution: bucketed build key multiplicity "
                "exceeds the reserved fanout within a lifespan")

    def _stage_bucket(self, bi: int, aux0):
        """Host-stage one bucket: split arithmetic, build materialization
        (device dispatches + small sync), chunk arrays.  Returns the
        ready-to-dispatch entry, or None for an empty bucket."""
        bucket = self.layout[bi]
        chunks = self._bucket_chunks(bucket.rows[self.probe_table])
        if not chunks:
            return None
        if bi == 0 and aux0 is not None:
            aux, dups = aux0
        else:
            aux, dups = self._bucket_aux(bucket)
        if self.chain.has_params:
            # shared aux carries the params vector bound when the runner
            # was built — swap in this execution's (traced arg: no retrace)
            aux = tuple(aux)[:-1] + (self.compiler.ctx.params,)
        pos_arr = jnp.asarray([c[0] for c in chunks], dtype=jnp.int64)
        cnt_arr = jnp.asarray([c[1] for c in chunks], dtype=jnp.int64)
        return len(chunks), pos_arr, cnt_arr, aux, dups

    def run(self):
        """Pipelined lifespan loop: keep up to grouped_prefetch_depth
        buckets STAGED (builds materialized, chunk arrays device-put)
        beyond the one being consumed, so bucket k+1's host reads and
        host->HBM transfers overlap bucket k's device compute — JAX async
        dispatch executes device programs in dispatch order, so staging
        ahead keeps the device queue full while downstream drains bucket
        k.  Depth 0 reproduces the strictly serial pre-pipelining loop.

        With lifespan sharding (TaskContext.grouped_shard = (i, n)) this
        task runs only buckets i, i+n, ... — the scheduler hands every
        task full splits and disjoint bucket subsets.

        RuntimeStats (when the runner wired a sink into the context):
        groupedBucketGenWallNanos  — host wall staging each bucket
        groupedBucketComputeWallNanos — wall from dispatching a bucket's
        program until downstream finished consuming it
        groupedRunWallNanos — whole loop; overlap shows as run wall <
        gen.sum + compute.sum."""
        import time
        from collections import deque
        ctx = self.compiler.ctx
        depth = max(0, getattr(ctx.config, "grouped_prefetch_depth", 1))
        stats = getattr(ctx, "runtime_stats", None)
        aux0 = self._aux0
        self._aux0 = None           # one-shot: don't pin HBM across runs
        indices = range(len(self.layout))
        shard = getattr(ctx, "grouped_shard", None)
        if shard is not None:
            indices = range(shard[0], len(self.layout), shard[1])
        t_run = time.perf_counter_ns()  # lint: allow-wall-clock
        it = iter(indices)
        staged = deque()
        exhausted = False
        while True:
            while not exhausted and len(staged) <= depth:
                bi = next(it, None)
                if bi is None:
                    exhausted = True
                    break
                t0 = time.perf_counter_ns()  # lint: allow-wall-clock
                ent = self._stage_bucket(bi, aux0)
                if stats is not None:
                    stats.add("groupedBucketGenWallNanos",
                              time.perf_counter_ns() - t0)  # lint: allow-wall-clock
                if ent is not None:
                    staged.append(ent)
            if not staged:
                break
            S, pos_arr, cnt_arr, aux, dups = staged.popleft()
            self._check_dups(dups)
            # per-bucket SORT aggregation: measured fastest on chip for
            # the SF100 shapes (argsort+segment scans beat both the
            # scatter table, ~100ms per scattered million rows, and a
            # streaming pre-grouped formulation whose extra segment
            # gathers outweighed the argsort it avoided)
            t0 = time.perf_counter_ns()  # lint: allow-wall-clock
            yield self._get_sort_prog(S)(pos_arr, cnt_arr, aux)
            if stats is not None:
                stats.add("groupedBucketComputeWallNanos",
                          time.perf_counter_ns() - t0)  # lint: allow-wall-clock
        if stats is not None:
            stats.add("groupedRunWallNanos",
                      time.perf_counter_ns() - t_run)  # lint: allow-wall-clock


def make_grouped_runner(compiler, node, chain, key_names, specs,
                        agg_exprs_fn, basic_specs, has_exprs2,
                        cfg) -> Optional[GroupedRunner]:
    """Eligibility + one-time prep.  Returns a GroupedRunner, or None to
    keep the single-lifespan path.  Called once per plan compile; cached
    by the aggregation compiler."""
    pool = compiler.ctx.memory
    if pool.budget is not None or has_exprs2 or not key_names:
        return None
    if not basic_specs:
        return None
    # parameterized chains are fine here: probe-side params ride the last
    # aux slot and _stage_bucket swaps in each execution's vector, and
    # bucketed builds re-materialize per run with the current params.
    # Shared builds / fanout reservations ARE frozen at build time, so
    # the caller rebuilds the runner when chain.build_params and the
    # fingerprint moved (see the gen() guard in pipeline.py).
    # PARTIAL is safe: each bucket's exact aggregate is a valid partial
    # state for the decomposable basic aggs, and the FINAL stage merges
    # per-bucket rows the same way it merges per-task rows
    if getattr(node, "step", P.SINGLE) not in (P.SINGLE, P.PARTIAL):
        return None
    K_conf = cfg.grouped_lifespans
    if K_conf == 1:
        return None
    meta = chain.scan_meta
    table, cid, sf = meta.get("table"), meta.get("cid"), meta.get("sf")
    if table is None:
        return None
    bcol = catalog.bucket_column(table, cid)
    if bcol is None:
        return None
    if not _full_coverage(meta["splits"], table, sf, cid):
        return None

    # lineage: which live column names carry the scan's bucket column
    colmap = meta.get("colmap", {})
    carriers = {n for n, c in colmap.items() if c == bcol}
    if not carriers:
        return None
    bucketed_joins: Dict[int, tuple] = {}
    for si, step in enumerate(chain.steps):
        kind = step[0]
        if kind == "project":
            carriers = {v.name for v, e in step[1]
                        if isinstance(e, VariableReferenceExpression)
                        and e.name in carriers}
        elif kind == "rename":
            carriers = {o for o, i in step[1] if i in carriers}
        elif kind == "join":
            jn = step[1]
            hit = None
            for left, right in jn.criteria:
                if left.name not in carriers:
                    continue
                res = _resolve_to_scan(jn.right, right.name)
                if res is None:
                    continue
                scan_node, col2 = res
                t2 = scan_node.table.table_name
                c2 = scan_node.table.connector_id
                if c2 == cid and catalog.bucket_column(t2, c2) == col2:
                    hit = (jn, scan_node, t2, right.name)
                    break
            if hit is not None:
                bucketed_joins[si] = hit
                if jn.join_type == P.INNER:
                    # the matched build key equals the probe key
                    carriers |= {r.name for l, r in jn.criteria
                                 if l.name in carriers}
            # non-bucketed joins replicate their build: correct, just no
            # memory win
        if not carriers:
            return None
    anchor = next((k for k in key_names if k in carriers), None)
    if anchor is None:
        return None     # groups would straddle buckets

    layout1 = catalog.bucket_layout(sf, 1, cid)
    if not layout1:
        return None
    span_total = layout1[-1].key_hi - layout1[0].key_lo
    if K_conf >= 2:
        K = K_conf
    else:               # auto: engage only for huge keyspaces
        if span_total <= AUTO_SPAN_THRESHOLD:
            return None
        K = -(-span_total // TARGET_BUCKET_SPAN)
    layout = catalog.bucket_layout(sf, K, cid)
    if len(layout) <= 1 and K_conf < 2:
        return None
    max_span = max(b.key_hi - b.key_lo for b in layout)
    if max_span > ops.SPAN_AGG_MAX_GROUPS:
        return None
    G = 1 << (max_span - 1).bit_length()

    # shared (bucket-invariant) builds once; bucketed builds defer to the
    # per-bucket lifespan (FusedChain.prep owns the aux-slot layout).  A
    # bucketed build must materialize through the fused path — its chunk
    # layout re-derives from the per-bucket split override — so
    # non-fusible bucketed builds are replicated instead.
    #
    # Fanout probing: the shared program must reserve a STATIC expansion
    # factor per deferred join, so probe bucket 0's build now and size k
    # from its maximum key run (k==1 -> direct table; k>1 -> hash table
    # probed with k-way expansion, e.g. a self-join on the bucket key).
    # Later buckets exceeding k fail loudly at runtime (_check_dups).
    from .fused import MAX_EXPAND, _drop_null_keys, _max_run, \
        assemble_chain

    fanouts: Dict[int, int] = {}
    for si, (jn, scan_node, t2, bkey) in bucketed_joins.items():
        if assemble_chain(compiler, jn.right) is None:
            continue                    # not fusible: replicate instead
        try:
            b0 = _materialize_bucket_build(compiler, jn, scan_node, t2,
                                           layout[0].rows[t2])
        except NotImplementedError:
            continue
        b0 = _drop_null_keys(b0, (bkey,))
        from .pipeline import _jits
        kmax = int(host_get(_max_run(_jits()[1](b0, (bkey,))),
                            "build_max_run"))
        if kmax > MAX_EXPAND:
            continue                    # too wide to reserve: replicate
        fanouts[si] = 1 if kmax <= 1 else 1 << (kmax - 1).bit_length()

    def _defer(si, jn):
        return fanouts.get(si, 0)

    try:
        prep_res = chain.prep(defer=_defer)
    except NotImplementedError:
        return None
    if prep_res is None:
        return None
    shared_aux, expands, deferred = prep_res
    shared_aux = list(shared_aux)
    per_bucket_builds = [
        (ai, jn, bucketed_joins[si][1], bucketed_joins[si][2],
         bucketed_joins[si][3], fanouts[si])
        for ai, si, jn in deferred]

    runner = GroupedRunner(compiler, chain, layout, anchor,
                           tuple(k for k in key_names if k != anchor),
                           key_names, specs, agg_exprs_fn, G, expands,
                           shared_aux, per_bucket_builds, {}, {}, table)

    # probe schema (dtypes/dicts of the grouping keys) from a shape-only
    # evaluation with bucket 0's aux; the materialized builds are kept on
    # the runner so the first run() does not repeat the device work
    try:
        aux0, dups0 = runner._bucket_aux(layout[0])
    except NotImplementedError:
        return None
    if dups0 and any(bool(d) for d in host_get(dups0,
                                               "grouped_build_dups")):
        return None     # non-unique bucketed build key: single lifespan
    runner._aux0 = (aux0, dups0)
    try:
        probe = chain.shape_probe(aux0, expands, runner.leaf_cap)
    except NotImplementedError:
        return None
    key_dtypes, key_dicts = {}, {}
    for k in key_names:
        c = probe.columns.get(k)
        if c is None or c.lazy is not None:
            return None
        key_dtypes[k] = c.values.dtype
        if c.dictionary is not None:
            key_dicts[k] = c.dictionary
    if probe.columns[anchor].dictionary is not None:
        return None
    if probe.columns[anchor].nulls is not None:
        # nullable bucket key: a NULL anchor has no home bucket, so its
        # group would be duplicated across lifespans (catalog.py
        # bucket_column contract) — keep the single-lifespan path
        return None
    runner.key_dtypes = key_dtypes
    runner.key_dicts = key_dicts
    return runner


# wrappers a fragment plants above its aggregation that don't change
# whether the agg itself can run grouped
_PEELABLE = (P.ProjectNode, P.FilterNode, P.SortNode, P.TopNNode,
             P.LimitNode)

_SHARDABLE_AGGS = {"sum", "avg", "count", "count_star", "min", "max"}


def stage_shards_lifespans(root: P.PlanNode, cfg) -> bool:
    """Plan-time predicate for the scheduler: may the tasks of this
    SOURCE-distributed fragment be given FULL splits plus disjoint
    round-robin lifespan subsets (TaskContext.grouped_shard) instead of
    the usual split round-robin?

    Mirrors make_grouped_runner's STATIC eligibility conditions (the
    ones decidable without compiling): one bucketed scan, a grouped
    basic aggregation keyed on its bucket column, config gates, and the
    force/auto lifespan-count decision.  A misprediction is safe in
    both directions — if grouped execution then fails to engage at
    runtime, shard 0 runs the ordinary fallback over the full splits
    and the other shards contribute nothing (pipeline.py gen()); if it
    would have engaged but this predicate said no, tasks fall back to
    split subsets, which _full_coverage rejects, and each task runs the
    ordinary single-lifespan path over its subset."""
    from .lowering import canonical_name
    if not cfg.grouped_lifespan_sharding or not cfg.fuse_pipelines:
        return False
    if cfg.grouped_lifespans == 1 or cfg.memory_budget_bytes is not None \
            or cfg.memory_max_query_bytes is not None:
        return False
    node = root
    while isinstance(node, _PEELABLE):
        node = node.source
    if not isinstance(node, P.AggregationNode):
        return False
    if getattr(node, "step", P.SINGLE) not in (P.SINGLE, P.PARTIAL):
        return False
    if not node.grouping_keys:
        return False
    for agg in node.aggregations.values():
        if agg.distinct or agg.mask is not None:
            return False
        fname = canonical_name(agg.call.display_name)
        if fname == "count" and not agg.call.arguments:
            fname = "count_star"
        if fname not in _SHARDABLE_AGGS:
            return False
    # exactly one scan subtree: broadcast build sides arrive as
    # RemoteSources in a SOURCE fragment, so >1 scan means a co-located
    # join shape the runtime walker would have to re-verify per task
    scans = [n for n in P.walk_plan(node)
             if isinstance(n, P.TableScanNode)]
    if len(scans) != 1:
        return False
    scan = scans[0]
    table = scan.table.table_name
    cid = scan.table.connector_id
    bcol = catalog.bucket_column(table, cid)
    if bcol is None:
        return False
    if not any((_resolve_to_scan(node.source, k.name) or (None, None))
               == (scan, bcol) for k in node.grouping_keys):
        return False
    if cfg.grouped_lifespans >= 2:
        return True
    sf = dict(scan.table.extra).get("scaleFactor", 0.01)
    layout1 = catalog.bucket_layout(sf, 1, cid)
    if not layout1:
        return False
    return layout1[-1].key_hi - layout1[0].key_lo > AUTO_SPAN_THRESHOLD
