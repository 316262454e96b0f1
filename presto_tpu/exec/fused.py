"""Whole-pipeline fusion of scan -> filter/project -> join chain -> (agg).

Round-1 fused only scan->filter/project->direct-agg (TPC-H Q1/Q6 shape);
join-heavy queries streamed probe batches with a host sync per batch, which
dominated wall-clock (per-sync cost ~0.1-1s on a remote device).  This module
generalizes fusion to probe-side JOIN CHAINS so an entire pipeline compiles
into ONE XLA program with a fori_loop over scan chunks — the TPU analog of the
reference Driver streaming pages through an operator chain with zero host
round-trips (presto-main-base/.../operator/Driver.java:421-451).

The enabling observation: TPC-H/DS probe joins are FK->PK.  When the build
side's keys are UNIQUE (checked once on the host after the build side is
materialized), a probe is fanout<=1: the join never expands rows, so the
chunk capacity is preserved through the whole chain, no overflow machinery is
needed in-loop, and a join step reduces to "lookup + gather build columns +
mask update".  Two lookup structures:

  * DirectTable — dense integer PK (orderkey/custkey/partkey/...): a direct-
    address array keyed by (key - base).  Probe is ONE int32 gather — no
    hashing, no searchsorted.  The TPU-native analog of the reference's
    LookupJoinOperator fast path for integer keys.
  * the hash-sorted ops.BuildTable — multi-column or sparse keys; probe is
    one searchsorted (fanout-1 variant of ops.probe_join).

Build sides are materialized BEFORE the loop compiles (they are plan
subtrees, usually small dims); rows with NULL keys are excluded from the
build and NULL probe keys never match, per SQL equi-join semantics (the
numpy oracle exec/reference.py:438-449 is the fixture for this).

Semi joins (IN/EXISTS markers) fuse the same way; duplicate build keys are
harmless there (the marker is existence), so semi steps never force a
fallback.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import cached_property, lru_cache
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..spi import plan as P
from .batch import Batch, Column
from . import operators as ops
from ..utils.runtime_stats import current_stats, host_get, jit_as

# absolute cap on a direct-address table (entries), and the max ratio of
# key span to build rows before falling back to the hash table: 4 bytes
# of table a key of the span, so a filtered scan of a dense key (one
# order in ten of TPC-H Q3's `orders` side) is still addressed directly
# and not sorted by hash and searched once a probe row (40 ms a 32K-row
# step on the v5e, PERF.md PR 32)
DIRECT_TABLE_MAX = 1 << 26
DIRECT_TABLE_SPAN_RATIO = 64
# the hidden column a cut chain carries its build row indices in
BUILD_ROW = "$build_row"
# largest per-join fanout the in-loop expansion handles, and the largest
# combined expansion across a chain (chunk capacity is divided by it)
MAX_EXPAND = 64
MAX_EXPAND_PRODUCT = 256


@dataclass
class DirectTable:
    """Direct-address build table for a dense integer key."""
    slots: jnp.ndarray                # int32 build-row index, -1 = absent
    base: jnp.ndarray                 # scalar int64: smallest key
    columns: Dict[str, Column]        # original build columns

    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        return ((self.slots, self.base,
                 tuple(self.columns[n] for n in names)), names)

    @classmethod
    def tree_unflatten(cls, names, children):
        slots, base, cols = children
        return cls(slots, base, dict(zip(names, cols)))


jax.tree_util.register_pytree_node_class(DirectTable)


@lru_cache(maxsize=None)
def _direct_builder(size: int):
    @jit_as("direct_table_build")
    def build(values, mask, base):
        k = jnp.where(mask, values.astype(jnp.int64) - base, size)
        k = jnp.clip(k, 0, size).astype(jnp.int32)   # size = drop slot
        rows = jnp.arange(values.shape[0], dtype=jnp.int32)
        slots = jnp.full(size, -1, jnp.int32).at[k].set(
            rows, mode="drop")
        counts = jnp.zeros(size, jnp.int32).at[k].add(
            mask.astype(jnp.int32), mode="drop")
        return slots, jnp.any(counts > 1)
    return build


@jit_as("key_stats")
def _key_stats(values, mask):
    """(min, max, live count) of a key column over live rows."""
    v = values.astype(jnp.int64)
    vmin = jnp.min(jnp.where(mask, v, jnp.iinfo(jnp.int64).max))
    vmax = jnp.max(jnp.where(mask, v, jnp.iinfo(jnp.int64).min))
    return vmin, vmax, jnp.sum(mask)


@jit_as("max_run")
def _max_run(table: ops.BuildTable):
    """Largest live-key duplicate run (the join's max fanout; padding runs
    excluded)."""
    n = table.run_len.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    return jnp.max(jnp.where(pos < table.valid_count, table.run_len, 0))


def _build_has_null_key(batch: Batch, key_names: Tuple[str, ...]) -> bool:
    """Whether any live build row has a NULL key — needed for the semi-join
    marker's three-valued output (x IN (...NULL...) is UNKNOWN on a miss)."""
    m = jnp.zeros((), dtype=bool)
    for k in key_names:
        c = batch.columns[k]
        if c.nulls is not None:
            m = m | jnp.any(batch.mask & c.nulls)
    return bool(host_get(m, "build_has_null_key"))


def _drop_null_keys(batch: Batch, key_names: Tuple[str, ...]) -> Batch:
    """Exclude build rows with NULL keys (SQL equi-join: NULL never
    matches).  Runs eagerly — a handful of elementwise ops, once per build."""
    m = batch.mask
    for k in key_names:
        c = batch.columns[k]
        if c.nulls is not None:
            m = m & ~c.nulls
    return batch.with_mask(m)


def probe_direct(batch: Batch, dt: DirectTable, key_name: str):
    """(hit, build_row_index) for a direct-address lookup (shared slot
    math: ops.direct_lookup)."""
    return ops.direct_lookup(batch, dt, key_name)


def probe_unique(batch: Batch, table: ops.BuildTable,
                 key_names: Tuple[str, ...]):
    """(hit, build_row_index) against a hash-sorted unique-key build."""
    cols = [batch.columns[k] for k in key_names]
    kh = ops._orderable_hash(ops.hash_columns(cols))
    nb = table.perm.shape[0]
    lo = jnp.clip(jnp.searchsorted(table.keyhash_sorted, kh, side="left",
                                   method="scan_unrolled")
                  .astype(jnp.int32), 0, nb - 1)
    hit = table.keyhash_sorted[lo] == kh
    for c in cols:
        if c.nulls is not None:
            hit = hit & ~c.nulls
    return hit, jnp.where(hit, table.perm[lo], 0)


class FusedChain:
    """A compile-time description of a fusible probe pipeline.

    steps (leaf->root order):
      ("filter", predicate)
      ("project", [(variable, expr), ...])
      ("rename", [(out_name, in_name), ...])
      ("join", JoinNode)         aux entry: DirectTable | BuildTable
      ("semi", SemiJoinNode)     aux entry: DirectTable | BuildTable

    prep() (runtime) returns (aux, expands): per-join lookup tables plus
    static per-join fanout factors.  A join whose build keys repeat up to
    k times expands each probe row into k candidate slots IN-LOOP; the
    chunk capacity is divided by the product of factors so the in-flight
    batch footprint stays at the configured batch size.
    """

    def __init__(self, compiler, steps: List[tuple], scan_meta: dict,
                 step_ids: Optional[List[str]] = None,
                 scan_id: Optional[str] = None,
                 root: Optional[P.PlanNode] = None):
        self.compiler = compiler
        # the subtree this chain compiles: what its programs are keyed on
        self.root = root
        self.steps = steps
        self.scan_meta = scan_meta
        # plan-node ids for EXPLAIN ANALYZE row counters: node_ids[0] is
        # the scan, node_ids[i+1] the node step i came from (None when the
        # chain was assembled without id tracking, e.g. in older tests)
        self.step_ids = step_ids or [None] * len(steps)
        self.scan_id = scan_id
        self.node_ids = [scan_id] + list(self.step_ids)
        self.cap = scan_meta["cap"]
        # parameterized probe expressions ride the traced aux pytree (last
        # element) so re-executions with different bound constants reuse
        # the compiled program; parameterized BUILD subtrees and pushdown
        # markers instead force per-execution refresh of cached prep/chunk
        # state (see fused_stream / run_fused)
        from .lowering import expr_has_params
        self.has_params = any(
            (s[0] == "filter" and expr_has_params(s[1]))
            or (s[0] == "project"
                and any(expr_has_params(e) for _v, e in s[1]))
            for s in steps)
        self.build_params = any(
            '"@type": "parameter"' in P.structural_key(
                s[1].right if s[0] == "join" else s[1].filtering_source)
            for s in steps if s[0] in ("join", "semi"))
        self.params_pushdown = any(
            isinstance(e.get("value"), (list, tuple))
            for e in scan_meta.get("pushdown") or ())
        self.chunks = self.chunks_for((1,) * sum(
            1 for s in steps if s[0] in ("join", "semi")))
        self.total_rows = sum(n for _, n in self.chunks)
        # what a jitted program closes over instead of this chain
        self.program = ChainProgram(steps, scan_meta, compiler.lowering,
                                    compiler.ctx.task_index,
                                    self.has_params)

    def chunks_for(self, expands: Tuple[int, ...],
                   meter: bool = False) -> List[Tuple[int, int]]:
        kprod = 1
        for k in expands:
            kprod *= k
        cap = max(1 << 12, self.cap // kprod)
        chunks = []
        for split in self.scan_meta["splits"]:
            p = split.start
            while p < split.end:
                chunks.append((p, min(cap, split.end - p)))
                p += cap
        zm = self.scan_meta.get("zone_maps")
        pd = self.scan_meta.get("pushdown")
        if zm and pd:
            # zone-map chunk skipping: host numpy over build-time stats.
            # For plan constants the pruned list is DETERMINISTIC per
            # compiled plan; ["param", i] marker entries resolve against
            # the CURRENT execution's parameter fingerprint, so consumers
            # that bake chunk counts into cached programs must recompute
            # this list per execution when self.params_pushdown is set
            from ..storage import prune_chunks
            dyn = self.scan_meta.get("dyn_summaries")
            detail: dict = {}
            chunks, _skipped = prune_chunks(
                chunks, zm, pd, self.compiler.ctx.params_fingerprint,
                dyn() if dyn is not None else None, detail=detail)
            if meter and detail.get("dyn_engaged"):
                # fused chains never reach the streaming scan's row-level
                # runtime filter, so chunk pruning IS the application
                # here — meter it once per execution (callers pass
                # meter=True only on their final pre-drain recompute)
                from .adaptive import ADAPTIVE_METRICS
                ADAPTIVE_METRICS.incr("filters_applied")
                ADAPTIVE_METRICS.incr("filter_rows_in", detail["rows_in"])
                ADAPTIVE_METRICS.incr("filter_rows_pruned",
                                      detail["dyn_rows_pruned"])
                ADAPTIVE_METRICS.incr("filter_chunks_skipped",
                                      detail["dyn_chunks_pruned"])
                rs = self.compiler.ctx.runtime_stats
                if rs is not None:
                    rs.add("dynamicFilterRowsIn", detail["rows_in"])
                    rs.add("dynamicFilterRowsPruned",
                           detail["dyn_rows_pruned"])
                    rs.add("dynamicFilterRowsOut", detail["rows_in"]
                           - detail["dyn_rows_pruned"])
        return chunks

    def leaf_cap(self, expands: Tuple[int, ...]) -> int:
        kprod = 1
        for k in expands:
            kprod *= k
        return max(1 << 12, self.cap // kprod)

    # -- runtime: materialize build sides ---------------------------------
    def prep(self, defer: Optional[Callable] = None
             ) -> Optional[Tuple[tuple, Tuple[int, ...], List[tuple]]]:
        """Materialize every build side and construct lookup tables.
        Returns (aux, expands, deferred), or None when a join's fanout
        exceeds the expansion limits (caller falls back to the streaming
        executor).  defer(step_index, JoinNode) -> k (falsy = build here)
        reserves the join's aux slot instead of building it, with static
        fanout k baked into the shared program (grouped execution fills
        those slots per bucket lifespan: k == 1 means a unique-key direct
        table, k > 1 a hash-sorted table probed with k-way expansion);
        deferred lists (aux_index, step_index, JoinNode)."""
        # aux[0] carries the scan's HBM-cached whole-table columns as a
        # traced argument pytree (closure constants of this size would be
        # inlined as XLA literals); join/semi lookup tables follow
        aux: List = [self.scan_meta.get("cached_cols", {})]
        expands: List[int] = []
        deferred: List[tuple] = []
        for si, step in enumerate(self.steps):
            kind = step[0]
            if kind == "join":
                node = step[1]
                k_defer = defer(si, node) if defer is not None else 0
                if k_defer:
                    aux.append(None)
                    deferred.append((len(aux) - 1, si, node))
                    expands.append(int(k_defer))
                    continue
                res = self._build_for(
                    node.right, tuple(r.name for _l, r in node.criteria),
                    for_join=True)
                if res is None:
                    return None
                tbl, k, _ = res
                aux.append(tbl)
                expands.append(k)
            elif kind == "semi":
                node = step[1]
                fkey = node.filtering_source_join_variable.name
                tbl, _k, had_null = self._build_for(
                    node.filtering_source, (fkey,), for_join=False)
                # (table, build-had-null-key) — the flag rides the traced
                # aux pytree so the marker can go three-valued without a
                # retrace per data change
                aux.append((tbl, jnp.asarray(had_null)))
                expands.append(1)
        kprod = 1
        for k in expands:
            kprod *= k
        if kprod > MAX_EXPAND_PRODUCT:
            return None
        if self.has_params:
            # LAST so join/semi aux indexing (aux[ji + 1]) is unaffected;
            # traced, so a different parameter vector re-runs the same
            # compiled program instead of retracing
            aux.append(self.compiler.ctx.params)
        return tuple(aux), tuple(expands), deferred

    def _build_for(self, build_node: P.PlanNode, keys: Tuple[str, ...],
                   for_join: bool):
        return build_lookup(self.compiler, build_node, keys, for_join)

    def shape_probe(self, aux, expands: Tuple[int, ...], leaf_cap: int):
        """Abstract output of `make` for one chunk: what callers read key
        dtypes, dictionaries and laziness from.  A trace, not a launch,
        and a cached entry like the chain's programs: the `ShapeProbe`
        comes from the process-wide program cache under their key, so
        only the first execution of a structure runs the Python body.
        `shapeProbeMisses` counts the executions on which it ran,
        `shapeProbeHits` those on which it did not."""
        prog = self.program
        return self.compiler.shared_entry(
            self.root, "chain_shape_probe",
            lambda: ShapeProbe(prog, expands, leaf_cap),
            extra=prog.signature(expands, leaf_cap))(aux)

    def make(self, pos, valid, aux, expands: Tuple[int, ...],
             leaf_cap: int, with_counts: bool = False):
        """`ChainProgram.make`: one scan chunk through the chain."""
        return self.program.make(pos, valid, aux, expands, leaf_cap,
                                 with_counts)


class ShapeProbe:
    """One chain structure's abstract outputs, by what `aux` looks like.

    The entry `FusedChain.shape_probe` keeps in the program cache: it
    closes over the `ChainProgram` and the host constants of the chain's
    signature and over nothing of a task, and takes `aux` as an ARGUMENT,
    so resident columns, build tables and bound parameters are avals, not
    constants.  A result is looked up by aux's treedef (column names,
    nullability, dictionaries, the store's plain / dict / rle choice) and
    its leaves' avals (dtypes, lengths): what the trace can depend on.
    Every asker gets the same Batch of shapes, to read and not to write.
    A body that raises stores nothing, so it raises on every execution;
    tasks that miss together each trace once and store the same thing."""

    MAX_RESULTS = 16

    def __init__(self, program: "ChainProgram", expands: Tuple[int, ...],
                 leaf_cap: int):
        def chain_shape_probe(p, v, aux):
            return program.make(p, v, aux, expands, leaf_cap)
        self._fn = chain_shape_probe
        self._results: Dict[tuple, Batch] = {}

    def __call__(self, aux) -> Batch:
        leaves, treedef = jax.tree_util.tree_flatten(aux)
        key = (treedef,) + tuple(jax.typeof(x) for x in leaves)
        out = self._results.get(key)
        stats = current_stats()
        if stats is not None:
            stats.add("shapeProbeMisses" if out is None else "shapeProbeHits",
                      1)
        if out is None:
            out = jax.eval_shape(self._fn, jnp.int64(0), jnp.int64(1), aux)
            if len(self._results) >= self.MAX_RESULTS:
                self._results.clear()   # an aux that never repeats
            self._results[key] = out
        return out


class ChainProgram:
    """The traceable half of a FusedChain: what `make` reads while JAX
    traces it, and nothing else.  Jitted programs close over THIS, not
    over the chain, because they outlive the task that built them in the
    process-wide program cache (serving/fragments.py): the chain holds
    the PlanCompiler and through it the TaskContext (memory context,
    runtime stats, exchange clients, dynamic filters); this holds plan
    expressions, the scan's pure kernel factory, the static dictionaries
    of the scanned columns, a stateless Lowering and two host constants.
    Every one of them is determined by (subtree, variable names, config)
    but `task_index`, which `signature` hands to the cache key when the
    chain assigns unique ids."""

    def __init__(self, steps: List[tuple], scan_meta: dict, lowering,
                 task_index: int, has_params: bool):
        self.steps = steps
        self.cap = scan_meta["cap"]
        self.table = str(scan_meta.get("table", ""))
        self.dicts = scan_meta["dicts"]
        self.lowering = lowering
        self.has_params = has_params
        self.task_index = task_index
        self.assigns_ids = any(s[0] == "uid" for s in steps)
        self._make_factory = scan_meta["make_factory"]
        self._leaf_make: Dict[int, Callable] = {self.cap: scan_meta["make"]}

    def signature(self, expands: Tuple[int, ...], leaf_cap: int) -> tuple:
        """The host constants a program over this chain bakes in beyond
        its subtree and config: join fanouts, the leaf capacity and,
        where a step assigns unique ids, the task's index."""
        return (expands, leaf_cap,
                self.task_index if self.assigns_ids else None)

    def dense_cut(self, expands: Tuple[int, ...]) -> Optional[int]:
        """The step a sparse chain is cut at when its rows are made dense
        (`fused_dense_stream`): its last INNER join of fanout 1 without an
        ON filter, whose build columns are then gathered for the rows
        that survive it and not for every scanned row; None where there
        is no such join."""
        cut, ji = None, 0
        if self.assigns_ids:    # ids are made from the scan position
            return None
        for i, step in enumerate(self.steps):
            if step[0] == "join":
                node = step[1]
                if expands[ji] == 1 and node.join_type == P.INNER \
                        and node.filter is None:
                    cut = i
            if step[0] in ("join", "semi"):
                ji += 1
        return cut

    def make(self, pos, valid, aux, expands: Tuple[int, ...],
             leaf_cap: int, with_counts: bool = False,
             cut: Optional[int] = None, found=None):
        """Apply the chain to one scan chunk.  With with_counts=True the
        return value is (Batch, int64[1+len(steps)]) where counts[0] is
        the scan's live rows and counts[i+1] the live rows after step i —
        the device-side OperatorStats row counters EXPLAIN ANALYZE reads
        (they ride the jitted program's outputs; no host syncs in-loop).
        With `cut` the chain stops after the lookup of the join at that
        step (`dense_cut`): the batch carries the matched build rows'
        indices as BUILD_ROW (-1: no match) and `finish` does the rest;
        `found`, the BUILD_ROW values an earlier pass over the same chunk
        came to, stands in for the lookup."""
        mk = self._leaf_make.get(leaf_cap)
        if mk is None:
            mk = self._leaf_make[leaf_cap] = self._make_factory(leaf_cap)
        # one named scope per operator type and table: a `profile=true`
        # capture maps the fused ops back to plan operators
        with jax.named_scope("scan:" + self.table):
            outs, live = mk(pos, valid, aux[0])
        dicts = self.dicts
        batch = Batch({n: Column(v, None, dicts.get(n))
                       for n, v in outs.items()}, live)
        counts = [jnp.sum(live)] if with_counts else None
        steps = self.steps if cut is None else self.steps[:cut + 1]
        return self._run(batch, aux, expands, steps, 0, counts, cut, pos,
                         found)

    def finish(self, batch: Batch, aux, expands: Tuple[int, ...],
               cut: int) -> Batch:
        """What `make(..., cut=cut)` left undone, for a batch of its rows
        (any rows: they carry their build row): the cut join's build
        columns, then the steps above it."""
        ji = sum(1 for s in self.steps[:cut] if s[0] in ("join", "semi"))
        node = self.steps[cut][1]
        cols = dict(batch.columns)
        bidx = jnp.maximum(cols.pop(BUILD_ROW).values, 0)
        batch = self._join_columns(Batch(cols, batch.mask), node,
                                   aux[ji + 1], bidx)
        return self._run(batch, aux, expands, self.steps[cut + 1:],
                         ji + 1, None, None, None)

    def _run(self, batch: Batch, aux, expands, steps, ji: int, counts,
             cut: Optional[int], pos, found=None):
        """`steps` over `batch`; `ji` is the join/semi ordinal of the
        first of them (aux[0] is the scan cache), `pos` the scan position
        of the batch's first row."""
        with_counts = counts is not None
        low = self.lowering
        params = aux[-1] if self.has_params else None

        def _pb(b):
            # bound-parameter vector rides along for expression lowering
            # (Batch.params is not a pytree child, so every derived Batch
            # above dropped it)
            return b.with_params(params) if self.has_params else b
        for si, step in enumerate(steps):
            kind = step[0]
            with jax.named_scope(kind):
                if kind == "filter":
                    batch = ops.apply_filter(batch,
                                             low.eval(step[1], _pb(batch)))
                elif kind == "project":
                    pb = _pb(batch)
                    batch = Batch({v.name: low.eval(e, pb)
                                   for v, e in step[1]}, batch.mask)
                elif kind == "rename":
                    batch = Batch({o: batch.columns[i] for o, i in step[1]},
                                  batch.mask)
                elif kind == "join":
                    if cut is not None and si == cut:
                        if found is None:
                            hit, bidx = self._lookup(batch, step[1],
                                                     aux[ji + 1])
                            found = jnp.where(batch.mask & hit,
                                              bidx.astype(jnp.int32), -1)
                        batch = Batch(
                            {**batch.columns, BUILD_ROW: Column(found)},
                            batch.mask & (found >= 0))
                    elif expands[ji] == 1:
                        batch = self._apply_join(batch, step[1], aux[ji + 1],
                                                 low)
                    else:
                        batch = self._apply_join_expand(
                            batch, step[1], aux[ji + 1], expands[ji], low)
                    ji += 1
                elif kind == "uid":
                    # position-keyed unique ids: chunk [pos, pos+leaf_cap)
                    # owns id range [pos*K, (pos+leaf_cap)*K) where K is the
                    # join expansion applied so far — disjoint across chunks
                    # and splits, deterministic per (chain, splits), so a
                    # deep-copied decorrelated subtree replays identical ids
                    # (same contract as the streaming operator,
                    # _compile_AssignUniqueIdNode)
                    node = step[1]
                    kprod = 1
                    for j in range(ji):
                        kprod *= expands[j]
                    cap_here = batch.mask.shape[0]
                    leaf_c = cap_here // kprod
                    base = self.task_index << 40
                    # id keyed by (global leaf row, expansion branch): the
                    # join-expand layout is slot = j*C + i, so slot s maps to
                    # leaf row s % leaf_c and branch s // leaf_c — unique even
                    # when a truncated chunk's live rows land in high branches
                    s = jnp.arange(cap_here, dtype=jnp.int64)
                    ids = (base
                           + (jnp.asarray(pos, dtype=jnp.int64) + s % leaf_c)
                           * kprod + s // leaf_c)
                    batch = batch.with_columns(
                        {node.id_variable.name: Column(ids)})
                elif kind == "semi":
                    node = step[1]
                    key = node.source_join_variable.name
                    tbl, bhn = aux[ji + 1]
                    hit, _ = (probe_direct(batch, tbl, key)
                              if isinstance(tbl, DirectTable)
                              else probe_unique(batch, tbl, (key,)))
                    # three-valued marker: NULL probe key, or miss against a
                    # build side that contained NULL (reference
                    # HashSemiJoinOperator semantics)
                    nulls = ~hit & bhn
                    pn = batch.columns[key].nulls
                    if pn is not None:
                        nulls = nulls | pn
                    batch = batch.with_columns(
                        {node.semi_join_output.name: Column(hit, nulls)})
                    ji += 1
            if with_counts:
                counts.append(jnp.sum(batch.mask))
        if with_counts:
            return batch, jnp.stack(counts).astype(jnp.int64)
        return batch

    @staticmethod
    def _lookup(batch: Batch, node: P.JoinNode, tbl):
        """(hit, build row index) of a fanout-1 probe."""
        probe_keys = tuple(l.name for l, _r in node.criteria)
        if isinstance(tbl, DirectTable):
            return probe_direct(batch, tbl, probe_keys[0])
        return probe_unique(batch, tbl, probe_keys)

    @staticmethod
    def _join_columns(batch: Batch, node: P.JoinNode, tbl, bidx) -> Batch:
        """`batch` with the join's build columns, gathered at `bidx`."""
        build_names = {v.name for v in node.right.output_variables}
        out_names = [v.name for v in node.outputs]
        cols = dict(batch.columns)
        gcols = _join_build_cols(node, out_names, build_names)
        gathered = ops._packed_gather([tbl.columns[n] for n in gcols],
                                      bidx)
        for n in gcols:
            cols[n] = gathered[id(tbl.columns[n])]
        return Batch(cols, batch.mask)

    def _apply_join(self, batch: Batch, node: P.JoinNode, tbl, low) -> Batch:
        hit, bidx = self._lookup(batch, node, tbl)
        build_names = {v.name for v in node.right.output_variables}
        out_names = [v.name for v in node.outputs]
        pairs = self._join_columns(batch, node, tbl, bidx)
        cols = dict(pairs.columns)
        matched = hit
        if node.filter is not None:
            pred = low.eval(node.filter, pairs)
            keep = pred.values.astype(bool)
            if pred.nulls is not None:
                keep = keep & ~pred.nulls
            matched = matched & keep
        if node.join_type == P.INNER:
            return Batch(cols, batch.mask & matched)
        # LEFT: keep every probe row; null-extend build columns on misses
        miss = ~matched
        for n in _join_build_cols(node, out_names, build_names):
            c = cols[n]
            cols[n] = Column(c.values, c.null_mask() | miss,
                             c.dictionary, c.lazy)
        return Batch(cols, batch.mask)

    def _apply_join_expand(self, batch: Batch, node: P.JoinNode,
                           tbl: ops.BuildTable, k: int, low) -> Batch:
        """Fanout-k join: each probe row expands into k candidate build
        slots (k = pow2-rounded max key run in the build).  Output capacity
        = k * input capacity; flat index j*C + i is (probe row i, match j)."""
        C = batch.capacity
        probe_keys = tuple(l.name for l, _r in node.criteria)
        pcols = [batch.columns[kk] for kk in probe_keys]
        kh = ops._orderable_hash(ops.hash_columns(pcols))
        nb = tbl.perm.shape[0]
        lo = jnp.clip(jnp.searchsorted(tbl.keyhash_sorted, kh, side="left",
                                       method="scan_unrolled")
                      .astype(jnp.int32), 0, nb - 1)
        hit = tbl.keyhash_sorted[lo] == kh
        for c in pcols:
            if c.nulls is not None:
                hit = hit & ~c.nulls
        cnt = jnp.where(hit & batch.mask, tbl.run_len[lo], 0)      # (C,)
        j = jnp.arange(k, dtype=jnp.int32)[:, None]                # (k,1)
        sub = j < cnt[None, :]                                     # (k,C)
        bpos = jnp.clip(lo[None, :] + j, 0, nb - 1)
        bidx = jnp.where(sub, tbl.perm[bpos], 0).reshape(k * C)

        build_names = {v.name for v in node.right.output_variables}
        out_names = [v.name for v in node.outputs]
        cols: Dict[str, Column] = {}
        for n, c in batch.columns.items():
            cols[n] = Column(jnp.tile(c.values, k),
                             None if c.nulls is None
                             else jnp.tile(c.nulls, k),
                             c.dictionary, c.lazy)
        gcols = _join_build_cols(node, out_names, build_names)
        gathered = ops._packed_gather([tbl.columns[n] for n in gcols],
                                      bidx)
        for n in gcols:
            cols[n] = gathered[id(tbl.columns[n])]
        pair_mask = (batch.mask[None, :] & sub).reshape(k * C)
        matched = pair_mask
        if node.filter is not None:
            pred = low.eval(node.filter, Batch(cols, pair_mask))
            keep = pred.values.astype(bool)
            if pred.nulls is not None:
                keep = keep & ~pred.nulls
            matched = matched & keep
        if node.join_type == P.INNER:
            return Batch(cols, matched)
        # LEFT: a probe row none of whose candidates survived emits one
        # null-extended row in its j==0 slot
        any_match = jnp.any(matched.reshape(k, C), axis=0)         # (C,)
        fill = jnp.where(jnp.arange(k, dtype=jnp.int32)[:, None] == 0,
                         (batch.mask & ~any_match)[None, :],
                         False).reshape(k * C)
        for n in _join_build_cols(node, out_names, build_names):
            c = cols[n]
            cols[n] = Column(c.values, c.null_mask() | fill,
                             c.dictionary, c.lazy)
        return Batch(cols, matched | fill)


def try_direct_table(batch: Batch, key: str,
                     allow_dup: bool) -> Optional[DirectTable]:
    """Direct-address table for a dense single integer key, or None when
    the key is non-integer / sparse / (for joins) duplicated.  Costs two
    small host fetches, once per build."""
    col = batch.columns[key]
    if col.values.dtype not in (jnp.int64, jnp.int32, jnp.int16):
        return None
    vmin, vmax, live = host_get(_key_stats(col.values, batch.mask),
                                "build_key_stats")
    span = int(vmax) - int(vmin) + 1
    if not (int(live) > 0 and span <= DIRECT_TABLE_MAX
            and span <= max(1024, DIRECT_TABLE_SPAN_RATIO * int(live))):
        return None
    size = 1 << (span - 1).bit_length()
    slots, dup = _direct_builder(size)(col.values, batch.mask,
                                       jnp.int64(int(vmin)))
    if not allow_dup and bool(host_get(dup, "build_dup_keys")):
        return None
    return DirectTable(slots, jnp.int64(int(vmin)), dict(batch.columns))


@dataclass
class JoinBuild:
    """One join's build side, ready to probe: what `PlanCompiler.
    shared_build` hands out and, where the plan allows, keeps in the
    process-wide cache (serving/builds.py).  It holds device arrays and
    host facts only -- nothing of the task that built it -- and whatever
    a later asker would otherwise launch or fetch again is remembered in
    `memo`, so a cached entry is probed with no launch and no sync."""
    # the subtree's whole output (None: it gave no batch), and the same
    # rows with NULL keys masked out: what the table indexes
    batch: Optional[Batch]
    keyed: Optional[Batch]
    # DirectTable | ops.BuildTable over `keyed` (None with `batch`)
    table: object
    # a live build row had a NULL key (fetched for semi builds only: the
    # marker's three-valued output; False for joins)
    had_null: bool
    # the subtree's output names when built: a structural twin renames
    names: Tuple[str, ...] = ()
    # facts fetched or launched on demand, once: "rows" (`joinBuildRows`),
    # "max_run" (the hash table's fanout), the dynamic filter's bounds
    memo: dict = field(default_factory=dict)
    # operator statistics the build recorded, by the subtree's pre-order
    # position (set by the door): replayed into a task that hits
    op_stats: tuple = ()

    @cached_property
    def nbytes(self) -> int:
        """Device bytes held, every array once (the table's columns are
        the batch's)."""
        seen, total = set(), 0
        for leaf in jax.tree_util.tree_leaves(
                (self.batch, self.keyed, self.table)):
            if id(leaf) not in seen:
                seen.add(id(leaf))
                total += int(getattr(leaf, "nbytes", 0))
        return total

    def rows(self) -> int:
        """Live build rows with non-NULL keys (`joinBuildRows`)."""
        if "rows" not in self.memo:
            from .pipeline import _jit_count_live
            self.memo["rows"] = 0 if self.keyed is None else int(host_get(
                _jit_count_live(self.keyed.mask), "join_build_rows"))
        return self.memo["rows"]

    def max_run(self) -> int:
        """Largest duplicate run of the hash-sorted table's keys."""
        if "max_run" not in self.memo:
            self.memo["max_run"] = int(host_get(_max_run(self.table),
                                                "build_max_run"))
        return self.memo["max_run"]

    def renamed(self, names: Tuple[str, ...]) -> "JoinBuild":
        """This build under a structural twin's output names (structural
        equality aligns the output order); `memo` is shared."""
        if self.batch is None or tuple(names) == self.names:
            return self
        to = dict(zip(self.names, names))

        def cols(columns):
            return {to[n]: c for n, c in columns.items() if n in to}
        return replace(
            self, names=tuple(names),
            batch=Batch(cols(self.batch.columns), self.batch.mask),
            keyed=Batch(cols(self.keyed.columns), self.keyed.mask),
            table=replace(self.table, columns=cols(self.table.columns)))


def finish_join_build(batch: Optional[Batch], keys: Tuple[str, ...],
                      for_join: bool) -> JoinBuild:
    """The lookup table over a materialised build side: a direct-address
    table where one integer key is dense (and, for a join, unique), else
    the hash-sorted table.  NULL keys never match and are dropped; a semi
    build (for_join=False) also learns whether it had one (joins skip the
    device round trip that costs)."""
    if batch is None:
        return JoinBuild(None, None, None, False)
    from .pipeline import _jits
    had_null = False if for_join else _build_has_null_key(batch, keys)
    keyed = _drop_null_keys(batch, keys)
    table = (try_direct_table(keyed, keys[0], allow_dup=not for_join)
             if len(keys) == 1 else None)
    if table is None:
        table = _jits()[1](keyed, tuple(keys))
    return JoinBuild(batch, keyed, table, had_null)


def join_build(compiler, node: P.PlanNode, keys: Tuple[str, ...],
               for_join: bool, dense: Optional[str] = None) -> JoinBuild:
    """The build side `node` of a join (or semi join) on `keys`, through
    the one door that remembers build sides (`PlanCompiler.shared_build`):
    materialised -- as one fused program where the subtree is a fusible
    chain, else by draining its stream (`dense`: through `dense_batches`
    under that key) -- and indexed on a miss, taken whole from the
    process-wide cache on a hit."""
    def build():
        batch = compiler._materialize_node(node, dense)
        if batch is not None and compiler.ctx.shared_jits is not None:
            # stage-shared tracing: sibling tasks' build sides differ by
            # a few rows, which would retrace every shared join program
            # per task -- normalize to a power-of-two bucket so the stage
            # converges on one build shape (costs one live-count sync)
            from .pipeline import _bucket_for, _jit_compact
            live = int(host_get(batch.mask.sum(), "join_build_live"))
            bucket = _bucket_for(live) or 1 << max(0, live - 1).bit_length()
            if bucket != batch.capacity:
                batch = _jit_compact(batch, bucket)
        return finish_join_build(batch, keys, for_join)
    return compiler.shared_build(node, keys, for_join, build)


def build_lookup(compiler, build_node: P.PlanNode, keys: Tuple[str, ...],
                 for_join: bool):
    """Returns (table, fanout, build_had_null_key) — fanout is the
    pow2-rounded max key multiplicity (1 = unique keys) — or None when
    fanout > MAX_EXPAND.  The null flag is computed only for semi builds
    (for_join=False); join builds report False unconditionally (they drop
    NULL keys either way)."""
    from .pipeline import _span
    # the build of a join fused into its probe's scan chain: the same
    # work under the same span as the unfused join's (`joinBuild`)
    with _span(compiler.ctx.runtime_stats, "joinBuild"):
        jb = join_build(compiler, build_node, keys, for_join)
        if jb.batch is None:
            jb = finish_join_build(_empty_build_batch(build_node), keys,
                                   for_join)
        if isinstance(jb.table, DirectTable) or not for_join:
            return jb.table, 1, jb.had_null
        kmax = jb.max_run()
        if kmax <= 1:
            return jb.table, 1, False
        if kmax > MAX_EXPAND:
            return None
        return jb.table, 1 << (kmax - 1).bit_length(), False


def assemble_chain(compiler, node: P.PlanNode) -> Optional[FusedChain]:
    """Walk a Filter/Project/Join/SemiJoin chain down to a device-generated
    TableScan.  Returns None when the plan shape is not fusible (the caller
    keeps the streaming path)."""
    steps: List[tuple] = []
    step_ids: List[str] = []
    nd = node
    while True:
        if isinstance(nd, P.FilterNode):
            steps.append(("filter", nd.predicate))
            step_ids.append(nd.id)
            nd = nd.source
        elif isinstance(nd, P.ProjectNode):
            steps.append(("project", list(nd.assignments.items())))
            step_ids.append(nd.id)
            nd = nd.source
        elif isinstance(nd, P.ExchangeNode) and not nd.inputs \
                and len(nd.exchange_sources) == 1:
            src = nd.exchange_sources[0]
            outer = [v.name for v in nd.partitioning_scheme.output_layout]
            inner = [v.name for v in src.output_variables]
            if outer != inner:
                steps.append(("rename", list(zip(outer, inner))))
                step_ids.append(nd.id)
            nd = src
        elif isinstance(nd, P.JoinNode) \
                and nd.join_type in (P.INNER, P.LEFT) and nd.criteria:
            steps.append(("join", nd))
            step_ids.append(nd.id)
            nd = nd.left
        elif isinstance(nd, P.SemiJoinNode):
            steps.append(("semi", nd))
            step_ids.append(nd.id)
            nd = nd.source
        elif isinstance(nd, P.AssignUniqueIdNode):
            # unique ids derive from the scan position (see make), so the
            # decorrelated EXISTS stacks (q21-class) stay in one program
            steps.append(("uid", nd))
            step_ids.append(nd.id)
            nd = nd.source
        elif isinstance(nd, P.TableScanNode):
            meta = getattr(compiler._compile(nd), "fused_scan", None)
            if meta is None:
                return None
            steps.reverse()
            step_ids.reverse()
            return FusedChain(compiler, steps, meta, step_ids, nd.id,
                              root=node)
        else:
            return None


def fused_materialize(compiler, node: P.PlanNode) -> Optional[Batch]:
    """Materialize a fusible chain's full output as ONE device batch via a
    single lax.map program over scan chunks — the zero-host-sync analog of
    draining a streaming subtree batch by batch.  Used for join build
    sides (what keeps one across executions is `join_build`'s door, not
    this) and sort/window inputs.  Returns None when the subtree is not a
    fusible chain (caller streams instead)."""
    if compiler.ctx.memory.limited:
        return None     # budgeted/limited runs keep the accounted
        # streaming path (a bare query.max-memory ceiling still needs
        # the reservations that enforce it)
    chain = assemble_chain(compiler, node)
    if chain is None or not chain.chunks:
        return None
    try:
        prep_res = chain.prep()
    except NotImplementedError:
        return None
    if prep_res is None:
        return None
    aux, expands, _deferred = prep_res
    leaf_cap = chain.leaf_cap(expands)
    chunks = chain.chunks_for(expands, meter=True)
    S = len(chunks)
    try:
        chain.shape_probe(aux, expands, leaf_cap)
    except NotImplementedError:
        return None
    pos_arr = jnp.asarray([c[0] for c in chunks], dtype=jnp.int64)
    cnt_arr = jnp.asarray([c[1] for c in chunks], dtype=jnp.int64)
    key = ("fmat", node.id, expands)
    run_all = compiler._jit_cache.get(key)
    if run_all is None:
        prog = chain.program

        def run_all(pos_arr, cnt_arr, aux):
            def step(pc):
                return prog.make(pc[0], pc[1], aux, expands, leaf_cap)
            stacked = jax.lax.map(step, (pos_arr, cnt_arr))
            return jax.tree_util.tree_map(
                lambda a: a.reshape((-1,) + a.shape[2:]), stacked)
        run_all = compiler._jit_cache[key] = compiler.shared_jit(
            node, "chain_materialize", run_all,
            extra=prog.signature(expands, leaf_cap))
    from .pipeline import _maybe_compact
    out = _maybe_compact(run_all(pos_arr, cnt_arr, aux))
    if compiler.ctx.stats is not None:
        probe = chain_counts_fn(chain, expands, leaf_cap,
                                compiler._jit_cache,
                                ("fmat_counts", node.id, expands))
        record_chain_stats(compiler.ctx.stats, chain,
                           probe(pos_arr, cnt_arr, aux), S)
    return out


def chain_counts_fn(chain: "FusedChain", expands: Tuple[int, ...],
                    leaf_cap: int, cache: dict, cache_key):
    """Cached jitted probe summing make()'s per-step row counters over
    every scan chunk — for executors whose main program cannot carry the
    counters in its loop state (sort-agg stacking, runtime-span)."""
    fn = cache.get(cache_key)
    if fn is None:
        prog = chain.program

        def fn(pos_arr, cnt_arr, aux):
            def body(i, acc):
                _b, c = prog.make(pos_arr[i], cnt_arr[i], aux, expands,
                                  leaf_cap, with_counts=True)
                return acc + c
            return jax.lax.fori_loop(
                0, pos_arr.shape[0], body,
                jnp.zeros(1 + len(prog.steps), dtype=jnp.int64))
        fn = cache[cache_key] = chain.compiler.shared_jit(
            chain.root, "chain_counts", fn,
            extra=prog.signature(expands, leaf_cap))
    return fn


def record_chain_stats(stats, chain: "FusedChain", counts, n_chunks: int,
                       wall_s: float = 0.0, skip_root: bool = False) -> None:
    """Fold the device-side chain row counters into the EXPLAIN ANALYZE
    stats map: one entry per chain plan node, marked fused.  The wall is
    the WHOLE fused program's — operators compiled into one XLA program
    share a single dispatch, so per-operator wall does not decompose.
    skip_root leaves the chain root's rows/wall to the consumer's
    _instrument wrapper (fused_stream yields through it)."""
    if stats is None or counts is None:
        return
    fold_chain_counts(stats, chain, host_get(counts, "chain_counts"),
                      n_chunks, wall_s, skip_root)


def fold_chain_counts(stats, chain: "FusedChain", counts, n_chunks: int,
                      wall_s: float = 0.0, skip_root: bool = False) -> None:
    """`record_chain_stats` for counters that are on the host already."""
    vals = [int(v) for v in counts]
    root = chain.node_ids[-1] if chain.node_ids else None
    for nid, rows in zip(chain.node_ids, vals):
        if nid is None:
            continue
        ent = stats.setdefault(
            nid, {"rows": 0, "wall_s": 0.0, "batches": 0})
        ent["fused"] = True
        if skip_root and nid == root:
            continue        # the consumer's _instrument wrapper owns it
        ent["rows"] += rows
        ent["batches"] += n_chunks
        ent["wall_s"] += wall_s


def _join_build_cols(node: P.JoinNode, out_names, build_names):
    """Build columns a join step must gather: join outputs plus any
    build-side columns the ON filter reads (pruning may have dropped the
    latter from the output list)."""
    needed = [n for n in out_names if n in build_names]
    if node.filter is not None:
        from ..spi.expr import free_variables
        for v in free_variables(node.filter):
            if v.name in build_names and v.name not in needed:
                needed.append(v.name)
    return needed


def fused_stream(compiler, node: P.PlanNode):
    """Stream a fusible chain's output chunk by chunk as device Batches —
    one dispatch per chunk, ZERO host syncs (the fanout-bounded probes
    need no overflow checks).  Used by the streaming Join/SemiJoin
    compilers so chains consumed by non-aggregation operators (window,
    AssignUniqueId, ...) avoid the per-batch overflow-fetch pattern.
    Returns a Batch iterator or None (caller keeps the classic path)."""
    if compiler.ctx.memory.limited:
        return None
    analyzing = compiler.ctx.stats is not None
    cfg = compiler.ctx.config
    rs = getattr(compiler.ctx, "runtime_stats", None)
    if not cfg.fuse_pipelines:
        if rs is not None:
            rs.add("fusionDeclinedDisabled", 1)
        return None
    if analyzing and cfg.analyze_unfused:
        # the knob retains the old per-operator streaming profile for
        # join/semi-join chains too, not just the aggregation door
        if rs is not None:
            rs.add("fusionDeclinedAnalyzeUnfused", 1)
        return None
    key = ("fstream", node.id)
    ent = compiler._jit_cache.get(key, False)
    if ent is None:          # negative-cached
        return None
    if ent is False:
        chain = assemble_chain(compiler, node)
        if chain is None or not chain.chunks:
            compiler._jit_cache[key] = None
            return None
        try:
            prep_res = chain.prep()
        except NotImplementedError:
            prep_res = None
        if prep_res is None:
            compiler._jit_cache[key] = None
            return None
        aux, expands, _deferred = prep_res
        leaf_cap = chain.leaf_cap(expands)
        chunks = chain.chunks_for(expands)
        try:
            chain.shape_probe(aux, expands, leaf_cap)
        except NotImplementedError:
            compiler._jit_cache[key] = None
            return None

        prog = chain.program

        def step(pos, valid, aux):
            # under EXPLAIN ANALYZE the per-step row counters ride the
            # same jitted program as extra outputs (zero host syncs)
            return prog.make(pos, valid, aux, expands, leaf_cap,
                             with_counts=analyzing)
        step = compiler.shared_jit(
            node, "chain_stream_step", step,
            extra=prog.signature(expands, leaf_cap) + (analyzing,))
        ent = (step, aux, chunks, chain, expands,
               compiler.ctx.params_fingerprint)
        compiler._jit_cache[key] = ent
    step, aux, chunks, chain, expands, ent_fp = ent

    # re-executions with different bound parameters: cached aux carries
    # the FIRST execution's parameter vector (and possibly stale build
    # tables / chunk lists) — refresh what depends on the params.  The
    # jitted step takes aux as a traced argument, so none of this retraces
    # unless a parameterized build's fanout changed.
    cur_fp = compiler.ctx.params_fingerprint
    if chain.build_params and cur_fp != ent_fp:
        try:
            prep_res = chain.prep()
        except NotImplementedError:
            prep_res = None
        if prep_res is None or prep_res[1] != expands:
            # build no longer fusible (or its fanout changed) under the
            # new constants: drop the entry and rebuild from scratch
            compiler._jit_cache.pop(key, None)
            return fused_stream(compiler, node)
        aux = prep_res[0]
        compiler._jit_cache[key] = (step, aux, chunks, chain, expands,
                                    cur_fp)
    if chain.has_params:
        aux = aux[:-1] + (compiler.ctx.params,)
    if chain.params_pushdown:
        chunks = chain.chunks_for(expands, meter=True)

    def gen():
        acc = None
        try:
            for pos, cnt in chunks:
                out = step(jnp.int64(pos), jnp.int64(cnt), aux)
                if analyzing:
                    out, c = out
                    acc = c if acc is None else acc + c
                yield out
        finally:
            if analyzing:
                record_chain_stats(compiler.ctx.stats, chain, acc,
                                   len(chunks), skip_root=True)
    return gen()


# a sparse chain's dense output is written by ONE program into a buffer
# of this many bytes at most; a larger one streams chunk by chunk
DENSE_STREAM_MAX_BYTES = 256 << 20
# under this many scan chunks the chunk-by-chunk stream is taken as it is:
# two more programs to compile for a handful of batches saved
DENSE_STREAM_MIN_CHUNKS = 8


def fused_dense_stream(compiler, node: P.PlanNode, chain=None, prep=None,
                       skip_root: bool = True):
    """A selective Filter/Project/Join chain over a resident scan as DENSE
    batches: what the chunk-by-chunk stream hands up as one nearly empty
    batch a scan chunk (a launch an operator a chunk, each paying for its
    whole-column arguments, and per-batch work in everything downstream)
    comes out of two programs a task.  The first runs the chain over
    every chunk and counts its live rows, a chunk (one host sync brings
    them back, with the per-step totals EXPLAIN ANALYZE reads); the
    second runs it again, moves each chunk's live rows to its front
    (`ops.compact_front`) and writes the chunk where the rows of the
    chunks before it end.  A chain with a fanout-1 join is cut at its
    last one (`ChainProgram.dense_cut`): both programs stop at that
    join's lookup, and the join's build columns and the steps above it
    are computed for the dense batches, a launch each, not for every
    scanned row.  Chosen by what the counts show: where a quarter of the
    scanned rows or more stay live the stream is dense already and this
    returns None (as it does for a chain it cannot assemble), and the
    caller streams as before.  `chain` and `prep`: the caller's own
    chain over `node` and its `prep()`, where it has them; `skip_root`:
    the caller's `_instrument` wrapper counts the root's rows itself."""
    cfg = compiler.ctx.config
    analyzing = compiler.ctx.stats is not None
    if compiler.ctx.memory.limited or not cfg.fuse_pipelines \
            or (analyzing and cfg.analyze_unfused):
        return None
    key = ("fdense", node.id)
    if compiler._jit_cache.get(key, False) is None:     # negative-cached
        return None
    if chain is None:
        chain = assemble_chain(compiler, node)
    if chain is None or not chain.chunks or any(
            s[0] not in ("filter", "project", "rename", "join", "semi")
            for s in chain.steps):
        compiler._jit_cache[key] = None
        return None
    if prep is None:
        try:
            prep = chain.prep()
        except NotImplementedError:
            prep = None
    if prep is None or any(k != 1 for k in prep[1]):
        # a build side the chain cannot hold, or one whose keys repeat
        compiler._jit_cache[key] = None
        return None
    aux, expands, _deferred = prep
    if chain.has_params:
        aux = aux[:-1] + (compiler.ctx.params,)
    leaf_cap = chain.leaf_cap(expands)
    chunks = chain.chunks_for(expands, meter=True)
    if len(chunks) < DENSE_STREAM_MIN_CHUNKS:
        return None
    prog = chain.program
    cut = prog.dense_cut(expands)
    if analyzing and cut is not None and any(
            s[0] not in ("project", "rename") for s in prog.steps[cut + 1:]):
        # operator statistics read every step's rows from the count pass,
        # which stops at the cut: only steps that keep every row may
        # follow it (they read the cut join's count)
        cut = None
    signature = prog.signature(expands, leaf_cap) + (cut,)
    pos_arr = jnp.asarray([c[0] for c in chunks], dtype=jnp.int64)
    cnt_arr = jnp.asarray([c[1] for c in chunks], dtype=jnp.int64)

    def count(pos_arr, cnt_arr, aux):
        def step(pc):
            with ops.lookup_paths() as paths:
                b, c = prog.make(pc[0], pc[1], aux, expands, leaf_cap,
                                 with_counts=True, cut=cut)
            # a cut chain's lookup is most of its work: what it found is
            # kept on the device, 4 bytes a row, and the write pass does
            # not look again
            found = b.columns[BUILD_ROW].values if cut is not None else ()
            # (the chunk has a lookup, every lookup of it read its blocks)
            blocked = jnp.all(jnp.stack(paths) == ops.LOOKUP_BLOCKED) \
                if paths else False
            lookups = jnp.asarray([len(paths) > 0, blocked], jnp.int32)
            return jnp.sum(b.mask, dtype=jnp.int32), c, lookups, found
        live, counts, lookups, found = jax.lax.map(step, (pos_arr, cnt_arr))
        return (live, jnp.sum(counts, axis=0),
                jnp.sum(lookups, axis=0)), found

    try:
        counted = compiler.shared_jit(
            node, "chain_dense_counts", count, extra=signature)(
            pos_arr, cnt_arr, aux)
    except NotImplementedError:     # an expression the chain cannot lower
        compiler._jit_cache[key] = None
        return None
    (live, totals, lookups), found = \
        host_get(counted[0], "chain_dense_counts"), counted[1]
    total = int(live.sum())
    # (8 value bytes + 1 null byte a column: _instrument's estimate; a cut
    # chain's rows have no more columns than the scan and one index)
    row_bytes = 9 * max(1, len(node.output_variables),
                        len(prog.dicts) + 1 if cut is not None else 0)
    # the buffer: whole batches of the chain's capacity, a power of two
    # of them (one compiled program a size class)
    n_out = -(-total // chain.cap)
    rows = chain.cap * (1 << max(0, n_out - 1).bit_length())
    if total * 4 >= sum(c[1] for c in chunks) \
            or rows * row_bytes > DENSE_STREAM_MAX_BYTES:
        return None
    if analyzing:
        totals = list(totals) + [totals[-1]] * (
            1 + len(prog.steps) - len(totals))
        fold_chain_counts(compiler.ctx.stats, chain, totals, len(chunks),
                          skip_root=skip_root)
    rs = compiler.ctx.runtime_stats
    if rs is not None:
        rs.add("denseStreamChunks", len(chunks))
        rs.add("denseStreamBatches", n_out)
        if lookups[0]:
            rs.add("chainLookupChunks", int(lookups[0]))
            rs.add("chainLookupBlockedChunks", int(lookups[1]))
    if total == 0:
        return iter(())

    def write(pos_arr, cnt_arr, offsets, aux, found):
        def body(i, out):
            # the chunk's live rows moved to its front (no scatter: 50-80
            # ns an index on the chip), the whole chunk written where the
            # rows before it end; the next one overwrites its dead tail
            b = ops.compact_front(prog.make(
                pos_arr[i], cnt_arr[i], aux, expands, leaf_cap, cut=cut,
                found=found[i] if cut is not None else None))
            return jax.tree_util.tree_map(
                lambda dst, src: jax.lax.dynamic_update_slice_in_dim(
                    dst, src, offsets[i], axis=0), out, b)
        # (a chunk traced for its shapes alone: its values are dead code;
        # one chunk of room behind the rows for the last chunk's tail)
        empty = jax.tree_util.tree_map(
            lambda a: jnp.zeros((rows + leaf_cap,) + a.shape[1:], a.dtype),
            prog.make(pos_arr[0], cnt_arr[0], aux, expands, leaf_cap,
                      cut=cut))
        return jax.lax.fori_loop(0, pos_arr.shape[0], body, empty)

    offsets = np.zeros(len(chunks), dtype=np.int32)
    np.cumsum(live[:-1], out=offsets[1:])
    dense = compiler.shared_jit(
        node, "chain_dense_write", write, extra=signature + (rows,))(
        pos_arr, cnt_arr, jnp.asarray(offsets), aux, found)
    from .pipeline import _jit_rows_at
    batches = (_jit_rows_at(dense, jnp.int32(i * chain.cap), chain.cap)
               for i in range(n_out))
    if cut is None:
        return batches
    finish = compiler.shared_jit(
        node, "chain_dense_finish",
        lambda b, aux: prog.finish(b, aux, expands, cut), extra=signature)
    return (finish(b, aux) for b in batches)


def _empty_build_batch(build_node: P.PlanNode) -> Batch:
    """8-row all-masked batch with the build schema (empty build side)."""
    from ..common.types import VarcharType, CharType
    from .lowering import _jnp_dtype
    cols = {}
    for v in build_node.output_variables:
        if isinstance(v.type, (VarcharType, CharType)):
            cols[v.name] = Column(jnp.zeros(8, dtype=jnp.int32), None, ("",))
        else:
            cols[v.name] = Column(jnp.zeros(8, dtype=_jnp_dtype(v.type)))
    return Batch(cols, jnp.zeros(8, dtype=bool))
