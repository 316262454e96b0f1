"""Distribution planning: exchange insertion + plan fragmentation.

The TPU analog of the reference's distribution passes and fragmenter
(presto-main-base/.../sql/planner/optimizations/AddExchanges.java:161,
PlanFragmenter.java:49, createSubPlans :73).  The single-task logical plan the
planner emits is rewritten so that:

- aggregations split into PARTIAL (runs where the data is) + a REMOTE
  repartition-by-group-keys exchange (or gather, for global aggs) + FINAL
  (the reference's PushPartialAggregationThroughExchange rule);
- joins pick a distribution: REPLICATED (broadcast the build side, the
  reference's join_distribution_type=BROADCAST) when the build side's
  estimated bytes fit join-max-broadcast-table-size and replicating moves
  no more bytes than partitioning (FragmenterConfig), else PARTITIONED
  (both sides repartitioned on the join keys, FIXED_HASH_DISTRIBUTION);
- sort/topN/limit split into partial (distributed) + final (after a gather);
- the root gets a GATHER exchange (the coordinator's result pump reads a
  SINGLE-distribution root stage, Query.java:116).

`fragment_plan` then cuts the plan at REMOTE exchanges into a SubPlan tree of
PlanFragments with RemoteSourceNode leaves, exactly where the reference's
coordinator would hand each fragment to a stage.

avg() is rewritten at the split (partial sum+count, final sums, then a
projection dividing them) so the engine only ever executes decomposable
aggregates — the reference does the same via its intermediate "avg state"
row type; a projection keeps the TPU pipeline in plain columns instead.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from ..common.types import BIGINT, DOUBLE, DecimalType, DoubleType, RealType, Type
from ..spi import plan as P
from ..spi.expr import (CallExpression, RowExpression,
                        VariableReferenceExpression)

Variable = VariableReferenceExpression


AUTOMATIC, BROADCAST, PARTITIONED = "AUTOMATIC", "BROADCAST", "PARTITIONED"


@dataclass
class FragmenterConfig:
    """Presto's own two join-distribution properties, with its documented
    defaults (`join-distribution-type`, `join-max-broadcast-table-size`;
    DetermineJoinDistributionType.java): under AUTOMATIC a side is
    replicated where its estimated BYTES fit the limit and sending it to
    every task moves no more bytes than partitioning both sides would;
    BROADCAST replicates every build side, PARTITIONED none."""
    join_distribution_type: str = AUTOMATIC
    join_max_broadcast_table_size: int = 100 << 20
    # tasks that would each receive a replicated side: the runner's task
    # count (the cost model's estimatedSourceDistributedTaskCount)
    n_tasks: int = 2

    def __post_init__(self):
        self.join_distribution_type = self.join_distribution_type.upper()
        if self.join_distribution_type not in (AUTOMATIC, BROADCAST,
                                               PARTITIONED):
            raise ValueError("join-distribution-type must be AUTOMATIC, "
                             "BROADCAST or PARTITIONED, not "
                             f"{self.join_distribution_type!r}")

    def replicates(self, side_bytes: Optional[float],
                   other_bytes: Optional[float]) -> bool:
        """Whether a join side of `side_bytes` is sent whole to every
        task, the other side (`other_bytes`) staying where it is."""
        if self.join_distribution_type != AUTOMATIC:
            return self.join_distribution_type == BROADCAST
        if side_bytes is None \
                or side_bytes > self.join_max_broadcast_table_size:
            return False
        return other_bytes is None \
            or side_bytes * self.n_tasks <= side_bytes + other_bytes


# ---------------------------------------------------------------------------
# cardinality estimation (the skeleton of the reference's StatsCalculator)
# ---------------------------------------------------------------------------

# connector id -> (TableHandle -> Optional[row count])
CONNECTOR_STATS: Dict[str, Callable[[P.TableHandle], Optional[float]]] = {}


def register_connector_stats(connector_id: str, fn) -> None:
    CONNECTOR_STATS[connector_id] = fn


def _connector_stats_fn(connector_id: str):
    if connector_id not in CONNECTOR_STATS \
            and connector_id in ("tpch", "tpcds"):
        # built-in connectors: load on demand so estimates don't silently
        # depend on unrelated import order
        from ..connectors import tpch, tpcds  # noqa: F401 (self-register)
    return CONNECTOR_STATS.get(connector_id)


def estimate_rows(node: P.PlanNode, calc=None) -> Optional[float]:
    """Output-cardinality estimate: the stats module's selectivity-aware
    estimator (sql/stats.py, the StatsCalculator analog) first, falling
    back to the original coarse heuristics when stats are unavailable.
    Pass a shared StatsCalculator (`calc`) when estimating many nodes of
    one plan — its memo makes the pass O(nodes) instead of O(nodes^2)."""
    from .stats import StatsCalculator
    calc = calc or StatsCalculator()
    est = calc.rows(node)
    if est is not None:
        return est
    return _estimate_rows_heuristic(node, calc)


def type_bytes(typ: Type) -> int:
    """Estimated bytes of one value of `typ` in a page: the fixed width,
    and for a string its declared length (32 where it has none)."""
    from ..common.types import CharType, VarcharType
    if isinstance(typ, (VarcharType, CharType)):
        length = getattr(typ, "length", None)
        return int(length) if length and length < (1 << 16) else 32
    if isinstance(typ, DecimalType):
        return 8 if typ.precision <= 18 else 16
    name = str(typ).lower()
    return {"boolean": 1, "tinyint": 1, "smallint": 2, "integer": 4,
            "date": 4, "real": 4}.get(name, 8)


def row_bytes(node: P.PlanNode) -> int:
    """Estimated bytes of one output row of `node`."""
    return max(1, sum(type_bytes(v.type) for v in node.output_variables))


def estimate_bytes(node: P.PlanNode, calc=None) -> Optional[float]:
    """Estimated bytes of `node`'s output, rows times row width: what
    Presto's cost model compares with join-max-broadcast-table-size."""
    rows = estimate_rows(node, calc)
    return None if rows is None else rows * row_bytes(node)


def _estimate_rows_heuristic(node: P.PlanNode, calc) -> Optional[float]:
    if isinstance(node, P.TableScanNode):
        fn = _connector_stats_fn(node.table.connector_id)
        return fn(node.table) if fn else None
    if isinstance(node, P.FilterNode):
        c = estimate_rows(node.source, calc)
        return None if c is None else c * 0.5
    if isinstance(node, (P.ProjectNode, P.OutputNode, P.SortNode,
                         P.MarkDistinctNode, P.AssignUniqueIdNode,
                         P.EnforceSingleRowNode, P.WindowNode)):
        return estimate_rows(node.sources[0], calc)
    if isinstance(node, (P.LimitNode, P.TopNNode, P.DistinctLimitNode)):
        c = estimate_rows(node.sources[0], calc)
        return node.count if c is None else min(float(node.count), c)
    if isinstance(node, P.AggregationNode):
        c = estimate_rows(node.source, calc)
        if not node.grouping_keys:
            return 1.0
        return None if c is None else max(1.0, c * 0.1)
    if isinstance(node, P.JoinNode):
        l, r = estimate_rows(node.left, calc), estimate_rows(node.right, calc)
        if l is None or r is None:
            return None
        return max(l, r)
    if isinstance(node, P.SemiJoinNode):
        return estimate_rows(node.source, calc)
    if isinstance(node, P.ValuesNode):
        return float(len(node.rows))
    if isinstance(node, (P.ExchangeNode, P.UnionNode)):
        ests = [estimate_rows(s, calc) for s in node.sources]
        if any(e is None for e in ests):
            return None
        return sum(ests)
    if isinstance(node, P.RemoteSourceNode):
        return None
    srcs = node.sources
    return estimate_rows(srcs[0], calc) if srcs else None


# ---------------------------------------------------------------------------
# exchange insertion
# ---------------------------------------------------------------------------

SINGLE = "single"          # all rows on one task
SOURCE = "source"          # split-partitioned leaf (scan-driven)
HASHED = "hashed"          # hash-partitioned on keys


@dataclass
class _Placed:
    node: P.PlanNode
    dist: str                       # SINGLE / SOURCE / HASHED
    hash_keys: Tuple[str, ...] = ()


class ExchangeInserter:
    def __init__(self, config: Optional[FragmenterConfig] = None):
        from .stats import StatsCalculator
        self.config = config or FragmenterConfig()
        self._counter = 0
        # shared memoized estimator for the whole pass (O(nodes))
        self._calc = StatsCalculator()

    # -- helpers ----------------------------------------------------------
    def _id(self, hint: str) -> str:
        self._counter += 1
        return f"x_{hint}_{self._counter}"

    def _var(self, hint: str, typ: Type) -> Variable:
        self._counter += 1
        return Variable(f"{hint}_x{self._counter}", typ)

    def _gather(self, child: P.PlanNode) -> P.PlanNode:
        layout = list(child.output_variables)
        return P.ExchangeNode(
            self._id("gather"), P.GATHER, P.REMOTE,
            P.PartitioningScheme(P.SINGLE_DISTRIBUTION, [], layout),
            [child], [layout])

    def _repartition(self, child: P.PlanNode, keys: List[Variable]) -> P.PlanNode:
        layout = list(child.output_variables)
        return P.ExchangeNode(
            self._id("repart"), P.REPARTITION, P.REMOTE,
            P.PartitioningScheme(P.FIXED_HASH_DISTRIBUTION, list(keys), layout),
            [child], [layout])

    def _broadcast(self, child: P.PlanNode) -> P.PlanNode:
        layout = list(child.output_variables)
        return P.ExchangeNode(
            self._id("bcast"), P.REPLICATE, P.REMOTE,
            P.PartitioningScheme(P.FIXED_BROADCAST_DISTRIBUTION, [], layout),
            [child], [layout])

    # -- entry ------------------------------------------------------------
    def rewrite(self, root: P.PlanNode) -> P.PlanNode:
        placed = self._visit(root)
        return placed.node

    # -- dispatch ---------------------------------------------------------
    def _visit(self, node: P.PlanNode) -> _Placed:
        m = getattr(self, "_visit_" + type(node).__name__, None)
        if m is not None:
            return m(node)
        # default: single-source passthrough keeps the child's distribution
        srcs = node.sources
        if len(srcs) == 1:
            child = self._visit(srcs[0])
            _set_source(node, child.node)
            return _Placed(node, child.dist, child.hash_keys)
        if not srcs:
            return _Placed(node, SINGLE)
        raise NotImplementedError(
            f"exchange insertion for {type(node).__name__}")

    # -- leaves -----------------------------------------------------------
    def _visit_TableScanNode(self, node: P.TableScanNode) -> _Placed:
        return _Placed(node, SOURCE)

    def _visit_ValuesNode(self, node: P.ValuesNode) -> _Placed:
        return _Placed(node, SINGLE)

    # -- structural -------------------------------------------------------
    def _visit_OutputNode(self, node: P.OutputNode) -> _Placed:
        child = self._visit(node.source)
        if child.dist != SINGLE:
            node.source = self._gather(child.node)
        else:
            node.source = child.node
        return _Placed(node, SINGLE)

    def _visit_AggregationNode(self, node: P.AggregationNode) -> _Placed:
        child = self._visit(node.source)
        node.source = child.node
        if child.dist == SINGLE:
            return _Placed(node, SINGLE)
        # distributed input: already partitioned on a subset of the grouping
        # keys -> grouping is partition-local, run SINGLE-step in place
        key_names = tuple(v.name for v in node.grouping_keys)
        if child.dist == HASHED and child.hash_keys and \
                set(child.hash_keys) <= set(key_names):
            return _Placed(node, HASHED, child.hash_keys)
        if any(a.distinct or a.mask for a in node.aggregations.values()):
            # non-decomposable: gather everything to one task
            node.source = self._gather(child.node)
            return _Placed(node, SINGLE)
        return self._split_aggregation(node, child)

    def _split_aggregation(self, node: P.AggregationNode,
                           child: _Placed) -> _Placed:
        """SINGLE agg -> PARTIAL + exchange + FINAL (+ avg projection)."""
        partial_aggs: Dict[Variable, P.Aggregation] = {}
        final_aggs: Dict[Variable, P.Aggregation] = {}
        # final output var -> expression over final agg outputs (avg division)
        post: Dict[Variable, RowExpression] = {}
        needs_post = False

        for v, agg in node.aggregations.items():
            fname = agg.call.display_name.lower().split(".")[-1]
            args = agg.call.arguments
            if fname == "avg":
                arg = args[0]
                sum_t = _sum_type(arg.type)
                psum = self._var(v.name + "_psum", sum_t)
                pcnt = self._var(v.name + "_pcnt", BIGINT)
                partial_aggs[psum] = P.Aggregation(
                    CallExpression("sum", sum_t, [arg]))
                partial_aggs[pcnt] = P.Aggregation(
                    CallExpression("count", BIGINT, [arg]))
                fsum = self._var(v.name + "_fsum", sum_t)
                fcnt = self._var(v.name + "_fcnt", BIGINT)
                final_aggs[fsum] = P.Aggregation(
                    CallExpression("sum", sum_t, [psum]))
                final_aggs[fcnt] = P.Aggregation(
                    CallExpression("sum", BIGINT, [pcnt]))
                post[v] = CallExpression("$operator$divide", v.type,
                                         [fsum, fcnt])
                needs_post = True
            elif fname in ("count",):
                pv = self._var(v.name + "_p", BIGINT)
                partial_aggs[pv] = agg
                final_aggs[v] = P.Aggregation(
                    CallExpression("sum", BIGINT, [pv]))
                post[v] = v
            elif fname in ("sum", "min", "max"):
                pv = self._var(v.name + "_p", v.type)
                partial_aggs[pv] = agg
                final_aggs[v] = P.Aggregation(
                    CallExpression(fname, v.type, [pv]))
                post[v] = v
            else:
                # unknown aggregate: bail out to single-node execution
                node.source = self._gather(child.node)
                return _Placed(node, SINGLE)

        keys = list(node.grouping_keys)
        partial = P.AggregationNode(node.id + "_partial", child.node,
                                    partial_aggs, keys, P.PARTIAL)
        if keys:
            ex = self._repartition(partial, keys)
            dist, hkeys = HASHED, tuple(v.name for v in keys)
        else:
            ex = self._gather(partial)
            dist, hkeys = SINGLE, ()
        final = P.AggregationNode(node.id, ex, final_aggs, keys, P.FINAL)
        out: P.PlanNode = final
        if needs_post:
            assignments: Dict[Variable, RowExpression] = {}
            for k in keys:
                assignments[k] = k
            for v in node.aggregations:
                assignments[v] = post[v]
            out = P.ProjectNode(node.id + "_avgdiv", final, assignments)
        return _Placed(out, dist, hkeys)

    def _visit_JoinNode(self, node: P.JoinNode) -> _Placed:
        left = self._visit(node.left)
        right = self._visit(node.right)
        node.left, node.right = left.node, right.node
        if left.dist == SINGLE and right.dist == SINGLE:
            return _Placed(node, SINGLE)

        lest = estimate_rows(node.left, self._calc)
        rest = estimate_rows(node.right, self._calc)
        # INNER joins may swap sides so the smaller relation is built --
        # unless exactly one side is the scan of a table on its own dense
        # primary key: that side builds a direct-address table (one
        # scatter to build, one gather a probe row, no per-batch sync:
        # exec/fused.py try_direct_table) where the other would be sorted
        # by hash and searched a probe row, so it builds whatever its size
        # (the optimizer's SwapJoinSides chose so already; this keeps it)
        from .stats import primary_key_sides
        pk_left, pk_right = primary_key_sides(self._calc, node)
        if node.join_type == P.INNER and lest is not None and rest is not None \
                and (pk_left if pk_left != pk_right else lest < rest):
            node.left, node.right = node.right, node.left
            node.criteria = [(r, l) for l, r in node.criteria]
            left, right = right, left
            lest, rest = rest, lest
            pk_left, pk_right = pk_right, pk_left

        # record the planner's build-side assumption so the scheduler can
        # compare it against observed rows at the stage boundary and flip
        # the exchange strategy (exec/adaptive.decide_exchange)
        node.planned_build_rows = int(rest) if rest is not None else None
        lbytes = estimate_bytes(node.left, self._calc)
        rbytes = estimate_bytes(node.right, self._calc)
        node.planned_build_bytes = int(rbytes) if rbytes is not None else None
        if node.join_type in (P.INNER, P.LEFT) \
                and self.config.replicates(rbytes, lbytes):
            node.distribution = P.REPLICATED
            if right.dist != SINGLE or left.dist != SINGLE:
                node.right = self._broadcast(node.right)
            return _Placed(node, left.dist, left.hash_keys)
        if pk_right and node.join_type == P.INNER and right.dist == SOURCE \
                and self.config.replicates(lbytes, rbytes):
            # the small side is the PROBE: it is broadcast, every task
            # builds over its own splits of the key's table and emits
            # the matches that fall there (INNER only: a probe row that
            # finds no match in one task may find it in another)
            node.distribution = P.REPLICATED
            node.left = self._broadcast(node.left)
            return _Placed(node, right.dist, right.hash_keys)

        node.distribution = P.PARTITIONED
        lkeys = [l for l, _ in node.criteria]
        rkeys = [r for _, r in node.criteria]
        lnames = tuple(v.name for v in lkeys)
        rnames = tuple(v.name for v in rkeys)
        if not (left.dist == HASHED and left.hash_keys == lnames):
            node.left = self._repartition(node.left, lkeys)
        if not (right.dist == HASHED and right.hash_keys == rnames):
            node.right = self._repartition(node.right, rkeys)
        return _Placed(node, HASHED, lnames)

    def _visit_SemiJoinNode(self, node: P.SemiJoinNode) -> _Placed:
        src = self._visit(node.source)
        filt = self._visit(node.filtering_source)
        node.source, node.filtering_source = src.node, filt.node
        if src.dist == SINGLE and filt.dist == SINGLE:
            return _Placed(node, SINGLE)
        if self.config.replicates(
                estimate_bytes(node.filtering_source, self._calc),
                estimate_bytes(node.source, self._calc)):
            if filt.dist != SINGLE or src.dist != SINGLE:
                node.filtering_source = self._broadcast(node.filtering_source)
            return _Placed(node, src.dist, src.hash_keys)
        skey, fkey = node.source_join_variable, node.filtering_source_join_variable
        if not (src.dist == HASHED and src.hash_keys == (skey.name,)):
            node.source = self._repartition(node.source, [skey])
        if not (filt.dist == HASHED and filt.hash_keys == (fkey.name,)):
            node.filtering_source = self._repartition(
                node.filtering_source, [fkey])
        return _Placed(node, HASHED, (skey.name,))

    def _visit_SortNode(self, node: P.SortNode) -> _Placed:
        child = self._visit(node.source)
        if child.dist == SINGLE:
            node.source = child.node
        else:
            node.source = self._gather(child.node)
        return _Placed(node, SINGLE)

    def _visit_TopNNode(self, node: P.TopNNode) -> _Placed:
        child = self._visit(node.source)
        if child.dist == SINGLE:
            node.source = child.node
            return _Placed(node, SINGLE)
        partial = P.TopNNode(node.id + "_partial", child.node, node.count,
                             node.ordering_scheme, P.PARTIAL)
        node.source = self._gather(partial)
        node.step = P.FINAL
        return _Placed(node, SINGLE)

    def _visit_LimitNode(self, node: P.LimitNode) -> _Placed:
        child = self._visit(node.source)
        if child.dist == SINGLE:
            node.source = child.node
            return _Placed(node, SINGLE)
        partial = P.LimitNode(node.id + "_partial", child.node, node.count,
                              P.PARTIAL)
        node.source = self._gather(partial)
        node.step = P.FINAL
        return _Placed(node, SINGLE)

    def _visit_DistinctLimitNode(self, node: P.DistinctLimitNode) -> _Placed:
        child = self._visit(node.source)
        if child.dist == SINGLE:
            node.source = child.node
            return _Placed(node, SINGLE)
        partial = P.DistinctLimitNode(node.id + "_partial", child.node,
                                      node.count, node.distinct_variables)
        node.source = self._gather(partial)
        return _Placed(node, SINGLE)

    def _visit_UnionNode(self, node: P.UnionNode) -> _Placed:
        """UNION ALL runs on one task; each distributed branch is gathered
        (the reference instead collapses union into the exchange — same
        wire shape, one stage per branch)."""
        new_inputs = []
        for s in node.inputs:
            child = self._visit(s)
            new_inputs.append(child.node if child.dist == SINGLE
                              else self._gather(child.node))
        node.inputs = new_inputs
        return _Placed(node, SINGLE)

    def _visit_WindowNode(self, node: P.WindowNode) -> _Placed:
        child = self._visit(node.source)
        if child.dist == SINGLE:
            node.source = child.node
            return _Placed(node, SINGLE)
        if node.partition_by:
            node.source = self._repartition(child.node,
                                            list(node.partition_by))
            return _Placed(node, HASHED,
                           tuple(v.name for v in node.partition_by))
        node.source = self._gather(child.node)
        return _Placed(node, SINGLE)

    def _visit_EnforceSingleRowNode(self, node) -> _Placed:
        child = self._visit(node.source)
        if child.dist == SINGLE:
            node.source = child.node
        else:
            node.source = self._gather(child.node)
        return _Placed(node, SINGLE)


def _set_source(node: P.PlanNode, new_source: P.PlanNode) -> None:
    if hasattr(node, "source"):
        node.source = new_source
    else:
        raise NotImplementedError(
            f"cannot replace source of {type(node).__name__}")


def _sum_type(input_type: Type) -> Type:
    if isinstance(input_type, (DoubleType, RealType)):
        return DOUBLE
    if isinstance(input_type, DecimalType):
        return DecimalType(38, input_type.scale)
    return BIGINT


# ---------------------------------------------------------------------------
# fragmentation
# ---------------------------------------------------------------------------

class Fragmenter:
    """Cuts a plan with REMOTE exchanges into a SubPlan tree
    (reference PlanFragmenter.createSubPlans :73)."""

    def __init__(self):
        self._next_id = 0

    def fragment(self, root: P.PlanNode) -> P.SubPlan:
        root_scheme = P.PartitioningScheme(
            P.SINGLE_DISTRIBUTION, [], list(root.output_variables))
        return self._make_fragment(root, root_scheme)

    def _make_fragment(self, root: P.PlanNode,
                       output_scheme: P.PartitioningScheme) -> P.SubPlan:
        fid = str(self._next_id)
        self._next_id += 1
        children: List[P.SubPlan] = []
        props = {"has_scan": False, "scan_ids": [], "consumed": []}
        new_root = self._rewrite(root, children, props)
        if props["has_scan"]:
            partitioning = P.SOURCE_DISTRIBUTION
        elif P.REPARTITION in props["consumed"]:
            partitioning = P.FIXED_HASH_DISTRIBUTION
        else:
            partitioning = P.SINGLE_DISTRIBUTION
        fragment = P.PlanFragment(fid, new_root, partitioning, output_scheme,
                                  props["scan_ids"])
        return P.SubPlan(fragment, children)

    def _rewrite(self, node: P.PlanNode, children: List[P.SubPlan],
                 props: dict) -> P.PlanNode:
        if isinstance(node, P.ExchangeNode) and node.scope == P.REMOTE:
            props["consumed"].append(node.exchange_type)
            ids = []
            for src in node.exchange_sources:
                sub = self._make_fragment(src, node.partitioning_scheme)
                children.append(sub)
                ids.append(sub.fragment.fragment_id)
            return P.RemoteSourceNode(
                node.id, ids, list(node.partitioning_scheme.output_layout))
        if isinstance(node, P.TableScanNode):
            props["has_scan"] = True
            props["scan_ids"].append(node.id)
            return node
        for attr in ("source", "left", "right", "filtering_source"):
            if hasattr(node, attr):
                setattr(node, attr,
                        self._rewrite(getattr(node, attr), children, props))
        if isinstance(node, P.ExchangeNode):  # LOCAL exchange
            node.exchange_sources = [
                self._rewrite(s, children, props)
                for s in node.exchange_sources]
        if isinstance(node, P.UnionNode):
            # branches carry their own REMOTE gathers (ExchangeInserter
            # _visit_UnionNode); skipping them left whole distributed
            # branches — scans included — inlined in the consuming
            # fragment (caught by the FRAGMENT_BOUNDARY checker)
            node.inputs = [self._rewrite(s, children, props)
                           for s in node.inputs]
        return node


def annotate_dynamic_filter_sources(subplan: P.SubPlan) -> P.SubPlan:
    """Stamp `PlanFragment.dynamic_filter_sources` (producer output column
    name -> dynamic filter id) on every child fragment whose output feeds
    the SOURCE side of an annotated join in its consumer fragment.

    The optimizer's `plan_dynamic_filters` keys `dynamic_filters` by the
    RECEIVING variable; the summarized domain comes from the opposite
    side (INNER: build/right, LEFT: probe/left, semi: filtering source).
    When fragmentation cut that side behind a RemoteSourceNode, the
    producing stage is where the key column's min/max/value-set summary
    must be built (exec/adaptive.summarize_key_column) — this pass tells
    each producer WHICH of its output columns feed filters, so the
    scheduler / worker tasks summarize them as pages stream out."""
    def source_sides(node) -> List[Tuple[P.PlanNode, str, str]]:
        """(source subtree, source variable name, filter id) triples.

        For INNER joins the receiving var may sit on EITHER side — the
        exchange inserter's build-side swap flips criteria after the
        optimizer annotated — and both directions are sound (neither
        side is preserved).  LEFT joins receive on the build (right)
        side only; semi joins on the probe source."""
        out: List[Tuple[P.PlanNode, str, str]] = []
        if isinstance(node, P.JoinNode) and node.dynamic_filters:
            for l, r in node.criteria:
                if l.name in node.dynamic_filters \
                        and node.join_type == P.INNER:
                    out.append((node.right, r.name,
                                node.dynamic_filters[l.name]))
                elif r.name in node.dynamic_filters:
                    out.append((node.left, l.name,
                                node.dynamic_filters[r.name]))
        elif isinstance(node, P.SemiJoinNode) \
                and getattr(node, "dynamic_filters", None):
            skey = node.source_join_variable.name
            if skey in node.dynamic_filters:
                out.append((node.filtering_source,
                            node.filtering_source_join_variable.name,
                            node.dynamic_filters[skey]))
        return out

    def side_remote(side) -> Optional[P.RemoteSourceNode]:
        """The RemoteSourceNode feeding a join side, if the fragment cut
        landed directly there (the common shape: repartition/broadcast
        exchanges become fragment boundaries)."""
        while isinstance(side, P.FilterNode):
            side = side.source
        return side if isinstance(side, P.RemoteSourceNode) else None

    def visit(sp: P.SubPlan) -> None:
        by_fid = {c.fragment.fragment_id: c for c in sp.children}
        for node in P.walk_plan(sp.fragment.root):
            for side, var_name, fid in source_sides(node):
                remote = side_remote(side)
                if remote is None:
                    continue
                out_names = [v.name for v in remote.outputs]
                if var_name not in out_names:
                    continue
                j = out_names.index(var_name)
                for cfid in remote.source_fragment_ids:
                    child = by_fid.get(cfid)
                    if child is None:
                        continue
                    layout = child.fragment.output_partitioning_scheme \
                        .output_layout
                    if j < len(layout):
                        child.fragment.dynamic_filter_sources[
                            layout[j].name] = fid
        for c in sp.children:
            visit(c)

    visit(subplan)
    return subplan


def plan_distributed(root: P.OutputNode,
                     config: Optional[FragmenterConfig] = None,
                     exec_config=None) -> P.SubPlan:
    """Full distribution pipeline: exchange insertion then fragmentation,
    then the final sanity pass (per-fragment tree checks + fragment
    boundary / partitioning / grouped-execution checks).  `exec_config`
    feeds the grouped-execution eligibility predicate; None uses the
    default ExecutionConfig."""
    rewritten = ExchangeInserter(config).rewrite(root)
    sub = Fragmenter().fragment(rewritten)
    annotate_dynamic_filter_sources(sub)
    from ..analysis import validate_subplan
    validate_subplan(sub, "post-fragment", exec_config=exec_config)
    return sub


def annotate_exchange_fabrics(subplan: P.SubPlan, exec_config=None,
                              mesh_size: int = 0,
                              batch_mode: bool = False) -> P.SubPlan:
    """Annotate every remote-exchange edge (each child fragment's output
    partitioning scheme) with its resolved fabric ("http" | "ici",
    parallel/fabric.py) for the given mesh.  The scheduler re-derives the
    same resolution when choosing task counts; annotating the plan makes
    the choice visible to EXPLAIN and checkable by the EXCHANGE_FABRIC
    validation pass.  A RemoteSourceNode reading several child fragments
    (union) must see ONE fabric across them — the device reader consumes
    all-device or nothing — so mixed resolutions demote to http."""
    from ..parallel.fabric import FABRIC_HTTP, FABRIC_ICI, resolve_fabric
    requested = getattr(exec_config, "exchange_fabric", None)

    def visit(sp: P.SubPlan) -> None:
        frag = sp.fragment
        by_fid = {c.fragment.fragment_id: c for c in sp.children}
        for node in P.walk_plan(frag.root):
            if not isinstance(node, P.RemoteSourceNode):
                continue
            resolved = []
            for fid in node.source_fragment_ids:
                child = by_fid.get(fid)
                if child is None:
                    continue
                scheme = child.fragment.output_partitioning_scheme
                fabric, _why = resolve_fabric(
                    scheme.fabric or requested, handle=scheme.handle,
                    producer_partitioning=child.fragment.partitioning,
                    consumer_partitioning=frag.partitioning,
                    mesh_size=mesh_size, batch_mode=batch_mode)
                resolved.append((scheme, fabric))
            mixed = len({f for _, f in resolved}) > 1
            for scheme, fabric in resolved:
                scheme.fabric = FABRIC_HTTP if mixed else fabric
        for c in sp.children:
            visit(c)

    visit(subplan)
    return subplan
