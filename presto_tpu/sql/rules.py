"""Iterative rule-based plan optimizer.

The skeleton of the reference's IterativeOptimizer
(presto-main-base/.../sql/planner/iterative/IterativeOptimizer.java:62 +
the presto-matching pattern DSL, Match.java:22), compressed for this
engine: a rule declares the node class it matches and a pure `apply`
returning a replacement subtree (or None for no match); the driver
rewrites bottom-up to a fixpoint under an exploration budget, recording
per-rule hit counts that EXPLAIN surfaces (the reference's
optimizerInformation).

Rules are ported from the reference's iterative rule set
(presto-main-base/.../planner/iterative/rule/): filter/limit/projection
algebra plus the cost-based join-side choice.  Whole-plan passes that
need global context (column pruning, dynamic filters) stay in
optimizer.py, mirroring the reference's PlanOptimizer/IterativeOptimizer
split (PlanOptimizers.java:209).

Node identity: rewrites keep the REPLACED node's id, so decorrelated
deep-copied subtrees (which share ids) rewrite identically in every copy
and the pipeline compiler's per-id memo stays coherent.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple, Type

from ..spi import plan as P
from ..spi.expr import (CallExpression, ConstantExpression, RowExpression,
                        SpecialFormExpression, VariableReferenceExpression,
                        and_, free_variables)

EXPLORATION_BUDGET = 10_000     # total rule firings per plan


# ---------------------------------------------------------------------------
# expression utilities
# ---------------------------------------------------------------------------

def substitute(expr: RowExpression,
               mapping: Dict[str, RowExpression]) -> RowExpression:
    """Replace variable references by name (pure; shared subtrees reused
    when nothing changes underneath)."""
    if isinstance(expr, VariableReferenceExpression):
        return mapping.get(expr.name, expr)
    if isinstance(expr, CallExpression):
        args = [substitute(a, mapping) for a in expr.arguments]
        if all(a is b for a, b in zip(args, expr.arguments)):
            return expr
        return CallExpression(expr.display_name, expr.type, args)
    if isinstance(expr, SpecialFormExpression):
        args = [substitute(a, mapping) for a in expr.arguments]
        if all(a is b for a, b in zip(args, expr.arguments)):
            return expr
        return SpecialFormExpression(expr.form, expr.type, args)
    return expr


def _empty_values(node: P.PlanNode) -> P.ValuesNode:
    return P.ValuesNode(node.id, list(node.output_variables), [])


# ---------------------------------------------------------------------------
# the rule protocol + driver
# ---------------------------------------------------------------------------

class Rule:
    """One rewrite: `node_class` is the match pattern root (reference
    Pattern.typeOf), `apply` returns the replacement or None."""
    name: str = "rule"
    node_class: Tuple[Type, ...] = ()

    def apply(self, node: P.PlanNode,
              ctx: "RuleContext") -> Optional[P.PlanNode]:
        raise NotImplementedError


class RuleContext:
    def __init__(self):
        from .stats import StatsCalculator
        self.stats = StatsCalculator()


_CHILD_ATTRS = ("source", "left", "right", "filtering_source")
_CHILD_LIST_ATTRS = ("inputs", "exchange_sources")


def _set_child(parent: P.PlanNode, old: P.PlanNode,
               new: P.PlanNode) -> bool:
    for attr in _CHILD_ATTRS:
        if getattr(parent, attr, None) is old:
            setattr(parent, attr, new)
            return True
    for attr in _CHILD_LIST_ATTRS:
        lst = getattr(parent, attr, None)
        if isinstance(lst, list):
            for i, x in enumerate(lst):
                if x is old:
                    lst[i] = new
                    return True
    return False


class IterativeOptimizer:
    def __init__(self, rules: List[Rule]):
        self._by_class: Dict[type, List[Rule]] = {}
        self.rules = rules

    def _rules_for(self, node: P.PlanNode) -> List[Rule]:
        cls = type(node)
        cached = self._by_class.get(cls)
        if cached is None:
            cached = [r for r in self.rules
                      if isinstance(node, r.node_class)]
            self._by_class[cls] = cached
        return cached

    def run(self, root: P.PlanNode,
            stats: Optional[Dict[str, int]] = None) -> P.PlanNode:
        ctx = RuleContext()
        budget = [EXPLORATION_BUDGET]
        stats = stats if stats is not None else {}
        # plan_validation=strict: validate the replacement subtree after
        # every firing so a violation is attributed to the rule that
        # introduced it (the whole tree is mid-rewrite bottom-up, so only
        # the subtree is consistent here; parent-level breakage is caught
        # by the post-optimize pass)
        from ..analysis import VALIDATION_STRICT, validation_mode
        strict = validation_mode() == VALIDATION_STRICT

        def explore(node: P.PlanNode) -> P.PlanNode:
            for s in list(node.sources):
                ns = explore(s)
                if ns is not s:
                    _set_child(node, s, ns)
            progress = True
            while progress and budget[0] > 0:
                progress = False
                for rule in self._rules_for(node):
                    out = rule.apply(node, ctx)
                    if out is not None and out is not node:
                        budget[0] -= 1
                        stats[rule.name] = stats.get(rule.name, 0) + 1
                        if strict:
                            from ..analysis import validate_plan
                            validate_plan(out, f"rule:{rule.name}")
                        node = explore(out)
                        progress = True
                        break
            return node

        return explore(root)


# ---------------------------------------------------------------------------
# rules (reference analogs cited per rule)
# ---------------------------------------------------------------------------

class MergeFilters(Rule):
    """Filter(Filter(x)) -> Filter(x) with ANDed predicate
    (iterative/rule/MergeFilters.java)."""
    name = "MergeFilters"
    node_class = (P.FilterNode,)

    def apply(self, node, ctx):
        if not isinstance(node.source, P.FilterNode):
            return None
        inner = node.source
        return P.FilterNode(node.id, inner.source,
                            and_(inner.predicate, node.predicate))


class RemoveTrivialFilters(Rule):
    """Constant TRUE predicate -> drop the filter; FALSE/NULL -> empty
    values (iterative/rule/RemoveTrivialFilters.java)."""
    name = "RemoveTrivialFilters"
    node_class = (P.FilterNode,)

    def apply(self, node, ctx):
        p = node.predicate
        if isinstance(p, ConstantExpression):
            if p.value is True:
                return node.source
            if p.value in (False, None):
                return _empty_values(node)
        return None


class MergeLimits(Rule):
    """Limit(Limit(x)) -> Limit(x, min) (iterative/rule/MergeLimits.java)."""
    name = "MergeLimits"
    node_class = (P.LimitNode,)

    def apply(self, node, ctx):
        if not isinstance(node.source, P.LimitNode):
            return None
        return P.LimitNode(node.id, node.source.source,
                           min(node.count, node.source.count), node.step)


class EvaluateZeroLimit(Rule):
    """LIMIT 0 -> empty values (iterative/rule/EvaluateZeroLimit.java)."""
    name = "EvaluateZeroLimit"
    node_class = (P.LimitNode, P.TopNNode)

    def apply(self, node, ctx):
        if node.count == 0:
            return _empty_values(node)
        return None


class CreateTopN(Rule):
    """Limit(Sort(x)) -> TopN(x) (iterative/rule/CreateTopN.java — the
    O(n log n) full sort becomes a bounded heap; on this engine a bounded
    device sort per batch)."""
    name = "CreateTopN"
    node_class = (P.LimitNode,)

    def apply(self, node, ctx):
        if not isinstance(node.source, P.SortNode):
            return None
        sort = node.source
        return P.TopNNode(node.id, sort.source, node.count,
                          sort.ordering_scheme)


class PushLimitThroughProject(Rule):
    """Limit(Project(x)) -> Project(Limit(x))
    (iterative/rule/PushLimitThroughProject.java): the limit cuts rows
    before projection work."""
    name = "PushLimitThroughProject"
    node_class = (P.LimitNode,)

    def apply(self, node, ctx):
        if not isinstance(node.source, P.ProjectNode):
            return None
        proj = node.source
        return P.ProjectNode(proj.id,
                             P.LimitNode(node.id, proj.source, node.count,
                                         node.step),
                             proj.assignments)


class RemoveIdentityProjection(Rule):
    """Project that re-emits exactly its input variables -> source
    (iterative/rule/RemoveRedundantIdentityProjections.java)."""
    name = "RemoveIdentityProjection"
    node_class = (P.ProjectNode,)

    def apply(self, node, ctx):
        src_vars = node.source.output_variables
        if len(node.assignments) != len(src_vars):
            return None
        src_names = [v.name for v in src_vars]
        out_names = []
        for v, e in node.assignments.items():
            if not (isinstance(e, VariableReferenceExpression)
                    and e.name == v.name):
                return None
            out_names.append(v.name)
        if out_names != src_names:
            return None     # a reorder is not identity for positional users
        return node.source


class InlineProjections(Rule):
    """Project(Project(x)) -> one Project when the inner is pure
    renames/constants (iterative/rule/InlineProjections.java, restricted
    to substitutions that cannot duplicate computation)."""
    name = "InlineProjections"
    node_class = (P.ProjectNode,)

    def apply(self, node, ctx):
        if not isinstance(node.source, P.ProjectNode):
            return None
        inner = node.source
        if not all(isinstance(e, (VariableReferenceExpression,
                                  ConstantExpression))
                   for e in inner.assignments.values()):
            return None
        mapping = {v.name: e for v, e in inner.assignments.items()}
        merged = {v: substitute(e, mapping)
                  for v, e in node.assignments.items()}
        return P.ProjectNode(node.id, inner.source, merged)


class PushFilterThroughProject(Rule):
    """Filter(Project(x)) -> Project(Filter(x)) when the predicate only
    reads renamed/constant columns (PredicatePushDown through projections,
    PredicatePushDown.java) — unlocks scan-adjacent filtering and chain
    fusion."""
    name = "PushFilterThroughProject"
    node_class = (P.FilterNode,)

    def apply(self, node, ctx):
        if not isinstance(node.source, P.ProjectNode):
            return None
        proj = node.source
        mapping = {v.name: e for v, e in proj.assignments.items()}
        for v in free_variables(node.predicate):
            e = mapping.get(v.name)
            if not isinstance(e, (VariableReferenceExpression,
                                  ConstantExpression)):
                return None
        pred = substitute(node.predicate, mapping)
        return P.ProjectNode(proj.id,
                             P.FilterNode(node.id, proj.source, pred),
                             proj.assignments)


class SwapJoinSides(Rule):
    """Put the smaller estimated side on the build (right) side of an
    inner equi join (DetermineJoinDistributionType.java /
    ReorderJoins.java side choice; hysteresis avoids flip-flopping on
    close estimates) -- unless exactly one side is the scan of a table
    on its own dense primary key: that side builds whatever its size,
    as a direct-address table (one scatter to build, one gather a probe
    row, no sync a batch), where the other side would be sorted by hash
    and searched once a probe row."""
    name = "SwapJoinSides"
    node_class = (P.JoinNode,)
    RATIO = 1.25

    def apply(self, node, ctx):
        if node.join_type != P.INNER or not node.criteria:
            return None
        left = ctx.stats.rows(node.left)
        right = ctx.stats.rows(node.right)
        if left is None or right is None:
            return None
        from .stats import primary_key_sides
        pk_left, pk_right = primary_key_sides(ctx.stats, node)
        if not (pk_left if pk_left != pk_right
                else right > left * self.RATIO):
            return None
        return P.JoinNode(node.id, node.join_type, node.right, node.left,
                          [(r, l) for l, r in node.criteria],
                          node.outputs, node.filter, node.distribution,
                          dict(node.dynamic_filters))


class MergeLimitWithDistinct(Rule):
    """Limit(Aggregation[no aggregates, keys=outputs]) -> DistinctLimit
    (iterative/rule/MergeLimitWithDistinct.java)."""
    name = "MergeLimitWithDistinct"
    node_class = (P.LimitNode,)

    def apply(self, node, ctx):
        agg = node.source
        if not isinstance(agg, P.AggregationNode) or agg.aggregations:
            return None
        if not agg.grouping_keys or agg.step != P.SINGLE:
            return None
        return P.DistinctLimitNode(node.id, agg.source, node.count,
                                   list(agg.grouping_keys))


class MergeLimitWithTopN(Rule):
    """Limit(TopN(x)) -> TopN(x, min)
    (iterative/rule/MergeLimitWithTopN.java)."""
    name = "MergeLimitWithTopN"
    node_class = (P.LimitNode,)

    def apply(self, node, ctx):
        if not isinstance(node.source, P.TopNNode):
            return None
        t = node.source
        return P.TopNNode(node.id, t.source, min(node.count, t.count),
                          t.ordering_scheme, t.step)


DEFAULT_RULES: List[Rule] = [
    RemoveTrivialFilters(),      # before MergeFilters: don't AND-in TRUE
    MergeFilters(),
    EvaluateZeroLimit(),
    MergeLimits(),
    MergeLimitWithTopN(),
    CreateTopN(),
    PushLimitThroughProject(),
    RemoveIdentityProjection(),
    InlineProjections(),
    PushFilterThroughProject(),
    SwapJoinSides(),
    MergeLimitWithDistinct(),
]
