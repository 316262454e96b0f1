"""Whole-plan rewrites over the logical plan.

The analog of the reference's PlanOptimizers pass list
(PlanOptimizers.java:209), split like the reference's Optimizer: local
algebraic rewrites (filter/limit/projection merging, join-side choice)
run through the iterative rule driver in sql/rules.py; the passes here
need GLOBAL plan context (requirement union across decorrelated copies,
dynamic-filter id allocation) and mutate the plan in place.
"""
from __future__ import annotations

from typing import Dict, Set

from ..spi import plan as P
from ..spi.expr import free_variables


# ---------------------------------------------------------------------------
# unused-output pruning (reference PruneUnreferencedOutputsRule family in
# presto-main-base/.../planner/iterative/rule/): drop columns no ancestor
# reads.  Critical on TPU: a table scan that materializes host-generated
# string columns nobody reads both wastes transfer AND disqualifies the
# scan from whole-pipeline fusion (exec/fused.py requires device-generated
# scans).  Decorrelated plans contain deep-copied subtrees SHARING node
# ids; the pipeline compiler memoizes by id, so requirements are unioned
# per id first and every copy is rewritten identically.
# ---------------------------------------------------------------------------

def prune_unused_outputs(root: P.PlanNode) -> P.PlanNode:
    req: Dict[str, Set[str]] = {}

    def expr_vars(*exprs) -> Set[str]:
        out: Set[str] = set()
        for e in exprs:
            if e is not None:
                out.update(v.name for v in free_variables(e))
        return out

    def visit(node: P.PlanNode, needed: Set[str]) -> None:
        prev = req.get(node.id)
        if prev is not None and needed <= prev:
            return
        needed = (prev or set()) | needed
        req[node.id] = set(needed)
        t = type(node).__name__
        if t == "OutputNode":
            visit(node.source, set(v.name
                                   for v in node.source.output_variables))
        elif t == "ProjectNode":
            child: Set[str] = set()
            for v, e in node.assignments.items():
                if v.name in needed:
                    child |= expr_vars(e)
            if not child:
                # keep at least one input column for row-count semantics
                if node.assignments:
                    child |= expr_vars(next(iter(node.assignments.values())))
                if not child and node.source.output_variables:
                    child.add(node.source.output_variables[0].name)
            visit(node.source, child)
        elif t == "FilterNode":
            visit(node.source, needed | expr_vars(node.predicate))
        elif t == "TableScanNode":
            pass
        elif t == "AggregationNode":
            child = {v.name for v in node.grouping_keys}
            for agg in node.aggregations.values():
                child |= expr_vars(agg.call)
                if agg.mask is not None:
                    child |= expr_vars(agg.mask)
            visit(node.source, child)
        elif t == "JoinNode":
            child = set(needed)
            for l, r in node.criteria:
                child.add(l.name)
                child.add(r.name)
            child |= expr_vars(node.filter)
            visit(node.left, child)
            visit(node.right, child)
        elif t == "SemiJoinNode":
            visit(node.source, (needed - {node.semi_join_output.name})
                  | {node.source_join_variable.name})
            visit(node.filtering_source,
                  {node.filtering_source_join_variable.name})
        elif t in ("SortNode", "TopNNode"):
            keys = {v.name for v, _o in node.ordering_scheme.orderings}
            visit(node.source, needed | keys)
        elif t == "WindowNode":
            child = needed & {v.name for v in node.source.output_variables}
            child |= {v.name for v in node.partition_by}
            if node.ordering_scheme:
                child |= {v.name for v, _o in
                          node.ordering_scheme.orderings}
            for wf in node.window_functions.values():
                child |= expr_vars(wf.call)
            visit(node.source, child)
        elif t == "DistinctLimitNode":
            visit(node.source, {v.name for v in node.distinct_variables})
        elif t == "MarkDistinctNode":
            visit(node.source, (needed - {node.marker.name})
                  | {v.name for v in node.distinct_variables})
        elif t == "AssignUniqueIdNode":
            visit(node.source, needed - {node.id_variable.name})
        elif t in ("LimitNode", "EnforceSingleRowNode"):
            visit(node.source, needed)
        elif t == "UnionNode":
            # every source is projected to the union's output variables;
            # a row-count-only consumer still needs one column to exist
            # in both the union's outputs and its branch projections
            if not needed and node.outputs:
                needed = {node.outputs[0].name}
                req[node.id] = set(needed)
            for s in node.inputs:
                visit(s, set(needed))
        elif t == "ExchangeNode":
            if not node.inputs and len(node.exchange_sources) == 1:
                visit(node.exchange_sources[0], set(needed))
            else:
                for s in node.exchange_sources:
                    visit(s, {v.name for v in s.output_variables})
        else:
            # conservative: require everything below (Values, Unnest,
            # RemoteSource, TableWriter/Finish, unknown nodes)
            for s in node.sources:
                visit(s, {v.name for v in s.output_variables})

    visit(root, {v.name for v in root.output_variables})

    # rewrite pass: every node-id copy sees the same unioned requirement
    def rewrite(node: P.PlanNode) -> None:
        needed = req.get(node.id)
        t = type(node).__name__
        if needed is not None:
            if t == "TableScanNode":
                keep = [v for v in node.outputs if v.name in needed]
                if not keep and node.outputs:
                    # keep one (prefer non-string: stays device-generable)
                    keep = sorted(
                        node.outputs,
                        key=lambda v: type(v.type).__name__
                        in ("VarcharType", "CharType"))[:1]
                if len(keep) != len(node.outputs):
                    node.outputs = keep
                    node.assignments = {v: c for v, c
                                        in node.assignments.items()
                                        if v in keep}
            elif t == "ProjectNode":
                keep = {v: e for v, e in node.assignments.items()
                        if v.name in needed}
                if not keep and node.assignments:
                    v0 = next(iter(node.assignments))
                    keep = {v0: node.assignments[v0]}
                node.assignments = keep
            elif t == "JoinNode":
                keep = [v for v in node.outputs if v.name in needed]
                if not keep and node.outputs:
                    # keep one probe column for row-count semantics
                    left_names = {v.name for v in
                                  node.left.output_variables}
                    keep = ([v for v in node.outputs
                             if v.name in left_names]
                            or node.outputs)[:1]
                node.outputs = keep
            elif t == "UnionNode":
                # branch projections were pruned to `needed`; the union's
                # own output list must shrink with them or the union
                # compile demands columns no branch carries
                keep = [v for v in node.outputs if v.name in needed]
                if not keep and node.outputs:
                    keep = node.outputs[:1]
                node.outputs = keep
        for s in node.sources:
            rewrite(s)

    rewrite(root)
    return root


def plan_dynamic_filters(root: P.PlanNode) -> P.PlanNode:
    """Annotate joins with dynamic filters (reference
    DynamicFilterSourceOperator + LocalDynamicFilter planning).  Keys of
    `dynamic_filters` are the RECEIVING variables — the side whose rows
    the filter may drop — and a filter may only ever shrink a
    NON-PRESERVED side:

    - INNER: the probe (left) receives the build (right) key domain;
      applied intra-task before the probe step AND cross-stage as
      runtime scan pushdown (plan_runtime_filter_pushdown).
    - LEFT: the probe is preserved (unmatched rows survive
      null-extended), so it must NEVER be filtered — but the build side
      is not preserved: build rows no probe key can match produce
      nothing, so the probe key domain may prune BUILD scans.  RIGHT
      joins were normalized to LEFT-with-swapped-sides by the planner
      before this pass, so they take this path with the original probe
      side receiving.
    - FULL: both sides preserved; no filter is safe.
    - SemiJoinNode: the source receives the filtering-source domain,
      but ONLY when the membership marker is consumed as a bare
      positive filter conjunct — then a source row outside the domain
      would get marker NULL/false and be dropped by that filter anyway.
      Under negation (NOT IN) the marker's false/NULL rows are the ones
      that SURVIVE, so dropping them early would be wrong.
    """
    from ..spi.expr import VariableReferenceExpression
    from ..storage.pushdown import split_conjuncts

    positive_markers = set()
    for node in P.walk_plan(root):
        if isinstance(node, P.FilterNode):
            for c in split_conjuncts(node.predicate):
                if isinstance(c, VariableReferenceExpression):
                    positive_markers.add(c.name)

    n = 0
    for node in P.walk_plan(root):
        if isinstance(node, P.JoinNode) and node.criteria:
            if node.join_type == P.INNER:
                node.dynamic_filters = {
                    l.name: f"df_{n}_{i}"
                    for i, (l, _r) in enumerate(node.criteria)}
                n += 1
            elif node.join_type == P.LEFT:
                node.dynamic_filters = {
                    r.name: f"df_{n}_{i}"
                    for i, (_l, r) in enumerate(node.criteria)}
                n += 1
        elif isinstance(node, P.SemiJoinNode) \
                and node.semi_join_output.name in positive_markers:
            node.dynamic_filters = {
                node.source_join_variable.name: f"df_{n}_0"}
            n += 1
    return root


def _runtime_filter_pairs(node):
    """(receiving var name, source var name, fid, receiving subtree)
    tuples for one annotated node, honoring the direction convention
    documented on plan_dynamic_filters."""
    out = []
    if isinstance(node, P.JoinNode):
        for i, (l, r) in enumerate(node.criteria):
            if node.join_type == P.INNER and l.name in node.dynamic_filters:
                out.append((l.name, r.name,
                            node.dynamic_filters[l.name], node.left))
            elif node.join_type == P.LEFT \
                    and r.name in node.dynamic_filters:
                out.append((r.name, l.name,
                            node.dynamic_filters[r.name], node.right))
    elif isinstance(node, P.SemiJoinNode):
        sv = node.source_join_variable.name
        if sv in node.dynamic_filters:
            out.append((sv, node.filtering_source_join_variable.name,
                        node.dynamic_filters[sv], node.source))
    return out


def plan_runtime_filter_pushdown(root: P.PlanNode) -> P.PlanNode:
    """Push each dynamic filter's receiving key down to its table scans
    as RUNTIME pushdown (the cross-stage half of dynamic filtering,
    reference analog DynamicFilterService + TupleDomain pushdown).

    Each reachable scan gets a `runtime_filters` annotation plus
    ``["dyn", fid, min|max|set]`` marker entries in `pushdown`, resolved
    at prune time from the summary a completed filter-source stage
    published (exec/adaptive.py).  Unresolved markers keep every chunk,
    so annotation is always safe to plan; correctness only requires that
    every row dropped at the scan would have been dropped by the
    annotated join anyway.  That holds when the path from scan to join
    is strictly row-preserving-or-narrowing for the traced key — bare
    Project renames and Filters.  Anything else (aggregations, limits,
    sorts, unions) stops the descent, and a scan whose node id appears
    more than once in the plan (decorrelated shared subtree — the
    pipeline compiler memoizes by id) is never annotated: another
    consumer outside the join could observe the missing rows."""
    from collections import Counter
    from ..spi.expr import VariableReferenceExpression

    occurrences: Counter = Counter()

    def count(node):
        occurrences[node.id] += 1
        for s in node.sources:
            count(s)
    count(root)

    def trace(node, var_name, out):
        if isinstance(node, P.TableScanNode):
            if occurrences[node.id] != 1:
                return
            for v, col in node.assignments.items():
                if v.name == var_name:
                    out.append((node, col.name))
            return
        if isinstance(node, P.ProjectNode):
            e = next((e for v, e in node.assignments.items()
                      if v.name == var_name), None)
            if isinstance(e, VariableReferenceExpression):
                trace(node.source, e.name, out)
            return
        if isinstance(node, P.FilterNode):
            trace(node.source, var_name, out)
            return
        if isinstance(node, P.ExchangeNode):
            # inputs[i][j] feeds output_layout[j] from source i
            layout = node.partitioning_scheme.output_layout
            idx = next((j for j, v in enumerate(layout)
                        if v.name == var_name), None)
            if idx is None:
                return
            for i, src in enumerate(node.exchange_sources):
                row = node.inputs[i] if i < len(node.inputs) else None
                trace(src, row[idx].name if row else var_name, out)
            return
        # conservative stop: any other node may change which rows exist
        # (aggregation, limit) or carry the variable non-positionally

    for node in P.walk_plan(root):
        if not getattr(node, "dynamic_filters", None):
            continue
        for recv, _src, fid, subtree in _runtime_filter_pairs(node):
            source = (node.filtering_source if isinstance(node, P.SemiJoinNode)
                      else node.left if subtree is node.right
                      else node.right)
            if isinstance(source, P.TableScanNode) and not source.pushdown:
                # a whole table's key domain prunes nothing of the other
                # side, and a scan that expects a summary waits for it
                # (dynamic_filtering_wait_timeout_s a task)
                continue
            scans = []
            trace(subtree, recv, scans)
            for scan, col in scans:
                if any(e.get("id") == fid and e.get("column") == col
                       for e in scan.runtime_filters):
                    continue
                scan.runtime_filters.append({"id": fid, "column": col})
                scan.pushdown.extend((
                    {"column": col, "op": "gte", "value": ["dyn", fid, "min"]},
                    {"column": col, "op": "lte", "value": ["dyn", fid, "max"]},
                    {"column": col, "op": "eq", "value": ["dyn", fid, "set"]}))
    return root


def plan_scan_pushdown(root: P.PlanNode) -> P.PlanNode:
    """Record range/equality-shaped conjuncts of a filter sitting directly
    on a table scan as the scan's pushdown metadata (the reference analog
    is PickTableLayout/TupleDomain pushdown into the connector).

    The FilterNode is NOT removed: pushdown here is advisory, consumed by
    the resident-storage scan for zone-map chunk skipping
    (storage/pushdown.py), and the residual exact filter preserves
    semantics unconditionally.  Runs after the iterative rules so filter
    merging/pushdown has already parked each scan's conjunction directly
    above it."""
    from ..storage.pushdown import extract_pushdown
    for node in P.walk_plan(root):
        if not isinstance(node, P.FilterNode) \
                or not isinstance(node.source, P.TableScanNode):
            continue
        scan = node.source
        var_to_col = {v.name: c.name for v, c in scan.assignments.items()}
        scan.pushdown = extract_pushdown(node.predicate, var_to_col)
    return root


def hoist_join_filter_string_calls(root: P.PlanNode) -> P.PlanNode:
    """Rewrite substr/like calls inside JOIN ON-filters into columns
    projected below the join when their argument is an open-domain
    (late-materialized) scan column.  A join filter evaluates inside the
    jitted probe step where a lazy column holds row ids and host hoisting
    cannot run; a projection below the join takes the Filter/Project
    hoisting path instead (the reference's analog is PushdownSubfields +
    expression pushdown below the join)."""
    from ..connectors import catalog
    from ..exec.lowering import canonical_name
    from ..spi.expr import (CallExpression, SpecialFormExpression,
                            VariableReferenceExpression)

    # variable name -> (table, column) for open-domain scan outputs
    open_vars: Dict[str, tuple] = {}
    for n in P.walk_plan(root):
        if isinstance(n, P.TableScanNode):
            for v in n.outputs:
                ch = n.assignments.get(v)
                if ch is not None and \
                        (n.table.table_name, ch.name) in catalog.OPEN_DOMAIN:
                    open_vars[v.name] = (n.table.table_name, ch.name)

    if not open_vars:
        return root
    counter = [0]

    def rewrite_filter(e, side_injections):
        if isinstance(e, CallExpression):
            name = canonical_name(e.display_name)
            if name in ("like", "substr") and e.arguments and isinstance(
                    e.arguments[0], VariableReferenceExpression) \
                    and e.arguments[0].name in open_vars:
                counter[0] += 1
                v = VariableReferenceExpression(
                    f"__jfhoist_{counter[0]}", e.type)
                side_injections.setdefault(
                    e.arguments[0].name, {})[v] = e
                return v
            return CallExpression(
                e.display_name, e.type,
                [rewrite_filter(a, side_injections) for a in e.arguments])
        if isinstance(e, SpecialFormExpression):
            return SpecialFormExpression(
                e.form, e.type,
                [rewrite_filter(a, side_injections) for a in e.arguments])
        return e

    def visit(node: P.PlanNode) -> None:
        for s in node.sources:
            visit(s)
        if not isinstance(node, P.JoinNode) or node.filter is None:
            return
        injections: Dict[str, Dict] = {}
        new_filter = rewrite_filter(node.filter, injections)
        if not injections:
            return
        for side_attr in ("left", "right"):
            side = getattr(node, side_attr)
            names = {v.name for v in side.output_variables}
            assigns = {}
            for src_name, mapping in injections.items():
                if src_name in names:
                    assigns.update(mapping)
            if assigns:
                full = {v: v for v in side.output_variables}
                full.update(assigns)
                setattr(node, side_attr, P.ProjectNode(
                    f"{node.id}.jfhoist_{side_attr}", side, full))
        node.filter = new_filter

    visit(root)
    return root


def optimize(root: P.PlanNode) -> P.PlanNode:
    """Reference Optimizer.java sequence, compressed: whole-plan passes
    (hoisting, pruning, dynamic filters) around the iterative rule driver
    (sql/rules.py).  Per-rule hit counts ride the root node for EXPLAIN
    (the reference's optimizerInformation)."""
    from .rules import DEFAULT_RULES, IterativeOptimizer
    root = hoist_join_filter_string_calls(root)
    rule_stats: Dict[str, int] = {}
    root = IterativeOptimizer(DEFAULT_RULES).run(root, rule_stats)
    root = prune_unused_outputs(root)
    root = plan_dynamic_filters(root)
    root = plan_scan_pushdown(root)
    root = plan_runtime_filter_pushdown(root)
    root.rule_stats = rule_stats
    return root
