"""Plan statistics: column stats + cardinality/selectivity estimation.

The analog of the reference's cost module (presto-main-base/.../cost/,
~9k LoC: StatsCalculator + per-node rules like FilterStatsCalculator /
JoinStatsRule) reduced to what drives real decisions here:

  * predicate selectivity from column (low, high, ndv, null_fraction)
    stats — range interpolation for comparisons, 1/ndv for equality,
    AND/OR/NOT composition (FilterStatsCalculator.java semantics);
  * join output cardinality |L|x|R| / max(ndv(l), ndv(r)) per equi-clause
    (JoinStatsRule.java);
  * aggregation group counts capped by the product of key NDVs.

Connector column stats are duck-typed: a connector module may expose
`column_stats(table, column, sf) -> ColumnStats | None` (the
ConnectorMetadata.getTableStatistics analog).  tpch/tpcds derive stats
analytically from their generator specs; the hive connector reads parquet
row-group metadata.

Consumers: the fragmenter's broadcast-vs-partitioned decision, the
build-side-swap optimizer pass (sql/optimizer.py), and EXPLAIN's per-node
`rows≈` annotations.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from decimal import Decimal
from typing import Dict, Optional

import numpy as np

from ..spi import plan as P
from ..spi.expr import (CallExpression, ConstantExpression, RowExpression,
                        SpecialFormExpression, VariableReferenceExpression)

UNKNOWN_FILTER_COEFFICIENT = 0.9   # reference: FilterStatsCalculator


@dataclass(frozen=True)
class ColumnStats:
    low: Optional[float] = None
    high: Optional[float] = None
    ndv: Optional[float] = None
    null_fraction: float = 0.0


@dataclass
class PlanStats:
    rows: Optional[float]
    columns: Dict[str, ColumnStats]

    def col(self, name: str) -> ColumnStats:
        return self.columns.get(name, ColumnStats())


def _const_float(e: ConstantExpression) -> Optional[float]:
    v = e.value
    if v is None:
        return None
    if isinstance(v, Decimal):
        return float(v)
    if isinstance(v, bool):
        return 1.0 if v else 0.0
    if isinstance(v, (int, float)):
        return float(v)
    if isinstance(v, str):
        try:   # date literals arrive as 'YYYY-MM-DD'
            return float(np.datetime64(v, "D").astype(np.int64))
        except ValueError:
            return None
    return None


def _canon(name: str) -> str:
    return name.lower().split(".")[-1].lstrip("$").replace("$operator$", "")


class StatsCalculator:
    """Memoized bottom-up estimator over a plan tree."""

    def __init__(self):
        self._memo: Dict[str, PlanStats] = {}
        self._unknown_terms = 0

    def stats(self, node: P.PlanNode) -> PlanStats:
        got = self._memo.get(node.id)
        if got is None:
            fn = getattr(self, "_stats_" + type(node).__name__, None)
            got = fn(node) if fn else self._passthrough(node)
            self._memo[node.id] = got
        return got

    def rows(self, node: P.PlanNode) -> Optional[float]:
        return self.stats(node).rows

    # -- leaves -----------------------------------------------------------
    def _stats_TableScanNode(self, node: P.TableScanNode) -> PlanStats:
        from ..connectors import catalog
        th = node.table
        sf = dict(th.extra).get("scaleFactor", 0.01)
        try:
            conn = catalog.module(th.connector_id)
            rows = float(conn.table_row_count(th.table_name, sf))
        except Exception:
            return PlanStats(None, {})
        cols: Dict[str, ColumnStats] = {}
        stats_fn = getattr(conn, "column_stats", None)
        if stats_fn is not None:
            for v in node.outputs:
                cs = stats_fn(th.table_name, node.assignments[v].name, sf)
                if cs is not None:
                    cols[v.name] = cs
        return PlanStats(rows, cols)

    def _stats_ValuesNode(self, node: P.ValuesNode) -> PlanStats:
        return PlanStats(float(len(node.rows)), {})

    # -- streaming --------------------------------------------------------
    def _passthrough(self, node: P.PlanNode) -> PlanStats:
        srcs = node.sources
        if not srcs:
            return PlanStats(None, {})
        return self.stats(srcs[0])

    def _stats_FilterNode(self, node: P.FilterNode) -> PlanStats:
        src = self.stats(node.source)
        if src.rows is None:
            return src
        self._unknown_terms = 0
        sel, cols = self._selectivity(node.predicate, src)
        if self._unknown_terms:
            sampled = _sampled_selectivity(node)
            if sampled is not None:
                sel = sampled
        return PlanStats(max(0.0, src.rows * sel), cols)

    def _unknown(self) -> float:
        """The coefficient of a term the column stats say nothing about
        (a comparison of two columns, a function of one); the filter's
        estimate then comes from a sample where one is at hand."""
        self._unknown_terms += 1
        return UNKNOWN_FILTER_COEFFICIENT

    def _stats_ProjectNode(self, node: P.ProjectNode) -> PlanStats:
        src = self.stats(node.source)
        cols = {}
        for v, e in node.assignments.items():
            if isinstance(e, VariableReferenceExpression):
                cols[v.name] = src.col(e.name)
            elif isinstance(e, CallExpression) and \
                    _canon(e.display_name) == "cast" and e.arguments and \
                    isinstance(e.arguments[0], VariableReferenceExpression):
                cols[v.name] = src.col(e.arguments[0].name)
        return PlanStats(src.rows, cols)

    def _stats_OutputNode(self, node: P.OutputNode) -> PlanStats:
        return self.stats(node.source)

    def _stats_LimitNode(self, node) -> PlanStats:
        src = self.stats(node.source)
        rows = (float(node.count) if src.rows is None
                else min(float(node.count), src.rows))
        return PlanStats(rows, src.columns)

    _stats_TopNNode = _stats_LimitNode
    _stats_DistinctLimitNode = _stats_LimitNode

    def _stats_AggregationNode(self, node: P.AggregationNode) -> PlanStats:
        src = self.stats(node.source)
        if not node.grouping_keys:
            return PlanStats(1.0, {})
        if src.rows is None:
            return PlanStats(None, {})
        groups = 1.0
        known = False
        for v in node.grouping_keys:
            ndv = src.col(v.name).ndv
            if ndv is not None:
                groups *= max(1.0, ndv)
                known = True
        if not known:
            groups = max(1.0, src.rows * 0.1)
        cols = {v.name: src.col(v.name) for v in node.grouping_keys}
        return PlanStats(min(groups, src.rows), cols)

    def _stats_JoinNode(self, node: P.JoinNode) -> PlanStats:
        l, r = self.stats(node.left), self.stats(node.right)
        cols = {**r.columns, **l.columns}
        if l.rows is None or r.rows is None:
            return PlanStats(None, cols)
        if not node.criteria:     # cross join
            rows = l.rows * r.rows
        else:
            rows = l.rows * r.rows
            for lv, rv in node.criteria:
                ndv = max(l.col(lv.name).ndv or 1.0,
                          r.col(rv.name).ndv or 1.0)
                rows /= max(1.0, ndv)
        if node.join_type == P.LEFT:
            rows = max(rows, l.rows)
        elif node.join_type == P.RIGHT:
            rows = max(rows, r.rows)
        elif node.join_type == P.FULL:
            rows = max(rows, l.rows, r.rows)
        return PlanStats(rows, cols)

    def _stats_SemiJoinNode(self, node: P.SemiJoinNode) -> PlanStats:
        src = self.stats(node.source)
        return PlanStats(src.rows, src.columns)

    def _stats_UnionNode(self, node: P.UnionNode) -> PlanStats:
        ests = [self.stats(s).rows for s in node.sources]
        if any(e is None for e in ests):
            return PlanStats(None, {})
        return PlanStats(float(sum(ests)), {})

    def _stats_ExchangeNode(self, node) -> PlanStats:
        ests = [self.stats(s) for s in node.sources]
        rows = [e.rows for e in ests]
        if any(e is None for e in rows):
            return PlanStats(None, ests[0].columns if ests else {})
        return PlanStats(float(sum(rows)), ests[0].columns if ests else {})

    # -- predicate selectivity -------------------------------------------
    def _selectivity(self, e: RowExpression, src: PlanStats):
        """Returns (selectivity, post-filter column stats)."""
        if isinstance(e, SpecialFormExpression):
            form = e.form.upper()
            if form == "AND":
                sel, cols = 1.0, dict(src.columns)
                cur = src
                for a in e.arguments:
                    s, cols = self._selectivity(a, cur)
                    sel *= s
                    cur = PlanStats(src.rows, cols)
                return sel, cols
            if form == "OR":
                sels = [self._selectivity(a, src)[0] for a in e.arguments]
                out = 0.0
                for s in sels:
                    out = out + s - out * s
                return out, dict(src.columns)
            if form == "IN":
                # IN (v1, v2, ...): value-list membership
                var = e.arguments[0]
                if isinstance(var, VariableReferenceExpression):
                    ndv = src.col(var.name).ndv
                    n = len(e.arguments) - 1
                    if ndv:
                        return min(1.0, n / ndv), dict(src.columns)
                return self._unknown(), dict(src.columns)
        if isinstance(e, CallExpression):
            name = _canon(e.display_name)
            args = e.arguments
            if name == "not" and len(args) == 1:
                s, _ = self._selectivity(args[0], src)
                return 1.0 - s, dict(src.columns)
            if name == "between" and len(args) == 3 and \
                    isinstance(args[0], VariableReferenceExpression):
                v = args[0]
                lo = _maybe_const(args[1])
                hi = _maybe_const(args[2])
                return self._range_sel(src, v.name, lo, hi)
            cmp_ops = {"lt": "lt", "lte": "lte", "gt": "gt", "gte": "gte",
                       "less_than": "lt", "less_than_or_equal": "lte",
                       "greater_than": "gt",
                       "greater_than_or_equal": "gte",
                       "eq": "eq", "equal": "eq",
                       "neq": "neq", "not_equal": "neq"}
            if name in cmp_ops and len(args) == 2:
                op = cmp_ops[name]
                a, b = args
                if isinstance(b, VariableReferenceExpression) and \
                        isinstance(a, ConstantExpression):
                    a, b = b, a
                    op = {"lt": "gt", "lte": "gte", "gt": "lt",
                          "gte": "lte"}.get(op, op)
                if isinstance(a, VariableReferenceExpression) and \
                        isinstance(b, ConstantExpression):
                    return self._cmp_sel(src, a.name, op, b)
        return self._unknown(), dict(src.columns)

    def _cmp_sel(self, src: PlanStats, var: str, op: str,
                 const: ConstantExpression):
        cs = src.col(var)
        cols = dict(src.columns)
        c = _const_float(const)
        if op == "eq":
            if cs.ndv:
                cols[var] = replace(cs, ndv=1.0,
                                    low=c if c is not None else cs.low,
                                    high=c if c is not None else cs.high)
                return min(1.0, 1.0 / cs.ndv), cols
            return self._unknown(), cols
        if op == "neq":
            if cs.ndv:
                return 1.0 - min(1.0, 1.0 / cs.ndv), cols
            return self._unknown(), cols
        if c is None or cs.low is None or cs.high is None \
                or cs.high <= cs.low:
            return self._unknown(), cols
        frac = (c - cs.low) / (cs.high - cs.low)
        frac = min(1.0, max(0.0, frac))
        if op in ("lt", "lte"):
            cols[var] = replace(cs, high=min(cs.high, c))
            return frac if frac > 0 else 0.0, cols
        cols[var] = replace(cs, low=max(cs.low, c))
        return 1.0 - frac, cols

    def _range_sel(self, src: PlanStats, var: str,
                   lo: Optional[float], hi: Optional[float]):
        cs = src.col(var)
        cols = dict(src.columns)
        if lo is None or hi is None or cs.low is None or cs.high is None \
                or cs.high <= cs.low:
            return self._unknown(), cols
        inter_lo = max(lo, cs.low)
        inter_hi = min(hi, cs.high)
        if inter_hi < inter_lo:
            return 0.0, cols
        cols[var] = replace(cs, low=inter_lo, high=inter_hi)
        return (inter_hi - inter_lo) / (cs.high - cs.low), cols


# rows of a generated table a filter is tried on where its column stats
# leave a term unknown, and the columns generated for it so far
SAMPLE_ROWS = 1 << 14
_SAMPLES: Dict[tuple, tuple] = {}


def _sampled_selectivity(node: P.FilterNode) -> Optional[float]:
    """The share of a generated table's first SAMPLE_ROWS rows that pass
    the filter directly above its scan, or None (another connector, a
    table no larger than the sample, an expression the host evaluator
    does not know, a bound parameter).
    The generated catalogs make any row range on the host in
    milliseconds, and their rows are drawn independently of position; a
    filter that keeps nothing of the sample reads as half a row of it."""
    scan = node.source
    if not isinstance(scan, P.TableScanNode) \
            or scan.table.connector_id not in ("tpch", "tpcds"):
        return None
    from ..connectors import catalog
    from ..exec import reference
    th = scan.table
    sf = dict(th.extra).get("scaleFactor", 0.01)
    try:
        n = SAMPLE_ROWS
        if int(catalog.table_row_count(th.table_name, sf,
                                       th.connector_id)) <= n:
            # a table the sample would hold whole: the coefficient's
            # error is bounded by its few rows, and asking every row is
            # running the query to plan it
            return None
        cols = {}
        for v in scan.outputs:
            key = (th.connector_id, th.table_name, sf,
                   scan.assignments[v].name, n)
            if key not in _SAMPLES:
                if len(_SAMPLES) >= 256:
                    _SAMPLES.clear()
                _SAMPLES[key] = reference.scan_column(
                    th.table_name, key[3], sf, 0, n, th.connector_id)
            cols[v.name] = _SAMPLES[key]
        values, nulls = reference._eval(node.predicate,
                                        reference.Table(cols, n))
        keep = np.asarray(values).astype(bool)
        if nulls is not None:
            keep = keep & ~nulls
        return max(0.5, float(np.count_nonzero(keep))) / n
    except Exception:       # noqa: BLE001 -- an estimate, never a failure
        return None


def primary_key_scan(calc: "StatsCalculator", side: P.PlanNode,
                     keys) -> bool:
    """Whether `side` is a bare scan joined on ONE column that its
    statistics call unique, dense and never null: what the executor
    turns into a direct-address table (exec/fused.py try_direct_table),
    one scatter to build and one gather a probe row."""
    if len(keys) != 1 or not isinstance(side, P.TableScanNode):
        return False
    st = calc.stats(side)
    cs = st.col(keys[0].name)
    if not st.rows or cs.ndv is None or cs.low is None \
            or cs.high is None or cs.null_fraction:
        return False
    from ..exec.fused import DIRECT_TABLE_MAX, DIRECT_TABLE_SPAN_RATIO
    span = cs.high - cs.low + 1
    return (cs.ndv >= st.rows and span <= DIRECT_TABLE_MAX
            and span <= DIRECT_TABLE_SPAN_RATIO * st.rows)


def primary_key_sides(calc: "StatsCalculator", join: P.JoinNode):
    """(left, right): which sides of `join` are `primary_key_scan`s."""
    return tuple(primary_key_scan(calc, side, [c[i] for c in join.criteria])
                 for i, side in enumerate((join.left, join.right)))


def _maybe_const(e) -> Optional[float]:
    return _const_float(e) if isinstance(e, ConstantExpression) else None


def estimate(node: P.PlanNode) -> Optional[float]:
    """One-shot row estimate (fresh memo)."""
    return StatsCalculator().rows(node)
