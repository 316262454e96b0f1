"""Plan IR (reference presto-spi/.../spi/plan/*.java + presto-main-base
sql/planner/plan/*.java).

Node set covers what the reference fragmenter can send to a leaf/intermediate
worker for the TPC-H / TPC-DS vocabulary.  JSON uses the reference's Jackson
MINIMAL_CLASS discriminator style ("@type": ".FilterNode").
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from ..common.types import Type, parse_type
from .expr import (CallExpression, RowExpression, VariableReferenceExpression)

Variable = VariableReferenceExpression


# ---------------------------------------------------------------------------
# handles
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ColumnHandle:
    """Connector column reference (reference spi/ColumnHandle)."""
    name: str
    type: Type

    def to_dict(self):
        return {"name": self.name, "type": self.type.signature}

    @staticmethod
    def from_dict(d):
        return ColumnHandle(d["name"], parse_type(d["type"]))


@dataclass(frozen=True)
class TableHandle:
    """Connector table reference (reference spi/TableHandle)."""
    connector_id: str
    schema_name: str
    table_name: str
    # connector-specific payload, e.g. {"scaleFactor": 1.0} for tpch
    extra: Tuple[Tuple[str, Any], ...] = ()

    def to_dict(self):
        return {"connectorId": self.connector_id, "schema": self.schema_name,
                "table": self.table_name, "extra": dict(self.extra)}

    @staticmethod
    def from_dict(d):
        return TableHandle(d["connectorId"], d["schema"], d["table"],
                           tuple(sorted(d.get("extra", {}).items())))


# Sort orders (reference spi/block/SortOrder.java)
ASC_NULLS_FIRST = "ASC_NULLS_FIRST"
ASC_NULLS_LAST = "ASC_NULLS_LAST"
DESC_NULLS_FIRST = "DESC_NULLS_FIRST"
DESC_NULLS_LAST = "DESC_NULLS_LAST"


@dataclass
class OrderingScheme:
    orderings: List[Tuple[Variable, str]]  # (variable, sort order)

    def to_dict(self):
        return {"orderBy": [{"variable": v.to_dict(), "sortOrder": o}
                            for v, o in self.orderings]}

    @staticmethod
    def from_dict(d):
        return OrderingScheme([
            (RowExpression.from_dict(e["variable"]), e["sortOrder"])
            for e in d["orderBy"]])


# Partitioning handles (reference SystemPartitioningHandle.java:62-68)
SINGLE_DISTRIBUTION = "SINGLE"
FIXED_HASH_DISTRIBUTION = "FIXED_HASH"
FIXED_ARBITRARY_DISTRIBUTION = "FIXED_ARBITRARY"
FIXED_BROADCAST_DISTRIBUTION = "FIXED_BROADCAST"
SOURCE_DISTRIBUTION = "SOURCE"
SCALED_WRITER_DISTRIBUTION = "SCALED_WRITER"


@dataclass
class PartitioningScheme:
    handle: str                      # one of the *_DISTRIBUTION constants
    arguments: List[Variable]        # partitioning columns (hash)
    output_layout: List[Variable]
    # resolved exchange fabric of the remote edge this scheme describes
    # ("http" | "ici", parallel/fabric.py), annotated post-fragmentation
    # by the fragmenter/scheduler; None = unannotated (local exchanges,
    # plans never fragmented).  Emitted in serde only when set so golden
    # plan JSON and structural keys of unannotated plans are unchanged
    fabric: Optional[str] = None

    def to_dict(self):
        d = {"partitioning": {"handle": self.handle,
                              "arguments": [a.to_dict() for a in self.arguments]},
             "outputLayout": [v.to_dict() for v in self.output_layout]}
        if self.fabric is not None:
            d["fabric"] = self.fabric
        return d

    @staticmethod
    def from_dict(d):
        return PartitioningScheme(
            d["partitioning"]["handle"],
            [RowExpression.from_dict(a) for a in d["partitioning"]["arguments"]],
            [RowExpression.from_dict(v) for v in d["outputLayout"]],
            d.get("fabric"))


# ---------------------------------------------------------------------------
# plan nodes
# ---------------------------------------------------------------------------

_NODE_REGISTRY: Dict[str, type] = {}


def _node(cls):
    _NODE_REGISTRY["." + cls.__name__] = cls
    return cls


@dataclass
class PlanNode:
    id: str

    @property
    def sources(self) -> List["PlanNode"]:
        return []

    @property
    def output_variables(self) -> List[Variable]:
        raise NotImplementedError

    def to_dict(self) -> dict:
        d = self._to_dict()
        d["@type"] = "." + type(self).__name__
        d["id"] = self.id
        return d

    @staticmethod
    def from_dict(d: dict) -> "PlanNode":
        cls = _NODE_REGISTRY[d["@type"]]
        return cls._from_dict(d)


def _vars_to_dict(vs):
    return [v.to_dict() for v in vs]


def _vars_from_dict(ds):
    return [RowExpression.from_dict(x) for x in ds]


@_node
@dataclass
class TableScanNode(PlanNode):
    table: TableHandle
    outputs: List[Variable] = field(default_factory=list)
    assignments: Dict[Variable, ColumnHandle] = field(default_factory=dict)
    # range/equality conjuncts pushed down from the parent FilterNode by
    # sql/optimizer.plan_scan_pushdown: [{"column", "op", "value"}, ...]
    # with op in storage.pushdown.PUSHDOWN_OPS.  ADVISORY — consumed for
    # zone-map chunk skipping; the filter itself stays in the plan.
    # Validated by analysis/checker.py (SCAN_PUSHDOWN).
    pushdown: List[dict] = field(default_factory=list)
    # runtime dynamic filters this scan may consume, planned by
    # sql/optimizer.plan_runtime_filter_pushdown:
    # [{"id": filter_id, "column": column_name}, ...].  Each entry also
    # appends ["dyn", id, bound] marker rows to `pushdown`, resolved at
    # prune time from summaries a completed build stage published.
    runtime_filters: List[dict] = field(default_factory=list)

    @property
    def output_variables(self):
        return self.outputs

    def _to_dict(self):
        d = {"table": self.table.to_dict(),
             "outputVariables": _vars_to_dict(self.outputs),
             "assignments": [{"variable": v.to_dict(), "column": c.to_dict()}
                             for v, c in self.assignments.items()]}
        if self.pushdown:
            # emitted only when present: golden plan JSON stays stable
            d["pushdown"] = [dict(e) for e in self.pushdown]
        if self.runtime_filters:
            d["runtimeFilters"] = [dict(e) for e in self.runtime_filters]
        return d

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], TableHandle.from_dict(d["table"]),
                   _vars_from_dict(d["outputVariables"]),
                   {RowExpression.from_dict(e["variable"]): ColumnHandle.from_dict(e["column"])
                    for e in d["assignments"]},
                   [dict(e) for e in d.get("pushdown", [])],
                   [dict(e) for e in d.get("runtimeFilters", [])])


@_node
@dataclass
class FilterNode(PlanNode):
    source: PlanNode
    predicate: RowExpression

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return self.source.output_variables

    def _to_dict(self):
        return {"source": self.source.to_dict(),
                "predicate": self.predicate.to_dict()}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   RowExpression.from_dict(d["predicate"]))


@_node
@dataclass
class ProjectNode(PlanNode):
    source: PlanNode
    assignments: Dict[Variable, RowExpression]

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return list(self.assignments.keys())

    def _to_dict(self):
        return {"source": self.source.to_dict(),
                "assignments": [{"variable": v.to_dict(), "expression": e.to_dict()}
                                for v, e in self.assignments.items()]}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   {RowExpression.from_dict(e["variable"]): RowExpression.from_dict(e["expression"])
                    for e in d["assignments"]})


# Aggregation steps (reference AggregationNode.Step)
PARTIAL = "PARTIAL"
FINAL = "FINAL"
INTERMEDIATE = "INTERMEDIATE"
SINGLE = "SINGLE"


@dataclass
class Aggregation:
    """One aggregate: call like sum(x), optional filter/mask, distinct flag."""
    call: CallExpression
    distinct: bool = False
    mask: Optional[Variable] = None

    def to_dict(self):
        return {"call": self.call.to_dict(), "distinct": self.distinct,
                "mask": self.mask.to_dict() if self.mask else None}

    @staticmethod
    def from_dict(d):
        return Aggregation(
            RowExpression.from_dict(d["call"]), d.get("distinct", False),
            RowExpression.from_dict(d["mask"]) if d.get("mask") else None)


@_node
@dataclass
class AggregationNode(PlanNode):
    source: PlanNode
    aggregations: Dict[Variable, Aggregation]
    grouping_keys: List[Variable]
    step: str = SINGLE

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return list(self.grouping_keys) + list(self.aggregations.keys())

    def _to_dict(self):
        return {"source": self.source.to_dict(),
                "aggregations": [{"variable": v.to_dict(), "aggregation": a.to_dict()}
                                 for v, a in self.aggregations.items()],
                "groupingKeys": _vars_to_dict(self.grouping_keys),
                "step": self.step}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   {RowExpression.from_dict(e["variable"]): Aggregation.from_dict(e["aggregation"])
                    for e in d["aggregations"]},
                   _vars_from_dict(d["groupingKeys"]), d["step"])


# Join types (reference spi/plan/JoinType.java)
INNER = "INNER"
LEFT = "LEFT"
RIGHT = "RIGHT"
FULL = "FULL"

PARTITIONED = "PARTITIONED"
REPLICATED = "REPLICATED"


@_node
@dataclass
class JoinNode(PlanNode):
    join_type: str
    left: PlanNode
    right: PlanNode
    criteria: List[Tuple[Variable, Variable]]  # left var == right var
    outputs: List[Variable]
    filter: Optional[RowExpression] = None
    distribution: Optional[str] = None  # PARTITIONED / REPLICATED
    # dynamic filter id per RECEIVING key variable (reference
    # JoinNode.dynamicFilters / DynamicFilterSourceOperator).  Direction
    # depends on join type — the filter may only drop rows from a
    # NON-PRESERVED side: INNER keys are probe (left) variables narrowed
    # by the build domain; LEFT keys are build (right) variables narrowed
    # by the probe domain (the probe is preserved and must never shrink).
    dynamic_filters: Dict[str, str] = field(default_factory=dict)
    # the fragmenter's build-side row estimate at exchange-decision time;
    # exec/adaptive.decide_exchange compares it against the observed
    # count at the stage boundary
    planned_build_rows: Optional[int] = None
    # and its estimate of the build side's bytes, which it compared with
    # join-max-broadcast-table-size (sql/fragmenter.py FragmenterConfig)
    planned_build_bytes: Optional[int] = None

    @property
    def sources(self):
        return [self.left, self.right]

    @property
    def output_variables(self):
        return self.outputs

    def _to_dict(self):
        d = {"type": self.join_type, "left": self.left.to_dict(),
             "right": self.right.to_dict(),
             "criteria": [{"left": l.to_dict(), "right": r.to_dict()}
                          for l, r in self.criteria],
             "outputVariables": _vars_to_dict(self.outputs),
             "filter": self.filter.to_dict() if self.filter else None,
             "distributionType": self.distribution,
             "dynamicFilters": dict(self.dynamic_filters)}
        if self.planned_build_rows is not None:
            d["plannedBuildRows"] = self.planned_build_rows
        if self.planned_build_bytes is not None:
            d["plannedBuildBytes"] = self.planned_build_bytes
        return d

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], d["type"], PlanNode.from_dict(d["left"]),
                   PlanNode.from_dict(d["right"]),
                   [(RowExpression.from_dict(c["left"]), RowExpression.from_dict(c["right"]))
                    for c in d["criteria"]],
                   _vars_from_dict(d["outputVariables"]),
                   RowExpression.from_dict(d["filter"]) if d.get("filter") else None,
                   d.get("distributionType"),
                   d.get("dynamicFilters", {}),
                   d.get("plannedBuildRows"), d.get("plannedBuildBytes"))


@_node
@dataclass
class SemiJoinNode(PlanNode):
    source: PlanNode
    filtering_source: PlanNode
    source_join_variable: Variable
    filtering_source_join_variable: Variable
    semi_join_output: Variable
    # dynamic filter id keyed by the SOURCE join variable, set only when
    # the membership marker is consumed as a positive filter conjunct
    # (so source rows outside the filtering-source domain are droppable)
    dynamic_filters: Dict[str, str] = field(default_factory=dict)

    @property
    def sources(self):
        return [self.source, self.filtering_source]

    @property
    def output_variables(self):
        return self.source.output_variables + [self.semi_join_output]

    def _to_dict(self):
        d = {"source": self.source.to_dict(),
             "filteringSource": self.filtering_source.to_dict(),
             "sourceJoinVariable": self.source_join_variable.to_dict(),
             "filteringSourceJoinVariable": self.filtering_source_join_variable.to_dict(),
             "semiJoinOutput": self.semi_join_output.to_dict()}
        if self.dynamic_filters:
            # emitted only when present: golden plan JSON stays stable
            d["dynamicFilters"] = dict(self.dynamic_filters)
        return d

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   PlanNode.from_dict(d["filteringSource"]),
                   RowExpression.from_dict(d["sourceJoinVariable"]),
                   RowExpression.from_dict(d["filteringSourceJoinVariable"]),
                   RowExpression.from_dict(d["semiJoinOutput"]),
                   d.get("dynamicFilters", {}))


# Exchange (reference sql/planner/plan/ExchangeNode.java)
GATHER = "GATHER"
REPARTITION = "REPARTITION"
REPLICATE = "REPLICATE"
LOCAL = "LOCAL"
REMOTE = "REMOTE"


@_node
@dataclass
class ExchangeNode(PlanNode):
    exchange_type: str                  # GATHER / REPARTITION / REPLICATE
    scope: str                          # LOCAL / REMOTE
    partitioning_scheme: PartitioningScheme
    exchange_sources: List[PlanNode]
    # inputs[i][j]: variable of sources[i] feeding output_layout[j]
    inputs: List[List[Variable]] = field(default_factory=list)

    @property
    def sources(self):
        return self.exchange_sources

    @property
    def output_variables(self):
        return self.partitioning_scheme.output_layout

    def _to_dict(self):
        return {"exchangeType": self.exchange_type, "scope": self.scope,
                "partitioningScheme": self.partitioning_scheme.to_dict(),
                "sources": [s.to_dict() for s in self.exchange_sources],
                "inputs": [_vars_to_dict(row) for row in self.inputs]}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], d["exchangeType"], d["scope"],
                   PartitioningScheme.from_dict(d["partitioningScheme"]),
                   [PlanNode.from_dict(s) for s in d["sources"]],
                   [_vars_from_dict(row) for row in d.get("inputs", [])])


@_node
@dataclass
class RemoteSourceNode(PlanNode):
    """Leaf in a fragment: reads the output of other fragments
    (reference sql/planner/plan/RemoteSourceNode.java)."""
    source_fragment_ids: List[str]
    outputs: List[Variable]
    ensure_source_ordering: bool = False
    ordering_scheme: Optional[OrderingScheme] = None

    @property
    def output_variables(self):
        return self.outputs

    def _to_dict(self):
        return {"sourceFragmentIds": self.source_fragment_ids,
                "outputVariables": _vars_to_dict(self.outputs),
                "ensureSourceOrdering": self.ensure_source_ordering,
                "orderingScheme": self.ordering_scheme.to_dict() if self.ordering_scheme else None}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], d["sourceFragmentIds"],
                   _vars_from_dict(d["outputVariables"]),
                   d.get("ensureSourceOrdering", False),
                   OrderingScheme.from_dict(d["orderingScheme"]) if d.get("orderingScheme") else None)


@_node
@dataclass
class SortNode(PlanNode):
    source: PlanNode
    ordering_scheme: OrderingScheme
    is_partial: bool = False

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return self.source.output_variables

    def _to_dict(self):
        return {"source": self.source.to_dict(),
                "orderingScheme": self.ordering_scheme.to_dict(),
                "isPartial": self.is_partial}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   OrderingScheme.from_dict(d["orderingScheme"]),
                   d.get("isPartial", False))


@_node
@dataclass
class TopNNode(PlanNode):
    source: PlanNode
    count: int
    ordering_scheme: OrderingScheme
    step: str = SINGLE  # SINGLE / PARTIAL / FINAL

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return self.source.output_variables

    def _to_dict(self):
        return {"source": self.source.to_dict(), "count": self.count,
                "orderingScheme": self.ordering_scheme.to_dict(),
                "step": self.step}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]), d["count"],
                   OrderingScheme.from_dict(d["orderingScheme"]),
                   d.get("step", SINGLE))


@_node
@dataclass
class LimitNode(PlanNode):
    source: PlanNode
    count: int
    step: str = SINGLE  # PARTIAL / FINAL

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return self.source.output_variables

    def _to_dict(self):
        return {"source": self.source.to_dict(), "count": self.count,
                "step": self.step}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]), d["count"],
                   d.get("step", SINGLE))


@_node
@dataclass
class DistinctLimitNode(PlanNode):
    source: PlanNode
    count: int
    distinct_variables: List[Variable] = field(default_factory=list)

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return self.distinct_variables

    def _to_dict(self):
        return {"source": self.source.to_dict(), "count": self.count,
                "distinctVariables": _vars_to_dict(self.distinct_variables)}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]), d["count"],
                   _vars_from_dict(d["distinctVariables"]))


@_node
@dataclass
class ValuesNode(PlanNode):
    outputs: List[Variable]
    rows: List[List[RowExpression]] = field(default_factory=list)

    @property
    def output_variables(self):
        return self.outputs

    def _to_dict(self):
        return {"outputVariables": _vars_to_dict(self.outputs),
                "rows": [[e.to_dict() for e in row] for row in self.rows]}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], _vars_from_dict(d["outputVariables"]),
                   [[RowExpression.from_dict(e) for e in row] for row in d["rows"]])


@_node
@dataclass
class OutputNode(PlanNode):
    source: PlanNode
    column_names: List[str]
    outputs: List[Variable] = field(default_factory=list)

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return self.outputs

    def _to_dict(self):
        return {"source": self.source.to_dict(), "columnNames": self.column_names,
                "outputVariables": _vars_to_dict(self.outputs)}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]), d["columnNames"],
                   _vars_from_dict(d["outputVariables"]))


@_node
@dataclass
class MarkDistinctNode(PlanNode):
    source: PlanNode
    marker: Variable
    distinct_variables: List[Variable] = field(default_factory=list)

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return self.source.output_variables + [self.marker]

    def _to_dict(self):
        return {"source": self.source.to_dict(), "marker": self.marker.to_dict(),
                "distinctVariables": _vars_to_dict(self.distinct_variables)}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   RowExpression.from_dict(d["marker"]),
                   _vars_from_dict(d["distinctVariables"]))


@_node
@dataclass
class GroupIdNode(PlanNode):
    """Grouping-set row expansion (reference GroupIdNode,
    presto_protocol_core.h:1340-1349, executed by GroupIdOperator.java):
    each input row is replicated once per grouping set with the grouping
    columns absent from that set null-filled and `group_id_variable` set to
    the set's ordinal.  The AggregationNode above groups by
    (grouping columns..., group_id)."""
    source: PlanNode
    grouping_sets: List[List[Variable]]           # per-set OUTPUT columns
    grouping_columns: Dict[Variable, Variable]    # output -> input column
    aggregation_arguments: List[Variable] = field(default_factory=list)
    group_id_variable: Variable = None

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return (list(self.grouping_columns) + self.aggregation_arguments
                + [self.group_id_variable])

    def _to_dict(self):
        return {"source": self.source.to_dict(),
                "groupingSets": [_vars_to_dict(s)
                                 for s in self.grouping_sets],
                "groupingColumns": [{"output": o.to_dict(),
                                     "input": i.to_dict()}
                                    for o, i in
                                    self.grouping_columns.items()],
                "aggregationArguments":
                    _vars_to_dict(self.aggregation_arguments),
                "groupIdVariable": self.group_id_variable.to_dict()}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   [_vars_from_dict(s) for s in d["groupingSets"]],
                   {RowExpression.from_dict(e["output"]):
                    RowExpression.from_dict(e["input"])
                    for e in d["groupingColumns"]},
                   _vars_from_dict(d["aggregationArguments"]),
                   RowExpression.from_dict(d["groupIdVariable"]))


@_node
@dataclass
class EnforceSingleRowNode(PlanNode):
    source: PlanNode

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return self.source.output_variables

    def _to_dict(self):
        return {"source": self.source.to_dict()}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]))


@_node
@dataclass
class AssignUniqueIdNode(PlanNode):
    source: PlanNode
    id_variable: Variable = None

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return self.source.output_variables + [self.id_variable]

    def _to_dict(self):
        return {"source": self.source.to_dict(),
                "idVariable": self.id_variable.to_dict()}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   RowExpression.from_dict(d["idVariable"]))


@dataclass
class WindowFunction:
    call: CallExpression
    frame: Optional[dict] = None  # frame spec; None == default RANGE UNBOUNDED..CURRENT

    def to_dict(self):
        return {"call": self.call.to_dict(), "frame": self.frame}

    @staticmethod
    def from_dict(d):
        return WindowFunction(RowExpression.from_dict(d["call"]), d.get("frame"))


@_node
@dataclass
class WindowNode(PlanNode):
    source: PlanNode
    partition_by: List[Variable]
    ordering_scheme: Optional[OrderingScheme]
    window_functions: Dict[Variable, WindowFunction] = field(default_factory=dict)

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return self.source.output_variables + list(self.window_functions.keys())

    def _to_dict(self):
        return {"source": self.source.to_dict(),
                "partitionBy": _vars_to_dict(self.partition_by),
                "orderingScheme": self.ordering_scheme.to_dict() if self.ordering_scheme else None,
                "windowFunctions": [{"variable": v.to_dict(), "function": f.to_dict()}
                                    for v, f in self.window_functions.items()]}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   _vars_from_dict(d["partitionBy"]),
                   OrderingScheme.from_dict(d["orderingScheme"]) if d.get("orderingScheme") else None,
                   {RowExpression.from_dict(e["variable"]): WindowFunction.from_dict(e["function"])
                    for e in d["windowFunctions"]})


@_node
@dataclass
class UnionNode(PlanNode):
    """UNION ALL of N sources (reference UnionNode / SetOperationNode).
    The planner projects every source to the same output variables, so no
    per-source variable mapping is needed; DISTINCT and INTERSECT/EXCEPT
    are lowered to UnionNode + aggregation (the reference's
    ImplementIntersectAsUnion / ImplementExceptAsUnion rules)."""
    inputs: List[PlanNode]
    outputs: List[Variable] = field(default_factory=list)

    @property
    def sources(self):
        return list(self.inputs)

    @property
    def output_variables(self):
        return list(self.outputs)

    def _to_dict(self):
        return {"sources": [s.to_dict() for s in self.inputs],
                "outputs": _vars_to_dict(self.outputs)}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], [PlanNode.from_dict(s) for s in d["sources"]],
                   _vars_from_dict(d["outputs"]))


@_node
@dataclass
class UnnestNode(PlanNode):
    source: PlanNode
    replicate_variables: List[Variable]
    unnest_variables: List[Tuple[Variable, List[Variable]]]  # array var -> element vars
    # WITH ORDINALITY output (reference UnnestNode.ordinalityVariable)
    ordinality_variable: Optional[Variable] = None

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        out = list(self.replicate_variables)
        for _, elems in self.unnest_variables:
            out.extend(elems)
        if self.ordinality_variable is not None:
            out.append(self.ordinality_variable)
        return out

    def _to_dict(self):
        return {"source": self.source.to_dict(),
                "replicateVariables": _vars_to_dict(self.replicate_variables),
                "unnestVariables": [{"variable": v.to_dict(),
                                     "elements": _vars_to_dict(elems)}
                                    for v, elems in self.unnest_variables],
                "ordinalityVariable":
                    None if self.ordinality_variable is None
                    else self.ordinality_variable.to_dict()}

    @classmethod
    def _from_dict(cls, d):
        ov = d.get("ordinalityVariable")
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   _vars_from_dict(d["replicateVariables"]),
                   [(RowExpression.from_dict(e["variable"]), _vars_from_dict(e["elements"]))
                    for e in d["unnestVariables"]],
                   None if ov is None else RowExpression.from_dict(ov))


# ---------------------------------------------------------------------------
# fragments
# ---------------------------------------------------------------------------

@dataclass
class PlanFragment:
    """A scheduling unit cut at exchange boundaries
    (reference sql/planner/PlanFragment.java:46)."""
    fragment_id: str
    root: PlanNode
    partitioning: str                       # how this fragment's tasks are distributed
    output_partitioning_scheme: PartitioningScheme
    # table-scan node ids in this fragment that receive splits
    partitioned_sources: List[str] = field(default_factory=list)
    # output column name -> dynamic filter id: this fragment's output is
    # a dynamic-filter SOURCE, so its tasks summarize the named column's
    # domain on completion (sql/fragmenter.plan_dynamic_filter_sources)
    dynamic_filter_sources: Dict[str, str] = field(default_factory=dict)

    def to_dict(self):
        d = {"id": self.fragment_id, "root": self.root.to_dict(),
             "partitioning": self.partitioning,
             "outputPartitioningScheme": self.output_partitioning_scheme.to_dict(),
             "partitionedSources": self.partitioned_sources}
        if self.dynamic_filter_sources:
            d["dynamicFilterSources"] = dict(self.dynamic_filter_sources)
        return d

    @staticmethod
    def from_dict(d):
        return PlanFragment(
            d["id"], PlanNode.from_dict(d["root"]), d["partitioning"],
            PartitioningScheme.from_dict(d["outputPartitioningScheme"]),
            d.get("partitionedSources", []),
            d.get("dynamicFilterSources", {}))


@_node
@dataclass
class TableWriterNode(PlanNode):
    """Write the source's rows into a connector table (reference
    TableWriterOperator.java:78).  Emits one row per task:
    (rows BIGINT, fragment VARCHAR) where `fragment` is the connector's
    staging token, committed by TableFinishNode."""
    source: PlanNode
    connector_id: str
    table_name: str
    column_names: List[str] = field(default_factory=list)
    outputs: List[Variable] = field(default_factory=list)

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return list(self.outputs)

    def _to_dict(self):
        return {"source": self.source.to_dict(),
                "connectorId": self.connector_id, "table": self.table_name,
                "columnNames": self.column_names,
                "outputs": _vars_to_dict(self.outputs)}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   d["connectorId"], d["table"], d["columnNames"],
                   _vars_from_dict(d["outputs"]))


@_node
@dataclass
class TableFinishNode(PlanNode):
    """Commit staged table writes and emit the total row count (reference
    TableFinishOperator.java: gathers writer fragments, runs the connector
    commit, outputs rows)."""
    source: PlanNode
    connector_id: str
    table_name: str
    outputs: List[Variable] = field(default_factory=list)

    @property
    def sources(self):
        return [self.source]

    @property
    def output_variables(self):
        return list(self.outputs)

    def _to_dict(self):
        return {"source": self.source.to_dict(),
                "connectorId": self.connector_id, "table": self.table_name,
                "outputs": _vars_to_dict(self.outputs)}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["id"], PlanNode.from_dict(d["source"]),
                   d["connectorId"], d["table"],
                   _vars_from_dict(d["outputs"]))


@dataclass
class SubPlan:
    """Tree of fragments (reference sql/planner/SubPlan.java)."""
    fragment: PlanFragment
    children: List["SubPlan"] = field(default_factory=list)

    def all_fragments(self) -> List[PlanFragment]:
        out = [self.fragment]
        for c in self.children:
            out.extend(c.all_fragments())
        return out


def walk_plan(node: PlanNode):
    """Pre-order traversal."""
    yield node
    for s in node.sources:
        yield from walk_plan(s)


def structural_key(node: PlanNode, canonical_params: bool = False) -> str:
    """Canonical text of a subtree that is identical for structurally
    equal plans regardless of node ids or variable names — node ids are
    blanked and variables renamed by first occurrence in a deterministic
    (sorted-key) traversal.  Lets execution-layer result caches recognize
    REPLAYED subtrees (scalar-subquery re-plans, decorrelated deep copies)
    whose node ids differ; a false mismatch only costs a cache miss, and
    structural equality implies identical output data (generated connector
    data is immutable and AssignUniqueId ids are deterministic).

    `canonical_params=True` additionally renames bound-parameter slot
    indices by first occurrence (both `{"@type": "parameter", "index": N}`
    expressions and scan-pushdown `["param", N]` markers share one
    mapping).  The serving tier's parameterizer gives every literal
    occurrence its own global slot, so decorrelated deep copies of the
    same source subtree (a CTE referenced by two subqueries) carry
    different indices while remaining structurally the same plan.  The
    DUPLICATE_NODE_ID checker compares plans under this mode; execution
    result caches must NOT — two subtrees bound to different slots of the
    same execution can carry different values, and params_fingerprint
    (whole-vector) would not disambiguate them."""
    return _structural(node, canonical_params)[0]


def named_structural_key(node: PlanNode) -> Tuple[str, Tuple[str, ...]]:
    """`structural_key(node)` and the subtree's REAL variable names in the
    order the key numbered them: together they identify a subtree up to
    node ids alone.  What a process-wide cache of traced closures keys on
    (`PlanCompiler.shared_jit`): a closure looks columns up, and names
    its output pytrees, by the real names, so two subtrees that differ
    only in names are one structure but two programs."""
    return _structural(node, False)


def _structural(node: PlanNode, canonical_params: bool
                ) -> Tuple[str, Tuple[str, ...]]:
    """(canonical text, variable names by first occurrence)."""
    rename: Dict[str, str] = {}
    param_rename: Dict[int, int] = {}

    def pidx(i: int) -> int:
        if i not in param_rename:
            param_rename[i] = len(param_rename)
        return param_rename[i]

    def canon(x):
        if isinstance(x, dict):
            if x.get("@type") == "variable" and "name" in x:
                nm = x["name"]
                if nm not in rename:
                    rename[nm] = f"v{len(rename)}"
                return {"@type": "variable", "name": rename[nm],
                        "type": x.get("type")}
            if (canonical_params and x.get("@type") == "parameter"
                    and isinstance(x.get("index"), int)):
                return {"@type": "parameter", "index": pidx(x["index"]),
                        "type": x.get("type")}
            out = {}
            for k in sorted(x):
                v = x[k]
                if k == "id":
                    out[k] = ""
                elif k == "dynamicFilters" and isinstance(v, dict):
                    # keys are probe variable names (renamed like any other
                    # variable); values are planner-counter filter ids,
                    # blanked like node ids — two decorrelated copies
                    # differing only in filter numbering are the same plan
                    out[k] = sorted(rename.get(n, n) for n in v)
                elif k == "runtimeFilters" and isinstance(v, list):
                    # filter ids blanked like node ids; columns are
                    # physical names, kept as-is
                    out[k] = sorted(
                        (e.get("column"), "") for e in v if isinstance(e, dict))
                else:
                    out[k] = canon(v)
            return out
        if isinstance(x, list):
            if (canonical_params and len(x) == 2 and x[0] == "param"
                    and isinstance(x[1], int)):
                return ["param", pidx(x[1])]
            if len(x) == 3 and x[0] == "dyn":
                # runtime-filter pushdown marker: the planner-counter
                # filter id is blanked like node ids
                return ["dyn", "", x[2]]
            return [canon(i) for i in x]
        return x

    import json as _json
    text = _json.dumps(canon(node.to_dict()), sort_keys=True, default=str)
    return text, tuple(rename)
