"""Adaptive query execution (ISSUE 19; reference DynamicFilterService +
AdaptivePlanOptimizer analogs): runtime dynamic filters summarized from
completed build stages and pushed into probe-side zone-map pruning,
cardinality-driven exchange decisions at stage boundaries, and
history-based sizing from prior runs of the same plan template.

Correctness bar throughout: rows bit-identical to the numpy reference
oracle with adaptivity on, off, and under the wait-timeout fallback —
every adaptive move is advisory, never semantic.
"""
import dataclasses

import pytest

from presto_tpu.exec.adaptive import (ADAPTIVE_METRICS,
                                      DynamicFilterCollector,
                                      DynamicFilterSummary, decide_exchange,
                                      decide_side_swap,
                                      reset_adaptive_metrics,
                                      summaries_to_runtime,
                                      summarize_key_column)
from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import (DistributedQueryRunner, LocalQueryRunner,
                                    _assert_rows_equal)
from presto_tpu.spi import plan as P
from presto_tpu.storage.pushdown import (entry_unsatisfiable, is_dyn_marker,
                                         prune_chunks, resolve_entry_value)


@pytest.fixture(autouse=True)
def _fresh_metrics():
    reset_adaptive_metrics()
    yield


# ---------------------------------------------------------------------------
# summaries
# ---------------------------------------------------------------------------

def test_summarize_key_column_bounds_and_set():
    import numpy as np
    s = summarize_key_column("df_0", np.array([7, 3, 3, 9]), None, 16)
    assert (s.min, s.max, s.row_count) == (3, 9, 4)
    assert s.values == (3, 7, 9)


def test_summarize_key_column_mask_excludes_rows():
    import numpy as np
    s = summarize_key_column("df_0", np.array([1, 100, 2]),
                             np.array([True, False, True]), 16)
    assert (s.min, s.max, s.row_count) == (1, 2, 2)


def test_summarize_key_column_empty_is_prune_everything():
    import numpy as np
    s = summarize_key_column("df_0", np.array([], dtype=np.int64), None, 16)
    assert s.empty and s.row_count == 0 and not s.bounded


def test_summarize_key_column_float_gets_no_bounds():
    import numpy as np
    s = summarize_key_column("df_0", np.array([1.5, 2.5]), None, 16)
    assert s.row_count == 2 and not s.bounded


def test_summarize_respects_distinct_cap():
    import numpy as np
    s = summarize_key_column("df_0", np.arange(100), None, 8)
    assert s.values is None          # over the cap: bounds only
    assert (s.min, s.max) == (0, 99)


def test_summary_merge_widens_and_unions():
    a = DynamicFilterSummary("df_0", 1, 5, (1, 3, 5), 3)
    b = DynamicFilterSummary("df_0", 4, 9, (4, 9), 2)
    m = a.merge(b, max_distinct=16)
    assert (m.min, m.max, m.row_count) == (1, 9, 5)
    assert m.values == (1, 3, 4, 5, 9)
    # union over the cap drops the exact set but keeps bounds
    m2 = a.merge(b, max_distinct=4)
    assert m2.values is None and (m2.min, m2.max) == (1, 9)


def test_summary_merge_with_empty_side_keeps_other_bounds():
    a = DynamicFilterSummary("df_0", 2, 8, (2, 8), 2)
    e = DynamicFilterSummary("df_0", row_count=0)
    m = a.merge(e, max_distinct=16)
    assert (m.min, m.max, m.row_count) == (2, 8, 2)


def test_summary_wire_round_trip():
    s = DynamicFilterSummary("df_1", 3, 7, (3, 7), 2)
    assert DynamicFilterSummary.from_dict(s.to_dict()) == s
    e = DynamicFilterSummary("df_2", row_count=0)
    assert DynamicFilterSummary.from_dict(e.to_dict()).empty


def test_collector_merges_partials_per_filter_id():
    c = DynamicFilterCollector(max_distinct=16)
    c.publish(DynamicFilterSummary("df_0", 1, 4, (1, 4), 2))
    c.publish(DynamicFilterSummary("df_0", 6, 9, (6, 9), 2))
    got = c.get("df_0")
    assert (got.min, got.max, got.row_count) == (1, 9, 4)
    wire = summaries_to_runtime({"df_0": got})
    assert wire["df_0"]["min"] == 1 and wire["df_0"]["rowCount"] == 4


# ---------------------------------------------------------------------------
# exchange decisions
# ---------------------------------------------------------------------------

def test_decide_exchange_flip_needs_big_estimate_gap():
    assert decide_exchange(planned_rows=10_000, observed_rows=100,
                           max_broadcast_rows=5_000)
    # observed close to plan: the planner was right, keep partitioned
    assert not decide_exchange(planned_rows=10_000, observed_rows=4_000,
                               max_broadcast_rows=5_000)
    # observed over the threshold never broadcasts, whatever the plan said
    assert not decide_exchange(planned_rows=10_000_000, observed_rows=6_000,
                               max_broadcast_rows=5_000)
    # absent estimate counts as a wrong estimate
    assert decide_exchange(planned_rows=None, observed_rows=10,
                           max_broadcast_rows=5_000)


def test_decide_side_swap():
    assert decide_side_swap(left_rows=100, right_rows=500)
    assert not decide_side_swap(left_rows=500, right_rows=100)
    assert not decide_side_swap(left_rows=None, right_rows=100)
    assert not decide_side_swap(left_rows=0, right_rows=0)


# ---------------------------------------------------------------------------
# dyn marker resolution + zone pruning
# ---------------------------------------------------------------------------

WIRE = {"df_0": {"filterId": "df_0", "rowCount": 3,
                 "min": 10, "max": 20, "values": [10, 15, 20]}}


def test_resolve_dyn_markers():
    assert resolve_entry_value(["dyn", "df_0", "min"], None, WIRE) == 10
    assert resolve_entry_value(["dyn", "df_0", "max"], None, WIRE) == 20
    assert resolve_entry_value(["dyn", "df_0", "set"], None, WIRE) \
        == (10, 15, 20)
    # unknown filter id / no summaries: unresolved, prune nothing
    assert resolve_entry_value(["dyn", "df_9", "min"], None, WIRE) is None
    assert resolve_entry_value(["dyn", "df_0", "min"], None, None) is None
    # zero-row summary resolves nothing here (empty-build pruning is the
    # scan's own convention, not a comparison value)
    empty = {"df_0": {"filterId": "df_0", "rowCount": 0}}
    assert resolve_entry_value(["dyn", "df_0", "min"], None, empty) is None
    assert is_dyn_marker(["dyn", "df_0", "min"])
    assert not is_dyn_marker(["param", 0])


def test_in_set_unsatisfiable_is_membership_over_zone_range():
    val = (10, 15, 20)
    assert entry_unsatisfiable("eq", val, 21, 30)       # all outside
    assert not entry_unsatisfiable("eq", val, 14, 16)   # 15 inside
    # non-eq ops never use set semantics
    assert not entry_unsatisfiable("lt", val, 21, 30)


class _Zones:
    """chunk_bounds stub: key = row index (identity layout)."""

    def chunk_bounds(self, pos, count):
        return (pos, pos + count - 1)


DYN_PD = [{"column": "k", "op": "gte", "value": ["dyn", "df_0", "min"]},
          {"column": "k", "op": "lte", "value": ["dyn", "df_0", "max"]},
          {"column": "k", "op": "eq", "value": ["dyn", "df_0", "set"]}]


def test_prune_chunks_dyn_attribution():
    chunks = [(0, 100), (100, 100), (200, 100)]   # df_0 covers [10, 20]
    detail = {}
    kept, skipped = prune_chunks(chunks, {"k": _Zones()}, DYN_PD,
                                 None, WIRE, detail=detail)
    assert kept == [(0, 100)] and skipped == 2
    assert detail["dyn_engaged"]
    assert detail["dyn_chunks_pruned"] == 2
    assert detail["dyn_rows_pruned"] == 200
    # callers passing detail own the metering: the registry is untouched
    assert ADAPTIVE_METRICS.snapshot()["filter_chunks_skipped"] == 0


def test_prune_chunks_without_summaries_keeps_everything():
    chunks = [(0, 100), (100, 100)]
    kept, skipped = prune_chunks(chunks, {"k": _Zones()}, DYN_PD, None, None)
    assert kept == chunks and skipped == 0


def test_prune_chunks_keep_one_floor_vs_streaming():
    chunks = [(100, 100), (200, 100)]             # nothing overlaps [10,20]
    kept, _ = prune_chunks(chunks, {"k": _Zones()}, DYN_PD, None, WIRE)
    assert kept == [(100, 100)]                   # fused floor: one survivor
    reset_adaptive_metrics()
    kept, skipped = prune_chunks(chunks, {"k": _Zones()}, DYN_PD, None, WIRE,
                                 keep_one=False)
    assert kept == [] and skipped == 2            # streaming: empty is fine
    assert ADAPTIVE_METRICS.snapshot()["filter_chunks_skipped"] == 2


# ---------------------------------------------------------------------------
# planning: which join types get dynamic filters (and in which direction)
# ---------------------------------------------------------------------------

def _plan(sql):
    return LocalQueryRunner("sf0.01").plan(sql)


def _join_filters(root, cls=P.JoinNode):
    return [n for n in P.walk_plan(root) if isinstance(n, cls)]


def test_inner_join_probe_receives_build_domain():
    root = _plan("SELECT count(*) FROM lineitem, orders "
                 "WHERE l_orderkey = o_orderkey")
    joins = [j for j in _join_filters(root) if j.dynamic_filters]
    assert joins, "INNER join lost its dynamic filter annotation"
    j = joins[0]
    left_names = {v.name for v in j.left.output_variables}
    assert set(j.dynamic_filters) <= left_names, \
        "INNER receiving side must be the probe (left)"


def test_left_join_build_receives_probe_domain():
    root = _plan("SELECT count(*) FROM orders LEFT JOIN lineitem "
                 "ON o_orderkey = l_orderkey")
    joins = [j for j in _join_filters(root) if j.join_type == P.LEFT]
    assert joins
    j = joins[0]
    right_names = {v.name for v in j.right.output_variables}
    assert j.dynamic_filters, "LEFT join build side is prunable"
    assert set(j.dynamic_filters) <= right_names, \
        "LEFT may only ever filter the non-preserved (build) side"


def test_right_join_normalized_and_annotated():
    root = _plan("SELECT count(*) FROM lineitem RIGHT JOIN orders "
                 "ON l_orderkey = o_orderkey")
    joins = _join_filters(root)
    assert joins and all(j.join_type != P.RIGHT for j in joins), \
        "RIGHT joins are normalized to LEFT-with-swapped-sides"
    annotated = [j for j in joins if j.dynamic_filters]
    assert annotated, "normalized RIGHT join keeps a dynamic filter"
    j = annotated[0]
    right_names = {v.name for v in j.right.output_variables}
    assert set(j.dynamic_filters) <= right_names


def test_full_join_gets_no_dynamic_filter():
    root = _plan("SELECT count(*) FROM lineitem FULL JOIN orders "
                 "ON l_orderkey = o_orderkey")
    fulls = [j for j in _join_filters(root) if j.join_type == P.FULL]
    assert fulls
    assert all(not j.dynamic_filters for j in fulls), \
        "both FULL sides are preserved: no filter is safe"


def test_semi_join_positive_membership_annotated():
    root = _plan("SELECT count(*) FROM lineitem WHERE l_orderkey IN "
                 "(SELECT o_orderkey FROM orders WHERE o_orderkey < 50)")
    semis = _join_filters(root, P.SemiJoinNode)
    assert semis
    assert any(s.dynamic_filters for s in semis), \
        "bare positive IN membership may prune the source"


def test_semi_join_negated_membership_not_annotated():
    root = _plan("SELECT count(*) FROM lineitem WHERE l_orderkey NOT IN "
                 "(SELECT o_orderkey FROM orders WHERE o_orderkey < 50)")
    semis = _join_filters(root, P.SemiJoinNode)
    assert semis
    assert all(not s.dynamic_filters for s in semis), \
        "NOT IN survivors are exactly the out-of-domain rows"


def test_runtime_filter_pushdown_reaches_probe_scan():
    root = _plan("SELECT count(*) FROM lineitem, orders "
                 "WHERE l_orderkey = o_orderkey AND o_orderkey < 50")
    scans = {n.table.table_name: n for n in P.walk_plan(root)
             if isinstance(n, P.TableScanNode)}
    li = scans["lineitem"]
    assert li.runtime_filters, "probe scan not annotated"
    fid = li.runtime_filters[0]["id"]
    bounds = {tuple(e["value"]) for e in li.pushdown
              if is_dyn_marker(e["value"])}
    assert bounds == {("dyn", fid, "min"), ("dyn", fid, "max"),
                      ("dyn", fid, "set")}


# ---------------------------------------------------------------------------
# checker: dyn markers must re-derive from the scan's own annotation
# ---------------------------------------------------------------------------

def _dyn_scan_plan(pushdown, runtime_filters):
    from presto_tpu.common.types import BigintType
    from presto_tpu.spi.expr import VariableReferenceExpression as V
    v = V("l_orderkey_0", BigintType())
    scan = P.TableScanNode(
        "s0", P.TableHandle("tpch", "tpch", "lineitem",
                            (("scaleFactor", 0.01),)),
        [v], {v: P.ColumnHandle("orderkey", BigintType())},
        list(pushdown), list(runtime_filters))
    return P.OutputNode("o0", scan, ["l_orderkey"], [v])


def test_checker_accepts_rederivable_dyn_markers():
    from presto_tpu.analysis import check_plan
    out = _dyn_scan_plan(
        [{"column": "orderkey", "op": "gte", "value": ["dyn", "df_0", "min"]},
         {"column": "orderkey", "op": "lte", "value": ["dyn", "df_0", "max"]},
         {"column": "orderkey", "op": "eq", "value": ["dyn", "df_0", "set"]}],
        [{"id": "df_0", "column": "orderkey"}])
    assert check_plan(out) == []


def test_checker_rejects_unannotated_dyn_marker():
    from presto_tpu.analysis import check_plan
    out = _dyn_scan_plan(
        [{"column": "orderkey", "op": "gte",
          "value": ["dyn", "df_9", "min"]}],
        [{"id": "df_0", "column": "orderkey"}])
    diags = check_plan(out)
    assert any("does not re-derive" in d.message for d in diags)


def test_checker_rejects_wrong_op_for_bound():
    from presto_tpu.analysis import check_plan
    out = _dyn_scan_plan(
        [{"column": "orderkey", "op": "lt",
          "value": ["dyn", "df_0", "min"]}],   # min must claim gte
        [{"id": "df_0", "column": "orderkey"}])
    diags = check_plan(out)
    assert any("does not re-derive" in d.message for d in diags)


def test_optimizer_dyn_annotations_validate_clean():
    r = LocalQueryRunner("sf0.01")
    res = r.execute("EXPLAIN (TYPE VALIDATE) SELECT count(*) "
                    "FROM lineitem, orders WHERE l_orderkey = o_orderkey "
                    "AND o_orderkey < 40")
    assert "plan validation PASSED" in res.rows[0][0]


# ---------------------------------------------------------------------------
# end to end: the adaptive path must never change answers
# ---------------------------------------------------------------------------

# the `+ 0` hides the range from the stats calculator
# (UNKNOWN_FILTER_COEFFICIENT), so the PLANNED build (~0.9 x orders) sits
# far above the OBSERVED 29 rows — the flip-to-broadcast setup
AQE_SQL = """
SELECT sum(l_extendedprice * (1 - l_discount)) AS revenue, count(*) AS cnt
FROM lineitem, orders
WHERE l_orderkey = o_orderkey AND o_orderkey + 0 < 30
"""

_AQE_CFG = dict(batch_rows=1 << 14, storage_zone_rows=4096)


def _dist_runner(**over):
    cfg = ExecutionConfig(**{**_AQE_CFG, **over})
    return DistributedQueryRunner("sf0.01", config=cfg, n_tasks=2,
                                  join_max_broadcast_table_size=64 << 10)


def test_adaptive_on_off_fallback_bit_identical():
    oracle = LocalQueryRunner("sf0.01").execute_reference(AQE_SQL)
    on = _dist_runner().execute(AQE_SQL)
    _assert_rows_equal(on, oracle, ordered=False)
    m = ADAPTIVE_METRICS.snapshot()
    assert m["filters_collected"] > 0
    assert m["filters_applied"] > 0
    assert m["filter_rows_pruned"] > 0 or m["filter_chunks_skipped"] > 0

    reset_adaptive_metrics()
    off = _dist_runner(dynamic_filtering=False,
                       adaptive_exchange=False).execute(AQE_SQL)
    _assert_rows_equal(off, oracle, ordered=False)
    assert not any(ADAPTIVE_METRICS.snapshot().values()), \
        "adaptive=off must leave no adaptive footprint"

    # wait-timeout fallback: a 0s wait means scans may run unfiltered —
    # results must be identical anyway (pruning is advisory)
    fb = _dist_runner(dynamic_filtering_wait_timeout_s=0.0).execute(AQE_SQL)
    _assert_rows_equal(fb, oracle, ordered=False)


def test_underestimated_build_flips_partitioned_to_broadcast():
    """Build observed (29) >= 10x below planned (~13.5k): the consumer
    stage must launch against a broadcast edge, visible in the metrics
    registry AND the EXPLAIN ANALYZE footer."""
    r = _dist_runner()
    sub, _names, _types = r.plan_subplan(AQE_SQL)
    joins = [n for s in _walk_stages(sub) for n in P.walk_plan(s.root)
             if isinstance(n, P.JoinNode)]
    assert any(j.distribution == P.PARTITIONED for j in joins), \
        "test premise broken: the join must PLAN partitioned"
    res = r.execute(AQE_SQL)
    oracle = LocalQueryRunner("sf0.01").execute_reference(AQE_SQL)
    _assert_rows_equal(res, oracle, ordered=False)
    assert ADAPTIVE_METRICS.snapshot()["exchange_broadcast_flips"] >= 1

    analyzed = r.execute("EXPLAIN ANALYZE " + AQE_SQL).rows[0][0]
    assert "flipped to broadcast" in analyzed
    assert "Dynamic filters:" in analyzed


def _walk_stages(subplan):
    yield subplan.fragment
    for c in subplan.children:
        yield from _walk_stages(c)


def test_explain_analyze_footer_reports_prune_fraction():
    r = _dist_runner()
    text = r.execute("EXPLAIN ANALYZE " + AQE_SQL).rows[0][0]
    line = next(ln for ln in text.splitlines()
                if ln.startswith("Dynamic filters:"))
    # "Dynamic filters: N collected, M applied, X% rows pruned"
    assert "collected" in line and "applied" in line \
        and "rows pruned" in line
    pct = float(line.split("applied,")[1].split("%")[0])
    assert pct > 0.0, line


# ---------------------------------------------------------------------------
# history-based sizing
# ---------------------------------------------------------------------------

AGG_SQL = "SELECT o_orderstatus, count(*) FROM orders GROUP BY o_orderstatus"


def test_local_repeat_run_sizes_from_history():
    from presto_tpu.telemetry.history import QueryHistoryStore
    hist = QueryHistoryStore()
    cfg = ExecutionConfig(adaptive_history_sizing=True)
    r = LocalQueryRunner("sf0.01", config=cfg, history=hist)
    first = r.execute(AGG_SQL)
    rec = hist.list()[0]
    assert rec["planTemplate"] and rec["aggGroups"] == len(first.rows)

    reset_adaptive_metrics()
    second = r.execute(AGG_SQL)
    assert second.rows == first.rows
    assert ADAPTIVE_METRICS.snapshot()["history_sized_queries"] >= 1
    # the sized config is what the compiler actually sees: 3 observed
    # groups -> 256-slot floor instead of the 4096 default estimate path
    sized = r._history_sized_config()
    assert sized.history_agg_groups == len(first.rows)
    assert sized.history_agg_groups != cfg.history_agg_groups


def test_history_sizing_off_by_default():
    from presto_tpu.telemetry.history import QueryHistoryStore
    hist = QueryHistoryStore()
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(), history=hist)
    r.execute(AGG_SQL)
    r.execute(AGG_SQL)
    # recording still happens (the store was attached), but nothing is
    # CONSUMED unless adaptive.history-sizing is on
    assert hist.list()
    assert ADAPTIVE_METRICS.snapshot()["history_sized_queries"] == 0


def test_distributed_repeat_run_seeds_task_count():
    from presto_tpu.sql import parser as A
    from presto_tpu.telemetry.history import QueryHistoryStore
    hist = QueryHistoryStore()
    cfg = ExecutionConfig(adaptive_history_sizing=True)
    r = DistributedQueryRunner("sf0.01", config=cfg, n_tasks=4,
                               history=hist)
    first = r.execute(AGG_SQL)
    assert hist.list(), "distributed run must record its template"

    ast = A.parse_sql(AGG_SQL)
    restore = r._apply_history_sizing(ast)
    try:
        assert r.config.history_agg_groups == len(first.rows)
        # 3 observed result rows: one hash task is plenty (vs n_tasks=4)
        assert r._history_tasks == 1
        assert r._scheduler_config().hash_tasks == 1
    finally:
        restore()
    assert r.config.history_agg_groups is None
    second = r.execute(AGG_SQL)
    assert sorted(second.rows) == sorted(first.rows)


def test_plan_cache_rekeys_on_history_hint():
    """history_agg_groups is part of the config fingerprint: a repeat run
    with a fresh hint must not reuse the unhinted compiled plan."""
    from presto_tpu.sql.canonical import cache_key_from_parts
    cfg = ExecutionConfig()
    hinted = dataclasses.replace(cfg, history_agg_groups=512)
    assert cache_key_from_parts("t", cfg, "tpch", "sf0.01") \
        != cache_key_from_parts("t", hinted, "tpch", "sf0.01")
