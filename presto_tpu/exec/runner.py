"""LocalQueryRunner: SQL string -> results, single process.

The analog of the reference LocalQueryRunner
(presto-main-base/.../testing/LocalQueryRunner.java:304): full
parse -> plan -> execute in one process with no HTTP, used for engine and
planner correctness tests and as the execution core the worker shell drives.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional

from ..common.block import block_to_values
from ..common.page import Page
from ..sql.planner import Planner
from .pipeline import (ExecutionConfig, PlanCompiler, TaskContext,
                       tuned_config)


@dataclass
class QueryResult:
    column_names: List[str]
    column_types: List
    rows: List[List]
    # per-query RuntimeStats map (§5.1; populated by the runners)
    runtime_stats: dict = None
    # the records behind it (`RuntimeStats.timelines()`) where the run's
    # stats were its own: a library call's, a micro-batched launch's
    timeline: list = None
    # statement-protocol side channel: PREPARE sets (name, text) so the
    # server can answer with X-Presto-Added-Prepare; DEALLOCATE the name
    added_prepare: tuple = None
    deallocated_prepare: str = None
    # device-profiler capture directory when the `profile` session
    # property was set (telemetry/profiler.py); None when not captured
    profile_trace_dir: Optional[str] = None

    def sorted_rows(self):
        return sorted(self.rows, key=lambda r: tuple(
            (v is None, str(type(v)), v) for v in r))


def plan_template_digest(template_sk) -> str:
    """Stable short digest of a parameterized plan's structural key — the
    join key between a query-history record ("planTemplate") and a later
    run of the same canonical plan (adaptive.history-sizing)."""
    import hashlib
    return hashlib.sha256(repr(template_sk).encode()).hexdigest()[:16]


_history_qid = itertools.count()


def _close_query(result: QueryResult, stats, began_ns: int) -> None:
    """A run under RuntimeStats of its own is the query level: its result
    carries its records and, in `runtime_stats`, the partition of its
    wall (telemetry/query_wall.py) from `began_ns` to now."""
    from ..telemetry.query_wall import runtime_stats_keys
    from ..utils.runtime_stats import unix_ns
    result.timeline = stats.timelines()
    result.runtime_stats = {
        **stats.to_dict(),
        **runtime_stats_keys(result.timeline, began_ns, unix_ns())}


def pages_to_result(pages, names, types) -> "QueryResult":
    """Decode host pages into a QueryResult row list."""
    rows: List[List] = []
    for page in pages:
        cols = [block_to_values(t, b) for t, b in zip(types, page.blocks)]
        for i in range(page.position_count):
            rows.append([c[i] for c in cols])
    return QueryResult(names, types, rows)


@dataclass
class _Execution:
    """One checked-out canonical-cache execution: the optimized template,
    an exclusively-owned compiler, and how to give both back (insert on
    miss, checkin on hit) after a SUCCESSFUL run — a failed run may leave
    the compiler's memory pool / partial state poisoned, so nothing is
    returned to the cache."""
    output: object                      # optimized OutputNode template
    compiler: PlanCompiler
    key: str
    fresh: bool                         # miss: insert; hit: checkin
    slot_types: list


class LocalQueryRunner:
    def __init__(self, schema: str = "sf0.01",
                 config: Optional[ExecutionConfig] = None,
                 catalog: str = "tpch", tracer_provider=None,
                 plan_cache=None, history=None):
        from ..serving import GLOBAL_PLAN_CACHE
        self.schema = schema
        self.catalog = catalog
        self.tracer_provider = tracer_provider   # utils.runtime_stats
        self.config = config or tuned_config()
        # optional telemetry.history.QueryHistoryStore: successful runs
        # record template-keyed observations, and — when the
        # adaptive.history-sizing knob is on — a repeat of the same plan
        # template seeds its aggregation-table size from the record
        self.history = history
        self._last_template_digest: Optional[str] = None
        # canonical plan/executable cache (presto_tpu/serving): keyed by
        # catalog + schema + config fingerprint + the structural key of
        # the PARAMETERIZED pre-optimizer plan, so re-executions with
        # different literal constants reuse the optimized template and
        # the compiled pipeline (jitted steps stay warm).  Process-global
        # by default; tests pass their own PlanCache for isolation.
        self.plan_cache = plan_cache if plan_cache is not None \
            else GLOBAL_PLAN_CACHE
        # session-scoped prepared statements (name -> SQL text); the HTTP
        # path passes its header map per call instead
        self._prepared: Dict[str, str] = {}
        # EXPLAIN ANALYZE side channel: node id -> operator stats from the
        # most recent analyzed execution (bench / tooling read this)
        self.last_operator_stats: Optional[dict] = None

    def _validation(self):
        """Scope plan validation (presto_tpu/analysis) to this runner's
        configured mode for the duration of a planning call."""
        from ..analysis import use_validation_mode
        return use_validation_mode(self.config.plan_validation)

    def plan(self, sql: str):
        with self._validation():
            return Planner(default_schema=self.schema,
                           default_catalog=self.catalog).plan(sql)

    # -- canonical plan cache ---------------------------------------------

    def _checkout(self, ast, stats, bound_params=None,
                  record_fast=None) -> _Execution:
        """Plan `ast` to the parameterized template, then check the
        canonical cache: a hit skips optimize (and, when a pooled compiler
        is available, every compiled XLA step); a miss optimizes and
        builds a compiler.  Either way the returned compiler's context
        carries the execution's bound-parameter vector."""
        from ..sql.canonical import cache_key_from_parts, parameterize
        from ..spi import plan as P
        with stats.span("queryPlan"), self._validation():
            planner = Planner(default_schema=self.schema,
                              default_catalog=self.catalog,
                              bound_params=bound_params)
            unopt = planner.plan_query_unoptimized(ast)
        pp = parameterize(unopt)
        # structural key taken BEFORE optimization (the optimizer mutates
        # the template in place) — it must match what the prepared fast
        # path re-derives from its recorded template_key
        template_sk = P.structural_key(pp.template)
        self._last_template_digest = plan_template_digest(template_sk)
        # adaptive.history-sizing: the effective config may carry the
        # prior run's observed group count — a fingerprinted field, so
        # the cache key below re-keys on a changed hint
        cfg = self._history_sized_config()
        key = cache_key_from_parts(template_sk, cfg, self.catalog,
                                   self.schema)
        hit = self.plan_cache.checkout(key)
        if hit is not None:
            output, slot_types, compiler = hit
            if compiler is None:
                # pooled compilers all checked out by concurrent
                # executions: rebuild one from the cached template —
                # parse/plan/optimize were still skipped
                compiler = self._new_compiler(cfg, stats)
            exe = _Execution(output, compiler, key, False,
                             list(slot_types))
        else:
            with stats.span("queryOptimize"), self._validation():
                output = Planner.optimize_output(pp.template)
            compiler = self._new_compiler(cfg, stats)
            exe = _Execution(output, compiler, key, True,
                             [s.type for s in pp.slots])
        if record_fast is not None and pp.origins_complete:
            from ..serving.prepared import FastPath
            record_fast(FastPath(
                template_sk,
                [(s.origin, s.type,
                  None if s.origin is not None else s.value)
                 for s in pp.slots]))
        self._bind(exe, [s.value for s in pp.slots])
        return exe

    @staticmethod
    def _new_compiler(cfg, stats) -> PlanCompiler:
        """A compiler for a miss or an exhausted pool: host Python only,
        part of the query's `pipelineBuild`."""
        from ..serving import SERVING_METRICS
        with stats.span("pipelineBuild"):
            compiler = PlanCompiler(TaskContext(config=cfg))
        SERVING_METRICS.incr("executable_builds")
        return compiler

    def _bind(self, exe: _Execution, values) -> None:
        from ..sql.canonical import device_params
        if exe.slot_types:
            dev, host = device_params(values, exe.slot_types)
            exe.compiler.ctx.params = dev
            exe.compiler.ctx.params_fingerprint = host
        else:
            exe.compiler.ctx.params = None
            exe.compiler.ctx.params_fingerprint = None

    def _release(self, exe: _Execution) -> None:
        """Return the compiler to the cache after a successful run."""
        if exe.fresh:
            self.plan_cache.insert(exe.key, exe.output, exe.slot_types,
                                   exe.compiler)
        else:
            self.plan_cache.checkin(exe.key, exe.compiler)

    # -- history-based sizing (adaptive.history-sizing) -------------------

    def _history_record(self) -> Optional[dict]:
        if (self.history is None or self._last_template_digest is None
                or not self.config.adaptive_history_sizing):
            return None
        return self.history.find_by_template(self._last_template_digest)

    def _history_sized_config(self) -> ExecutionConfig:
        """A prior FINISHED run of the same plan template seeds the
        aggregation table size: the observed group count replaces the
        optimizer's estimate (exec/pipeline.py initial_slots)."""
        rec = self._history_record()
        groups = (rec or {}).get("aggGroups")
        if not groups:
            return self.config
        import dataclasses

        from .adaptive import ADAPTIVE_METRICS
        ADAPTIVE_METRICS.incr("history_sized_queries")
        return dataclasses.replace(self.config,
                                   history_agg_groups=int(groups))

    def _record_history(self, result: QueryResult, root,
                        subplan=None) -> None:
        """Record one template-keyed observation after a successful run.
        aggGroups is recorded only when the output chain is
        Output -> (Project|Sort)* -> grouped Aggregation, where the
        result row count IS the observed group count."""
        if self.history is None or self._last_template_digest is None:
            return
        from ..spi import plan as P
        node = getattr(root, "source", None)
        while isinstance(node, (P.ProjectNode, P.SortNode,
                                P.RemoteSourceNode)):
            if isinstance(node, P.RemoteSourceNode):
                # distributed: the chain continues in the (sole) child
                # fragment feeding this gather edge
                if subplan is None or len(node.source_fragment_ids) != 1:
                    break
                by_id = {c.fragment.fragment_id: c
                         for c in subplan.children}
                child = by_id.get(node.source_fragment_ids[0])
                if child is None:
                    break
                subplan, node = child, child.fragment.root
            else:
                node = node.source
        rec = {"queryId": f"run-{next(_history_qid)}",
               "state": "FINISHED",
               "planTemplate": self._last_template_digest,
               "rows": len(result.rows),
               "peakMemoryBytes": getattr(result, "peak_memory_bytes",
                                          0) or 0}
        if isinstance(node, P.AggregationNode) and node.grouping_keys:
            rec["aggGroups"] = len(result.rows)
        try:
            self.history.record(rec)
        except Exception:   # noqa: BLE001 — history is advisory
            pass

    # -- prepared statements ----------------------------------------------

    def _prepared_text(self, name: str, prepared) -> str:
        text = (prepared or {}).get(name) or self._prepared.get(name)
        if text is None:
            raise KeyError(f"prepared statement {name!r} does not exist")
        return text

    def _execute_prepared(self, ast, stats, prepared) -> _Execution:
        """EXECUTE name USING v1, ... -> a ready _Execution.  The fast
        path (statement seen before, all origins extracted) rebuilds the
        cache key from recorded slots and skips parse+plan entirely; any
        mismatch — unbindable value, NULL, cold cache — replans with the
        USING values bound into the planner."""
        from ..serving import PREPARED_REGISTRY, SERVING_METRICS
        from ..sql.canonical import (BindError, cache_key_from_parts,
                                     literal_value)
        text = self._prepared_text(ast.name, prepared)
        ps = PREPARED_REGISTRY.get_or_parse(text)
        if len(ast.values) != ps.param_count:
            raise ValueError(
                f"prepared statement {ast.name!r} expects "
                f"{ps.param_count} parameters, got {len(ast.values)}")
        fast = ps.fast
        if fast is not None:
            try:
                raw = [literal_value(v) for v in ast.values]
                values = fast.bind(raw)
            except BindError:
                values = None
            if values is not None:
                self._last_template_digest = \
                    plan_template_digest(fast.template_key)
                key = cache_key_from_parts(fast.template_key, self.config,
                                           self.catalog, self.schema)
                hit = self.plan_cache.checkout(key)
                if hit is not None:
                    output, slot_types, compiler = hit
                    if compiler is None:
                        compiler = self._new_compiler(self.config, stats)
                    exe = _Execution(output, compiler, key, False,
                                     list(slot_types))
                    self._bind(exe, values)
                    SERVING_METRICS.incr("prepared_fast_path")
                    return exe
        # full pipeline with the USING values bound into the planner;
        # record the fast path for the NEXT execution of this statement
        SERVING_METRICS.incr("prepared_replans")
        return self._checkout(ps.statement, stats,
                              bound_params=list(ast.values),
                              record_fast=ps.record_fast_path)

    # -- micro-batched execution ------------------------------------------

    def execute_prepared_batch(self, sqls: List[str], prepared=None
                               ) -> Optional[List[Optional[QueryResult]]]:
        """Execute N concurrent EXECUTE..USING statements that share one
        prepared template as ONE device launch (serving/batched.py).

        `prepared` is a name->text map, or a list of such maps aligned
        with `sqls` (the HTTP path carries per-request header maps).
        Returns a list aligned with `sqls` — QueryResult for every lane
        served by the batched drain, None for lanes the caller must run
        sequentially (bind errors, arity mismatches: their solo run
        raises the right per-query error) — or None when no batch was
        possible at all (cold template, ineligible plan shape, cache
        miss).  Every returned lane's rows are bit-identical to a solo
        run: the vmapped program replays the sequential fused path's
        exact update sequence per lane."""
        from ..serving import PREPARED_REGISTRY, SERVING_METRICS
        from ..serving.batched import batched_runner_for, disable_for
        from ..sql import parser as A
        from ..sql.canonical import (BindError, cache_key_from_parts,
                                     device_params, literal_value)
        if len(sqls) < 2:
            return None
        pmaps = (list(prepared) if isinstance(prepared, (list, tuple))
                 else [prepared] * len(sqls))
        text = None
        asts = []
        try:
            for s, pm in zip(sqls, pmaps):
                ast = A.parse_sql(s)
                if not isinstance(ast, A.ExecuteStmt):
                    return None
                t = self._prepared_text(ast.name, pm)
                if text is None:
                    text = t
                elif t != text:
                    return None     # mixed templates: not one batch
                asts.append(ast)
        except Exception:   # noqa: BLE001 — unknown name etc: sequential
            return None
        ps = PREPARED_REGISTRY.get_or_parse(text)
        fast = ps.fast
        if fast is None:
            return None             # cold: a solo run records the path
        values_by_lane: List[Optional[list]] = [None] * len(sqls)
        for i, ast in enumerate(asts):
            if len(ast.values) != ps.param_count:
                continue            # isolated arity error -> solo run
            try:
                raw = [literal_value(v) for v in ast.values]
                values_by_lane[i] = fast.bind(raw)
            except BindError:
                continue            # isolated bind error -> solo run
        lanes = [i for i, v in enumerate(values_by_lane) if v is not None]
        if len(lanes) < 2:
            return None
        key = cache_key_from_parts(fast.template_key, self.config,
                                   self.catalog, self.schema)
        hit = self.plan_cache.checkout(key)
        if hit is None:
            return None
        output, slot_types, compiler = hit
        # the launch serves every lane: it records into a RuntimeStats of
        # its own (not the leader's, whose thread this is), and each
        # lane's result carries that whole map -- what the launch that
        # served it did -- beside `servingBatchOccupancy` to divide by
        from ..utils.runtime_stats import RuntimeStats
        batch_stats = RuntimeStats()
        if compiler is None:
            compiler = self._new_compiler(self.config, batch_stats)
        exe = _Execution(output, compiler, key, False, list(slot_types))
        if not exe.slot_types:
            self.plan_cache.checkin(key, compiler)
            return None
        self._bind(exe, values_by_lane[lanes[0]])
        with batch_stats.activate():
            runner = batched_runner_for(compiler, output)
            if runner is None:
                self.plan_cache.checkin(key, compiler)
                return None
            dev_list = [device_params(values_by_lane[i],
                                      exe.slot_types)[0] for i in lanes]
            try:
                pages, launch_ns, demux_ns = runner.run(dev_list)
            except Exception:   # noqa: BLE001 — whole drain failed: the
                # compiler may be poisoned (not returned to the pool) and
                # the template is pinned sequential; every lane re-runs
                # solo
                disable_for(compiler)
                return None
        batch_stats.add("servingBatchOccupancy", len(lanes))
        batch_stats.add("servingBatchLaunchNanos", launch_ns, "NANO")
        lane_stats = batch_stats.to_dict()
        lane_lines = batch_stats.timelines("batch")
        self._last_template_digest = plan_template_digest(
            fast.template_key)
        names = output.column_names
        types = [v.type for v in output.outputs]
        results: List[Optional[QueryResult]] = [None] * len(sqls)
        width = 1 << max(0, len(lanes) - 1).bit_length()
        for j, i in enumerate(lanes):
            res = pages_to_result([pages[j]], names, types)
            res.peak_memory_bytes = (compiler.ctx.memory.peak
                                     if compiler.ctx.memory is not None
                                     else 0)
            res.runtime_stats = dict(lane_stats)
            res.timeline = lane_lines
            results[i] = res
            SERVING_METRICS.incr("prepared_fast_path")
            self._record_history(res, output)
        self._release(exe)
        SERVING_METRICS.record_batch(len(lanes), demux_ns,
                                     padded_lanes=width - len(lanes))
        return results

    # -- execution --------------------------------------------------------

    def execute(self, sql: str, prepared: Optional[Dict[str, str]] = None
                ) -> QueryResult:
        from contextlib import ExitStack

        from ..utils.runtime_stats import (RuntimeStats, current_stats,
                                           unix_ns)
        tracer = self.tracer_provider.new_tracer(sql) \
            if self.tracer_provider else None
        # the statement executor's stats when it set one (the query's
        # RuntimeStats in QueryInfo), else this execution's own
        owner = current_stats()
        stats = owner or RuntimeStats(tracer=tracer, root="query")
        began = unix_ns()
        with ExitStack() as stack:
            root = stack.enter_context(tracer.span("query", sql=sql)) \
                if tracer else None
            stack.enter_context(stats.activate(parent_span=root))
            return self._execute_owned(
                sql, prepared, stats, None if owner else began)

    def _execute_owned(self, sql: str, prepared, stats,
                       began: Optional[int] = None) -> QueryResult:
        """`execute` under its RuntimeStats, which owns the thread: the
        pipeline's launches and host syncs and JAX's events record into
        it beside the phases below.  `began` (unix ns) where the stats
        are this execution's own: it is then the query level."""
        from ..common.types import BOOLEAN
        from ..serving import PREPARED_REGISTRY
        from ..sql import parser as A
        with stats.span("queryParse"):
            ast = A.parse_sql(sql)
        if isinstance(ast, A.Explain):
            return self._explain(ast)
        if isinstance(ast, (A.CreateTableAs, A.InsertInto, A.DropTable)):
            return self._execute_ddl(ast)
        if isinstance(ast, A.Prepare):
            self._prepared[ast.name] = ast.text
            PREPARED_REGISTRY.get_or_parse(ast.text)   # warm the memo
            res = QueryResult(["result"], [BOOLEAN], [[True]])
            res.added_prepare = (ast.name, ast.text)
            return res
        if isinstance(ast, A.Deallocate):
            self._prepared.pop(ast.name, None)
            res = QueryResult(["result"], [BOOLEAN], [[True]])
            res.deallocated_prepare = ast.name
            return res
        if isinstance(ast, A.ExecuteStmt):
            exe = self._execute_prepared(ast, stats, prepared)
        else:
            exe = self._checkout(ast, stats)
        output, compiler = exe.output, exe.compiler
        names = output.column_names
        types = [v.type for v in output.outputs]
        # operators add fine-grained counters (grouped bucket walls, ...)
        compiler.ctx.runtime_stats = stats
        from ..telemetry import profile_capture
        with profile_capture(self.config.profile_dir, "query",
                             enabled=self.config.profile) as trace_dir:
            with stats.span("queryExecute"):
                with stats.span("pipelineBuild"):
                    src = compiler.compile_root(output)
                result = pages_to_result(
                    compiler.source_to_pages(src), names, types)
        result.profile_trace_dir = trace_dir
        result.runtime_stats = stats.to_dict()
        if began is not None:
            _close_query(result, stats, began)
        # peak MemoryPool reservation, for QueryCompletedEvent enrichment
        result.peak_memory_bytes = (compiler.ctx.memory.peak
                                    if compiler.ctx.memory is not None
                                    else 0)
        self._release(exe)
        self._record_history(result, output)
        return result

    def execute_streaming(self, sql: str,
                          prepared: Optional[Dict[str, str]] = None):
        """(columns-meta, row iterator) for a plain SELECT — pages are
        decoded and yielded as they are produced, so callers (the
        statement protocol) never hold the full result set (reference
        Query.java:116 streams from the root-stage ExchangeClient).
        Returns None for statements that need materialized execution
        (DDL / EXPLAIN / PREPARE / DEALLOCATE)."""
        from ..sql import parser as A
        from ..utils.runtime_stats import RuntimeStats, current_stats
        stats = current_stats() or RuntimeStats()
        with stats.span("queryParse"):
            ast = A.parse_sql(sql)
        if isinstance(ast, (A.Explain, A.CreateTableAs, A.InsertInto,
                            A.DropTable, A.Prepare, A.Deallocate)):
            return None
        if isinstance(ast, A.ExecuteStmt):
            exe = self._execute_prepared(ast, stats, prepared)
        else:
            exe = self._checkout(ast, stats)
        output, compiler = exe.output, exe.compiler
        names = output.column_names
        types = [v.type for v in output.outputs]
        compiler.ctx.runtime_stats = stats
        columns = [{"name": n, "type": str(t)}
                   for n, t in zip(names, types)]

        def rows():
            # runs on the thread that pulls the rows: the statement layer
            # makes the query's stats that thread's owner meanwhile
            from ..common.block import block_to_values
            with stats.span("queryExecute"):
                with stats.span("pipelineBuild"):
                    src = compiler.compile_root(output)
                for page in compiler.source_to_pages(src):
                    cols = [block_to_values(t, b)
                            for t, b in zip(types, page.blocks)]
                    for i in range(page.position_count):
                        yield [c[i] for c in cols]
            # release only after a fully successful drain (mirrors execute)
            self._release(exe)
        return columns, rows(), stats

    def _execute_ddl(self, ast) -> QueryResult:
        """CREATE TABLE AS / INSERT INTO / DROP TABLE (reference
        DataDefinitionExecution + TableWriter/TableFinish plans; writes run
        through the normal pipeline compiler)."""
        from ..common.types import BIGINT
        from ..connectors import catalog as cat
        from ..sql import parser as A
        writable = [cid for cid in cat._CONNECTORS
                    if hasattr(cat.module(cid), "begin_write")]
        if isinstance(ast, A.DropTable):
            # droppable catalogs win the name lookup: a generated tpch
            # table of the same name must not shadow the stored one
            cid = next((c for c in writable
                        if ast.table in cat.module(c).SCHEMAS), None)
            if cid is None or not hasattr(cat.module(cid), "drop_table"):
                if ast.if_exists:
                    return QueryResult(["rows"], [BIGINT], [[0]])
                raise KeyError(f"unknown or non-droppable table "
                               f"{ast.table!r}")
            # cached plans may reference the dropped table
            self._invalidate_plans()
            cat.module(cid).drop_table(ast.table)
            return QueryResult(["rows"], [BIGINT], [[0]])
        if isinstance(ast, A.CreateTableAs) and ast.if_not_exists:
            # IF NOT EXISTS consults only writable catalogs: a read-only
            # generated table of the same name does not shadow the target
            if any(ast.table in cat.module(cid).SCHEMAS for cid in writable):
                return QueryResult(["rows"], [BIGINT], [[0]])
        with self._validation():
            output = Planner(default_schema=self.schema,
                             default_catalog=self.catalog).plan_write(ast)
        compiler = PlanCompiler(TaskContext(config=self.config))
        names = output.column_names
        types = [v.type for v in output.outputs]
        # writes invalidate any cached plans that scanned the target table
        self._invalidate_plans()
        return pages_to_result(compiler.run_to_pages(output), names, types)

    def _invalidate_plans(self) -> None:
        """DDL changed table contents: every cached plan/executable (and
        every recorded prepared fast path, whose template keys assume the
        old tables) may be stale."""
        from ..serving import PREPARED_REGISTRY
        from ..serving.builds import invalidate_compiled
        self.plan_cache.invalidate_all()
        PREPARED_REGISTRY.invalidate_fast_paths()
        invalidate_compiled()

    def _explain(self, ast) -> QueryResult:
        """EXPLAIN: plan text.  EXPLAIN ANALYZE: execute with per-node
        instrumentation and annotate the plan (reference PlanPrinter /
        ExplainAnalyzeOperator).  EXPLAIN (TYPE VALIDATE): run the plan
        checker at every stage and print the diagnostic list."""
        from ..common.types import VarcharType
        from ..sql.explain import format_analyze_footer, format_plan
        from ..utils.runtime_stats import RuntimeStats
        if ast.explain_type == "VALIDATE":
            return self._explain_validate(ast)
        with self._validation():
            output = Planner(default_schema=self.schema,
                             default_catalog=self.catalog) \
                .plan_query_to_output(ast.query)
        stats = rstats = None
        trace_dir = None
        if ast.analyze:
            # fusion stays ENABLED: the fused chain emits device-side row
            # counters as extra jit outputs, so this profiles the real
            # execution path.  analyze_unfused retains the old per-node
            # interpreted profiling.
            from ..telemetry import profile_capture
            stats = {}
            rstats = RuntimeStats()
            ctx = TaskContext(config=self.config, stats=stats,
                              runtime_stats=rstats)
            compiler = PlanCompiler(ctx)
            # local EXPLAIN ANALYZE runs single-driver on this thread:
            # sample thread CPU at the same driver boundary the
            # scheduler/worker paths use so the footer's CPU-vs-wall
            # line is populated here too
            import time as _t
            from ..telemetry.query_wall import with_partition
            from ..utils.runtime_stats import unix_ns
            began = unix_ns()
            t0 = _t.perf_counter()  # lint: allow-wall-clock
            c0 = _t.thread_time()
            with profile_capture(self.config.profile_dir, "analyze",
                                 enabled=self.config.profile) as trace_dir:
                with rstats.activate(), rstats.span("queryExecute"):
                    for _page in compiler.run_to_pages(output):
                        pass
            rstats.add("driverCpuNanos",
                       (_t.thread_time() - c0) * 1e9, "NANO")
            rstats.add("driverWallNanos",
                       (_t.perf_counter() - t0) * 1e9, "NANO")  # lint: allow-wall-clock
            self.last_operator_stats = stats
            # the analysed run is the query here: its wall, partitioned
            rstats = with_partition(rstats, began)
        text = format_plan(output, stats)
        if rstats is not None:
            footer = format_analyze_footer(rstats, profile_dir=trace_dir)
            if footer:
                text += "\n\n" + footer
        return QueryResult(["Query Plan"], [VarcharType(max(1, len(text)))],
                           [[text]], runtime_stats=rstats)

    def _fragmenter_config(self):
        from ..sql.fragmenter import FragmenterConfig
        return FragmenterConfig()

    def _explain_validate(self, ast) -> QueryResult:
        """EXPLAIN (TYPE VALIDATE): run every checker stage (post-plan,
        post-optimize, post-fragment) with fail-fast raising DISABLED so
        the full diagnostic list is reported instead of the first error —
        the debugging surface for a plan the validator rejects."""
        from ..analysis import (VALIDATION_OFF, check_plan, check_subplan,
                                use_validation_mode)
        from ..common.types import VarcharType
        from ..sql.explain import format_validation
        from ..sql.fragmenter import plan_distributed
        from ..sql.optimizer import optimize
        from ..spi import plan as P
        planner = Planner(default_schema=self.schema,
                          default_catalog=self.catalog)
        with use_validation_mode(VALIDATION_OFF):
            node, names, out_vars = planner.plan_query_any(ast.query)
            out = P.OutputNode(planner.new_id("output"), node, names,
                               out_vars)
            sections = [("post-plan", check_plan(out, "post-plan"))]
            out = optimize(out)
            sections.append(("post-optimize",
                             check_plan(out, "post-optimize")))
            # scan-pushdown decisions: collected BEFORE fragmentation
            # (plan_distributed moves the scans into fragment subplans,
            # mutating this tree); appended OUTSIDE format_validation so
            # informational entries don't count as diagnostics
            seen, decisions = set(), []
            for n in P.walk_plan(out):
                if id(n) in seen or not isinstance(n, P.TableScanNode):
                    continue
                seen.add(id(n))
                tname = f"{n.table.connector_id}.{n.table.table_name}"
                if getattr(n, "pushdown", None):
                    for e in n.pushdown:
                        decisions.append(
                            f"  {tname} [{n.id}]: "
                            f"{e['column']} {e['op']} {e['value']}")
                else:
                    decisions.append(f"  {tname} [{n.id}]: (no pushdown)")
            subplan = plan_distributed(out, self._fragmenter_config())
            from ..parallel.mesh import mesh_size
            from ..sql.fragmenter import annotate_exchange_fabrics
            annotate_exchange_fabrics(
                subplan, exec_config=self.config,
                mesh_size=mesh_size(getattr(self, "mesh", None)),
                batch_mode=getattr(self, "_batch_mode", False))
            sections.append(("post-fragment",
                             check_subplan(subplan, "post-fragment",
                                           exec_config=self.config)))
        text = format_validation(sections)
        text += "\n\n== scan-pushdown ==\n" + "\n".join(
            decisions if decisions else ["  (no table scans)"])
        return QueryResult(["Query Plan"], [VarcharType(max(1, len(text)))],
                           [[text]])

    def execute_reference(self, sql: str) -> QueryResult:
        """Same query through the numpy reference interpreter (the oracle).

        Per-node {rows, wall_s, batches} land in
        `last_reference_operator_stats` keyed by plan-node id, so
        differential tests can diff the stats surface against the
        engine's EXPLAIN ANALYZE / QueryInfo counters too."""
        from .reference import execute_reference
        output = self.plan(sql)
        stats: dict = {}
        rows = execute_reference(output, stats=stats)
        self.last_reference_operator_stats = stats
        types = [v.type for v in output.outputs]
        return QueryResult(output.column_names, types, rows)

    def assert_same_as_reference(self, sql: str, ordered: bool = False):
        got = self.execute(sql)
        exp = self.execute_reference(sql)
        _assert_rows_equal(got, exp, ordered)
        return got


class DistributedQueryRunner(LocalQueryRunner):
    """Plans with exchange insertion + fragmentation and executes the fragment
    DAG as multi-task stages through the in-process scheduler — the analog of
    the reference DistributedQueryRunner (presto-tests/.../DistributedQueryRunner.java:108)
    with in-process "workers"."""

    def __init__(self, schema: str = "sf0.01",
                 config: Optional[ExecutionConfig] = None,
                 n_tasks: int = 2,
                 join_max_broadcast_table_size: int = 100 << 20,
                 catalog: str = "tpch", mesh=None, tracer_provider=None,
                 history=None):
        super().__init__(schema, config, catalog,
                         tracer_provider=tracer_provider, history=history)
        self.n_tasks = n_tasks
        self.join_max_broadcast_table_size = join_max_broadcast_table_size
        # jax.sharding.Mesh: hashed exchanges between stages whose task
        # count equals the mesh size run as ICI all_to_all collectives
        self.mesh = mesh
        # history-seeded hash-stage task count for the CURRENT query
        # (adaptive.history-sizing); None means use n_tasks
        self._history_tasks: Optional[int] = None

    # materialized exchanges can't stay device-resident; overridden by
    # BatchQueryRunner so fabric resolution demotes its edges to http
    _batch_mode = False

    def _annotate_fabrics(self, subplan):
        """Resolve and stamp each remote-exchange edge's fabric on the
        fragment output schemes (sql/fragmenter.annotate_exchange_fabrics)
        so EXPLAIN / EXPLAIN (TYPE VALIDATE) show the same choice the
        scheduler will make at runtime."""
        from ..parallel.mesh import mesh_size
        from ..sql.fragmenter import annotate_exchange_fabrics
        return annotate_exchange_fabrics(
            subplan, exec_config=self.config,
            mesh_size=mesh_size(self.mesh),
            batch_mode=self._batch_mode)

    def plan_subplan(self, sql: str, ast=None, bound_params=None):
        from ..sql.fragmenter import plan_distributed
        with self._validation():
            if ast is not None:
                output = Planner(default_schema=self.schema,
                                 default_catalog=self.catalog,
                                 bound_params=bound_params) \
                    .plan_query_to_output(ast)
            else:
                output = self.plan(sql)
            names = output.column_names
            types = [v.type for v in output.outputs]
            subplan = plan_distributed(output, self._fragmenter_config(),
                                       exec_config=self.config)
            self._annotate_fabrics(subplan)
        return subplan, names, types

    def _fragmenter_config(self):
        from ..sql.fragmenter import FragmenterConfig
        return FragmenterConfig(
            join_max_broadcast_table_size=self.join_max_broadcast_table_size,
            n_tasks=self.n_tasks)

    def _explain_distributed(self, ast, sql: str = "") -> QueryResult:
        """EXPLAIN over the fragmented (distributed) plan — the analog of
        the reference's EXPLAIN (TYPE DISTRIBUTED).  ANALYZE executes the
        fragment DAG through the in-process scheduler with per-task
        operator stats enabled and annotates every fragment from the
        merged (task-rolled-up) map."""
        from ..common.types import VarcharType
        from ..sql.explain import format_analyze_footer, format_subplan
        from ..sql.fragmenter import plan_distributed
        if ast.explain_type == "VALIDATE":
            return self._explain_validate(ast)
        with self._validation():
            output = Planner(default_schema=self.schema,
                             default_catalog=self.catalog) \
                .plan_query_to_output(ast.query)
            subplan = plan_distributed(output, self._fragmenter_config(),
                                       exec_config=self.config)
            self._annotate_fabrics(subplan)
        stats = None
        footer = ""
        if ast.analyze:
            from contextlib import nullcontext

            from ..telemetry import profile_capture
            from .scheduler import InProcessScheduler
            sched = InProcessScheduler(self._scheduler_config())
            sched.node_stats = stats = {}
            # ANALYZE collects per-node stats, so the scheduler can also
            # emit the full query->fragment->task->operator span hierarchy
            tracer = self.tracer_provider.new_tracer(sql) \
                if (self.tracer_provider and sql) else None
            if tracer is not None:
                sched.tracer = tracer
            from ..telemetry.query_wall import with_partition
            from ..utils.runtime_stats import unix_ns
            began = unix_ns()
            with (tracer.span("query", sql=sql) if tracer
                  else nullcontext()):
                with profile_capture(self.config.profile_dir, "analyze",
                                     enabled=self.config.profile) \
                        as trace_dir:
                    for _page in sched.execute(subplan):
                        pass
            self.last_operator_stats = stats
            footer = format_analyze_footer(
                with_partition(sched.stats, began), profile_dir=trace_dir)
        text = format_subplan(subplan, stats)
        if footer:
            text += "\n\n" + footer
        return QueryResult(["Query Plan"], [VarcharType(max(1, len(text)))],
                           [[text]])

    def execute_streaming(self, sql: str, prepared=None):
        """Stages hand their output over whole: nothing streams, the
        statement layer takes `execute`'s rows."""
        return None

    def execute_prepared_batch(self, sqls, prepared=None):
        """No lane is batched: every EXECUTE runs its own stages."""
        return None

    def execute(self, sql: str, prepared: Optional[Dict[str, str]] = None
                ) -> QueryResult:
        from ..sql import parser as A
        from ..utils.runtime_stats import (RuntimeStats, current_stats,
                                           unix_ns)
        # the statement executor's stats when it set one (the query's
        # RuntimeStats in QueryInfo), else this execution's own
        owner = current_stats()
        stats = owner or RuntimeStats()
        began = unix_ns()
        with stats.span("queryParse"):
            ast = A.parse_sql(sql)
        if isinstance(ast, A.Explain):
            return self._explain_distributed(ast, sql=sql)
        if isinstance(ast, (A.CreateTableAs, A.InsertInto, A.DropTable)):
            # writes run single-task through the local pipeline (the
            # reference's scaled-writer distribution is future work)
            return self._execute_ddl(ast)
        if isinstance(ast, (A.Prepare, A.Deallocate)):
            return super().execute(sql, prepared)    # the registry only
        bound = None
        if isinstance(ast, A.ExecuteStmt):
            from ..serving import PREPARED_REGISTRY
            bound = list(ast.values)
            ast = PREPARED_REGISTRY.get_or_parse(
                self._prepared_text(ast.name, prepared)).statement
        from contextlib import nullcontext

        from ..telemetry import profile_capture
        from .scheduler import InProcessScheduler
        restore = self._apply_history_sizing(ast)
        try:
            with stats.span("queryPlan"):
                subplan, names, types = self.plan_subplan(
                    sql, ast=ast, bound_params=bound)
            sched = InProcessScheduler(self._scheduler_config(), stats)
            tracer = self.tracer_provider.new_tracer(sql) \
                if self.tracer_provider else None
            if tracer is not None:
                sched.tracer = tracer
            with (tracer.span("query", sql=sql) if tracer
                  else nullcontext()):
                with profile_capture(self.config.profile_dir, "query",
                                     enabled=self.config.profile) \
                        as trace_dir, stats.activate(), \
                        stats.span("queryExecute"):
                    result = pages_to_result(sched.execute(subplan),
                                             names, types)
        finally:
            restore()
        result.profile_trace_dir = trace_dir
        # fabric-tagged exchange stats (bytes / walls per fabric) collected
        # while the result drained
        result.runtime_stats = sched.stats.to_dict()
        if owner is None:
            _close_query(result, sched.stats, began)
        # query-level context peak (all tasks' reservations bubbled up)
        result.peak_memory_bytes = (sched.memory.peak
                                    if sched.memory is not None else 0)
        self._record_history(result, subplan.fragment.root, subplan=subplan)
        return result

    def _apply_history_sizing(self, ast):
        """adaptive.history-sizing (distributed): parameterize the plan
        to its template digest; when a prior FINISHED run matches, seed
        the aggregation-table hint (config, consumed by every task's
        compiler) and the hash-stage task count from what that run
        observed.  Returns a restore callback for the per-query state."""
        self._last_template_digest = None
        if self.history is None:
            return lambda: None
        from ..spi import plan as P
        from ..sql.canonical import parameterize
        try:
            with self._validation():
                unopt = Planner(default_schema=self.schema,
                                default_catalog=self.catalog) \
                    .plan_query_unoptimized(ast)
            self._last_template_digest = plan_template_digest(
                P.structural_key(parameterize(unopt).template))
        except Exception:   # noqa: BLE001 — sizing is advisory
            return lambda: None
        rec = self._history_record()
        if rec is None:
            return lambda: None
        import dataclasses

        from .adaptive import ADAPTIVE_METRICS
        saved_cfg, saved_tasks = self.config, self._history_tasks
        changed = False
        groups = rec.get("aggGroups")
        if groups:
            self.config = dataclasses.replace(
                self.config, history_agg_groups=int(groups))
            changed = True
        rows = rec.get("rows")
        if rows is not None:
            # one hash task per ~500k observed output rows: a repeat of
            # a small query skips the fan-out cost the planned
            # parallelism assumed (never raised above n_tasks)
            seeded = max(1, min(self.n_tasks, -(-int(rows) // 500_000)))
            if seeded != self.n_tasks:
                self._history_tasks = seeded
                changed = True
        if changed:
            ADAPTIVE_METRICS.incr("history_sized_queries")

        def restore():
            self.config, self._history_tasks = saved_cfg, saved_tasks
        return restore

    def _scheduler_config(self):
        from .scheduler import SchedulerConfig
        return SchedulerConfig(
            exec_config=self.config, source_tasks=self.n_tasks,
            hash_tasks=self._history_tasks or self.n_tasks,
            mesh=self.mesh,
            join_max_broadcast_table_size=self.join_max_broadcast_table_size)


class BatchQueryRunner(DistributedQueryRunner):
    """Batch-mode execution — the Presto-on-Spark analog (SURVEY.md §2.7:
    PrestoSparkRunner.java:55 / PrestoSparkQueryExecutionFactory.java:164).
    The same fragment DAG runs stage-by-stage with every inter-stage
    exchange MATERIALIZED to local shuffle files (the Spark-shuffle /
    presto_cpp ShuffleWrite analog) and per-task retry from those durable
    inputs — batch fault tolerance instead of fail-fast MPP."""

    _batch_mode = True

    def __init__(self, schema: str = "sf0.01", config=None,
                 n_tasks: int = 2, catalog: str = "tpch",
                 task_retries: int = 2, temp_dir=None,
                 fault_injector=None):
        super().__init__(schema, config, n_tasks=n_tasks, catalog=catalog)
        self.task_retries = task_retries
        self.temp_dir = temp_dir
        self.fault_injector = fault_injector

    def _scheduler_config(self):
        cfg = super()._scheduler_config()
        cfg.batch_mode = True
        cfg.task_retries = self.task_retries
        cfg.temp_dir = self.temp_dir
        cfg.fault_injector = self.fault_injector
        return cfg


def _assert_rows_equal(got: QueryResult, exp: QueryResult, ordered: bool):
    g = got.rows if ordered else got.sorted_rows()
    e = exp.rows if ordered else exp.sorted_rows()
    if len(g) != len(e):
        raise AssertionError(
            f"row count mismatch: engine {len(g)} vs reference {len(e)}\n"
            f"engine head: {g[:5]}\nreference head: {e[:5]}")
    for i, (rg, re_) in enumerate(zip(g, e)):
        if len(rg) != len(re_):
            raise AssertionError(f"column count mismatch at row {i}")
        for j, (a, b) in enumerate(zip(rg, re_)):
            if not _value_eq(a, b):
                raise AssertionError(
                    f"value mismatch at row {i} col {j} "
                    f"({got.column_names[j]}): engine {a!r} vs reference {b!r}\n"
                    f"engine row: {rg}\nreference row: {re_}")


def _value_eq(a, b) -> bool:
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) or isinstance(b, float):
        fa, fb = float(a), float(b)
        if fa == fb:
            return True
        denom = max(abs(fa), abs(fb), 1e-30)
        return abs(fa - fb) / denom < 1e-9
    return a == b
