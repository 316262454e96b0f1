"""The process-wide program cache on the worker's path (serving/fragments.py,
PlanCompiler.shared_jit): a second task of the same fragment builds no
program again; nothing that differs in what a program bakes in is ever
shared; a cached program keeps no task alive; eviction and invalidation
leave a running task its programs."""
import gc
import weakref

import numpy as np
import pytest

from presto_tpu.common.block import block_from_values
from presto_tpu.common.page import Page
from presto_tpu.common.serde import deserialize_pages
from presto_tpu.common.types import BIGINT, VarcharType
from presto_tpu.connectors import catalog
from presto_tpu.exec.pipeline import (ExecutionConfig, PlanCompiler,
                                      TaskContext)
from presto_tpu.exec.reference import execute_reference
from presto_tpu.exec.runner import (LocalQueryRunner, _assert_rows_equal,
                                    pages_to_result)
from presto_tpu.serving import FRAGMENT_JIT_CACHE
from presto_tpu.spi import plan as P
from presto_tpu.spi.expr import VariableReferenceExpression
from presto_tpu.sql import parser as A
from presto_tpu.sql.fragmenter import FragmenterConfig, plan_distributed
from presto_tpu.sql.planner import Planner
from presto_tpu.telemetry import jax_events
from presto_tpu.utils.runtime_stats import RuntimeStats
from presto_tpu.worker.protocol import (OutputBuffersSpec, TaskSource,
                                        TaskUpdateRequest)
from presto_tpu.worker.task import TaskManager

CONFIG = ExecutionConfig(batch_rows=1 << 13)

Q6 = ("select sum(l_extendedprice * l_discount) as revenue from lineitem "
      "where l_shipdate >= date '1994-01-01' "
      "and l_shipdate < date '1995-01-01' "
      "and l_discount between 0.05 and 0.07 and l_quantity < {q}")
Q1 = ("select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
      "avg(l_discount) as avg_disc, count(*) as count_order from lineitem "
      "where l_shipdate <= date '1998-09-02' "
      "group by l_returnflag, l_linestatus")


@pytest.fixture(autouse=True)
def empty_cache():
    FRAGMENT_JIT_CACHE.invalidate_all()
    yield
    FRAGMENT_JIT_CACHE.invalidate_all()


def _plan(sql, planner=None):
    planner = planner or Planner(default_schema="sf0.01",
                                 default_catalog="tpch")
    return Planner.optimize_output(
        planner.plan_query_unoptimized(A.parse_sql(sql)))


def _run(output, stats=None, **ctx_kw):
    """One execution of a whole plan by a NEW PlanCompiler, as a worker
    task makes one: (rows, this execution's RuntimeStats as a dict)."""
    owner = RuntimeStats()
    ctx = TaskContext(config=CONFIG, stats=stats, runtime_stats=owner,
                      **ctx_kw)
    with owner.activate():
        res = pages_to_result(PlanCompiler(ctx).run_to_pages(output),
                              output.column_names,
                              [v.type for v in output.outputs])
    return res, owner.to_dict()


def _count(stats, key):
    return int(stats.get(key, {"sum": 0})["sum"])


# ---------------------------------------------------------------------------
# (a) the same fragment as two TpuTasks in one process
# ---------------------------------------------------------------------------

def _source_fragment(sql):
    sub = plan_distributed(_plan(sql), FragmenterConfig(), exec_config=CONFIG)
    while sub.children:
        sub = sub.children[0]
    assert sub.fragment.partitioned_sources
    return sub.fragment


def _start_task(tm, task_id, fragment, session=None):
    splits = [s.to_dict() for s in catalog.make_splits("lineitem", 0.01, 2)]
    tm.create_or_update(TaskUpdateRequest.make(
        task_id, 0, fragment,
        [TaskSource.from_dict({"planNodeId": sid, "splits": splits,
                               "noMoreSplits": True})
         for sid in fragment.partitioned_sources],
        OutputBuffersSpec("PARTITIONED", 1), session=session or {}))
    return tm.get(task_id)


def _finish_task(task):
    """(the rows the task put in its buffer, its RuntimeStats)."""
    task._thread.join(timeout=120)
    assert not task._thread.is_alive()
    assert task.state == "FINISHED", task.failures
    data, _token, complete = task.buffers.get(0, 0, 1.0)
    assert complete
    rows = [[blk.to_pylist() for blk in page.blocks]
            for buf in data for page in deserialize_pages(buf)]
    return rows, task.stats.to_dict()


def _run_task(tm, task_id, fragment, session=None):
    return _finish_task(_start_task(tm, task_id, fragment, session))


@pytest.mark.parametrize("sql", [Q6.format(q=24), Q1], ids=["q6", "q1"])
def test_second_task_of_a_fragment_builds_no_program(sql):
    jax_events.install()
    tm = TaskManager("http://127.0.0.1:0", config=CONFIG)
    fragment = _source_fragment(sql)
    session = {"collect_operator_stats": "true"}   # the coordinator's default
    _run_task(tm, "pc.0.0.9.0", fragment, session)  # builds the columns
    FRAGMENT_JIT_CACHE.invalidate_all()
    rows1, first = _run_task(tm, "pc.0.0.0.0", fragment, session)
    before = jax_events.PROGRAMS.snapshot()
    rows2, second = _run_task(tm, "pc.0.0.1.0", fragment, session)
    after = jax_events.PROGRAMS.snapshot()
    assert rows1 == rows2 and rows1
    assert _count(first, "programCacheMisses") >= 1
    assert _count(second, "programCacheHits") >= 1
    assert _count(second, "programCacheMisses") == 0
    # nothing is lowered or loaded again, and the fused program is not
    # traced again (what still traces is the chain's eval_shape probe)
    assert "jaxLowerWallNanos" not in second, second
    assert "jaxBackendCompiles" not in second, second
    fused = [n for n in after if n.startswith("scan_agg_")]
    assert fused
    for n in fused:
        assert after[n] == before[n], (n, before[n], after[n])
    assert _count(second, "pipelineLaunches") \
        == _count(first, "pipelineLaunches")
    assert _count(second, "jaxTraces") < _count(first, "jaxTraces")


def test_tasks_racing_a_cold_fragment_build_each_program_once():
    """A stage's tasks start together and ask for the same programs while
    nobody has built them: every program is built by exactly one of them,
    and all of them answer alike."""
    tm = TaskManager("http://127.0.0.1:0", config=CONFIG)
    fragment = _source_fragment(Q1)
    _rows, solo = _run_task(tm, "race.0.0.9.0", fragment)
    FRAGMENT_JIT_CACHE.invalidate_all()
    tasks = [_start_task(tm, f"race.0.0.{i}.0", fragment) for i in range(6)]
    results = [_finish_task(task) for task in tasks]
    assert all(rows == _rows for rows, _ in results)
    lookups = _count(solo, "programCacheMisses") \
        + _count(solo, "programCacheHits")
    assert sum(_count(st, "programCacheMisses") for _, st in results) \
        == _count(solo, "programCacheMisses")
    assert sum(_count(st, "programCacheHits") for _, st in results) \
        == len(tasks) * lookups - _count(solo, "programCacheMisses")


# ---------------------------------------------------------------------------
# (b) no false share
# ---------------------------------------------------------------------------

def _final_agg_over_remote(dictionary):
    """SINGLE aggregation over a RemoteSourceNode whose pages carry the
    key as a dictionary of `dictionary`: what a gather stage compiles."""
    k = VariableReferenceExpression("k", VarcharType())
    x = VariableReferenceExpression("x", BIGINT)
    total = VariableReferenceExpression("total", BIGINT)
    remote = P.RemoteSourceNode("remote.1", ["7"], [k, x])
    from presto_tpu.spi.expr import CallExpression
    agg = P.AggregationNode(
        "agg.2", remote,
        {total: P.Aggregation(CallExpression("sum", BIGINT, [x]))},
        [k], P.SINGLE)
    n = 3 * len(dictionary)
    keys = [dictionary[i % len(dictionary)] for i in range(n)]
    vals = list(range(1, n + 1))
    page = Page([block_from_values(VarcharType(), keys),
                 block_from_values(BIGINT, vals)], n)
    expected = {}
    for key, v in zip(keys, vals):
        expected[key] = expected.get(key, 0) + v
    return agg, page, sorted([key, v] for key, v in expected.items())


def _run_remote(agg, page):
    owner = RuntimeStats()
    ctx = TaskContext(config=CONFIG, runtime_stats=owner)
    ctx.remote_pages["remote.1"] = lambda: iter([page])
    with owner.activate():
        pages = list(PlanCompiler(ctx).run_to_pages(agg))
    rows = sorted([k, int(v)] for p in pages
                  for k, v in zip(p.blocks[0].to_pylist(),
                                  p.blocks[1].to_pylist()))
    return rows, owner.to_dict()


def _case_literal():
    for q in (24, 11):
        sql = Q6.format(q=q)
        yield (lambda o=_plan(sql): _run(o)), sql


def _case_operator_stats():
    sql = Q6.format(q=24)
    yield (lambda o=_plan(sql): _run(o)), sql
    yield (lambda o=_plan(sql): _run(o, stats={})), sql


def _case_renamed_variables():
    # one planner's counter runs on: the same structure under new names
    planner = Planner(default_schema="sf0.01", default_catalog="tpch")
    a, b = _plan(Q1, planner), _plan(Q1, planner)
    ka, kb = (P.named_structural_key(o.source) for o in (a, b))
    assert ka[0] == kb[0] and ka[1] != kb[1]
    yield (lambda: _run(a)), Q1
    yield (lambda: _run(b)), Q1


CASES = {"literal": _case_literal, "operator_stats": _case_operator_stats,
         "renamed_variables": _case_renamed_variables}


@pytest.mark.parametrize("case", sorted(CASES))
def test_no_false_share_between_plans(case):
    """Each variant runs after the other has filled the cache: its rows
    equal the reference's, and its fused program is its own."""
    oracle = LocalQueryRunner("sf0.01")
    for run, sql in CASES[case]():
        res, stats = run()
        _assert_rows_equal(res, oracle.execute_reference(sql), False)
        assert _count(stats, "programCacheMisses") >= 1, (case, stats)
        # and again, now from the cache: still this variant's answer
        res, stats = run()
        _assert_rows_equal(res, oracle.execute_reference(sql), False)
        assert _count(stats, "programCacheMisses") == 0, (case, stats)
        assert _count(stats, "programCacheHits") >= 1


def test_no_false_share_between_task_indexes_under_assign_unique_id():
    """`task_index << 40` is a constant of a chain that assigns unique
    ids: two tasks of a stage must not share that program."""
    out = _plan("select s_suppkey from supplier")
    scan = next(n for n in P.walk_plan(out)
                if isinstance(n, P.TableScanNode))
    uid = VariableReferenceExpression("unique", BIGINT)
    node = P.AssignUniqueIdNode("uid.9", scan, uid)
    key_name = scan.outputs[0].name
    seen = {}
    for task_index in (0, 1, 0):
        ctx = TaskContext(config=CONFIG, task_index=task_index)
        batch = PlanCompiler(ctx)._materialize_node(node)
        live = np.asarray(batch.mask)
        ids = np.asarray(batch.columns["unique"].values)[live]
        keys = np.asarray(batch.columns[key_name].values)[live]
        assert len(set(ids.tolist())) == len(ids) == 100
        assert set((ids >> 40).tolist()) == {task_index}
        assert sorted(keys.tolist()) == sorted(
            r[0] for r in execute_reference(out))
        seen.setdefault(task_index, []).append(ids.tolist())
    assert seen[0][0] == seen[0][1]                 # deterministic per task
    assert not set(seen[0][0]) & set(seen[1][0])    # distinct across tasks
    assert FRAGMENT_JIT_CACHE.info()["entries"] >= 2


@pytest.mark.parametrize("second", [("X", "Y"), ("D", "E", "F")],
                         ids=["other_domain_size", "same_size_other_values"])
def test_no_false_share_between_key_dictionaries(second):
    """The gather stage's aggregation sees the key dictionary of the pages
    it is sent: another domain size is another program (G and strides are
    in the key), other values of the same size a retrace inside the one
    jit (a Batch's dictionaries are part of its treedef)."""
    for dictionary in (("A", "N", "R"), second, ("A", "N", "R")):
        agg, page, expected = _final_agg_over_remote(dictionary)
        rows, _stats = _run_remote(agg, page)
        assert rows == expected, (dictionary, rows)


# ---------------------------------------------------------------------------
# (c) a cached program keeps no task alive
# ---------------------------------------------------------------------------

def test_cached_programs_do_not_retain_the_first_task(monkeypatch):
    refs = []
    real_init = PlanCompiler.__init__

    def spying_init(self, ctx):
        real_init(self, ctx)
        refs.append((weakref.ref(ctx), weakref.ref(self)))
    monkeypatch.setattr(PlanCompiler, "__init__", spying_init)
    tm = TaskManager("http://127.0.0.1:0", config=CONFIG)
    rows, _stats = _run_task(tm, "keep.0.0.0.0", _source_fragment(Q1),
                             {"collect_operator_stats": "true"})
    assert rows and len(refs) == 1
    task = tm.get("keep.0.0.0.0")
    task._thread.join(timeout=30)
    assert FRAGMENT_JIT_CACHE.info()["entries"] >= 2   # programs stay
    owner = weakref.ref(task.stats)
    del tm.tasks["keep.0.0.0.0"], task
    gc.collect()
    ctx_ref, compiler_ref = refs[0]
    assert compiler_ref() is None, gc.get_referrers(compiler_ref())
    assert ctx_ref() is None, gc.get_referrers(ctx_ref())
    assert owner() is None
    # ... and a second task is served by them
    _rows, second = _run_task(tm, "keep.0.0.1.0", _source_fragment(Q1),
                              {"collect_operator_stats": "true"})
    assert _rows == rows
    assert _count(second, "programCacheMisses") == 0


# ---------------------------------------------------------------------------
# (d) eviction and invalidation under a running task
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["lru_of_one_entry",
                                 "invalidate_after_every_lookup"])
def test_eviction_and_invalidation_do_not_fail_a_running_task(how,
                                                              monkeypatch):
    if how == "lru_of_one_entry":
        monkeypatch.setattr(FRAGMENT_JIT_CACHE, "max_entries", 1)
    else:
        real = FRAGMENT_JIT_CACHE.get_or_build

        def get_then_drop(key, build):
            fn = real(key, build)
            FRAGMENT_JIT_CACHE.invalidate_all()
            return fn
        monkeypatch.setattr(FRAGMENT_JIT_CACHE, "get_or_build",
                            get_then_drop)
    tm = TaskManager("http://127.0.0.1:0", config=CONFIG)
    fragment = _source_fragment(Q1)
    rows1, first = _run_task(tm, f"{how}.0.0.0.0", fragment)
    rows2, second = _run_task(tm, f"{how}.0.0.1.0", fragment)
    assert rows1 == rows2 and rows1
    assert FRAGMENT_JIT_CACHE.info()["entries"] <= 1
    # whatever was dropped is simply built again
    assert _count(second, "programCacheMisses") >= 1
    oracle = LocalQueryRunner("sf0.01")
    res, _stats = _run(_plan(Q1))
    _assert_rows_equal(res, oracle.execute_reference(Q1), False)
