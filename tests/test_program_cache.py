"""The process-wide program cache on the worker's path (serving/fragments.py,
PlanCompiler.shared_jit): a second task of the same fragment builds no
program again; nothing that differs in what a program bakes in is ever
shared; a cached program keeps no task alive; eviction and invalidation
leave a running task its programs.  The fused chain's shape probe is an
entry of the same cache: a second execution runs no trace, and nothing
the trace can depend on is ever shared."""
import gc
import sys
import threading
import weakref
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.common.block import block_from_values
from presto_tpu.common.page import Page
from presto_tpu.common.serde import deserialize_pages
from presto_tpu.common.types import BIGINT, VarcharType
from presto_tpu.connectors import catalog
from presto_tpu.exec.fused import ChainProgram, assemble_chain
from presto_tpu.exec.pipeline import (ExecutionConfig, PlanCompiler,
                                      TaskContext, _fragment_batch_sig)
from presto_tpu.exec.reference import execute_reference
from presto_tpu.exec.runner import (LocalQueryRunner, _assert_rows_equal,
                                    pages_to_result)
from presto_tpu.serving import FRAGMENT_JIT_CACHE, PlanCache
from presto_tpu.spi import plan as P
from presto_tpu.spi.expr import VariableReferenceExpression
from presto_tpu.sql import parser as A
from presto_tpu.sql.fragmenter import FragmenterConfig, plan_distributed
from presto_tpu.sql.planner import Planner
from presto_tpu.storage.encodings import ResidentColumn
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
    # nothing is traced, lowered or loaded again: the chain's shape probe
    # is served from the cache like the fused program it keys
    assert "jaxLowerWallNanos" not in second, second
    assert "jaxBackendCompiles" not in second, second
    fused = [n for n in after if n.startswith("scan_agg_")]
    assert fused
    for n in fused:
        assert after[n] == before[n], (n, before[n], after[n])
    assert _count(second, "pipelineLaunches") \
        == _count(first, "pipelineLaunches")
    assert _count(first, "jaxTraces") >= 1 and "jaxTraces" not in second
    assert _count(first, "shapeProbeMisses") >= 1
    assert _count(second, "shapeProbeHits") >= 1
    assert _count(second, "shapeProbeMisses") == 0


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
    assert _cached_purposes()["chain_shape_probe"] == 1   # and the probe
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
    assert _count(second, "shapeProbeHits") >= 1
    assert _count(second, "shapeProbeMisses") == 0


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


# ---------------------------------------------------------------------------
# (e) the fused chain's shape probe: an entry of the same cache
# ---------------------------------------------------------------------------

JOIN = ("select n_name, sum(s_acctbal) as bal from supplier "
        "join nation on s_nationkey = n_nationkey group by n_name")


def _cached_purposes():
    """{purpose: entries} of the process-wide cache."""
    return Counter(key[0] for key in list(FRAGMENT_JIT_CACHE._entries))


def _probe_traces():
    return jax_events.PROGRAMS.snapshot().get(
        "chain_shape_probe", {"traces": 0})["traces"]


def _chain(sql, owner=None):
    """The fused chain under `sql`'s aggregation, assembled by a NEW
    PlanCompiler as a task's would."""
    agg = next(n for n in P.walk_plan(_plan(sql))
               if isinstance(n, P.AggregationNode))
    return assemble_chain(
        PlanCompiler(TaskContext(config=CONFIG, runtime_stats=owner)),
        agg.source)


def _probe(sql, edit=None):
    """One task's shape probe of `sql`'s chain: (abstract batch, "hit" |
    "miss", the aux it was asked with).  `edit(aux)` changes what the
    task holds before it asks.  A miss IS the body running: JAX's own
    trace count of `chain_shape_probe` must agree."""
    jax_events.install()
    owner = RuntimeStats()
    with owner.activate():
        chain = _chain(sql, owner)
        aux, expands, _deferred = chain.prep()
        if edit is not None:
            aux = edit(aux)
        before = _probe_traces()
        out = chain.shape_probe(aux, expands, chain.leaf_cap(expands))
        traced = _probe_traces() > before
    stats = owner.to_dict()
    hits, misses = (_count(stats, "shapeProbe" + k)
                    for k in ("Hits", "Misses"))
    assert hits + misses == 1, stats
    assert traced == bool(misses)
    return out, "hit" if hits else "miss", aux


def _with_dictionary(dictionary):
    """aux whose build table carries n_name under another dictionary."""
    def edit(aux):
        scan, table = aux
        cols = dict(table.columns)
        name = next(n for n, c in cols.items() if c.dictionary is not None)
        cols[name] = type(cols[name])(cols[name].values, None, dictionary)
        return scan, type(table)(table.slots, table.base, cols)
    return edit


def _other_residency(column, shorten=0):
    """aux whose resident `column` is held the other way round (plain
    for dict, dict for plain; what the store chose depends on who built
    the column first in this process), or, with `shorten`, as a dict
    with that many values fewer."""
    def edit(aux):
        scan = dict(aux[0])
        rc = scan[column]
        if rc.kind == "plain" or shorten:
            values, codes = np.unique(np.asarray(rc.decode_full()),
                                      return_inverse=True)
            arrays = (jnp.asarray(codes.astype(np.int8)),
                      jnp.asarray(values[:len(values) - shorten]))
            scan[column] = ResidentColumn("dict", arrays, rc.n_rows)
        else:
            scan[column] = ResidentColumn("plain", (rc.decode_full(),),
                                          rc.n_rows)
        return (scan,) + tuple(aux[1:])
    return edit


def _shape(batch):
    """What the probe's callers read of it."""
    return (_fragment_batch_sig(batch),
            {n: (c.values.dtype, c.values.shape, c.dictionary, c.lazy)
             for n, c in batch.columns.items()}, batch.mask.shape)


def test_second_probe_of_a_chain_runs_no_trace():
    _out, first, _aux = _probe(Q1)
    _out, second, _aux = _probe(Q1)
    assert (first, second) == ("miss", "hit")
    assert _cached_purposes()["chain_shape_probe"] == 1


def test_tasks_probing_a_cold_chain_together_agree():
    """Pinned tasks of a stage ask at once while nobody has traced: every
    one of them is answered alike, each counts one hit or one miss, and
    the entry ends with the one result (a lost update would leave none,
    or two)."""
    chains = [_chain(Q1) for _ in range(12)]
    prepared = [(c,) + c.prep()[:2] for c in chains]
    owners = [RuntimeStats() for _ in chains]
    shapes, errors = [None] * len(chains), []
    start = threading.Barrier(len(chains))

    def ask(i):
        chain, aux, expands = prepared[i]
        try:
            with owners[i].activate():
                start.wait(timeout=60)
                shapes[i] = _shape(chain.shape_probe(
                    aux, expands, chain.leaf_cap(expands)))
        except Exception as e:   # noqa: BLE001 -- reported below
            errors.append(e)
    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(chains))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads), errors
    assert all(sh == shapes[0] for sh in shapes) and shapes[0]
    counts = [(_count(o.to_dict(), "shapeProbeHits"),
               _count(o.to_dict(), "shapeProbeMisses")) for o in owners]
    assert all(h + m == 1 for h, m in counts), counts
    assert sum(m for _h, m in counts) >= 1
    entries = [e for key, e in list(FRAGMENT_JIT_CACHE._entries.items())
               if key[0] == "chain_shape_probe"]
    assert len(entries) == 1 and len(entries[0]._results) == 1
    out, how, _aux = _probe(Q1)
    assert how == "hit" and _shape(out) == shapes[0]


def test_no_false_share_of_the_shape_probe_between_literals():
    a, b = Q6.format(q=24), Q6.format(q=11)
    assert [_probe(sql)[1] for sql in (a, b, a, b)] \
        == ["miss", "miss", "hit", "hit"]
    assert _cached_purposes()["chain_shape_probe"] == 2


@pytest.mark.parametrize("other", [("X", "Y"),
                                   tuple("ABCDEFGHIJKLMNOPQRSTUVWXY")],
                         ids=["other_domain_size", "same_size_other_values"])
def test_no_false_share_of_the_shape_probe_between_key_dictionaries(other):
    """A build table's dictionary is part of aux's treedef: the probe of
    a task that holds another one is its own, and says so."""
    mine, how, _aux = _probe(JOIN)
    assert how == "miss"
    key = next(n for n, c in mine.columns.items() if c.dictionary)
    assert len(mine.columns[key].dictionary) == 25
    theirs, how, _aux = _probe(JOIN, _with_dictionary(other))
    assert how == "miss"
    assert theirs.columns[key].dictionary == other
    for edit, expected in ((None, mine), (_with_dictionary(other), theirs)):
        again, how, _aux = _probe(JOIN, edit)
        assert how == "hit" and _shape(again) == _shape(expected)
    assert _cached_purposes()["chain_shape_probe"] == 1     # one key


def test_no_false_share_of_the_shape_probe_between_plain_and_dict_residency():
    """The resident store's choice for a column is part of aux's treedef
    (`ResidentColumn.kind`), and a dictionary's length of its avals."""
    out, how, aux = _probe(Q1)
    assert how == "miss"
    other, how, flipped = _probe(Q1, _other_residency("quantity"))
    assert how == "miss" and _shape(other) == _shape(out)
    assert {aux[0]["quantity"].kind, flipped[0]["quantity"].kind} \
        == {"plain", "dict"}
    assert _probe(Q1, _other_residency("quantity", shorten=1))[1] == "miss"
    assert _probe(Q1, _other_residency("quantity"))[1] == "hit"
    assert _probe(Q1)[1] == "hit"


def test_shape_probe_after_invalidation_runs_its_body_again():
    assert _probe(Q1)[1] == "miss"
    assert _probe(Q1)[1] == "hit"
    assert FRAGMENT_JIT_CACHE.invalidate_all() >= 1     # what DDL does
    assert _probe(Q1)[1] == "miss"


@pytest.mark.parametrize("sql", [Q1, Q6.format(q=24), JOIN],
                         ids=["q1", "q6", "join"])
def test_a_probe_hit_hands_back_what_a_miss_did(sql):
    """... and both what an uncached `jax.eval_shape` over the chain
    says: `_fragment_batch_sig`, key dtypes, dictionaries, laziness."""
    miss, how, aux = _probe(sql)
    assert how == "miss"
    hit, how, _aux = _probe(sql)
    assert how == "hit" and _shape(hit) == _shape(miss)
    chain = _chain(sql)
    _aux, expands, _deferred = chain.prep()
    plain = jax.eval_shape(
        lambda p, v: chain.make(p, v, aux, expands, chain.leaf_cap(expands)),
        jnp.int64(0), jnp.int64(1))
    assert _shape(plain) == _shape(hit)


def test_a_probe_that_raises_raises_on_every_execution(monkeypatch):
    """A chain the probe cannot trace is declined on the second execution
    as on the first (nothing is cached of a failure), and the query
    answers through the streaming path both times."""
    def unsupported(self, *args, **kwargs):
        raise NotImplementedError("not in this test")
    monkeypatch.setattr(ChainProgram, "make", unsupported)
    sql = Q6.format(q=24)
    r = LocalQueryRunner("sf0.01", plan_cache=PlanCache(), config=CONFIG)
    expected = r.execute_reference(sql)
    for _execution in range(3):
        res = r.execute(sql)
        stats = res.runtime_stats
        _assert_rows_equal(res, expected, False)
        assert _count(stats, "fusionDeclinedProbeUnsupported") == 1, stats
        assert _count(stats, "shapeProbeMisses") == 1
        assert "shapeProbeHits" not in stats
