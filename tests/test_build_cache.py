"""The process-wide cache of join build sides (serving/builds.py) and its
one door, `PlanCompiler.shared_build`: a build side over immutable tables
is built once a process and found again by a worker task's new compiler;
what a key cannot hold (a RemoteSourceNode's pages, a stored table) is
never an entry; bound by bytes, cleared by DDL, built once by two askers."""
import json
import os
import sys
import threading
import time
import urllib.request

import pytest

from presto_tpu.exec import fused
from presto_tpu.exec.pipeline import (ExecutionConfig, PlanCompiler,
                                      TaskContext, tuned_config)
from presto_tpu.exec.runner import LocalQueryRunner
from presto_tpu.serving.builds import JOIN_BUILD_CACHE, JoinBuildCache
from presto_tpu.serving.cache import PlanCache
from presto_tpu.spi import plan as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SF = 0.01
BUILD_SYNCS = ("hostSync.build_key_stats", "hostSync.build_dup_keys",
               "hostSync.build_max_run", "hostSync.build_has_null_key",
               "hostSync.join_build_rows", "hostSync.chain_counts",
               "hostSync.maybe_compact_live")


def _sum(stats, key):
    m = (stats or {}).get(key)
    return 0 if m is None else m["sum"]


@pytest.fixture(autouse=True)
def empty_cache():
    JOIN_BUILD_CACHE.invalidate_all()
    yield
    JOIN_BUILD_CACHE.invalidate_all()


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, imported as the harness imports them:
    the two join cells, their plans and one reference."""
    added = [p for p in (BENCH, ROOT) if p not in sys.path]
    sys.path[:0] = added
    import cells
    import check
    import load
    found = {}
    for name in ("tpch10-joins.join-power", "tpch10-q3.q3-power"):
        cell = cells.Cell(name)
        found[name] = (cell, load.Plan(cell.traffic, cell.queries, 1))
    queries = {t: q for cell, _plan in found.values()
               for t, q in cell.queries.items()}
    yield found, check.Reference(queries, SF)
    for p in added:
        sys.path.remove(p)


@pytest.fixture(scope="module")
def cluster(bench):
    """Coordinator + one announced worker from `tpch10-q3`'s properties
    (`tpch10-joins`' plus the catalog); a new PlanCompiler every task."""
    from presto_tpu.client import StatementClient
    from presto_tpu.worker import WorkerServer
    spec = bench[0]["tpch10-q3.q3-power"][0].config["servers"]
    coordinator = WorkerServer(coordinator=True, **spec["coordinator"])
    worker = WorkerServer(discovery_uri=coordinator.uri, **spec["worker"])
    deadline = time.time() + 30
    while not coordinator.worker_uris() and time.time() < deadline:
        time.sleep(0.05)
    assert coordinator.worker_uris(), "the worker never announced itself"
    client = StatementClient(coordinator.uri, schema=f"sf{SF:g}",
                             catalog="tpch", source="test", timeout_s=600.0)

    def run(sql):
        res = client.execute(sql)
        with urllib.request.urlopen(
                f"{coordinator.uri}/v1/query/{res.query_id}") as resp:
            return res.rows, json.loads(resp.read())
    yield run
    worker.close()
    coordinator.close()


def _join_tasks(info):
    return [t["stats"]["runtimeStats"] for st in info["stages"]
            for t in st["tasks"]
            if "joinBuildWallNanos" in t["stats"]["runtimeStats"]]


# ---------------------------------------------------------------------------
# coordinator -> worker: the second execution takes the first one's build
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("template", ["tpch/q12", "tpch/q14"])
def test_second_execution_takes_the_build_from_the_cache(
        bench, cluster, monkeypatch, template):
    cells_, reference = bench
    _cell, plan = cells_["tpch10-joins.join-power"]
    values = plan.pool[template][0]
    sql = plan.statement(template, values)
    tables_built = []
    real = fused.try_direct_table

    def counting(*a, **k):
        tables_built.append(1)
        return real(*a, **k)
    monkeypatch.setattr(fused, "try_direct_table", counting)

    first_rows, first = cluster(sql)
    built = len(tables_built)
    second_rows, second = cluster(sql)
    want = [list(r) for r in reference.answer(template, values)]
    assert [list(r) for r in first_rows] == want
    assert [list(r) for r in second_rows] == want

    cold, warm = _join_tasks(first), _join_tasks(second)
    assert len(cold) == len(warm) >= 1
    for task in cold:
        assert _sum(task, "joinBuildCacheMisses") == 1
        assert _sum(task, "joinBuildCacheHits") == 0
    for task in warm:
        assert _sum(task, "joinBuildCacheHits") == 1
        assert _sum(task, "joinBuildCacheMisses") == 0
        assert _sum(task, "joinBuildCacheBytes") > 0
        # nothing fetched for the build, no table built
        assert [k for k in BUILD_SYNCS if k in task] == []
    assert built == len(cold) and len(tables_built) == built
    assert any(k in task for task in cold for k in BUILD_SYNCS)
    # the same operator statistics and joinBuildRows, hit or miss
    rows = lambda info: {n: (s["rows"], s["batches"], s.get("fused"))  # noqa: E731
                         for n, s in info["operatorStats"].items()}
    assert rows(first) == rows(second)
    assert sorted(_sum(t, "joinBuildRows") for t in cold) \
        == sorted(_sum(t, "joinBuildRows") for t in warm)
    assert _sum(second["runtimeStats"], "joinBuildWallNanos") \
        < _sum(first["runtimeStats"], "joinBuildWallNanos")


def test_q3_builds_come_through_an_exchange_and_are_never_entries(
        bench, cluster):
    """Q3's build sides are broadcast pages of a FILTERED customer and of
    filtered orders: the same subtree, another (SEGMENT, DATE), another
    answer.  Two tuples, each like the reference, and no lookup at all."""
    cells_, reference = bench
    _cell, plan = cells_["tpch10-q3.q3-power"]
    pool = plan.pool["tpchx/q3"]
    assert pool[0] != pool[1]
    for values in (pool[0], pool[1], pool[0]):
        rows, info = cluster(plan.statement("tpchx/q3", values))
        want = reference.answer("tpchx/q3", values)
        assert [list(r) for r in rows] == [list(r) for r in want]
        stats = info["runtimeStats"]
        assert _sum(stats, "joinBuildWallNanos") > 0
        assert "joinBuildCacheHits" not in stats
        assert "joinBuildCacheMisses" not in stats
    assert JOIN_BUILD_CACHE.info()["entries"] == 0


# ---------------------------------------------------------------------------
# what may be an entry is read off the plan
# ---------------------------------------------------------------------------

def _builds(sql, schema="sf10"):
    """(fragment id, join node, build subtree, build keys) of every join
    the coordinator's fragmenter plans for `sql` (planning only)."""
    from presto_tpu.worker.coordinator import HttpQueryRunner
    runner = HttpQueryRunner([], schema=schema, config=tuned_config())
    sub, _names, _types = runner.plan_subplan(sql)
    for frag in sub.all_fragments():
        for node in P.walk_plan(frag.root):
            if isinstance(node, P.JoinNode):
                yield node.right, [r.name for _l, r in node.criteria]


@pytest.mark.parametrize("cell,template,shareable", [
    ("tpch10-joins.join-power", "tpch/q12", [True]),
    ("tpch10-joins.join-power", "tpch/q14", [True]),
    ("tpch10-q3.q3-power", "tpchx/q3", [False, False]),
])
def test_eligibility_at_the_served_scale(bench, cell, template, shareable):
    """At SF10, as the cells run: Q12 and Q14 build on a bare scan in the
    join's own fragment; both of Q3's builds hold a RemoteSourceNode."""
    _cell, plan = bench[0][cell]
    sql = plan.statement(template, plan.pool[template][0])
    got = []
    for build, keys in _builds(sql):
        compiler = PlanCompiler(TaskContext(config=tuned_config()))
        names = tuple(v.name for v in build.output_variables)
        key = compiler._build_share_key(build, names, keys, True)
        remote = any(isinstance(n, P.RemoteSourceNode)
                     for n in P.walk_plan(build))
        assert (key is None) == remote
        got.append(key is not None)
    assert got == shareable


JOIN_SQL = ("select o_orderpriority, count(*) from lineitem join orders "
            "on l_orderkey = o_orderkey where l_shipmode = 'MAIL' "
            "group by 1 order by 1")


def _runner(**config):
    return LocalQueryRunner("sf0.01", plan_cache=PlanCache(),
                            config=ExecutionConfig(**config))


@pytest.mark.parametrize("why,config", [
    ("memory budget", dict(memory_budget_bytes=1 << 30)),
    ("query.max-memory ceiling", dict(memory_max_query_bytes=1 << 30)),
])
def test_a_budgeted_task_keeps_its_own_build(why, config):
    r = _runner(**config)
    want = r.execute_reference(JOIN_SQL).rows
    for _ in range(2):
        res = r.execute(JOIN_SQL)
        assert res.rows == want
        assert "joinBuildCacheHits" not in (res.runtime_stats or {})
    assert JOIN_BUILD_CACHE.info()["entries"] == 0


def test_a_stored_table_is_never_an_entry():
    """A memory-connector table changes under INSERT: a join that builds
    on it sees the new rows, and is in no cache."""
    from presto_tpu.connectors import catalog
    from presto_tpu.connectors.memory import MemoryConnector
    catalog.register_connector("memory", MemoryConnector())
    try:
        r = _runner()
        r.execute("create table build_side as select o_orderkey k "
                  "from orders where o_orderkey < 100")
        sql = ("select count(*) from lineitem join build_side "
               "on l_orderkey = k")
        before = r.execute(sql).rows[0][0]
        r.execute("insert into build_side select o_orderkey from orders "
                  "where o_orderkey between 100 and 200")
        after = r.execute(sql).rows[0][0]
        assert after > before > 0
        assert after == r.execute(
            "select count(*) from lineitem where l_orderkey <= 200"
        ).rows[0][0]
        assert JOIN_BUILD_CACHE.info()["entries"] == 0
    finally:
        catalog.unregister_connector("memory")


# ---------------------------------------------------------------------------
# the key: bound parameters; the bound: bytes; DDL; one build for two
# ---------------------------------------------------------------------------

PARAM_SQL = ("select count(*) from lineitem join "
             "(select o_orderkey from orders where o_custkey < {}) o "
             "on l_orderkey = o_orderkey")


def test_a_bound_parameter_hits_only_under_the_same_binding():
    """The plan cache turns the literal into a parameter: one structural
    key, told apart by the bound values (a runner each, as a worker's
    tasks have a compiler each: a pooled compiler's chain keeps its own
    tables while the binding stays)."""
    seen = []
    for bound in (300, 300, 600, 300):
        r = _runner()
        sql = PARAM_SQL.format(bound)
        res = r.execute(sql)
        assert res.rows == r.execute_reference(sql).rows
        seen.append((_sum(res.runtime_stats, "joinBuildCacheHits"),
                     _sum(res.runtime_stats, "joinBuildCacheMisses")))
    assert seen == [(0, 1), (1, 0), (0, 1), (1, 0)]
    assert JOIN_BUILD_CACHE.info()["entries"] == 2


def test_ddl_empties_the_cache():
    r = _runner()
    want = r.execute_reference(JOIN_SQL).rows
    assert r.execute(JOIN_SQL).rows == want
    assert JOIN_BUILD_CACHE.info()["entries"] == 1
    assert JOIN_BUILD_CACHE.info()["bytes"] > 0
    r._invalidate_plans()
    assert JOIN_BUILD_CACHE.info() == dict(JOIN_BUILD_CACHE.info(),
                                           entries=0, bytes=0)
    res = r.execute(JOIN_SQL)
    assert res.rows == want
    assert _sum(res.runtime_stats, "joinBuildCacheMisses") == 1


def test_least_recently_used_goes_first_by_bytes(monkeypatch):
    """Every execution on a new compiler, as a worker's tasks are (a
    pooled compiler's chain keeps the tables of its last execution)."""
    other = JOIN_SQL.replace(
        "join orders on l_orderkey = o_orderkey",
        "join (select o_orderkey k, o_orderpriority from orders "
        "where o_orderkey > 1000) o on l_orderkey = k")
    want = _runner().execute_reference(JOIN_SQL).rows
    want_other = _runner().execute_reference(other).rows

    def run(sql):
        res = _runner().execute(sql)
        assert res.rows == (want if sql is JOIN_SQL else want_other)
        return (_sum(res.runtime_stats, "joinBuildCacheHits"),
                _sum(res.runtime_stats, "joinBuildCacheMisses"))
    assert run(JOIN_SQL) == (0, 1)
    one = JOIN_BUILD_CACHE.info()["bytes"]
    assert run(other) == (0, 1)
    both = JOIN_BUILD_CACHE.info()["bytes"]
    assert JOIN_BUILD_CACHE.info()["entries"] == 2 and both > one > 0
    # room for either build side, not for both
    monkeypatch.setattr(JOIN_BUILD_CACHE, "max_bytes", both - 1)
    JOIN_BUILD_CACHE.invalidate_all()
    assert run(JOIN_SQL) == (0, 1)
    assert run(other) == (0, 1)         # the older entry goes
    info = JOIN_BUILD_CACHE.info()
    assert info["entries"] == 1 and info["bytes"] == both - one
    assert run(other) == (1, 0)
    assert run(JOIN_SQL) == (0, 1)      # evicted: built again, still right
    assert JOIN_BUILD_CACHE.info()["bytes"] == one
    # an entry larger than the whole bound is handed on and not kept
    monkeypatch.setattr(JOIN_BUILD_CACHE, "max_bytes", one // 2)
    JOIN_BUILD_CACHE.invalidate_all()
    assert run(JOIN_SQL) == (0, 1)
    assert JOIN_BUILD_CACHE.info()["entries"] == 0


class _Built:
    def __init__(self, nbytes):
        self.nbytes = nbytes


@pytest.mark.parametrize("askers,keys", [(2, 1), (8, 1), (32, 3)])
def test_askers_of_one_key_build_once(askers, keys):
    """More askers than cores, the interpreter switching threads every
    10 us: every key is built once, every other asker waits and hits."""
    cache = JoinBuildCache(max_bytes=1000)
    builds, results = [], []
    gate = threading.Barrier(askers)

    def ask(i):
        key = ("k", i % keys)

        def build():
            builds.append(key)
            time.sleep(0.05)
            return _Built(10)
        gate.wait(timeout=30)
        results.append((key,) + cache.get_or_build(key, build))
    threads = [threading.Thread(target=ask, args=(i,)) for i in range(askers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert sorted(builds) == [("k", k) for k in range(keys)]
    assert sum(1 for _key, _ent, hit in results if not hit) == keys
    assert len(results) == askers
    for k in range(keys):
        assert len({id(ent) for key, ent, _hit in results
                    if key == ("k", k)}) == 1
    assert cache.info() == {"entries": keys, "bytes": 10 * keys,
                            "maxBytes": 1000}


def test_a_build_in_flight_across_a_clear_is_not_kept():
    cache = JoinBuildCache(max_bytes=1000)

    def build():
        cache.invalidate_all()      # DDL lands while the build runs
        return _Built(10)
    ent, hit = cache.get_or_build(("k",), build)
    assert ent.nbytes == 10 and not hit
    assert cache.info()["entries"] == 0


def test_twins_share_one_entry_under_their_own_names():
    """Two structurally equal build subtrees with different variable
    names (a self-join's two sides) are one entry, each asker reading it
    under its own names."""
    r = _runner()
    sql = ("select count(*) from lineitem l join orders a "
           "on l.l_orderkey = a.o_orderkey join orders b "
           "on l.l_orderkey = b.o_orderkey where l.l_quantity > 49")
    res = r.execute(sql)
    assert res.rows == r.execute_reference(sql).rows
    lookups = (_sum(res.runtime_stats, "joinBuildCacheHits")
               + _sum(res.runtime_stats, "joinBuildCacheMisses"))
    assert lookups == 2
    assert JOIN_BUILD_CACHE.info()["entries"] == 1
