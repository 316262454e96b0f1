"""Serving tier (presto_tpu/serving/): plan canonicalization, the
canonical plan/executable cache, prepared statements, and fair-share +
memory-headroom admission.

The reference analogs: QueryPreparer / ParameterRewriter (prepared
statements), the coordinator's plan cache discussion in
presto-main-base, InternalResourceGroupManager's WEIGHTED_FAIR policy,
and the cluster memory manager's admission headroom — collapsed onto the
TPU serving problem where the expensive artifact is the compiled XLA
executable, so the cache key must be the canonical (value-free) plan
structure plus the execution-config fingerprint."""
import threading
import urllib.request

import pytest

from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner
from presto_tpu.serving import (GLOBAL_PLAN_CACHE, PREPARED_REGISTRY,
                                PlanCache, SERVING_METRICS)
from presto_tpu.sql.canonical import (config_fingerprint, parameterize,
                                      plan_cache_key)


@pytest.fixture(autouse=True)
def _reset_serving():
    SERVING_METRICS.reset()
    PREPARED_REGISTRY.clear()
    yield


def _snapshot():
    return SERVING_METRICS.snapshot()


# ---------------------------------------------------------------------------
# canonicalization units
# ---------------------------------------------------------------------------

def _template_key(sql, schema="sf0.01"):
    from presto_tpu.spi import plan as P
    from presto_tpu.sql.parser import parse_sql
    from presto_tpu.sql.planner import Planner
    planner = Planner(default_schema=schema)
    unopt = planner.plan_query_unoptimized(parse_sql(sql))
    pp = parameterize(unopt)
    return P.structural_key(pp.template), pp


def test_parameterize_extracts_comparison_literals():
    k1, pp1 = _template_key(
        "select count(*) from lineitem where l_quantity < 24")
    k2, pp2 = _template_key(
        "select count(*) from lineitem where l_quantity < 30")
    assert k1 == k2                     # literal is out of the template
    assert [s.value for s in pp1.slots] != [s.value for s in pp2.slots]
    assert '"@type": "parameter"' in k1


def test_parameterize_keeps_structure_distinct():
    k1, _ = _template_key(
        "select count(*) from lineitem where l_quantity < 24")
    k2, _ = _template_key(
        "select count(*) from lineitem where l_quantity > 24")
    assert k1 != k2                     # operator is structure, not data


def test_parameterize_leaves_strings_in_template():
    # string literals are not extractable: the value stays in the key, so
    # different strings replan (correct, just uncached across values)
    k1, pp1 = _template_key(
        "select count(*) from orders where o_orderstatus = 'F'")
    k2, _ = _template_key(
        "select count(*) from orders where o_orderstatus = 'O'")
    assert k1 != k2
    assert all(not isinstance(s.value, str) or s.type.__class__.__name__
               == "DateType" for s in pp1.slots)


def test_config_fingerprint_covers_every_field():
    import dataclasses
    a = ExecutionConfig()
    for f in dataclasses.fields(ExecutionConfig):
        if f.name == "plan_validation":
            b = dataclasses.replace(a, plan_validation="off")
            assert config_fingerprint(a) != config_fingerprint(b)


def test_cache_key_changes_with_session_property():
    # satellite (b) regression: a session-property (config) change must
    # never serve the old entry
    from presto_tpu.sql.parser import parse_sql
    from presto_tpu.sql.planner import Planner
    import dataclasses
    sql = "select count(*) from nation where n_nationkey < 10"
    cfg_a = ExecutionConfig()
    cfg_b = dataclasses.replace(cfg_a, plan_validation="off")
    planner = Planner(default_schema="sf0.01")
    pp = parameterize(planner.plan_query_unoptimized(parse_sql(sql)))
    ka = plan_cache_key(pp.template, cfg_a, "tpch", "sf0.01")
    kb = plan_cache_key(pp.template, cfg_b, "tpch", "sf0.01")
    assert ka != kb
    kc = plan_cache_key(pp.template, cfg_a, "tpch", "sf0.1")
    assert ka != kc                     # schema is in the key too


# ---------------------------------------------------------------------------
# canonical cache through the runner
# ---------------------------------------------------------------------------

def test_canonical_cache_reuses_executable_across_constants():
    cache = PlanCache(max_entries=16)
    r = LocalQueryRunner("sf0.01", plan_cache=cache)
    a = r.execute("select count(*) from lineitem where l_quantity < 10")
    builds_after_first = _snapshot()["executableBuilds"]
    b = r.execute("select count(*) from lineitem where l_quantity < 20")
    s = _snapshot()
    # second constant: same canonical entry, NO new executable build —
    # parse/plan/optimize/compile all skipped (the acceptance gate)
    assert s["executableBuilds"] == builds_after_first
    assert s["planCacheHits"] >= 1
    # and the answers are the real per-constant answers
    assert a.rows == [[10803]] or a.rows[0][0] > 0
    assert b.rows[0][0] > a.rows[0][0]
    ref = LocalQueryRunner("sf0.01", plan_cache=PlanCache())
    assert b.rows == ref.execute_reference(
        "select count(*) from lineitem where l_quantity < 20").rows


def test_canonical_cache_results_match_reference_across_values():
    cache = PlanCache()
    r = LocalQueryRunner("sf0.01", plan_cache=cache)
    for q in (10, 25, 40):
        r.assert_same_as_reference(
            f"select l_returnflag, count(*), sum(l_extendedprice) "
            f"from lineitem where l_quantity < {q} group by l_returnflag")
    assert cache.info()["hits"] >= 2


def test_session_property_change_never_serves_stale_plan():
    # same SQL, two configs sharing one cache: each must get its own entry
    import dataclasses
    cache = PlanCache()
    cfg = ExecutionConfig()
    r1 = LocalQueryRunner("sf0.01", config=cfg, plan_cache=cache)
    r2 = LocalQueryRunner(
        "sf0.01", config=dataclasses.replace(cfg, plan_validation="off"),
        plan_cache=cache)
    sql = "select count(*) from region where r_regionkey < 3"
    assert r1.execute(sql).rows == [[3]]
    misses = cache.info()["misses"]
    assert r2.execute(sql).rows == [[3]]
    assert cache.info()["misses"] == misses + 1   # not a (stale) hit


def test_ddl_invalidates_plan_cache():
    from presto_tpu.connectors import catalog
    from presto_tpu.connectors.memory import MemoryConnector
    catalog.register_connector("memory", MemoryConnector())
    try:
        cache = PlanCache()
        r = LocalQueryRunner("sf0.01", catalog="memory", plan_cache=cache)
        r.execute("create table t1 as select 1 as x")
        r.execute("select count(*) from t1 where x < 5")
        assert cache.info()["entries"] >= 1
        r.execute("drop table t1")
        info = cache.info()
        assert info["entries"] == 0
        assert info["invalidations"] >= 1
    finally:
        catalog.unregister_connector("memory")


def test_plan_cache_lru_evicts_and_counts():
    cache = PlanCache(max_entries=2)
    r = LocalQueryRunner("sf0.01", plan_cache=cache)
    r.execute("select count(*) from region")
    r.execute("select count(*) from nation")
    r.execute("select count(*) from supplier")
    info = cache.info()
    assert info["entries"] == 2
    assert info["evictions"] >= 1


# ---------------------------------------------------------------------------
# prepared statements
# ---------------------------------------------------------------------------

Q6ISH = ("select sum(l_extendedprice * l_discount) from lineitem "
         "where l_discount between ? - 0.01 and ? + 0.01 "
         "and l_quantity < ?")


def test_prepare_execute_fast_path_skips_parse_and_plan():
    r = LocalQueryRunner("sf0.01", plan_cache=PlanCache())
    res = r.execute(f"prepare q6 from {Q6ISH}")
    assert res.added_prepare == ("q6", Q6ISH)
    r.execute("execute q6 using 0.06, 0.06, 24")     # compiles + records
    builds = _snapshot()["executableBuilds"]
    out = r.execute("execute q6 using 0.05, 0.05, 30")
    s = _snapshot()
    assert s["preparedFastPath"] >= 1
    assert s["executableBuilds"] == builds           # no recompile
    want = r.execute_reference(
        "select sum(l_extendedprice * l_discount) from lineitem "
        "where l_discount between 0.04 and 0.06 and l_quantity < 30")
    assert out.rows == want.rows


def test_execute_null_parameter_replans():
    r = LocalQueryRunner("sf0.01", plan_cache=PlanCache())
    r.execute("prepare pn from select count(*) from lineitem "
              "where l_quantity < ?")
    r.execute("execute pn using 24")
    # NULL cannot ride the fast path (BindError) — full replan, and the
    # replan folds `x < NULL` correctly
    out = r.execute("execute pn using null")
    assert _snapshot()["preparedReplans"] >= 1
    assert out.rows == [[0]]


def test_execute_wrong_arity_raises():
    r = LocalQueryRunner("sf0.01", plan_cache=PlanCache())
    r.execute("prepare pa from select count(*) from region "
              "where r_regionkey < ?")
    with pytest.raises(ValueError, match="parameter"):
        r.execute("execute pa using 1, 2")


def test_deallocate_removes_statement():
    r = LocalQueryRunner("sf0.01", plan_cache=PlanCache())
    r.execute("prepare pd from select count(*) from region")
    res = r.execute("deallocate prepare pd")
    assert res.deallocated_prepare == "pd"
    with pytest.raises(KeyError):
        r.execute("execute pd")


def test_prepared_header_map_is_stateless():
    # the statement text arrives via the header map each request — a
    # different runner (fresh coordinator) serves it without prior PREPARE
    r = LocalQueryRunner("sf0.01", plan_cache=PlanCache())
    out = r.execute("execute h1 using 3",
                    prepared={"h1": "select count(*) from region "
                                    "where r_regionkey < ?"})
    assert out.rows == [[3]]


# ---------------------------------------------------------------------------
# fair-share + headroom admission
# ---------------------------------------------------------------------------

def _mq(qid, group, est=None):
    from presto_tpu.worker.statement import ManagedQuery
    q = ManagedQuery(qid, "select 1", "u", "s", {}, "tpch", "sf0.01")
    q.resource_group = group
    q.memory_estimate = est
    return q


def test_weighted_fair_share_interleaves_by_weight():
    from presto_tpu.worker.statement import (ResourceGroupManager,
                                             ResourceGroupSpec)
    m = ResourceGroupManager(
        [ResourceGroupSpec("a", hard_concurrency_limit=10, weight=3.0),
         ResourceGroupSpec("b", hard_concurrency_limit=10, weight=1.0)],
        [], total_concurrency=1)
    first = _mq("q0", "a")
    assert m.admit(first)
    queued = []
    for i in range(12):
        q = _mq(f"qa{i}", "a")
        assert not m.admit(q)
        queued.append(q)
    for i in range(12):
        q = _mq(f"qb{i}", "b")
        assert not m.admit(q)
        queued.append(q)
    # drain one slot at a time; weight-3 group should win ~3 of every 4
    order = []
    cur = first
    for _ in range(16):
        nxt = m.release(cur)
        assert len(nxt) == 1            # one slot frees one admission
        cur = nxt[0]
        order.append(cur.resource_group)
    a_share = order.count("a") / len(order)
    assert 0.6 <= a_share <= 0.85       # ~0.75 for weights 3:1


def test_memory_headroom_rejects_impossible_and_queues_tight():
    from presto_tpu.exec.memory import MemoryPool
    from presto_tpu.worker.statement import (QueryMemoryLimitError,
                                             ResourceGroupManager,
                                             ResourceGroupSpec)
    pool = MemoryPool(budget=1000)
    m = ResourceGroupManager(
        [ResourceGroupSpec("g", hard_concurrency_limit=10)], [],
        memory_pool=pool, headroom_fraction=0.8,
        query_memory_estimate=300)
    # 300 + 300 <= 800: two admit; the third queues (temporarily blocked)
    q1, q2, q3 = _mq("m1", "g"), _mq("m2", "g"), _mq("m3", "g")
    assert m.admit(q1) and m.admit(q2)
    assert not m.admit(q3)
    # an estimate that can NEVER fit rejects immediately
    with pytest.raises(QueryMemoryLimitError):
        m.admit(_mq("huge", "g", est=900))
    # releasing the claim admits the queued query
    admitted = m.release(q1)
    assert admitted == [q3]
    info = m.info()["__admission"]
    assert info["memoryAdmittedBytes"] == 600
    assert info["memoryHeadroomBytes"] == 800


def test_release_admits_multiple_when_memory_gated():
    from presto_tpu.exec.memory import MemoryPool
    from presto_tpu.worker.statement import (ResourceGroupManager,
                                             ResourceGroupSpec)
    pool = MemoryPool(budget=1000)
    m = ResourceGroupManager(
        [ResourceGroupSpec("g", hard_concurrency_limit=10)], [],
        memory_pool=pool, headroom_fraction=1.0,
        query_memory_estimate=100)
    big = _mq("big", "g", est=1000)
    assert m.admit(big)
    small = [_mq(f"s{i}", "g") for i in range(4)]
    for q in small:
        assert not m.admit(q)
    # one release (the 1000-byte claim) unblocks all four 100-byte queries
    assert m.release(big) == small


def test_resource_group_manager_backward_compat():
    # pre-serving positional construction and single-group FIFO behavior
    from presto_tpu.worker.statement import (QueryQueueFullError,
                                             ResourceGroupManager,
                                             ResourceGroupSpec, Selector)
    m = ResourceGroupManager(
        [ResourceGroupSpec("g", hard_concurrency_limit=1, max_queued=1)],
        [Selector("g", user="u.*")])
    assert m.select("user", "") == "g"
    q1, q2 = _mq("c1", "g"), _mq("c2", "g")
    assert m.admit(q1)
    assert not m.admit(q2)
    with pytest.raises(QueryQueueFullError):
        m.admit(_mq("c3", "g"))
    assert m.release(q1) == [q2]


# ---------------------------------------------------------------------------
# end to end over HTTP
# ---------------------------------------------------------------------------

@pytest.fixture
def coordinator():
    from presto_tpu.worker.server import WorkerServer
    s = WorkerServer(coordinator=True)
    yield s
    s.close()


def test_http_concurrent_parameterized_serving(coordinator):
    """N threads hammer repeated parameterized shapes: every result must
    match the reference and the cache must be absorbing the repeats."""
    from presto_tpu.client import StatementClient
    ref = LocalQueryRunner("sf0.01", plan_cache=PlanCache())
    shapes = [
        ("cq", "select count(*) from lineitem where l_quantity < ?",
         ["10", "20", "30"]),
        ("sq", "select sum(l_extendedprice) from lineitem "
               "where l_orderkey < ?",
         ["500", "1500", "2500"]),
    ]
    want = {}
    for name, template, values in shapes:
        for v in values:
            want[(name, v)] = ref.execute_reference(
                template.replace("?", v)).rows
    # warm one compile per shape through the real protocol
    warm = StatementClient(coordinator.uri)
    warm.prepared = {n: t for n, t, _ in shapes}
    for name, _t, values in shapes:
        warm.execute(f"execute {name} using {values[0]}")
    SERVING_METRICS.reset()

    errors = []

    def worker(tid):
        c = StatementClient(coordinator.uri, source=f"t{tid}")
        c.prepared = {n: t for n, t, _ in shapes}
        for i in range(6):
            name, _t, values = shapes[(tid + i) % len(shapes)]
            v = values[(tid * 7 + i) % len(values)]
            got = c.execute(f"execute {name} using {v}").rows
            if [list(r) for r in got] != \
                    [list(r) for r in want[(name, v)]]:
                errors.append((name, v, got, want[(name, v)]))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:3]
    assert SERVING_METRICS.hit_rate() > 0.0
    s = _snapshot()
    assert s["planCacheHits"] > 0


def test_http_fair_share_across_groups():
    """Two groups under total_concurrency=1: completions interleave
    rather than one group draining first."""
    from presto_tpu.worker.server import WorkerServer
    from presto_tpu.worker.statement import (ResourceGroupManager,
                                             ResourceGroupSpec, Selector)
    from presto_tpu.client import StatementClient
    rgm = ResourceGroupManager(
        [ResourceGroupSpec("ga", hard_concurrency_limit=4, weight=1.0),
         ResourceGroupSpec("gb", hard_concurrency_limit=4, weight=1.0)],
        [Selector("ga", source="src-a"), Selector("gb", source="src-b")],
        total_concurrency=1)
    s = WorkerServer(coordinator=True, resource_groups=rgm)
    try:
        done = []
        lock = threading.Lock()

        # both groups offer work from the same instant: a 50 ms head
        # start let group a drain four warm queries before b had asked
        # once, and said nothing of fairness either way
        start = threading.Barrier(2)

        def run(source, n):
            c = StatementClient(s.uri, source=source)
            start.wait(timeout=30)
            for _ in range(n):
                c.execute("select count(*) from region")
                with lock:
                    done.append(source)

        threads = [threading.Thread(target=run, args=("src-a", 4)),
                   threading.Thread(target=run, args=("src-b", 4))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # fair share: group b finishes work before group a fully drains
        first_half = done[:4]
        assert "src-b" in first_half, done
        info = s.dispatch.resource_groups.info()
        assert info["ga"]["virtualTime"] > 0
        assert info["gb"]["virtualTime"] > 0
    finally:
        s.close()


def test_http_admission_rejects_when_headroom_exhausted():
    from presto_tpu.exec.memory import MemoryPool
    from presto_tpu.worker.server import WorkerServer
    from presto_tpu.worker.statement import (ResourceGroupManager,
                                             ResourceGroupSpec)
    from presto_tpu.client import QueryError, StatementClient
    rgm = ResourceGroupManager(
        [ResourceGroupSpec("global", hard_concurrency_limit=8)], [],
        memory_pool=MemoryPool(budget=1 << 20), headroom_fraction=0.5,
        query_memory_estimate=1 << 10)
    s = WorkerServer(coordinator=True, resource_groups=rgm)
    try:
        c = StatementClient(s.uri)
        # fits: runs normally
        assert c.execute("select count(*) from region").rows == [[5]]
        # session-declared estimate beyond the headroom: rejected outright
        big = StatementClient(
            s.uri, session={"query_memory_bytes": str(1 << 30)})
        with pytest.raises(QueryError, match="headroom"):
            big.execute("select count(*) from region")
    finally:
        s.close()


def test_dbapi_server_side_binding_hits_cache(coordinator):
    import presto_tpu.dbapi as dbapi
    conn = dbapi.connect(coordinator.uri)
    cur = conn.cursor()
    cur.execute("select count(*) from region where r_regionkey < ?", (3,))
    assert cur.fetchall() == [(3,)]
    SERVING_METRICS.reset()
    cur.execute("select count(*) from region where r_regionkey < ?", (4,))
    assert cur.fetchall() == [(4,)]
    s = _snapshot()
    assert s["preparedFastPath"] >= 1       # bound server-side, cached
    # explicit fallback: textual substitution still works
    conn2 = dbapi.connect(coordinator.uri, server_side_binding=False)
    cur2 = conn2.cursor()
    cur2.execute("select count(*) from region where r_regionkey < ?", (2,))
    assert cur2.fetchall() == [(2,)]


def test_status_and_metrics_expose_serving_section(coordinator):
    import json
    c_url = coordinator.uri
    from presto_tpu.client import StatementClient
    StatementClient(c_url).execute("select count(*) from region")
    status = json.loads(
        urllib.request.urlopen(c_url + "/v1/status").read())
    assert "serving" in status
    sv = status["serving"]
    assert {"planCache", "preparedStatements", "metrics",
            "resourceGroups"} <= set(sv)
    assert "global" in sv["resourceGroups"]
    mets = urllib.request.urlopen(c_url + "/v1/metrics").read().decode()
    assert "presto_tpu_serving_plan_cache_hits_total" in mets
    assert 'presto_tpu_serving_group_running{group="global"' in mets
