"""Distributed execution tests: fragmenter + in-process multi-task scheduler
vs the numpy reference interpreter (the analog of the reference's
DistributedQueryRunner-based AbstractTestDistributedQueries suites)."""
import pytest

from presto_tpu.exec.runner import DistributedQueryRunner
from presto_tpu.spi import plan as P

from test_queries import TPCH_Q1, TPCH_Q3, TPCH_Q5, TPCH_Q6


@pytest.fixture(scope="module")
def runner():
    # broadcast joins (everything under threshold at sf0.01)
    return DistributedQueryRunner("sf0.01", n_tasks=2)


@pytest.fixture(scope="module")
def part_runner():
    # force hash-partitioned joins + exchanges everywhere
    return DistributedQueryRunner("sf0.01", n_tasks=3, join_max_broadcast_table_size=0)


def check(r, sql, ordered=False):
    return r.assert_same_as_reference(sql, ordered=ordered)


# ---------------------------------------------------------------------------
# fragmentation shape
# ---------------------------------------------------------------------------

def test_group_by_splits_partial_final(runner):
    sub, _, _ = runner.plan_subplan(
        "select o_orderstatus, count(*) from orders group by o_orderstatus")
    frags = sub.all_fragments()
    assert len(frags) == 3  # root gather, final agg (hash), partial agg (source)
    parts = {f.fragment_id: f.partitioning for f in frags}
    assert parts["2"] == P.SOURCE_DISTRIBUTION
    assert parts["1"] == P.FIXED_HASH_DISTRIBUTION
    assert parts["0"] == P.SINGLE_DISTRIBUTION
    steps = [n.step for f in frags for n in P.walk_plan(f.root)
             if isinstance(n, P.AggregationNode)]
    assert sorted(steps) == [P.FINAL, P.PARTIAL]


def test_partitioned_join_repartitions_both_sides(part_runner):
    sub, _, _ = part_runner.plan_subplan(
        "select n_name, r_name from nation join region "
        "on n_regionkey = r_regionkey")
    frags = sub.all_fragments()
    hash_outputs = [f for f in frags
                    if f.output_partitioning_scheme.handle
                    == P.FIXED_HASH_DISTRIBUTION]
    assert len(hash_outputs) == 2


def test_broadcast_join_keeps_probe_in_place(runner):
    sub, _, _ = runner.plan_subplan(
        "select n_name, r_name from nation join region "
        "on n_regionkey = r_regionkey")
    frags = sub.all_fragments()
    bcast = [f for f in frags
             if f.output_partitioning_scheme.handle
             == P.FIXED_BROADCAST_DISTRIBUTION]
    assert len(bcast) == 1


# ---------------------------------------------------------------------------
# correctness vs reference
# ---------------------------------------------------------------------------

def test_global_agg(runner):
    check(runner, "select count(*), sum(l_quantity), avg(l_extendedprice), "
                  "min(l_discount), max(l_tax) from lineitem")


def test_group_by(runner):
    check(runner, "select o_orderstatus, count(*), sum(o_totalprice), "
                  "avg(o_totalprice) from orders group by o_orderstatus")


def test_group_by_high_cardinality(part_runner):
    check(part_runner, "select l_orderkey, count(*), sum(l_quantity) "
                       "from lineitem group by l_orderkey")


def test_join_broadcast(runner):
    check(runner, "select n_name, r_name from nation "
                  "join region on n_regionkey = r_regionkey")


def test_join_partitioned(part_runner):
    check(part_runner, "select c_custkey, o_orderkey from customer "
                       "join orders on c_custkey = o_custkey")


def test_left_join_partitioned(part_runner):
    check(part_runner, """
        select c_custkey, o_orderkey from customer
        left join orders on c_custkey = o_custkey
        where c_custkey < 50""")


def test_string_group_keys_cross_task(part_runner):
    # dictionary codes differ per producer task; exchange must hash values
    check(part_runner, "select c_mktsegment, count(*) from customer "
                       "group by c_mktsegment")


def test_order_by_limit(runner):
    check(runner, "select c_custkey, c_acctbal from customer "
                  "order by c_acctbal desc, c_custkey limit 20", ordered=True)


def test_distinct(part_runner):
    check(part_runner, "select distinct o_orderstatus from orders")


def test_tpch_q1(runner):
    res = check(runner, TPCH_Q1, ordered=True)
    assert len(res.rows) == 4


def test_tpch_q3(runner):
    res = check(runner, TPCH_Q3, ordered=True)
    assert len(res.rows) == 10


def test_tpch_q3_partitioned(part_runner):
    check(part_runner, TPCH_Q3, ordered=True)


def test_tpch_q5(runner):
    check(runner, TPCH_Q5, ordered=True)


def test_tpch_q5_partitioned(part_runner):
    check(part_runner, TPCH_Q5, ordered=True)


def test_tpch_q6(runner):
    check(runner, TPCH_Q6)


def test_left_join_empty_build_varchar(part_runner):
    # build side yields zero pages in a partition; varchar build columns must
    # null-extend with a valid dictionary (review regression)
    check(part_runner, """
        select c_custkey, o_orderstatus from customer
        left join (select o_custkey, o_orderstatus from orders
                   where o_totalprice < 0) t
        on c_custkey = o_custkey where c_custkey < 5""")


def test_window_repartitioned_by_partition_keys(part_runner):
    # WindowNode over a distributed source: fragmenter must hash-repartition
    # on the window partition keys so each task sees whole partitions
    check(part_runner, """
        select o_custkey, o_orderkey,
               row_number() over (partition by o_custkey order by o_orderkey),
               sum(o_totalprice) over (partition by o_custkey)
        from orders where o_custkey < 200""")


def test_window_no_partition_gathers_single(part_runner):
    check(part_runner, """
        select c_custkey,
               rank() over (order by c_acctbal desc)
        from customer where c_custkey < 100""")


def test_union_all_distributed(part_runner):
    check(part_runner, """
        select n_regionkey k from nation
        union all select r_regionkey from region
        union all select o_custkey from orders where o_orderkey < 50""")


def test_union_distinct_distributed(part_runner):
    check(part_runner, """
        select o_orderstatus from orders
        union select o_orderpriority from orders""")


def test_intersect_distributed(part_runner):
    check(part_runner, """
        select n_nationkey from nation
        intersect select c_nationkey from customer where c_custkey < 40""")


def test_partition_hash_matches_scalar_fnv():
    """The vectorized exchange-path string hash (one numpy pass per byte
    position) must equal the scalar FNV-1a spec byte for byte, and the
    dictionary path must agree with the flat path so both sides of an
    exchange partition identically."""
    import numpy as np

    from presto_tpu.common.block import (DictionaryBlock,
                                         VariableWidthBlock)
    from presto_tpu.common.types import VARCHAR
    from presto_tpu.exec.scheduler import _hash_block

    def scalar_fnv(data: bytes) -> int:
        h = 0xCBF29CE484222325
        for b in data:
            h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
        return h

    strings = ["", "a", "hello world", "x" * 200, "unicode: déjà vu",
               None, "PROMO BURNISHED"]
    flat = VariableWidthBlock.from_strings(strings)
    got = _hash_block(VARCHAR, flat, len(strings))
    for s, h in zip(strings, got):
        if s is not None:
            assert int(h) == scalar_fnv(s.encode("utf-8")), s
    entries = [s for s in strings if s is not None]
    ids = np.array([0, 2, 1, 4, 3, 0], dtype=np.int32)
    dict_block = DictionaryBlock(
        ids, VariableWidthBlock.from_strings(entries))
    got_d = _hash_block(VARCHAR, dict_block, len(ids))
    want = _hash_block(VARCHAR,
                       VariableWidthBlock.from_strings(
                           [entries[i] for i in ids]), len(ids))
    assert (got_d == want).all()


def test_varwidth_take_vectorized():
    from presto_tpu.common.block import VariableWidthBlock
    strings = ["alpha", "", "bravo charlie", "δ", "e" * 99]
    blk = VariableWidthBlock.from_strings(strings)
    import numpy as np
    taken = blk.take(np.array([4, 0, 2, 2, 1]))
    assert taken.to_pylist() == [strings[4], strings[0], strings[2],
                                 strings[2], strings[1]]


# ---------------------------------------------------------------------------
# fault tolerance over the HTTP task protocol (chaos tests)
# ---------------------------------------------------------------------------
# The analog of the reference's TestDistributedQueriesWithTaskRetries /
# presto-spark retry suites: inject worker death and task failures into a
# real loopback cluster and require oracle-correct, exactly-once output.

def _reference(sql, ordered=False):
    from presto_tpu.exec.runner import LocalQueryRunner
    return LocalQueryRunner("sf0.01").execute_reference(sql)


def _assert_same(got, sql, ordered=False):
    from presto_tpu.exec.runner import _assert_rows_equal
    _assert_rows_equal(got, _reference(sql), ordered)


def _metric(uri, name):
    import urllib.request
    with urllib.request.urlopen(uri + "/v1/metrics", timeout=5) as r:
        text = r.read().decode()
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return None


CHAOS_SQL = ("select o_orderstatus, count(*), sum(o_totalprice) "
             "from orders, customer where c_custkey = o_custkey "
             "group by o_orderstatus")


@pytest.fixture
def lock_validation():
    """Chaos runs double as runtime lock-order validation runs: the
    lock_validation=on session property (exec/pipeline.py) makes every
    task driver thread record its OrderedLock acquisition stack
    (common/locks.py), and the fixture requires the whole run — retries,
    worker death, drains and all — to finish with ZERO rank inversions."""
    from presto_tpu.common.locks import LOCK_METRICS
    before = LOCK_METRICS.snapshot()["violations"]
    yield
    after = LOCK_METRICS.snapshot()["violations"]
    assert after == before, \
        f"{after - before} lock-order violation(s) during chaos run"


def test_chaos_worker_killed_mid_query_recovers(lock_validation):
    """Kill a worker the moment it starts running a task: the coordinator
    must classify the loss as retryable, reschedule the lost lineages onto
    the survivors, and still return oracle-correct rows exactly once."""
    import threading
    from presto_tpu.common.errors import InjectedTaskFailure
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w1, w2, w3 = WorkerServer(), WorkerServer(), WorkerServer()
    killed = threading.Event()

    def kill_on_first_task(task_id):
        if not killed.is_set():
            killed.set()
            threading.Thread(target=w2.close, daemon=True).start()
            raise InjectedTaskFailure(
                f"chaos: worker dying under task {task_id}")

    w2.task_manager.fault_injector = kill_on_first_task
    try:
        r = HttpQueryRunner(
            [w1.uri, w2.uri, w3.uri], "sf0.01", n_tasks=2,
            session={"exchange_max_error_duration": "5s",
                     "lock_validation": "on"})
        got = r.execute(CHAOS_SQL)
        _assert_same(got, CHAOS_SQL)
        assert killed.is_set(), "chaos hook never fired"
        assert r.tasks_retried >= 1
        # retry attempts land on the survivors with .rN lineage ids and
        # show up in their metrics
        retried = sum(w.task_manager.tasks_retried for w in (w1, w3))
        assert retried >= 1
        assert any(_metric(w.uri, "presto_tpu_task_retries_total") >= 1
                   for w in (w1, w3))
    finally:
        for w in (w1, w2, w3):
            w.close()


def test_chaos_injected_failure_exactly_once(lock_validation):
    """A transient (retryable) injected task failure: the query output must
    match the oracle exactly — no dropped and no duplicated pages — and the
    failure/retry counters must be visible in /v1/metrics."""
    from presto_tpu.common.errors import InjectedTaskFailure
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w1, w2 = WorkerServer(), WorkerServer()
    flaked = []

    def flaky_once(task_id):
        if not flaked:
            flaked.append(task_id)
            raise InjectedTaskFailure(f"chaos: flaky task {task_id}")

    w1.task_manager.fault_injector = flaky_once
    w2.task_manager.fault_injector = flaky_once
    try:
        r = HttpQueryRunner([w1.uri, w2.uri], "sf0.01", n_tasks=2,
                            session={"lock_validation": "on"})
        got = r.execute(CHAOS_SQL)
        _assert_same(got, CHAOS_SQL)
        assert len(flaked) == 1
        assert r.tasks_retried >= 1
        failed = sum(_metric(w.uri, "presto_tpu_tasks_failed_total")
                     for w in (w1, w2))
        retried = sum(_metric(w.uri, "presto_tpu_task_retries_total")
                      for w in (w1, w2))
        assert failed >= 1 and retried >= 1
    finally:
        w1.close()
        w2.close()


def test_chaos_user_error_fails_fast_without_retry(lock_validation):
    """A USER_ERROR-shaped failure must fail the query immediately: no task
    retry attempts anywhere, and the typed error survives the HTTP hop."""
    from presto_tpu.common.errors import PrestoUserError
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w = WorkerServer()
    calls = []

    def user_bug(task_id):
        calls.append(task_id)
        raise ValueError("chaos: user's input is malformed")

    w.task_manager.fault_injector = user_bug
    try:
        r = HttpQueryRunner([w.uri], "sf0.01", n_tasks=1,
                            session={"lock_validation": "on"})
        with pytest.raises(PrestoUserError):
            r.execute("select count(*) from nation")
        assert r.tasks_retried == 0
        assert w.task_manager.tasks_retried == 0
        assert all(".r" not in t for t in calls)
    finally:
        w.close()


def test_chaos_retry_budget_exhausts(lock_validation):
    """A permanently failing task consumes its attempt budget and then
    fails the query with a typed error instead of retrying forever."""
    from presto_tpu.common.errors import (InjectedTaskFailure,
                                          PrestoQueryError)
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w = WorkerServer()
    calls = []

    def always_fail(task_id):
        calls.append(task_id)
        raise InjectedTaskFailure(f"chaos: permanent failure {task_id}")

    w.task_manager.fault_injector = always_fail
    try:
        r = HttpQueryRunner(
            [w.uri], "sf0.01", n_tasks=1,
            session={"remote_task_retry_attempts": "1",
                     "lock_validation": "on"})
        with pytest.raises(PrestoQueryError, match="retry attempt"):
            r.execute("select count(*) from region")
        # at least one budgeted retry reached the worker, and no lineage
        # was ever charged past its budget of 1.  (The exact worker-side
        # tasks_retried count depends on which failure event the status
        # watcher delivers first — a producer restart cascades an
        # UNcharged consumer restart — so assert the budget invariant,
        # not the event ordering.)
        assert w.task_manager.tasks_retried >= 1
        budget_used = r.last_execution.budget_used
        assert budget_used and max(budget_used.values()) == 1
        # bounded: permanent failure must not retry beyond budget+cascades
        assert len(calls) <= 6
    finally:
        w.close()


def test_probabilistic_fault_injection_session_property(lock_validation):
    """fault_injection_probability=1.0 via session property trips the
    deterministic sha256 roll on every attempt; with retry disabled the
    query fails on the first injected fault."""
    from presto_tpu.common.errors import PrestoQueryError
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w = WorkerServer()
    try:
        r = HttpQueryRunner(
            [w.uri], "sf0.01", n_tasks=1,
            session={"fault_injection_probability": "1.0",
                     "remote_task_retry_attempts": "0",
                     "lock_validation": "on"})
        with pytest.raises(PrestoQueryError):
            r.execute("select count(*) from region")
        assert w.task_manager.tasks_failed >= 1
    finally:
        w.close()


# ---------------------------------------------------------------------------
# adaptive execution under chaos (dynamic filters are advisory, never load-
# bearing: every failure mode must degrade to "scan ran unfiltered", with
# rows still oracle-exact)
# ---------------------------------------------------------------------------

# `+ 0` keeps the predicate opaque to the stats calculator; zones finer
# than the table (storage_zone_rows) give the runtime filter chunks to prune
AQE_CHAOS_SQL = ("select sum(l_extendedprice), count(*) "
                 "from lineitem, orders "
                 "where l_orderkey = o_orderkey and o_orderkey + 0 < 30")

AQE_SESSION = {"lock_validation": "on", "storage_zone_rows": "4096"}


def _build_stage_paths(r, sql):
    """Task-id stage-path markers ('0_0' style) of every fragment that is
    a dynamic-filter SOURCE (the build stages)."""
    sub, _, _ = r.plan_subplan(sql)
    out = []

    def walk(sp, path):
        if sp.fragment.dynamic_filter_sources:
            out.append(path.replace(".", "_"))
        for i, c in enumerate(sp.children):
            walk(c, f"{path}.{i}")

    walk(sub, "0")
    return out


def test_chaos_build_worker_killed_scans_fall_back_unfiltered(
        lock_validation):
    """Kill the worker running the dynamic-filter BUILD task before it can
    summarize: downstream scans wait out dynamic-filtering.wait-timeout,
    proceed unfiltered, and the (retried) query still returns oracle-exact
    rows — losing the filter may cost pruning, never correctness."""
    import threading
    from presto_tpu.common.errors import InjectedTaskFailure
    from presto_tpu.exec.adaptive import ADAPTIVE_METRICS
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    workers = [WorkerServer() for _ in range(3)]
    killed = threading.Event()
    before = ADAPTIVE_METRICS.snapshot()
    try:
        r = HttpQueryRunner(
            [w.uri for w in workers], "sf0.01", n_tasks=2,
            session={**AQE_SESSION,
                     "dynamic_filtering_wait_timeout": "50ms",
                     "exchange_max_error_duration": "5s"})
        build_paths = _build_stage_paths(r, AQE_CHAOS_SQL)
        assert build_paths, "test premise broken: no dynamic-filter source"

        def kill_build(w):
            def injector(task_id):
                if killed.is_set():
                    return
                if any(f".{p}." in task_id for p in build_paths):
                    killed.set()
                    threading.Thread(target=w.close, daemon=True).start()
                    raise InjectedTaskFailure(
                        f"chaos: build worker dying under {task_id}")
            return injector

        for w in workers:
            w.task_manager.fault_injector = kill_build(w)
        got = r.execute(AQE_CHAOS_SQL)
        _assert_same(got, AQE_CHAOS_SQL)
        assert killed.is_set(), "chaos hook never saw a build task"
        assert r.tasks_retried >= 1
        after = ADAPTIVE_METRICS.snapshot()
        # probe scans started while the build was dying: the bounded wait
        # expired and they ran unfiltered (workers share this process, so
        # the registry sees their counters)
        assert after["filter_wait_timeouts"] > before["filter_wait_timeouts"]
    finally:
        for w in workers:
            w.close()


def test_chaos_late_dynamic_filter_is_ignored_not_fatal(lock_validation):
    """A summary pushed AFTER a task's wait expired (or after the task
    finished entirely) is metered as a late arrival and otherwise ignored:
    the coordinator pump racing task completion must never fail a query."""
    from presto_tpu.exec.adaptive import ADAPTIVE_METRICS
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w = WorkerServer()
    try:
        r = HttpQueryRunner([w.uri], "sf0.01", n_tasks=1,
                            session=dict(AQE_SESSION))
        got = r.execute(AQE_CHAOS_SQL)
        _assert_same(got, AQE_CHAOS_SQL)
        tasks = list(w.task_manager.tasks.values())
        assert tasks, "finished tasks already evicted"
        before = ADAPTIVE_METRICS.snapshot()["filter_late_arrivals"]
        tasks[0].deliver_dynamic_filters(
            {"df_late": {"filterId": "df_late", "rowCount": 1,
                         "min": 1, "max": 1}})
        after = ADAPTIVE_METRICS.snapshot()["filter_late_arrivals"]
        assert after == before + 1
    finally:
        w.close()


def test_chaos_lock_validation_over_adaptive_paths(lock_validation):
    """The new coordinator<->task surfaces (summary collection polls,
    TaskUpdateRequest filter pushes, task-side waits) run under
    lock_validation=on: oracle-exact rows, filters demonstrably collected
    AND applied, zero lock-order violations (fixture)."""
    from presto_tpu.exec.adaptive import ADAPTIVE_METRICS
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w1, w2 = WorkerServer(), WorkerServer()
    before = ADAPTIVE_METRICS.snapshot()
    try:
        r = HttpQueryRunner([w1.uri, w2.uri], "sf0.01", n_tasks=2,
                            session=dict(AQE_SESSION))
        got = r.execute(AQE_CHAOS_SQL)
        _assert_same(got, AQE_CHAOS_SQL)
        after = ADAPTIVE_METRICS.snapshot()
        assert after["filters_collected"] > before["filters_collected"]
        assert after["filters_applied"] > before["filters_applied"]
        # a summary landing before task creation prunes whole chunks; one
        # landing mid-scan prunes rows — either way something was dropped
        pruned = (after["filter_rows_pruned"] - before["filter_rows_pruned"]
                  + after["filter_chunks_skipped"]
                  - before["filter_chunks_skipped"])
        assert pruned > 0
        # the loopback workers also export the registry as prometheus text
        assert _metric(w1.uri,
                       "presto_tpu_adaptive_filters_applied_total") >= 1
    finally:
        w1.close()
        w2.close()


def test_task_manager_abort_hook_and_counters():
    from presto_tpu.worker.protocol import (OutputBuffersSpec,
                                            TaskUpdateRequest)
    from presto_tpu.worker.task import TaskManager

    tm = TaskManager()
    tm.create_or_update(TaskUpdateRequest(
        "qx.0.0", 0, None, [], OutputBuffersSpec("PARTITIONED", 1)))
    tm.abort("qx.0.0", "chaos abort")
    st = tm.get("qx.0.0").status()
    assert st.state == "FAILED"
    assert st.error_type == "INTERNAL_ERROR"
    counts = tm.counts()
    assert counts["failed"] == 1 and counts["retried"] == 0
    # retry-suffixed creations are counted as coordinator retry attempts
    tm.create_or_update(TaskUpdateRequest(
        "qx.0.0.r1", 0, None, [], OutputBuffersSpec("PARTITIONED", 1)))
    assert tm.counts()["retried"] == 1


def test_task_manager_periodic_reaper():
    """Terminal tasks are evicted by the background reaper even when no new
    create_or_update call ever arrives (PeriodicTaskManager analog)."""
    import time
    from presto_tpu.worker.protocol import (OutputBuffersSpec,
                                            TaskUpdateRequest)
    from presto_tpu.worker.task import TaskManager

    tm = TaskManager()
    tm.TASK_TTL_S = 0.05
    tm.create_or_update(TaskUpdateRequest(
        "qr.0.0", 0, None, [], OutputBuffersSpec("PARTITIONED", 1)))
    tm.abort("qr.0.0")
    tm.start_reaper(interval_s=0.05)
    try:
        deadline = time.time() + 5
        while "qr.0.0" in tm.tasks and time.time() < deadline:
            time.sleep(0.02)
        assert "qr.0.0" not in tm.tasks
    finally:
        tm.stop_reaper()


def test_exchange_lost_on_missing_task():
    """404 on a results pull means the producer task is GONE (worker
    restarted): a typed ExchangeLostError carrying the location, not a
    KeyError query failure."""
    from presto_tpu.common.errors import ExchangeLostError
    from presto_tpu.worker.exchange import pull_pages
    from presto_tpu.worker.server import WorkerServer

    w = WorkerServer()
    try:
        loc = f"{w.uri}/v1/task/ghost.0.0/results/0"
        with pytest.raises(ExchangeLostError) as ei:
            list(pull_pages(loc, max_error_duration_s=0.5))
        assert ei.value.location == loc
    finally:
        w.close()


def test_exchange_budget_bounds_unreachable_source():
    """An unreachable exchange source retries with backoff only until the
    error budget expires, then surfaces ExchangeLostError."""
    import time
    from presto_tpu.common.errors import ExchangeLostError
    from presto_tpu.worker.exchange import pull_pages

    loc = "http://127.0.0.1:1/v1/task/gone.0.0/results/0"
    t0 = time.monotonic()
    with pytest.raises(ExchangeLostError):
        list(pull_pages(loc, max_error_duration_s=0.3))
    assert time.monotonic() - t0 < 10.0


def test_error_classifier_taxonomy():
    import urllib.error
    from presto_tpu.common.errors import (EXTERNAL, INSUFFICIENT_RESOURCES,
                                          INTERNAL_ERROR, USER_ERROR,
                                          classify_exception, is_retryable,
                                          parse_error_type,
                                          producer_task_from_text)

    assert classify_exception(ValueError("bad sql")) == USER_ERROR
    assert classify_exception(ConnectionRefusedError()) == EXTERNAL
    assert classify_exception(TimeoutError()) == EXTERNAL
    assert classify_exception(MemoryError()) == INSUFFICIENT_RESOURCES
    assert classify_exception(RuntimeError("boom")) == INTERNAL_ERROR
    assert classify_exception(
        urllib.error.HTTPError("u", 503, "busy", {}, None)) == EXTERNAL
    assert classify_exception(
        urllib.error.HTTPError("u", 400, "bad", {}, None)) == USER_ERROR
    # tags survive string-typed failure chains
    assert parse_error_type("task q.0.0 failed [USER_ERROR]: x") \
        == USER_ERROR
    assert not is_retryable(
        RuntimeError("remote said [USER_ERROR] bad query"))
    assert is_retryable(RuntimeError("remote said [EXTERNAL] net down"))
    # a malformed plan re-plans identically: PLAN_VALIDATION fails fast
    from presto_tpu.common.errors import PLAN_VALIDATION, PlanValidationError
    assert classify_exception(PlanValidationError("bad")) == PLAN_VALIDATION
    assert not is_retryable(PlanValidationError("bad"))
    assert parse_error_type(
        "task q.0.0 failed [PLAN_VALIDATION]: bad") == PLAN_VALIDATION
    assert producer_task_from_text(
        "exchange source http://h:1/v1/task/q1.0_0.1.r2/results/3 "
        "vanished") == "q1.0_0.1.r2"


# ---------------------------------------------------------------------------
# concurrent exchange client (ExchangeClient)
# ---------------------------------------------------------------------------
# The tentpole of the concurrent-shuffle round: pulls from all upstream
# locations at once into a bounded arrival-order buffer.  These tests run
# it against a scriptable fake buffer server (per-location delay / stall /
# injected failure) and against real loopback clusters.

def _page_bytes(values):
    from presto_tpu.common.block import long_array_block
    from presto_tpu.common.page import Page
    from presto_tpu.common.serde import serialize_page
    return serialize_page(Page([long_array_block(values)]))


class _FakeBufferServer:
    """Minimal results-protocol producer with scriptable per-task behavior:
    specs maps task_id -> {"pages": [serialized bytes], "delay_s": float
    (per results GET), "stall_s": float (first GET only), "fail": (code,
    body) served instead of data}."""

    def __init__(self, specs):
        import http.server
        import re
        import threading
        import time as _t

        self.specs = specs
        rx = re.compile(
            r"^/v1/task/(?P<task>[^/]+)/results/(?P<buffer>\d+)"
            r"(?:/(?P<token>\d+)(?P<ack>/acknowledge)?)?$")
        stalled = {}
        outer = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _reply(self, code, body=b"", headers=()):
                self.send_response(code)
                for k, v in headers:
                    self.send_header(k, v)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                m = rx.match(self.path.split("?")[0])
                if not m:
                    return self._reply(404)
                spec = outer.specs.get(m.group("task"))
                if spec is None:
                    return self._reply(404)
                if m.group("ack"):
                    return self._reply(200)
                if spec.get("fail"):
                    code, msg = spec["fail"]
                    return self._reply(code, msg.encode())
                if spec.get("stall_s") and not stalled.get(m.group("task")):
                    stalled[m.group("task")] = True
                    _t.sleep(spec["stall_s"])
                if spec.get("delay_s"):
                    _t.sleep(spec["delay_s"])
                pages = spec["pages"]
                token = int(m.group("token"))
                per_round = spec.get("per_round", 1)
                body = b"".join(pages[token:token + per_round])
                nxt = min(len(pages), token + per_round)
                return self._reply(200, body, [
                    ("X-Presto-Page-Sequence-Id", str(token)),
                    ("X-Presto-Page-End-Sequence-Id", str(nxt)),
                    ("X-Presto-Buffer-Complete",
                     "true" if nxt >= len(pages) else "false"),
                ])

            def do_DELETE(self):
                self._reply(200)

        self._httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                                      Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        threading.Thread(target=self._httpd.serve_forever,
                         daemon=True).start()

    def location(self, task_id, buffer_id=0):
        return (f"http://127.0.0.1:{self.port}/v1/task/{task_id}"
                f"/results/{buffer_id}")

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()


def test_concurrent_client_beats_sequential_with_slow_producers():
    """Acceptance: with 4 upstream producers each charging an artificial
    per-request latency, the concurrent client's end-to-end drain wall
    beats the sequential baseline by roughly the producer count."""
    import time
    from presto_tpu.worker.exchange import ExchangeClient, pull_pages

    specs = {f"t{i}": {"pages": [_page_bytes([i * 10 + j]) for j in range(3)],
                       "delay_s": 0.1} for i in range(4)}
    srv = _FakeBufferServer(specs)
    try:
        locations = [srv.location(f"t{i}") for i in range(4)]
        t0 = time.perf_counter()
        seq_values = []
        for loc in locations:
            for page in pull_pages(loc):
                seq_values.append(page.blocks[0].values[0])
        seq_wall = time.perf_counter() - t0

        t0 = time.perf_counter()
        client = ExchangeClient(locations, client_threads=4)
        conc_values = [p.blocks[0].values[0] for p in client.pages()]
        conc_wall = time.perf_counter() - t0

        assert sorted(conc_values) == sorted(seq_values)
        assert len(conc_values) == 12
        # 4 producers x 3 rounds x 0.1s sequentially vs ~3 rounds overlapped
        assert conc_wall < seq_wall * 0.6, (conc_wall, seq_wall)
    finally:
        srv.close()


def test_stalled_producer_does_not_starve_other_pullers():
    """Chaos: one producer stalls its first response; pages from the other
    producers must keep flowing through the shared buffer meanwhile."""
    import time
    from presto_tpu.worker.exchange import ExchangeClient

    specs = {"slow": {"pages": [_page_bytes([999])], "stall_s": 1.5}}
    for i in range(3):
        specs[f"fast{i}"] = {
            "pages": [_page_bytes([i * 10 + j]) for j in range(2)]}
    srv = _FakeBufferServer(specs)
    try:
        locations = [srv.location(t) for t in specs]
        client = ExchangeClient(locations, client_threads=4)
        t0 = time.perf_counter()
        arrivals = [(p.blocks[0].values[0], time.perf_counter() - t0)
                    for p in client.pages()]
        values = {v for v, _ in arrivals}
        assert values == {0, 1, 10, 11, 20, 21, 999}
        fast_done = max(at for v, at in arrivals if v != 999)
        slow_done = max(at for v, at in arrivals if v == 999)
        assert fast_done < 1.0, arrivals   # not starved behind the stall
        assert slow_done >= 1.0, arrivals  # the stall really happened
    finally:
        srv.close()


def test_exchange_client_backpressure_bounds_buffered_bytes():
    """Chaos: a fast producer against a slow consumer must park at the
    buffer bound — resident bytes stay <= exchange.max-buffer-size."""
    import time
    from presto_tpu.worker.exchange import ExchangeClient

    pages = [_page_bytes(list(range(k * 256, (k + 1) * 256)))
             for k in range(48)]          # ~2KB serialized each
    page_size = len(pages[0])
    limit = 4 * page_size                 # room for ~4 pages
    srv = _FakeBufferServer({"t0": {"pages": pages, "per_round": 2}})
    try:
        client = ExchangeClient([srv.location("t0")], client_threads=2,
                                max_buffer_bytes=limit)
        got = 0
        for _ in client.pages():
            got += 1
            time.sleep(0.005)             # slow consumer: queue fills
        assert got == len(pages)
        assert client.buffered_peak <= limit, (client.buffered_peak, limit)
        assert client.buffered_peak >= 2 * page_size  # it DID buffer ahead
    finally:
        srv.close()


def test_failed_sibling_aborts_client_promptly():
    """A failing producer surfaces its typed error through the concurrent
    client immediately — a stalled sibling location cannot delay failure
    propagation (the sequential client would sit in the stall first)."""
    import time
    from presto_tpu.common.errors import RemoteTaskError
    from presto_tpu.worker.exchange import ExchangeClient

    srv = _FakeBufferServer({
        "stalled": {"pages": [_page_bytes([1])], "stall_s": 5.0},
        "failing": {"pages": [], "fail": (
            500, "task failing failed [INTERNAL_ERROR]: boom")},
    })
    try:
        client = ExchangeClient(
            [srv.location("stalled"), srv.location("failing")],
            client_threads=2)
        t0 = time.perf_counter()
        with pytest.raises(RemoteTaskError, match="INTERNAL_ERROR"):
            list(client.pages())
        assert time.perf_counter() - t0 < 2.5
    finally:
        srv.close()


def test_failed_task_aborts_worker_remote_source_promptly():
    """Regression (the should_abort bug): a worker task's remote source
    must stop pulling as soon as the task turns terminal — e.g. a FAILED
    sibling propagated by the coordinator — even while its producer is
    stalled and would otherwise hold the puller for seconds."""
    import threading
    import time
    from presto_tpu.exec.pipeline import ExecutionConfig
    from presto_tpu.worker.exchange import (ExchangeAbortedError,
                                            remote_page_reader)
    from presto_tpu.worker.task import TpuTask

    srv = _FakeBufferServer(
        {"slow": {"pages": [_page_bytes([1])], "stall_s": 10.0}})
    task = TpuTask("q.1.0", "http://127.0.0.1:0", ExecutionConfig())
    outcome = []

    def consume():
        # the exact reader wiring TpuTask.start() builds for remote splits
        reader = remote_page_reader([srv.location("slow")],
                                    should_abort=task._exchange_abort)
        try:
            list(reader())
            outcome.append("drained")
        except ExchangeAbortedError:
            outcome.append("aborted")

    t = threading.Thread(target=consume, daemon=True)
    try:
        t.start()
        time.sleep(0.3)                  # puller is inside the 10s stall
        task.fail("chaos: sibling task failed")
        t.join(timeout=3.0)
        assert not t.is_alive(), "remote source kept draining a dead task"
        assert outcome == ["aborted"]
    finally:
        srv.close()


def test_chaos_worker_kill_exactly_once_with_four_producers(lock_validation):
    """Worker death mid-pull with >= 4 upstream producers per consumer:
    the concurrent client + retained-buffer replay must still deliver
    oracle-correct rows exactly once."""
    import threading
    from presto_tpu.common.errors import InjectedTaskFailure
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w1, w2, w3 = WorkerServer(), WorkerServer(), WorkerServer()
    killed = threading.Event()

    def kill_on_first_task(task_id):
        if not killed.is_set():
            killed.set()
            threading.Thread(target=w2.close, daemon=True).start()
            raise InjectedTaskFailure(
                f"chaos: worker dying under task {task_id}")

    w2.task_manager.fault_injector = kill_on_first_task
    try:
        r = HttpQueryRunner(
            [w1.uri, w2.uri, w3.uri], "sf0.01", n_tasks=4,
            session={"exchange_max_error_duration": "5s",
                     "lock_validation": "on"})
        got = r.execute(CHAOS_SQL)
        _assert_same(got, CHAOS_SQL)
        assert killed.is_set(), "chaos hook never fired"
        assert r.tasks_retried >= 1
    finally:
        for w in (w1, w2, w3):
            w.close()


def test_exchange_metrics_and_buffer_bound_via_http():
    """Acceptance: the /v1/metrics exchange section reports pages/bytes
    moved, and the buffered-bytes peak stays under the session's
    exchange.max-buffer-size while a shuffle query runs."""
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.exchange import EXCHANGE_METRICS
    from presto_tpu.worker.server import WorkerServer

    w1, w2 = WorkerServer(), WorkerServer()
    try:
        EXCHANGE_METRICS.reset()
        r = HttpQueryRunner(
            [w1.uri, w2.uri], "sf0.01", n_tasks=2,
            session={"exchange_max_buffer_size": "1MB",
                     "exchange_max_response_size": "64kB"})
        got = r.execute(CHAOS_SQL)
        _assert_same(got, CHAOS_SQL)
        assert _metric(w1.uri, "presto_tpu_exchange_pages_total") > 0
        assert _metric(w1.uri, "presto_tpu_exchange_bytes_total") > 0
        assert _metric(w1.uri, "presto_tpu_exchange_clients_total") > 0
        peak = _metric(w1.uri, "presto_tpu_exchange_buffered_bytes_peak")
        assert 0 < peak <= 1 << 20, peak
        # every client is closed: the live gauge must drain back to zero
        assert _metric(w1.uri, "presto_tpu_exchange_buffered_bytes") == 0
    finally:
        w1.close()
        w2.close()


def test_exchange_runtime_stats_surfaced():
    """The root pull's per-client walls/bytes land in the query result's
    runtime stats (and per-task clients land in TaskInfo)."""
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w = WorkerServer()
    try:
        r = HttpQueryRunner([w.uri], "sf0.01", n_tasks=2)
        got = r.execute(CHAOS_SQL)
        _assert_same(got, CHAOS_SQL)
        stats = got.runtime_stats or {}
        assert stats["exchangeClientPages"]["sum"] > 0
        assert stats["exchangeClientBytes"]["sum"] > 0
        assert stats["exchangeClientPullWallNanos"]["sum"] > 0
        assert stats["exchangeClientDrainWallNanos"]["sum"] > 0
    finally:
        w.close()


# ---------------------------------------------------------------------------
# fault-tolerant execution mode (retry-policy=task): durable spooled
# exchange, task-granular retry, graceful decommission, query deadlines
# ---------------------------------------------------------------------------

_RETRY_SUFFIX_RX = None


def _base_lineage(task_id):
    import re
    global _RETRY_SUFFIX_RX
    if _RETRY_SUFFIX_RX is None:
        _RETRY_SUFFIX_RX = re.compile(r"\.r\d+$")
    return _RETRY_SUFFIX_RX.sub("", task_id)


def test_chaos_task_retry_policy_retries_only_failed_task(lock_validation):
    """Tentpole: under retry-policy=task a transient task failure retries
    ONLY the failed lineage — ancestors' spooled output replays, so no
    ancestor stage gets a .rN re-run — and rows stay oracle-exact."""
    from presto_tpu.common.errors import InjectedTaskFailure
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer
    from presto_tpu.worker.spooling import SPOOL_METRICS

    w1, w2 = WorkerServer(), WorkerServer()
    flaked = []

    def flaky_once(task_id):
        if not flaked:
            flaked.append(task_id)
            raise InjectedTaskFailure(f"chaos: flaky task {task_id}")

    w1.task_manager.fault_injector = flaky_once
    w2.task_manager.fault_injector = flaky_once
    SPOOL_METRICS.reset()
    try:
        r = HttpQueryRunner([w1.uri, w2.uri], "sf0.01", n_tasks=2,
                            session={"retry_policy": "task",
                                     "lock_validation": "on"})
        got = r.execute(CHAOS_SQL)
        _assert_same(got, CHAOS_SQL)
        assert len(flaked) == 1
        assert r.tasks_retried >= 1
        exe = r.last_execution
        failed_lineage = _base_lineage(flaked[0])
        # ONLY the failed lineage was charged against the attempt budget
        assert dict(exe.budget_used) == {failed_lineage: 1}
        # ...and every .rN attempt anywhere in the cluster belongs to it:
        # no ancestor stage was restarted
        retry_ids = [t.task_id for t in exe.all_tasks
                     if _base_lineage(t.task_id) != t.task_id]
        assert retry_ids, "no retry attempt was placed"
        assert {_base_lineage(t) for t in retry_ids} == {failed_lineage}
        # the durable spool actually carried stage output
        snap = SPOOL_METRICS.snapshot()
        assert snap["spooled_pages"] > 0 and snap["spooled_bytes"] > 0
        assert _metric(w1.uri, "presto_tpu_spool_spooled_bytes_total") > 0
    finally:
        w1.close()
        w2.close()


def test_chaos_worker_killed_task_policy_no_ancestor_rerun(lock_validation):
    """Tentpole acceptance: kill a worker mid-query under
    retry-policy=task.  Recovery re-runs only the lineages that were
    placed on the dead worker (their consumers redirect to the
    replacements' spooled buffers) and the rows are oracle-exact."""
    import threading
    from presto_tpu.common.errors import InjectedTaskFailure
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w1, w2, w3 = WorkerServer(), WorkerServer(), WorkerServer()
    killed = threading.Event()

    def kill_on_first_task(task_id):
        if not killed.is_set():
            killed.set()
            threading.Thread(target=w2.close, daemon=True).start()
            raise InjectedTaskFailure(
                f"chaos: worker dying under task {task_id}")

    w2.task_manager.fault_injector = kill_on_first_task
    try:
        r = HttpQueryRunner(
            [w1.uri, w2.uri, w3.uri], "sf0.01", n_tasks=2,
            session={"retry_policy": "task",
                     "exchange_max_error_duration": "10s",
                     "lock_validation": "on"})
        got = r.execute(CHAOS_SQL)
        _assert_same(got, CHAOS_SQL)
        assert killed.is_set(), "chaos hook never fired"
        assert r.tasks_retried >= 1
        exe = r.last_execution
        dead_lineages = {_base_lineage(t.task_id) for t in exe.all_tasks
                         if t.worker_uri == w2.uri}
        # every charged lineage and every .rN attempt traces back to a
        # task that was on the dead worker: survivors never re-ran
        assert set(exe.budget_used) <= dead_lineages
        retried = {_base_lineage(t.task_id) for t in exe.all_tasks
                   if _base_lineage(t.task_id) != t.task_id}
        assert retried and retried <= dead_lineages
        for t in exe.all_tasks:
            if _base_lineage(t.task_id) != t.task_id:
                assert t.worker_uri != w2.uri  # retries land on survivors
    finally:
        for w in (w1, w2, w3):
            w.close()


def test_chaos_graceful_drain_zero_failures(lock_validation):
    """PUT /v1/info/state SHUTTING_DOWN on a worker while queries are in
    flight: every query completes with oracle-exact rows (its spooled
    output survives until consumed), the scheduler stops placing tasks on
    the draining worker, and the process exits cleanly."""
    import threading
    import time
    import urllib.request
    from presto_tpu.worker.auth import outbound_headers
    from presto_tpu.worker.coordinator import (HeartbeatFailureDetector,
                                               HttpQueryRunner)
    from presto_tpu.worker.server import WorkerServer

    w1, w2, w3 = WorkerServer(), WorkerServer(), WorkerServer()
    uris = [w1.uri, w2.uri, w3.uri]
    det = HeartbeatFailureDetector(uris, interval_s=0.1)
    session = {"retry_policy": "task", "lock_validation": "on"}
    runners = [HttpQueryRunner(uris, "sf0.01", n_tasks=2,
                               failure_detector=det, session=session)
               for _ in range(2)]
    results, errors = [], []

    def run_one(runner):
        try:
            results.append(runner.execute(CHAOS_SQL))
        except Exception as e:  # noqa: BLE001 — the test asserts on it
            errors.append(e)

    try:
        # warm both runners so tasks have landed on every worker and the
        # pipelines are compiled before the chaos window opens
        for r in runners:
            _assert_same(r.execute(CHAOS_SQL), CHAOS_SQL)
        threads = [threading.Thread(target=run_one, args=(r,))
                   for r in runners]
        for t in threads:
            t.start()
        time.sleep(0.1)                    # queries are mid-flight
        req = urllib.request.Request(
            w3.uri + "/v1/info/state", data=b'"SHUTTING_DOWN"',
            method="PUT", headers={"Content-Type": "application/json",
                                   **outbound_headers()})
        urllib.request.urlopen(req, timeout=5).close()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors          # zero query failures
        assert len(results) == 2
        for got in results:
            _assert_same(got, CHAOS_SQL)
        # the detector observes the drain and excludes w3 from placement
        deadline = time.time() + 5
        while time.time() < deadline and \
                det.snapshot()[w3.uri]["draining"] is not True:
            time.sleep(0.05)
        assert det.snapshot()[w3.uri]["draining"] is True
        created_before = w3.task_manager.counts()["created"]
        _assert_same(runners[0].execute(CHAOS_SQL), CHAOS_SQL)
        assert w3.task_manager.counts()["created"] == created_before, \
            "draining worker was given new tasks"
        # drained output is consumed, so the server exits on its own
        deadline = time.time() + 45
        while time.time() < deadline and not w3._closed:
            time.sleep(0.2)
        assert w3._closed, "graceful drain never completed"
    finally:
        det.close()
        for w in (w1, w2, w3):
            w.close()


def test_chaos_query_deadline_typed_error_no_retry(lock_validation):
    """query.max-execution-time mints a typed, NON-retryable
    EXCEEDED_TIME_LIMIT user error at the coordinator: no task retry is
    attempted anywhere and the failure surfaces promptly."""
    import time
    from presto_tpu.common.errors import (PrestoUserError,
                                          QueryDeadlineExceededError)
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w = WorkerServer()
    try:
        r = HttpQueryRunner(
            [w.uri], "sf0.01", n_tasks=2,
            session={"query_max_execution_time": "50ms",
                     "lock_validation": "on"})
        t0 = time.monotonic()
        with pytest.raises(QueryDeadlineExceededError,
                           match="EXCEEDED_TIME_LIMIT"):
            r.execute(CHAOS_SQL)
        elapsed = time.monotonic() - t0
        assert elapsed < 15.0, elapsed     # enforced, not TTL'd out
        assert r.tasks_retried == 0
        assert w.task_manager.tasks_retried == 0
        # typed USER_ERROR: the classifier must never call this retryable
        from presto_tpu.common.errors import is_retryable
        assert issubclass(QueryDeadlineExceededError, PrestoUserError)
        assert not is_retryable(QueryDeadlineExceededError(1.0, 0.05))
    finally:
        w.close()


def test_chaos_poison_split_quarantined(lock_validation):
    """A split that fails with the SAME internal error signature on two
    distinct workers is poison: the query fails fast with the split
    identity in the typed error instead of burning the whole attempt
    budget re-running a crasher."""
    from presto_tpu.common.errors import (InjectedTaskFailure,
                                          PoisonSplitError)
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer

    w1, w2 = WorkerServer(), WorkerServer()
    target = []

    def poison(task_id):
        base = _base_lineage(task_id)
        if not target:
            target.append(base)
        if base == target[0]:
            raise InjectedTaskFailure("chaos: poison split crash")

    w1.task_manager.fault_injector = poison
    w2.task_manager.fault_injector = poison
    try:
        r = HttpQueryRunner(
            [w1.uri, w2.uri], "sf0.01", n_tasks=2,
            session={"remote_task_retry_attempts": "4",
                     "lock_validation": "on"})
        with pytest.raises(PoisonSplitError, match="POISON_SPLIT") as ei:
            r.execute(CHAOS_SQL)
        # the split identity is in the message, and quarantine fired well
        # inside the 4-attempt budget (one charge, then two distinct
        # workers had seen the signature)
        assert target[0] in str(ei.value)
        exe = r.last_execution
        assert exe.budget_used.get(target[0], 0) <= 2
    finally:
        w1.close()
        w2.close()


def test_producer_coalesces_small_pages_per_response():
    """Producer-side exchange.max-response-size: many tiny pages come back
    in few coalesced pull rounds, but an X-Presto-Max-Size cap well below
    the coalesce target still bounds each response."""
    from presto_tpu.worker.buffers import PageBuffer

    tiny = _page_bytes([1, 2, 3])
    buf = PageBuffer(coalesce_target_bytes=len(tiny) * 4)
    for _ in range(10):
        buf.add(tiny)
    buf.set_complete()
    pages, nxt, done = buf.get(0, max_wait_s=0.1)
    # 10 tiny adds -> 3 coalesced entries (4 + 4 + final 2), not 10 rounds
    assert [len(p) // len(tiny) for p in pages] == [4, 4, 2]
    assert done and nxt == 3
    # consumer byte cap takes precedence over the coalesced batch count
    capped, nxt2, done2 = buf.get(0, max_wait_s=0.1,
                                  max_bytes=len(tiny) * 4)
    assert len(capped) == 1 and not done2 and nxt2 == 1
