"""Serving-plane micro-batching (serving/batching.py + serving/batched.py),
the persistent executable cache (serving/persist.py), and fragment-level
executable sharing (serving/fragments.py).

The load-bearing property throughout: a batched EXECUTE..USING produces
ROWS BIT-IDENTICAL to its solo run — the vmapped program replays the
sequential fused direct path's exact update sequence per lane — and one
lane's bind error never fails its batchmates."""
import random
import threading

import pytest

from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner
from presto_tpu.serving import (FRAGMENT_JIT_CACHE, GLOBAL_PLAN_CACHE,
                                MicroBatcher, PREPARED_REGISTRY,
                                PlanCache, PlanCacheSidecar,
                                SERVING_METRICS)


@pytest.fixture(autouse=True)
def _reset_serving():
    SERVING_METRICS.reset()
    PREPARED_REGISTRY.clear()
    FRAGMENT_JIT_CACHE.invalidate_all()
    yield


def _snapshot():
    return SERVING_METRICS.snapshot()


def _runner(schema="sf0.01", **cfg):
    config = ExecutionConfig(**cfg) if cfg else None
    return LocalQueryRunner(schema, config=config, plan_cache=PlanCache())


# ---------------------------------------------------------------------------
# MicroBatcher unit behavior
# ---------------------------------------------------------------------------

def test_micro_batcher_disabled_runs_inline():
    b = MicroBatcher(window_ms=50, max_batch=1)
    assert not b.enabled
    calls = []
    out = b.run("k", 1, lambda items: [i * 10 for i in items],
                lambda item: calls.append(item) or item + 100)
    assert out == 101 and calls == [1]


def test_micro_batcher_groups_concurrent_items():
    b = MicroBatcher(window_ms=200, max_batch=8)
    results, solo = {}, []

    def worker(i):
        results[i] = b.run(
            "k", i, lambda items: [x * 10 for x in items],
            lambda item: solo.append(item) or item)
    ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert results == {0: 0, 1: 10, 2: 20, 3: 30}
    assert solo == []       # everyone rode the batch


def test_micro_batcher_full_batch_short_circuits_window():
    b = MicroBatcher(window_ms=10_000, max_batch=2)
    results = {}

    def worker(i):
        results[i] = b.run("k", i, lambda items: [x + 1 for x in items],
                           lambda item: -item)
    ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in ts), "window was not cut short"
    assert results == {0: 1, 1: 2}


def test_micro_batcher_none_lane_falls_back_isolated():
    b = MicroBatcher(window_ms=200, max_batch=8)
    results = {}

    def execute_batch(items):
        # lane for item 1 'fails' inside the drain
        return [None if x == 1 else x * 10 for x in items]

    def worker(i):
        results[i] = b.run("k", i, execute_batch, lambda item: -item)
    ts = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert results[1] == -1             # solo fallback, on its own thread
    assert results[0] == 0 and results[2] == 20
    assert _snapshot()["servingBatchFallbacks"] == 1


def test_micro_batcher_batch_exception_everyone_falls_back():
    b = MicroBatcher(window_ms=200, max_batch=8)
    results = {}

    def worker(i):
        results[i] = b.run(
            "k", i, lambda items: (_ for _ in ()).throw(RuntimeError()),
            lambda item: item + 100)
    ts = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert results == {0: 100, 1: 101, 2: 102}
    assert _snapshot()["servingBatchFallbacks"] == 3


def test_micro_batcher_single_item_runs_solo():
    b = MicroBatcher(window_ms=1, max_batch=8)
    batches = []
    out = b.run("k", 7, lambda items: batches.append(items) or [70],
                lambda item: item)
    assert out == 7 and batches == []   # occupancy-1: never drained


# ---------------------------------------------------------------------------
# batched execution: bit-identity vs sequential
# ---------------------------------------------------------------------------

Q6_TEMPLATE = ("select sum(l_extendedprice * l_discount) as revenue "
               "from lineitem where l_shipdate >= ? and l_shipdate < ? "
               "and l_discount between ? and ? and l_quantity < ?")
GROUPED_TEMPLATE = ("select l_returnflag, count(*) as c, "
                    "sum(l_quantity) as q, min(l_extendedprice) as lo, "
                    "max(l_extendedprice) as hi from lineitem "
                    "where l_quantity < ? group by l_returnflag")


def _rows_equal(a, b):
    return sorted(map(tuple, a)) == sorted(map(tuple, b))


def test_batched_q6_bit_identical_to_sequential():
    r = _runner()
    r.execute(f"prepare q6 from {Q6_TEMPLATE}")
    binds = [
        "execute q6 using date '1994-01-01', date '1995-01-01', "
        "0.05, 0.07, 24",
        "execute q6 using date '1994-01-01', date '1995-01-01', "
        "0.04, 0.06, 30",
        "execute q6 using date '1995-01-01', date '1996-01-01', "
        "0.01, 0.03, 10",
    ]
    seq = [r.execute(s).rows for s in binds]
    out = r.execute_prepared_batch(binds)
    assert out is not None
    for a, b in zip(seq, out):
        assert b is not None and a == b.rows    # exact, order and all
    sv = _snapshot()
    assert sv["servingBatches"] == 1
    assert sv["servingBatchQueries"] == 3
    assert sv["servingBatchLaunchesSaved"] == 2
    assert sv["servingBatchOccupancy"] == {"3": 1}
    assert sv["servingBatchPaddedLanes"] == 1   # 3 lanes -> width 4


def test_batched_grouped_bit_identical():
    r = _runner()
    r.execute(f"prepare sp from {GROUPED_TEMPLATE}")
    binds = [f"execute sp using {v}" for v in (11, 24, 37, 50)]
    seq = [r.execute(s).rows for s in binds]
    out = r.execute_prepared_batch(binds)
    assert out is not None
    for a, b in zip(seq, out):
        assert b is not None and _rows_equal(a, b.rows)


def test_batched_bind_error_lane_is_isolated():
    r = _runner()
    r.execute(f"prepare sp from {GROUPED_TEMPLATE}")
    binds = ["execute sp using 24",
             "execute sp using 'not a number'",     # bad bind mid-batch
             "execute sp using 30"]
    want0 = r.execute(binds[0]).rows
    want2 = r.execute(binds[2]).rows
    out = r.execute_prepared_batch(binds)
    assert out is not None
    assert out[1] is None                   # caller re-runs it solo
    assert _rows_equal(out[0].rows, want0)
    assert _rows_equal(out[2].rows, want2)


def test_batched_null_bind_lane_is_isolated():
    r = _runner()
    r.execute(f"prepare sp from {GROUPED_TEMPLATE}")
    binds = ["execute sp using 24", "execute sp using null",
             "execute sp using 30"]
    out = r.execute_prepared_batch(binds)
    if out is None:
        pytest.skip("NULL binds to a typed slot on this build")
    assert out[0] is not None and out[2] is not None


def test_batched_declines_mixed_templates_and_cold_cache():
    r = _runner()
    r.execute(f"prepare q6 from {Q6_TEMPLATE}")
    r.execute(f"prepare sp from {GROUPED_TEMPLATE}")
    # cold: no solo execution recorded the fast path yet
    assert r.execute_prepared_batch(
        ["execute sp using 1", "execute sp using 2"]) is None
    r.execute("execute sp using 24")
    # mixed templates are not one batch
    assert r.execute_prepared_batch(
        ["execute sp using 24",
         "execute q6 using date '1994-01-01', date '1995-01-01', "
         "0.05, 0.07, 24"]) is None
    # fewer than two bindable lanes
    assert r.execute_prepared_batch(["execute sp using 24"]) is None


def test_batched_fuzz_concurrent_mixed_binds():
    """Randomized concurrent EXECUTE..USING traffic through the batcher:
    mixed templates, bad binds mid-batch; every batched result must be
    bit-identical to the solo run of the same statement."""
    rng = random.Random(20260807)
    r = _runner()
    r.execute(f"prepare q6 from {Q6_TEMPLATE}")
    r.execute(f"prepare sp from {GROUPED_TEMPLATE}")

    def q6_stmt():
        y0 = rng.choice(["1993", "1994", "1995"])
        lo = rng.choice(["0.01", "0.03", "0.05"])
        q = rng.randint(5, 49)
        return (f"execute q6 using date '{y0}-01-01', "
                f"date '{int(y0) + 1}-01-01', {lo}, "
                f"{float(lo) + 0.02:.2f}, {q}")

    def sp_stmt():
        if rng.random() < 0.15:
            return "execute sp using 'bogus'"        # bind error lane
        return f"execute sp using {rng.randint(1, 50)}"

    stmts = [q6_stmt() if rng.random() < 0.5 else sp_stmt()
             for _ in range(24)]
    expected = []
    for s in stmts:
        try:
            expected.append(r.execute(s).rows)
        except Exception as e:    # noqa: BLE001 — bind errors expected
            expected.append(type(e).__name__)

    batcher = MicroBatcher(window_ms=150, max_batch=8)
    got = [None] * len(stmts)

    def template_of(s):
        return s.split()[1]

    def serve(i):
        s = stmts[i]

        def run_one(item):
            try:
                return r.execute(item).rows
            except Exception as e:  # noqa: BLE001
                return type(e).__name__

        def run_batch(items):
            out = r.execute_prepared_batch(items)
            return None if out is None else [
                (o.rows if o is not None else None) for o in out]
        got[i] = batcher.run((template_of(s),), s, run_batch, run_one)

    threads = [threading.Thread(target=serve, args=(i,))
               for i in range(len(stmts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, (want, have) in enumerate(zip(expected, got)):
        if isinstance(want, str):
            assert have == want, f"lane {i}: error class changed"
        else:
            assert _rows_equal(want, have), f"lane {i} diverged"
    assert _snapshot()["servingBatches"] >= 1, "no batch ever formed"


def test_batched_results_stable_across_widths():
    """The same statement must produce identical rows whatever batch it
    rides in (pow2 padding, different batchmates)."""
    r = _runner()
    r.execute(f"prepare sp from {GROUPED_TEMPLATE}")
    pin = "execute sp using 24"
    want = r.execute(pin).rows
    others = [f"execute sp using {v}" for v in (5, 11, 17, 29, 35, 41)]
    for width in (2, 3, 5, 7):
        batch = [pin] + others[:width - 1]
        out = r.execute_prepared_batch(batch)
        assert out is not None and out[0] is not None
        assert out[0].rows == want, f"width {width} changed lane 0"


# ---------------------------------------------------------------------------
# compiler-pool contention metering
# ---------------------------------------------------------------------------

def test_checkout_contention_metrics():
    cache = PlanCache()
    r = LocalQueryRunner("sf0.01", plan_cache=cache)
    sql = "select count(*) from lineitem where l_quantity < 24"
    r.execute(sql)
    key = [k for k in cache._entries][0]
    held = [cache.checkout(key) for _ in range(6)]   # drain the pool
    sv = _snapshot()
    assert sv["compilerCheckouts"] >= 6
    assert sv["compilerPoolExhausted"] >= 1         # pool is 4 deep
    assert sv["compilerCheckoutDepthPeak"] >= 6
    info = cache.info()
    assert info["poolExhausted"] >= 1
    assert info["checkedOut"] == 6
    for _t, _s, comp in held:
        cache.checkin(key, comp)    # None = rebuilt-and-dropped checkout
    assert cache.info()["checkedOut"] == 0


# ---------------------------------------------------------------------------
# persistent plan-cache sidecar
# ---------------------------------------------------------------------------

def test_sidecar_record_dedup_load_clear(tmp_path):
    p = tmp_path / "plans.jsonl"
    sc = PlanCacheSidecar(str(p))
    prepared = {"q6": Q6_TEMPLATE}
    assert sc.record("execute q6 using 1", prepared, "tpch", "sf0.01")
    # same template, different binding -> dedup'd
    assert not sc.record("execute q6 using 2", prepared, "tpch", "sf0.01")
    # different schema is a different entry
    assert sc.record("execute q6 using 1", prepared, "tpch", "sf1")
    # no prepared map: dedup by statement text
    assert sc.record("select 1", None, "tpch", "sf0.01")
    assert not sc.record("select 1", None, "tpch", "sf0.01")
    recs = sc.load()
    assert len(recs) == 3
    assert recs[0]["prepared"] == prepared

    # a fresh instance re-reads the file (restart)
    sc2 = PlanCacheSidecar(str(p))
    assert not sc2.record("execute q6 using 9", prepared, "tpch", "sf0.01")
    sc2.clear()
    assert sc2.load() == [] and not p.exists()


def test_sidecar_tolerates_torn_tail(tmp_path):
    p = tmp_path / "plans.jsonl"
    sc = PlanCacheSidecar(str(p))
    sc.record("select 1", None, "tpch", "sf0.01")
    with open(p, "a", encoding="utf-8") as f:
        f.write('{"sql": "select 2", "cat')    # torn write at crash
    assert [r["sql"] for r in PlanCacheSidecar(str(p)).load()] == \
        ["select 1"]


def test_enable_compilation_cache(tmp_path):
    import jax
    from presto_tpu.serving import enable_compilation_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        d = tmp_path / "xla-cache"
        assert enable_compilation_cache(str(d))
        assert d.is_dir()
        assert jax.config.jax_compilation_cache_dir == str(d)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_env_cache_dir_wins_over_enable(tmp_path, monkeypatch):
    # where JAX_COMPILATION_CACHE_DIR places the cache no code sets
    # another directory: enable_compilation_cache (and with it the
    # serving.compilation-cache-dir property) only lowers the thresholds
    import jax
    from presto_tpu.serving import enable_compilation_cache
    env_dir = tmp_path / "from-env"
    other = tmp_path / "other"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
            jax.config.jax_persistent_cache_min_entry_size_bytes)
    try:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        assert enable_compilation_cache(str(other)) == str(env_dir)
        assert jax.config.jax_compilation_cache_dir == prev[0]
        assert not other.exists()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          prev[2])


def test_enable_compilation_cache_failure_raises(tmp_path, monkeypatch):
    # a directory that cannot be created is an error, not a silent no-op
    from presto_tpu.serving import enable_compilation_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    with pytest.raises(OSError):
        enable_compilation_cache(str(blocker / "cache"))


def test_default_cache_dir_is_fixed_inside_checkout():
    import os
    import presto_tpu
    repo = os.path.dirname(os.path.dirname(os.path.abspath(
        presto_tpu.__file__)))
    assert presto_tpu.DEFAULT_COMPILE_CACHE_DIR == os.path.join(
        repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def _child_import_report(env_overrides):
    """What a fresh `python -c "import presto_tpu ..."` child reports:
    (backend initialised?, jax_compilation_cache_dir)."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME",
                        "JAX_COMPILATION_CACHE_DIR",
                        "PRESTO_TPU_NO_COMPILE_CACHE")}
    env.update(env_overrides, PYTHONPATH=repo)
    code = ("import json, jax, presto_tpu\n"
            "import presto_tpu.worker.launcher\n"
            "import presto_tpu.benchmarks.suite_runner\n"
            "from jax._src import xla_bridge\n"
            "print(json.dumps([xla_bridge.backends_are_initialized(),"
            " jax.config.jax_compilation_cache_dir]))")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_import_initialises_no_backend(tmp_path):
    # JAX_PLATFORMS unset: the parents that spawn one process per chip
    # (worker/launcher.py, benchmarks/suite_runner.py) import the package
    # and must not hold the chip -- and the cache lands at the fixed
    # default without asking jax.default_backend().  The opt-out keeps
    # this child from creating the directory in the checkout under test.
    import presto_tpu
    initialised, cache_dir = _child_import_report(
        {"PRESTO_TPU_NO_COMPILE_CACHE": "1"})
    assert initialised is False
    assert cache_dir is None
    initialised, cache_dir = _child_import_report(
        {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "placed")})
    assert initialised is False
    assert cache_dir == str(tmp_path / "placed")   # JAX's own env read
    initialised, cache_dir = _child_import_report({"JAX_PLATFORMS": "cpu"})
    assert initialised is False
    assert cache_dir is None                      # off for the CPU backend
    initialised, cache_dir = _child_import_report({"JAX_PLATFORMS": "tpu"})
    assert initialised is False
    assert cache_dir == presto_tpu.DEFAULT_COMPILE_CACHE_DIR


# ---------------------------------------------------------------------------
# fragment-level executable sharing
# ---------------------------------------------------------------------------

def test_fragment_share_across_different_plans():
    """Two DIFFERENT full plans whose scan->filter subchain is structurally
    identical (same columns, same predicate, different aggregations above)
    share fragment-jit entries; a fresh runner (fresh PlanCompiler, own
    plan cache) shares them too; results match the unshared config."""
    r1 = _runner()
    sql_a = ("select sum(l_extendedprice) from lineitem "
             "where l_quantity < 24")
    sql_b = ("select min(l_extendedprice), max(l_extendedprice) "
             "from lineitem where l_quantity < 24")
    rows_a = r1.execute(sql_a).rows
    misses_after_a = _snapshot()["fragmentJitMisses"]
    rows_b = r1.execute(sql_b).rows
    sv = _snapshot()
    assert misses_after_a > 0, "fragment cache never engaged"
    assert sv["fragmentJitHits"] > 0, \
        "plans sharing a scan fragment did not share jits"

    # a different runner instance (new compilers) hits the global cache
    hits_before = sv["fragmentJitHits"]
    r2 = _runner()
    assert r2.execute(sql_a).rows == rows_a
    assert _snapshot()["fragmentJitHits"] > hits_before

    # same statements with sharing off: identical rows
    r3 = _runner(fragment_share=False)
    assert r3.execute(sql_a).rows == rows_a
    assert r3.execute(sql_b).rows == rows_b


def test_fragment_share_off_uses_no_global_cache():
    FRAGMENT_JIT_CACHE.invalidate_all()
    SERVING_METRICS.reset()
    r = _runner(fragment_share=False)
    r.execute("select count(*) from lineitem where l_quantity < 24")
    sv = _snapshot()
    assert sv["fragmentJitMisses"] == 0 and sv["fragmentJitHits"] == 0
    assert FRAGMENT_JIT_CACHE.info()["entries"] == 0


def test_fragment_cache_invalidated_by_ddl():
    runner = LocalQueryRunner("sf0.01", plan_cache=PlanCache())
    runner.execute("select count(*) from lineitem where l_quantity < 24")
    assert FRAGMENT_JIT_CACHE.info()["entries"] > 0
    runner._invalidate_plans()
    assert FRAGMENT_JIT_CACHE.info()["entries"] == 0


def test_fragment_share_key_isolates_configs():
    """The fragment key fingerprints the FULL execution config: the same
    plan under a different config must not share artifacts."""
    import dataclasses
    from presto_tpu.exec.pipeline import tuned_config
    r1 = _runner()
    sql = ("select sum(l_extendedprice) from lineitem "
           "where l_quantity < 24")
    want = r1.execute(sql).rows
    hits0 = _snapshot()["fragmentJitHits"]
    base = tuned_config()
    other = dataclasses.replace(base, batch_rows=base.batch_rows * 2)
    r2 = LocalQueryRunner("sf0.01", config=other, plan_cache=PlanCache())
    assert r2.execute(sql).rows == want
    assert _snapshot()["fragmentJitHits"] == hits0, \
        "different configs shared a compiled fragment"


# ---------------------------------------------------------------------------
# end to end over HTTP: the server-side batch intercept
# ---------------------------------------------------------------------------

def test_http_concurrent_executes_one_launch():
    from presto_tpu.client import StatementClient
    from presto_tpu.worker.server import WorkerServer
    srv = WorkerServer(coordinator=True, batch_window_ms=150,
                       max_batch_size=8)
    try:
        c = StatementClient(srv.uri, schema="sf0.01")
        c.execute(f"prepare q6 from {Q6_TEMPLATE}")
        stmts = ["execute q6 using date '1994-01-01', "
                 f"date '1995-01-01', 0.05, 0.07, {20 + i}"
                 for i in range(4)]
        c.execute(stmts[0])     # warm the template's fast path
        SERVING_METRICS.reset()

        results = [None] * 4

        def go(i):
            cc = StatementClient(srv.uri, schema="sf0.01")
            cc.prepared = dict(c.prepared)
            results[i] = cc.execute(stmts[i]).rows
        ts = [threading.Thread(target=go, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert all(r for r in results)
        sv = _snapshot()
        assert sv["servingBatchQueries"] >= 2, "no batch formed over HTTP"
        assert sv["servingBatchLaunchesSaved"] >= 1
        # batched lanes must equal solo re-runs (occupancy-1 = solo path)
        for i, s in enumerate(stmts):
            assert c.execute(s).rows == results[i], f"lane {i} diverged"
    finally:
        srv.close()


def test_http_batching_disabled_by_property():
    from presto_tpu.client import StatementClient
    from presto_tpu.worker.server import WorkerServer
    srv = WorkerServer(coordinator=True, max_batch_size=1)
    try:
        assert not srv._batcher.enabled
        c = StatementClient(srv.uri, schema="sf0.01")
        c.execute(f"prepare q6 from {Q6_TEMPLATE}")
        r = c.execute("execute q6 using date '1994-01-01', "
                      "date '1995-01-01', 0.05, 0.07, 24")
        assert r.rows
        assert _snapshot()["servingBatches"] == 0
    finally:
        srv.close()


def _write_etc(tmp_path, extra=""):
    etc = tmp_path / "etc"
    etc.mkdir(exist_ok=True)
    (etc / "config.properties").write_text(
        "coordinator=true\nhttp-server.http.port=0\n" + extra)
    return str(etc)


def test_server_properties_map_serving_keys(tmp_path):
    from presto_tpu.worker.properties import server_kwargs_from_etc
    etc = _write_etc(tmp_path,
                     "serving.batch-window-ms=7.5\n"
                     "serving.max-batch-size=32\n"
                     "serving.compilation-cache-dir=/tmp/x\n"
                     "serving.plan-cache-path=/tmp/p.jsonl\n")
    kw, _props = server_kwargs_from_etc(etc)
    assert kw["batch_window_ms"] == 7.5
    assert kw["max_batch_size"] == 32
    assert kw["compilation_cache_dir"] == "/tmp/x"
    assert kw["plan_cache_path"] == "/tmp/p.jsonl"
    with pytest.raises(ValueError):
        server_kwargs_from_etc(
            _write_etc(tmp_path, "serving.max-batch-size=0\n"))
    with pytest.raises(ValueError):
        server_kwargs_from_etc(
            _write_etc(tmp_path, "serving.batch-window-ms=-1\n"))


# ---------------------------------------------------------------------------
# warm restart through the sidecar + compilation cache
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_warm_restart_zero_recompiles(tmp_path):
    import jax
    from presto_tpu.client import StatementClient
    from presto_tpu.worker.server import WorkerServer
    prev_dir = jax.config.jax_compilation_cache_dir
    kw = {"compilation_cache_dir": str(tmp_path / "xla"),
          "plan_cache_path": str(tmp_path / "plans.jsonl")}
    try:
        srv = WorkerServer(coordinator=True, **kw)
        try:
            c = StatementClient(srv.uri, schema="sf0.01")
            c.execute(f"prepare q6 from {Q6_TEMPLATE}")
            stmt = ("execute q6 using date '1994-01-01', "
                    "date '1995-01-01', 0.05, 0.07, 24")
            want = c.execute(stmt).rows
        finally:
            srv.close()
        assert (tmp_path / "plans.jsonl").exists()

        # 'restart': drop every in-memory serving artifact
        GLOBAL_PLAN_CACHE.invalidate_all()
        PREPARED_REGISTRY.clear()
        FRAGMENT_JIT_CACHE.invalidate_all()

        srv = WorkerServer(coordinator=True, **kw)   # replays the sidecar
        try:
            SERVING_METRICS.reset()
            c2 = StatementClient(srv.uri, schema="sf0.01")
            c2.prepared["q6"] = Q6_TEMPLATE
            assert c2.execute(stmt).rows == want
            sv = _snapshot()
            assert sv["planCacheMisses"] == 0, "reload missed the cache"
            assert sv["preparedReplans"] == 0, "reload replanned"
        finally:
            srv.close()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)


def test_ddl_clears_sidecar(tmp_path):
    from presto_tpu.connectors import catalog
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.worker.server import WorkerServer
    from presto_tpu.client import StatementClient
    catalog.register_connector("memory", MemoryConnector())
    kw = {"plan_cache_path": str(tmp_path / "plans.jsonl")}
    srv = WorkerServer(coordinator=True, **kw)
    try:
        c = StatementClient(srv.uri, schema="sf0.01")
        c.execute("select count(*) from lineitem where l_quantity < 24")
        assert srv._sidecar.info()["entries"] == 1
        cm = StatementClient(srv.uri, catalog="memory", schema="sf0.01")
        cm.execute("create table t_sidecar as select 1 as x")
        assert srv._sidecar.info()["entries"] == 0
        cm.execute("drop table t_sidecar")
    finally:
        srv.close()
        catalog.unregister_connector("memory")


# ---------------------------------------------------------------------------
# client re-PREPARE after coordinator restart (satellite fix)
# ---------------------------------------------------------------------------

def test_client_replays_prepare_on_unknown_statement(monkeypatch):
    from presto_tpu.client import StatementClient
    from presto_tpu.worker.server import WorkerServer
    srv = WorkerServer(coordinator=True)
    try:
        c = StatementClient(srv.uri, schema="sf0.01")
        c.execute(f"prepare q6 from {Q6_TEMPLATE}")
        assert "q6" in c.prepared
        stmt = ("execute q6 using date '1994-01-01', "
                "date '1995-01-01', 0.05, 0.07, 24")
        want = c.execute(stmt).rows

        # simulate a restarted coordinator that lost its registry: the
        # next resolution fails once, then the client's transparent
        # re-PREPARE must recover without surfacing an error
        real = LocalQueryRunner._prepared_text
        state = {"failed": False}

        def flaky(self, name, prepared):
            if not state["failed"]:
                state["failed"] = True
                raise KeyError(
                    f"prepared statement {name!r} does not exist")
            return real(self, name, prepared)
        monkeypatch.setattr(LocalQueryRunner, "_prepared_text", flaky)
        assert c.execute(stmt).rows == want
        assert state["failed"], "fault was never exercised"
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# distributed peak-memory rollup (satellite fix)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_distributed_peak_memory_recorded():
    from presto_tpu.worker.coordinator import HttpQueryRunner
    from presto_tpu.worker.server import WorkerServer
    w = WorkerServer()
    try:
        r = HttpQueryRunner([w.uri], "sf0.01", n_tasks=1)
        res = r.execute("select l_returnflag, count(*) from lineitem "
                        "group by l_returnflag")
        assert res.rows
        assert res.peak_memory_bytes > 0, \
            "distributed run still records 0 peak memory"
        snap = r.last_execution.query_info_snapshot()
        assert snap["peakMemoryBytes"] > 0
        assert all("peakMemoryBytes" in st for st in snap["stages"])
    finally:
        w.close()
