#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip, TPC-H SF10
    python chip_smoke.py --chips 4  # one four-chip host: the ICI exchange only
    JAX_PLATFORMS=cpu python chip_smoke.py --sf 0.01   # rehearsal, ends ok:false

One process (a chip belongs to one process).  With no arguments it drives
the main path -- SQL text -> StatementClient -> HTTP statement protocol on a
coordinator -> scheduler -> one announced worker -> /v1/task -> SerializedPage
results -- and checks what comes back:

  oracle   at sf1, Q6/Q1/Q3 through the cluster equal the numpy oracle
           (LocalQueryRunner.execute_reference) row for row;
  scale    at --sf (default 10: lineitem 60,000,000 rows here, its Q1/Q6
           columns resident in HBM through presto_tpu/storage) Q6 and Q1 run
           cold then warm, and Q6's scalar and Q1's per-group count_order
           equal a few lines of numpy over the generated columns fetched
           from the device -- independent of the engine; Q3 runs cold then
           warm there too since PR 34 (the four-chip phase still cuts it
           above sf1: see MESH_Q3_MAX_SF);
  device   the chip did the work: peak device bytes cover the resident
           columns, and the compile cache directory gained entries (or,
           warm, served hits).

Every earlier stdout line is one JSON object; walls are smoke output, not
benchmark numbers.  Any failed phase raises: the last line is then
{"ok": false, ...} and the exit code is not 0.  Without a TPU the script
fails at once -- `--sf` lets the phases be rehearsed on the CPU, and the run
still ends ok:false.  On success the last line is exactly
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
"""
import argparse
import contextlib
import json
import os
import sys
import time
from decimal import Decimal

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

Q6 = """
select sum(l_extendedprice * l_discount) as revenue from lineitem
where l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07 and l_quantity < 24
"""

Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""

QUERIES = (("q6", Q6), ("q1", Q1), ("q3", Q3))
# The cut (PR 24, CHANGES.md) that is left: the FOUR-CHIP phase runs no Q3
# above sf1.  Through coordinator -> worker Q3 at SF10 takes ~3 s since
# PR 34 (the join's distribution by bytes, a dense stream above the join,
# an aggregation that reads its input once; before it two SF10 runs were
# killed at 1500 s and a third at 540 s).  The mesh node's scheduler
# (exec/scheduler.py) has its own exchange and has not run Q3 at SF10 on
# four chips: ROADMAP queue 2b item 2.
MESH_Q3_MAX_SF = 1.0


def mesh_queries_at(sf: float):
    return tuple((n, q) for n, q in QUERIES
                 if n != "q3" or sf <= MESH_Q3_MAX_SF)


# this script's lines go to the real stdout; whatever the engine itself
# prints while the phases run (task trace lines) is sent to stderr
OUT = sys.stdout
T0 = time.perf_counter()


def emit(**record):
    if "ok" not in record:      # the last line carries what is asked, only
        record["at_s"] = round(time.perf_counter() - T0, 1)
    print(json.dumps(record), file=OUT, flush=True)


def schema_of(sf: float) -> str:
    return f"sf{sf:g}"


def cache_entries(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


CACHE_EVENTS = {"cache_hits": 0, "cache_misses": 0}


def count_cache_events():
    """JAX's own compilation-cache events: a run on a warm cache adds no
    entry to the directory, and shows that it used it by its hits."""
    import jax

    def listener(event, **_kw):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") \
                and name in CACHE_EVENTS:
            CACHE_EVENTS[name] += 1
    jax.monitoring.register_event_listener(listener)


# ---------------------------------------------------------------------------
# one chip: client -> coordinator -> worker -> pages
# ---------------------------------------------------------------------------

class Cluster:
    """A coordinator and one announced worker in this process, reached
    only through StatementClient over HTTP."""

    def __init__(self):
        from presto_tpu.worker import WorkerServer
        # both with the server's default ExecutionConfig (tuned_config)
        self.coordinator = WorkerServer(coordinator=True)
        self.worker = WorkerServer(discovery_uri=self.coordinator.uri,
                                   announce_interval_s=0.1)
        deadline = time.time() + 30
        while not self.coordinator.worker_uris():
            if time.time() > deadline:
                raise RuntimeError("the worker never announced itself")
            time.sleep(0.05)

    def client(self, sf: float):
        from presto_tpu.client import StatementClient
        return StatementClient(self.coordinator.uri, schema=schema_of(sf),
                               timeout_s=900.0)

    def close(self):
        self.worker.close()
        self.coordinator.close()


def timed(client, sql):
    t0 = time.perf_counter()
    result = client.execute(sql)
    return result, time.perf_counter() - t0


def phase_oracle(cluster, sf: float):
    """Q6, Q1, Q3 through the cluster equal the numpy oracle row for row.
    The oracle is host numpy and the first run of each query mostly waits
    for the compiler, so the oracle computes on a thread of its own."""
    from concurrent.futures import ThreadPoolExecutor
    from presto_tpu.exec.runner import LocalQueryRunner, _assert_rows_equal
    client = cluster.client(sf)
    oracle = LocalQueryRunner(schema_of(sf))
    with ThreadPoolExecutor(max_workers=1) as pool:
        wanted = {name: pool.submit(oracle.execute_reference, sql)
                  for name, sql in QUERIES}
        for name, sql in QUERIES:
            got, wall = timed(client, sql)
            # every one of the three is ORDER BY'd or a single row
            _assert_rows_equal(got, wanted[name].result(), ordered=True)
            emit(phase="oracle", query=name, schema=schema_of(sf),
                 rows=len(got.rows), equals_numpy_oracle=True,
                 wall_s_including_compile=wall)


def generated_lineitem(sf: float, columns) -> dict:
    """The generator's own lineitem columns, made on the device and fetched
    to host numpy: what the tables hold, without the engine.  The resident
    store's builder is reused so that its jitted generator programs, which
    the queries have just compiled, are not compiled a second time."""
    import numpy as np
    from presto_tpu.connectors import tpch
    from presto_tpu.exec.pipeline import tuned_config
    from presto_tpu.storage import get_store, store
    cfg = tuned_config()
    built = {(table, col, s): as_i32 for _cid, table, col, s, as_i32
             in get_store(cfg.storage_budget_bytes,
                          cfg.storage_max_column_bytes).entries}
    n = tpch.table_row_count("lineitem", sf)
    return {col: np.asarray(store._build_rows(
        "tpch", "lineitem", col, sf, 0, n, 0,
        built[("lineitem", col, float(sf))])) for col in columns}


def independent_answers(sf: float):
    """Q6's revenue and Q1's count_order per (returnflag, linestatus), by
    numpy over the fetched columns.  Decimals are unscaled int64 (two
    digits), dates are days since the epoch, flags are dictionary codes."""
    import numpy as np
    from presto_tpu.connectors import device_gen
    c = generated_lineitem(sf, ("shipdate", "discount", "quantity",
                                "extendedprice", "returnflag", "linestatus"))
    day = lambda s: int(np.datetime64(s, "D").astype(np.int64))  # noqa: E731
    q6 = ((c["shipdate"] >= day("1994-01-01"))
          & (c["shipdate"] < day("1995-01-01"))
          & (c["discount"] >= 5) & (c["discount"] <= 7)
          & (c["quantity"] < 2400))
    revenue = Decimal(int((c["extendedprice"][q6].astype(np.int64)
                           * c["discount"][q6]).sum())).scaleb(-4)
    flags = device_gen.dictionary("tpch", "lineitem", "returnflag")
    status = device_gen.dictionary("tpch", "lineitem", "linestatus")
    q1 = c["shipdate"] <= day("1998-09-02")
    code = c["returnflag"][q1].astype(np.int64) * len(status) \
        + c["linestatus"][q1]
    counts = np.bincount(code, minlength=len(flags) * len(status))
    count_order = {(flags[i // len(status)], status[i % len(status)]): int(n)
                   for i, n in enumerate(counts) if n}
    return revenue, count_order, len(c["shipdate"])


def phase_scale(cluster, sf: float):
    """The queries cold then warm at the real size, Q6 and Q1 checked
    against the engine-independent numpy answers."""
    client = cluster.client(sf)
    results = {}
    for name, sql in QUERIES:
        cold, cold_wall = timed(client, sql)
        warm, warm_wall = timed(client, sql)
        assert warm.rows == cold.rows, f"{name}: warm rows differ from cold"
        assert cold.rows, f"{name}: no rows"
        results[name] = warm
        emit(phase="scale", query=name, schema=schema_of(sf),
             rows=len(warm.rows), cold_wall_s_including_compile=cold_wall,
             warm_wall_s=warm_wall)
    revenue, count_order, n_rows = independent_answers(sf)
    got_revenue = results["q6"].rows[0][0]
    assert got_revenue == revenue, \
        f"q6 revenue {got_revenue} != numpy over generated columns {revenue}"
    got_counts = {(r[0], r[1]): r[-1] for r in results["q1"].rows}
    assert got_counts == count_order, \
        f"q1 count_order {got_counts} != numpy {count_order}"
    emit(phase="scale", check="numpy over fetched generated columns",
         schema=schema_of(sf), lineitem_rows=n_rows,
         q6_revenue=str(revenue), q1_count_order=sorted(
             [*k, v] for k, v in count_order.items()), equal=True)


def phase_device(device, cache_dir, entries_before, on_chip: bool):
    """The device did the work, natively, and the compile cache filled."""
    from presto_tpu.storage import STORAGE_METRICS
    resident = int(STORAGE_METRICS["resident_bytes"])
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    entries_after = cache_entries(cache_dir)
    emit(phase="device", resident_column_bytes=resident,
         peak_bytes_in_use=peak, bytes_in_use=stats.get("bytes_in_use"),
         compile_cache_dir=cache_dir, cache_entries_before=entries_before,
         cache_entries_after=entries_after, **CACHE_EVENTS)
    # resident_column_bytes is what the store's pool has reserved, which
    # charges a column once for every task that built it at the same time
    # (twice here, two tasks per scan stage: ROADMAP queue 1 item 5), so
    # the comparison with the peak asks more than it has to
    assert resident > 0, "no column became resident in device memory"
    if on_chip:
        assert peak is not None and peak >= resident, \
            f"peak device bytes {peak} < resident column bytes {resident}"
        assert entries_after > entries_before \
            or CACHE_EVENTS["cache_hits"] > 0, \
            f"compile cache {cache_dir} was neither written nor read"


def run_one_chip(args, device, on_chip: bool):
    import jax
    from presto_tpu import native
    cache_dir = jax.config.jax_compilation_cache_dir
    entries_before = cache_entries(cache_dir)
    count_cache_events()
    emit(phase="start", native_library_loaded=native.load() is not None,
         compile_cache_dir=cache_dir, cache_entries_before=entries_before)
    cluster = Cluster()
    try:
        phase_oracle(cluster, min(1.0, args.sf))
        phase_scale(cluster, args.sf)
    finally:
        cluster.close()
    phase_device(device, cache_dir, entries_before, on_chip)


# ---------------------------------------------------------------------------
# four chips: the partitioned exchange over ICI, and nothing else
# ---------------------------------------------------------------------------

def run_four_chips(args, devices, on_chip: bool):
    """Q1 (and Q3: see MESH_Q3_MAX_SF) through the in-process distributed
    scheduler on a four-device mesh, cold and then warm, rows equal to
    one device's LocalQueryRunner; the hashed stages must resolve to
    fabric ici, the all_to_all must engage, every device must hold data
    (make_mesh takes jax.devices() in order; code that has only seen a
    virtual CPU mesh may have left every array on device 0) and none may
    hold the whole of the resident tables: a shard each, which a store
    that lets every pinned task build the whole column does not leave."""
    from presto_tpu.exec import scheduler as S
    from presto_tpu.exec.runner import (DistributedQueryRunner,
                                        LocalQueryRunner, _assert_rows_equal)
    from presto_tpu.parallel.fabric import FABRIC_METRICS
    from presto_tpu.parallel.mesh import make_mesh
    from presto_tpu.exec.pipeline import tuned_config
    from presto_tpu.storage import get_store
    schema = schema_of(args.sf)
    dist = DistributedQueryRunner(schema, n_tasks=4, mesh=make_mesh(4))
    local = LocalQueryRunner(schema)
    cfg = tuned_config()
    store = get_store(cfg.storage_budget_bytes, cfg.storage_max_column_bytes)

    engaged = []
    in_use = []      # per query, per device: bytes held after the mesh runs
    resident = []    # per query: bytes of the resident columns, all shards
    ici_exchange = S.InProcessScheduler._ici_exchange

    def counting(self, stage, task_batches, keys):
        ok = ici_exchange(self, stage, task_batches, keys)
        engaged.append((stage.fragment.fragment_id, stage.fabric, ok))
        return ok

    S.InProcessScheduler._ici_exchange = counting
    try:
        for name, sql in mesh_queries_at(args.sf)[1:]:  # Q1; Q3 at sf <= 1
            engaged.clear()
            FABRIC_METRICS.reset()
            t0 = time.perf_counter()
            got = dist.execute(sql)
            wall = time.perf_counter() - t0
            # a second, warm run: every task finds its shard where the
            # first run built it
            t0 = time.perf_counter()
            again = dist.execute(sql)
            warm_wall = time.perf_counter() - t0
            fabric = FABRIC_METRICS.snapshot()["ici"]
            in_use.append([(d.memory_stats() or {}).get("bytes_in_use")
                           for d in devices])
            resident.append(sum(e.nbytes for e in store.entries.values()))
            # (the one-device run rebuilds the columns whole on device 0)
            want = local.execute(sql)
            _assert_rows_equal(got, want, ordered=True)
            _assert_rows_equal(again, want, ordered=True)
            emit(phase="four_chips", query=name, schema=schema,
                 rows=len(got.rows), equals_one_device=True,
                 wall_s_including_compile=wall, warm_wall_s=warm_wall,
                 resident_column_bytes=resident[-1],
                 ici_stages=[list(e) for e in engaged], ici=fabric)
            assert engaged and all(
                f == "ici" and ok for _fid, f, ok in engaged), \
                f"{name}: hashed stages did not ride ici: {engaged}"
            assert fabric["exchanges"] >= 2 and fabric["fallbacks"] == 0 \
                and fabric["host_bytes"] == 0, fabric
    finally:
        S.InProcessScheduler._ici_exchange = ici_exchange
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    emit(phase="four_chips", bytes_in_use_after_each_query=in_use,
         peak_bytes_in_use=peaks)
    if on_chip:
        assert all(b is not None and b > 0 for q in in_use for b in q), \
            f"a device of the mesh holds nothing: bytes_in_use {in_use}"
        # a quarter of the resident bytes and what the programs keep; the
        # whole of them on every device is the replicated build
        assert all(b < held / 2 for q, held in zip(in_use, resident)
                   for b in q), \
            f"a device holds more than its shard: bytes_in_use {in_use} " \
            f"of resident columns {resident}"


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=None,
                    help="scale factor of the scale phase (default 10); "
                         "giving it allows a rehearsal without a TPU, "
                         "which still ends ok:false")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the ICI exchange phase on four chips")
    args = ap.parse_args()
    rehearsal = args.sf is not None
    if args.sf is None:
        args.sf = 10.0

    # first, and before JAX is touched: in a directory that holds nothing
    # else of the repo the script must fail here, off the chip
    import presto_tpu  # noqa: F401 -- x64 + the compile cache directory

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_chip = device["platform"] == "tpu"
    emit(phase="devices", **device)
    if not on_chip and not rehearsal:
        raise SystemExit(fail(f"JAX found no TPU: platform is "
                              f"{device['platform']!r}", device))
    if len(devices) < args.chips:
        raise SystemExit(fail(f"--chips {args.chips} needs {args.chips} "
                              f"devices, JAX found {len(devices)}", device))

    with contextlib.redirect_stdout(sys.stderr):
        if args.chips == 4:
            run_four_chips(args, devices[:4], on_chip)
        else:
            run_one_chip(args, devices[0], on_chip)
    if not on_chip:
        raise SystemExit(fail(f"every phase passed, but on platform "
                              f"{device['platform']!r}: a rehearsal is not "
                              f"a chip run", device))
    emit(ok=True, device=device)


def fail(error: str, device=None) -> int:
    emit(ok=False, error=error, device=device)
    return 1


if __name__ == "__main__":
    try:
        main()
    except Exception as e:   # noqa: BLE001 -- reported, then re-raised
        fail(f"{type(e).__name__}: {e}")
        raise
