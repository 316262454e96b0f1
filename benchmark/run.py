#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that starts the cell's servers in itself, makes the tables
resident, warms the cell's statements, drives the window from the
client's side through StatementClient.execute against the coordinator's
URI, frees the program's state, and then checks what the clients fetched
against the plain reference.  The last line of standard output is the
result (benchmark/README.md).  Without a TPU it fails and prints no
result; `--rehearse-sf <sf>` rehearses the same path on whatever device
JAX finds, at that scale factor, and always ends `correct: false`.
"""
import argparse
import contextlib
import json
import os
import shutil
import sys
import threading
import time

T0 = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
OUT_DIR = os.path.join(ROOT, ".bench_out")
OUT = sys.stdout
WARM_ATTEMPTS = 20


def host_rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def say(**record):
    """An earlier line of standard output: one JSON object."""
    record["at_s"] = round(time.perf_counter() - T0, 2)
    record["host_rss_bytes"] = host_rss_bytes()
    print(json.dumps(record, default=str), file=OUT, flush=True)


def warm_up(servers, plan, traffic):
    """Every statement the window will send, once: builds the touched
    columns and compiles the cell's programs (set-up, not measured)."""
    import load
    import sampler
    warm = servers.client("warm")
    walls = {}
    for t in plan.templates:
        q = plan.queries[t]
        if plan.prepared:
            warm.execute(sampler.prepare_statement(q))
        tuples = plan.pool[t] if plan.pool else [plan.values(t, "warm", 0)]
        for k, values in enumerate(tuples):
            # the client gives up on an HTTP response after 30 s, which a
            # first run that compiles can outlast on the single-node path;
            # the server goes on compiling, so set-up asks again
            for attempt in range(WARM_ATTEMPTS):
                rec = load.send(warm, t, values, plan.statement(t, values),
                                T0, {"client": "warm", "seq": k})
                if rec["ok"] or "timed out" not in rec["error"]:
                    break
            if not rec["ok"]:
                raise RuntimeError(f"warm-up of {t} failed: {rec['error']}")
            walls.setdefault(t, []).append(
                round(rec["done_s"] - rec["submit_s"] + 30.0 * attempt, 3))
    # concurrent clients: let the server form (and compile) every batch
    # width the window can reach
    width = traffic["clients"]
    rounds = traffic.get("warm_burst_rounds", 2)
    while width >= 2:
        for t in plan.templates:
            for k in range(rounds):
                for rec in load.burst(servers, plan, t, width, (width, k)):
                    if not rec["ok"]:
                        raise RuntimeError(
                            f"warm-up burst of {t} failed: {rec['error']}")
        width //= 2
    return walls


def run_cell(cell, config, seed, seconds, trace, control, devices, peaks):
    """Set-up, window, numbers and check of one run; returns the result
    line's object.  The look for a chip is main()'s."""
    traffic, sf = cell.traffic, config["scale_factor"]
    platform, kind = devices[0].platform, devices[0].device_kind
    import jax
    import check
    import collect
    import load
    import metrics
    import trace_reduce
    from cluster import Servers

    os.makedirs(OUT_DIR, exist_ok=True)
    stem = os.path.join(
        OUT_DIR, f"{cell.name}.seed{seed}.trace{trace}")
    events = collect.JaxEvents()
    say(phase="start", workload=cell.name, seed=seed,
        device={"platform": platform, "kind": kind, "count": len(devices)},
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        scale_factor=sf)

    with contextlib.redirect_stdout(sys.stderr):
        # ---- set-up -----------------------------------------------------
        servers = Servers(config)
        plan = load.Plan(traffic, cell.queries, seed)
        warm_walls = warm_up(servers, plan, traffic)
        clients = [servers.client(f"bench-{c}")
                   for c in range(traffic["clients"])]
        if plan.prepared:
            import sampler
            for c in clients:
                for q in cell.queries.values():
                    c.execute(sampler.prepare_statement(q))
        resident = collect.resident_columns()
        after_setup = collect.counters(events)
        say(phase="setup", warm_walls_s=warm_walls,
            resident_column_bytes=sum(resident.values()),
            resident_columns=len(resident),
            pool=plan.pool or {},
            jax={k: v for k, v in after_setup.items() if k.startswith("jax_")})

        # ---- the window -------------------------------------------------
        # a traced run traces the first `trace_seconds` of its window (a
        # minute of eight clients is three million device ops to read);
        # its per-layer metrics are of that span
        trace_dir = stem + ".profile"
        span = {"lock": threading.Lock(), "open": bool(trace)}
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)

        def close_span(record=None):
            if not span["open"] or (record is not None and record["done_s"]
                                    < traffic.get("trace_seconds", 15)):
                return
            with span["lock"]:
                if span["open"]:
                    span.update(open=False, end_s=time.perf_counter() - t_window,
                                counters=collect.counters(events))
                    # writing the profile takes seconds: not on a client
                    span["writer"] = threading.Thread(
                        target=jax.profiler.stop_trace, name="stop-trace")
                    span["writer"].start()

        before = collect.counters(events)
        setup_s = time.perf_counter() - T0
        t_window = time.perf_counter()
        requests, window_s = load.closed_loop(
            servers, plan, clients, seconds, on_done=close_span if trace else None)
        after = collect.counters(events)
        close_span()
        if trace:
            span["writer"].join()
        traced = None
        device = collect.device_record(devices)
        infos = {}
        for r in requests[:traffic.get("query_info_limit", 400)]:
            if r["ok"]:
                infos[r["query_id"]] = collect.query_info(servers.uri,
                                                          r["query_id"])
        say(phase="window_closed", requests=len(requests),
            device_memory_peak_bytes=device["memory_peak_bytes"])
        collect.free_program_state(servers)
        say(phase="program_state_freed")
        if trace:
            xplane = trace_reduce.find_xplane(trace_dir)
            flat, seen = trace_reduce.extract(
                xplane, rehearsal=platform != "tpu")
            traced = trace_reduce.reduce(flat, window_s=span["end_s"])
            with open(stem + ".trace_events_head.json", "w") as f:
                # a small sample of the flattened events, for the tests
                json.dump(sorted(flat, key=lambda e: e[3])[:3000], f)
            say(phase="trace", xplane_bytes=os.path.getsize(xplane),
                events=len(flat), lines=len(seen),
                device_lines={k: n for k, n in seen.items()
                              if k.startswith("/device:")})
            shutil.rmtree(trace_dir, ignore_errors=True)
            if traced is None:
                raise SystemExit("the trace holds no device operation")
            device.update(busy_s=traced["busy_s"],
                          window_s=traced["window_s"])

        # ---- the numbers ------------------------------------------------
        scale = sf / traffic["rows_at_scale_factor"]
        run = {"requests": requests, "window_s": window_s,
               "setup_s": setup_s,
               "rows_per_query": {t: n * scale for t, n in
                                  traffic["rows_per_query"].items()},
               "counters": {"before": before, "after": after},
               "query_info": infos, "trace": traced, "resident": resident,
               "queries": cell.queries, "peaks": peaks}
        if trace:
            in_span = dict(run, window_s=span["end_s"],
                           window_requests=requests,
                           requests=[r for r in requests
                                     if r["done_s"] <= span["end_s"]],
                           counters={"before": before,
                                     "after": span["counters"]})
            readers = {m["name"]: metrics.layer_reader(m["name"])
                       for m in cell.per_layer}
            values = metrics.metric_values(
                cell.per_layer, lambda name: readers[name](in_span))
        else:
            values = metrics.metric_values(
                cell.end_to_end, lambda name: metrics.END_TO_END[name](run))

        # ---- correct ----------------------------------------------------
        t_ref = time.perf_counter()
        reference = check.Reference(cell.queries, sf)
        say(phase="reference_tables", seconds=round(time.perf_counter() - t_ref, 2))
        picked = check.sample(requests, traffic["check"]["sample"], seed)
        verdict = check.compare(requests, picked, reference)
        if control:
            say(phase="program", seed=seed, correct=verdict["correct"],
                numbers={n: v["value"] for n, v in verdict["numbers"].items()})
        for k in range(control):
            # the control in the program's place, on the requests that
            # seed + k would have sent: it has to come out not correct
            other = load.Plan(traffic, cell.queries, seed + k)
            sent = [dict(r, template=t, values=v) for r in picked
                    for t, v, _sql in [other.request(r["client"], r["seq"])]]
            verdict = check.compare(requests, sent, reference,
                                    control=traffic["check"]["control"])
            say(phase="control", seed=seed + k,
                control=traffic["check"]["control"],
                numbers={n: v["value"] for n, v in verdict["numbers"].items()},
                correct=verdict["correct"])
        reference_s = time.perf_counter() - t_ref

    with open(stem + ".requests.jsonl", "w") as f:
        for r in requests:
            f.write(json.dumps(r, default=str) + "\n")
    by_template = {}
    for r in requests:
        by_template.setdefault(r["template"], []).append(r["wall_s"])
    say(phase="window", window_s=window_s, setup_s=setup_s,
        reference_s=round(reference_s, 2),
        requests={t: {"n": len(w), "mean_wall_s": sum(w) / len(w),
                      "max_wall_s": max(w)} for t, w in by_template.items()},
        jax_in_window={k: after[k] - before[k] for k in after
                       if k.startswith("jax_")},
        requests_file=os.path.relpath(stem + ".requests.jsonl", ROOT))

    compared = {k: {"value": n["value"], "limit": n["limit"]}
                for k, n in verdict["numbers"].items()}
    if verdict["first_wrong"]:
        print("first wrong answer:", json.dumps(verdict["first_wrong"]),
              file=sys.stderr)
    result = {"correct": verdict["correct"], "attempted": len(requests),
              "failed": sum(1 for r in requests if not r["ok"]),
              "metrics": values, "device": device}
    if traced:
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["compared"] = compared
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-sf", type=float, default=None,
                    help="rehearse at this scale factor on any device; "
                         "the run ends correct:false")
    ap.add_argument("--control", type=int, default=0,
                    help="K > 0: after the window, put the cell's control "
                         "(the reference with a guarantee broken) in the "
                         "program's place for the requests of seeds seed .. "
                         "seed+K-1; the run must end correct:false")
    args = ap.parse_args()

    from cells import Cell, read_json
    cell = Cell(args.workload)
    config = cell.config
    if args.rehearse_sf is not None:
        config = dict(config, scale_factor=args.rehearse_sf)

    # before JAX is touched: in a directory that holds only the benchmark
    # this import fails, and the run with it
    import presto_tpu  # noqa: F401 -- x64, and the compile cache's place
    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu" and args.rehearse_sf is None:
        raise SystemExit(f"JAX found no TPU: platform {platform!r}, "
                         f"device {kind!r}; the benchmark never falls "
                         "back (--rehearse-sf rehearses)")
    if len(devices) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips, JAX "
                         f"found {len(devices)} ({kind})")
    devices = devices[:cell.chips]
    peaks = read_json(BENCH, "peaks.json").get(kind)
    if peaks is None and platform == "tpu":
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")

    result = run_cell(cell, config, args.seed, args.seconds, args.trace,
                      args.control, devices, peaks)
    if platform != "tpu":       # a rehearsal is not a chip run
        result["correct"] = False
        result["compared"]["rehearsal_on"] = {"value": platform,
                                              "limit": "tpu"}
    for name, n in result["compared"].items():
        print(f"compared {name}: value {n['value']} limit {n['limit']}",
              file=sys.stderr)
    print(json.dumps(result), file=OUT, flush=True)


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    sys.stderr.flush()
    # server and exchange threads of the program are daemons; nothing is
    # left to wait for
    if threading.active_count() > 1:
        os._exit(0)
