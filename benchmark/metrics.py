"""The arithmetic of the end-to-end metrics, and the loader of the
per-layer readers (layer_metrics/<name>.py, one `read(run)` each)."""
import importlib.util
import math
import os

from cells import BENCH


def rows_per_s(requests, rows_per_query: dict, window_s: float) -> float:
    """Rows of the base tables behind every request that completed, over
    all the seconds of the window: a stall anywhere lowers it."""
    done = sum(rows_per_query[r["template"]] for r in requests if r["ok"])
    return done / window_s


def percentile(values, q: float) -> float:
    """The q-th percentile, nearest rank, of all the values."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    return ordered[max(1, math.ceil(len(ordered) * q / 100)) - 1]


def wall_p95_ms(requests) -> float:
    """95th percentile of client wall over every request of the window; a
    failed request counts as slower than any that completed."""
    walls = [r["wall_s"] * 1000 if r["ok"] else float("inf")
             for r in requests]
    return percentile(walls, 95)


END_TO_END = {
    "rows_per_s": lambda run: rows_per_s(run["requests"],
                                         run["rows_per_query"],
                                         run["window_s"]),
    "setup_s": lambda run: run["setup_s"],
}


def layer_reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def delta(run: dict, name: str):
    """A counter's growth over the window, or None where it is absent."""
    before, after = run["counters"]["before"], run["counters"]["after"]
    if name not in after:
        return None
    return after[name] - before.get(name, 0)


def scanned_bytes(run: dict) -> float:
    """Bytes the window's queries had to read from device memory: what
    the columns a query touches hold resident in the store (each column
    once, as stored, whatever program reads them), once per device launch
    -- a batched launch reads them once for all its lanes, so launches are
    queries minus the launches the batcher saved."""
    total, queries = 0, 0
    for r in run["requests"]:
        if r["ok"]:
            queries += 1
            total += sum(run["resident"].get(f"{table}.{c}", 0)
                         for table, cols in
                         run["queries"][r["template"]].tables.items()
                         for c in cols)
    saved = delta(run, "serving_servingBatchLaunchesSaved") or 0
    return total * (queries - saved) / queries if queries else 0


def metric_values(entries, compute) -> dict:
    """{name: {"value", "unit"}} for the entries whose value exists; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in entries:
        value = compute(m["name"])
        # (an infinite tail -- failed requests in it -- is no JSON number)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
