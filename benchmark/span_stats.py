"""What the ten span-and-counter readers share: the RuntimeStats the
program itself recorded per query (presto_tpu/utils/runtime_stats.py),
as QueryInfo `runtimeStats` serves them, averaged over the requests that
completed inside the traced span.

A query whose map carries `pipelineLaunches` was instrumented (a program
without the spans, the parent of the PR that brought them, carries none:
every reader then finds nothing and returns None).  In an instrumented
query a missing key reads 0: nothing compiled is 0 ms of executable
loads, not no reading."""

INSTRUMENTED = "pipelineLaunches"


def instrumented(run) -> list:
    """runtimeStats of every completed, instrumented request of the span."""
    found = []
    for r in run["requests"]:
        if not r["ok"]:
            continue
        info = run["query_info"].get(r["query_id"]) or {}
        stats = info.get("runtimeStats") or {}
        if INSTRUMENTED in stats:
            found.append(stats)
    return found


def per_query(run, keys, field: str = "sum", scale: float = 1.0):
    """Mean per instrumented query of the sum over `keys` of `field`
    (`sum` of a wall or a count, `max` of a merged task metric)."""
    queries = instrumented(run)
    if not queries:
        return None
    total = sum(stats.get(k, {}).get(field, 0)
                for stats in queries for k in keys)
    return total * scale / len(queries)


def per_query_ms(run, keys, field: str = "sum"):
    return per_query(run, keys, field, scale=1e-6)
