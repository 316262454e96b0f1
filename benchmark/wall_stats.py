"""What the nine `wall.*` readers share: the partition of a query's wall
that the program reduces from its own spans' intervals once a query
(presto_tpu/telemetry/query_wall.py), served as `queryWall.<state>` /
`queryWallCpu.<state>` nanoseconds in QueryInfo `runtimeStats`.  Every
instant of a query's created .. finished is charged to ONE state, so the
eight states sum to `elapsedTimeMillis`: unlike the span sums beside them
(`sched.stage_wall_ms`, `exchange.fetch_wait_ms`, ...), which add up over
a query's threads, a state's milliseconds are what the query would be
shorter by without them.  Under concurrency the partition is of each
query's own wall by its own threads: `device` includes waiting behind a
neighbour's programs.

A query without `queryWall.device` was not partitioned (the parent of
the PR that brought the partition): every reader then returns None."""
from span_stats import instrumented, per_query_ms

STATES = ("device", "pipeline", "exchange", "sched", "plan", "statement",
          "wait", "unattributed")
HOST_STATES = ("pipeline", "exchange", "sched", "plan", "statement")
MARK = "queryWall.device"


def partitioned(run) -> list:
    """runtimeStats of the span's queries whose wall was partitioned."""
    return [stats for stats in instrumented(run) if MARK in stats]


def _total(queries, keys) -> float:
    return sum(stats.get(k, {}).get("sum", 0)
               for stats in queries for k in keys)


def state_ms(run, state: str):
    """Mean per query of the milliseconds of its wall in `state`."""
    if not partitioned(run):
        return None
    return per_query_ms(run, ("queryWall." + state,))


def unattributed_share(run):
    """Wall no record of any thread covers, over the whole wall, %."""
    queries = partitioned(run)
    whole = _total(queries, ["queryWall." + s for s in STATES])
    if not whole:
        return None
    return 100.0 * _total(queries, ["queryWall.unattributed"]) / whole


def host_cpu_share(run):
    """Thread CPU time of the records that won the host-work states'
    instants over those states' wall, %: below 100 a host-work thread was
    runnable and not running (the interpreter lock) or blocked inside its
    span."""
    queries = partitioned(run)
    wall = _total(queries, ["queryWall." + s for s in HOST_STATES])
    if not wall:
        return None
    return 100.0 * _total(
        queries, ["queryWallCpu." + s for s in HOST_STATES]) / wall
