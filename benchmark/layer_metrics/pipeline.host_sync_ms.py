"""Mean per query of the time host_get waited for the device
(`hostSyncWaitWallNanos`), summed over the query's tasks: where a task
blocks until its programs finish."""
from span_stats import per_query_ms


def read(run):
    return per_query_ms(run, ("hostSyncWaitWallNanos",))
