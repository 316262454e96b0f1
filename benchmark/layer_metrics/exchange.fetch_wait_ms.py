"""Mean per query of the time exchange clients waited for a page
(`exchangeClientWaitWallNanos`): the tasks' pulls from their sources and
the coordinator's pull of the root stage, rolled up per query."""
from span_stats import per_query_ms


def read(run):
    return per_query_ms(run, ("exchangeClientWaitWallNanos",))
