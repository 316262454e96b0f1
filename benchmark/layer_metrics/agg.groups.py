"""Mean per query of the groups those aggregations found (`aggGroups`,
summed over the query's tasks: a group that two tasks both hold counts
twice, as it does in the tables)."""
from span_stats import instrumented, per_query

KEY = "aggGroups"


def read(run):
    if not any(KEY in stats for stats in instrumented(run)):
        return None
    return per_query(run, (KEY,))
