"""Mean per query of the walls of the TopN operators (`topNWallNanos`:
from the first batch asked of the source to the ordered rows handed up,
the source's own time included), summed over the query's tasks."""
from span_stats import instrumented, per_query_ms

KEY = "topNWallNanos"


def read(run):
    if not any(KEY in stats for stats in instrumented(run)):
        return None
    return per_query_ms(run, (KEY,))
