"""Mean per query of the probe batches that reached a join step
(`joinProbeBatches`), summed over the query's tasks: what a probe stream
costs in launches and padded work whatever its live rows are."""
from span_stats import instrumented, per_query

KEY = "joinProbeBatches"


def read(run):
    if not any(KEY in stats for stats in instrumented(run)):
        return None     # a program without the counter, or no join ran
    return per_query(run, (KEY,))
