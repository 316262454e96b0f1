"""Mean per query of the walls of the grouped updates of the aggregations
whose input is a stream of batches and not a scan they are fused with (the
aggregation above a join): `aggUpdateWallNanos`, from the first batch
asked of the source to the last one folded into the table, the source's
own time included, summed over the query's tasks."""
from span_stats import instrumented, per_query_ms

KEY = "aggUpdateWallNanos"


def read(run):
    if not any(KEY in stats for stats in instrumented(run)):
        return None     # a program without the span, or no such aggregation ran
    return per_query_ms(run, (KEY,))
