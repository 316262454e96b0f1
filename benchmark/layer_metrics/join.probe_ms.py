"""Mean per query of the walls of the join operators' probe streams
(`joinProbeWallNanos`: from the first probe batch asked for to the last
joined batch handed up, the probe input's own time included), summed over
the query's tasks."""
from span_stats import instrumented, per_query_ms

KEY = "joinProbeWallNanos"


def read(run):
    if not any(KEY in stats for stats in instrumented(run)):
        return None     # a program without the span, or no join ran
    return per_query_ms(run, (KEY,))
