"""Mean per query of the walls in which join operators made their build
sides ready (`joinBuildWallNanos`: draining the build input, concatenating
it and building the lookup table), summed over the query's tasks."""
from span_stats import instrumented, per_query_ms

KEY = "joinBuildWallNanos"


def read(run):
    if not any(KEY in stats for stats in instrumented(run)):
        return None     # a program without the span, or no join ran
    return per_query_ms(run, (KEY,))
