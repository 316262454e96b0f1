"""Join build sides that the process-wide build cache answered
(`joinBuildCacheHits` over hits + `joinBuildCacheMisses`), summed over the
completed queries of the traced span: the RuntimeStats keys that
`PlanCompiler.shared_build` (exec/pipeline.py, serving/builds.py) records
on the task that asked, rolled up task -> query.  A miss is a build side
that was materialised and whose lookup table was built on that execution;
a hit launched nothing and fetched nothing for its build.  A build side
the door declines (one fed by an exchange, pruned by a dynamic filter, or
of a task under a memory budget) counts as neither, so the share is over
the builds that could have been shared.

Read from the queries' own keys, as `pipeline.shape_probe_hit_share` is:
a program that builds every join's table anew records neither key.  None
where no query of the span carries either key."""
from span_stats import instrumented


def read(run):
    hits = misses = 0
    for stats in instrumented(run):
        hits += stats.get("joinBuildCacheHits", {}).get("sum", 0)
        misses += stats.get("joinBuildCacheMisses", {}).get("sum", 0)
    if not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
