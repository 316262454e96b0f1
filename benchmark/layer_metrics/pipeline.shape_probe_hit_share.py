"""Shape probes of fused chains that the program cache answered
(`shapeProbeHits` over hits + `shapeProbeMisses`), summed over the
completed queries of the traced span: the RuntimeStats keys that
exec/fused.py `FusedChain.shape_probe` records on the task or query that
asked, rolled up task -> query.  A miss is an execution on which the
probe's Python body ran (a trace of the whole filter/project chain and
the resident store's decode); 100 % means no warm query traced its chain
again before launching the chain's cached program.

Read from the queries' own keys, as `pipeline.program_cache_hit_share`
is: a program that probes by tracing anew on every execution records
neither key.  None where no query of the span carries either key."""
from span_stats import instrumented


def read(run):
    hits = misses = 0
    for stats in instrumented(run):
        hits += stats.get("shapeProbeHits", {}).get("sum", 0)
        misses += stats.get("shapeProbeMisses", {}).get("sum", 0)
    if not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
