"""Lookups of the process-wide program cache that found their program
built (`programCacheHits` over hits + `programCacheMisses`), summed over
the completed queries of the traced span: the RuntimeStats keys that
serving/fragments.py `get_or_build` records on the task or query that
asked, rolled up task -> query.  A miss is a jitted program built anew
(traced, lowered and loaded at its first call); 100 % means every
program a query needed had been built once in this process before.

Read from the queries' own keys, not from the process-wide
`serving_fragmentJitHits/Misses` counters of collect.py: those exist in a
program whose fused programs never reach the cache and read ~100 % there
(only its scan kernel is looked up), so they cannot tell the mechanism
from its absence.  None where no query of the span carries either key."""
from span_stats import instrumented


def read(run):
    hits = misses = 0
    for stats in instrumented(run):
        hits += stats.get("programCacheHits", {}).get("sum", 0)
        misses += stats.get("programCacheMisses", {}).get("sum", 0)
    if not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
