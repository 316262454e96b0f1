"""Scan chunks whose direct-address lookup read the table block by block
(`chainLookupBlockedChunks` over `chainLookupChunks`), summed over the
completed queries of the traced span: the RuntimeStats keys the count pass
of a dense stream (`fused_dense_stream`, exec/fused.py) records on its
task, rolled up task -> query, from the way each chunk's `gather_near`
took (exec/operators.py): a chunk is blocked where every lookup of it
read LOOKUP_BLOCK rows at a time from two neighbouring tiles, without a
gather.

Read from the queries' own keys, as `join.build_cache_hit_share` is: a
program without the block-local read records neither key.  None where no
query of the span carries a chunk with a lookup."""
from span_stats import instrumented


def read(run):
    chunks = blocked = 0
    for stats in instrumented(run):
        chunks += stats.get("chainLookupChunks", {}).get("sum", 0)
        blocked += stats.get("chainLookupBlockedChunks", {}).get("sum", 0)
    if not chunks:
        return None
    return 100.0 * blocked / chunks
