"""Mean per query of the bytes handed to the all_to_all over ICI
(`exchangeFabricIciBytes`: values, null masks and row masks of every
chunk, padding included).  A Q1 moves its partial groups, a Q6 nothing."""
from span_stats import per_query


def read(run):
    return per_query(run, ("exchangeFabricIciBytes",))
