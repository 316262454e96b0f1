"""Mean per query of the times an aggregation executed its source again
because its table was too small (`aggRestreams`; a table that grows in
place counts none): 0 wherever the answer does not depend on the first
table's size."""
from span_stats import instrumented, per_query

KEY = "aggRestreams"


def read(run):
    if not any(KEY in stats for stats in instrumented(run)):
        return None
    return per_query(run, (KEY,))
