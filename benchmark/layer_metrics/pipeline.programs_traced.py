"""Mean per query of the jitted functions JAX traced on the query's
threads (`jaxTraces`, one per jaxpr_trace_duration event).  A path that
reuses its compilers should read 0."""
from span_stats import per_query


def read(run):
    return per_query(run, ("jaxTraces",))
