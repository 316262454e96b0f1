"""Mean per query of calls of a named jitted program
(`pipelineLaunches`, counted by named_jit): the device programs the
pipeline launches, summed over the query's tasks.  JAX's own eager ops
are not counted."""
from span_stats import per_query


def read(run):
    return per_query(run, ("pipelineLaunches",))
