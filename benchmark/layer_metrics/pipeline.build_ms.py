"""Mean per query of `pipelineBuild`: PlanCompiler(ctx) plus
compile(root), the host's Python before the first batch is pulled,
summed over the query's tasks (or, single-node, the query's compiler
rebuilds and its one compile)."""
from span_stats import per_query_ms


def read(run):
    return per_query_ms(run, ("pipelineBuildWallNanos",))
