"""Mean per query of JAX's "backend compile" events on the query's
threads (`jaxBackendCompileWallNanos`): executables deserialised from the
persistent cache again by each new PlanCompiler, and true compiles.
0 where nothing compiled."""
from span_stats import per_query_ms


def read(run):
    return per_query_ms(run, ("jaxBackendCompileWallNanos",))
