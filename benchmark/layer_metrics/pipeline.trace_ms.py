"""Host milliseconds per query that JAX spent tracing and lowering inside
the window (jax.monitoring duration events): the re-trace every new
PlanCompiler pays, compile-cache hit or not."""
from metrics import delta


def read(run):
    queries = sum(1 for r in run["requests"] if r["ok"])
    seconds = delta(run, "jax_trace_s")
    if seconds is None or not queries:
        return None
    return seconds * 1000 / queries
