"""Programs the backend compiled inside the window that JAX's persistent
cache did not serve: backend-compile events minus cache hits.  A program
that compiles in under the cache's minimum time is never stored, and one
that JAX does not put through the cache never hits, so each is compiled
again by every new PlanCompiler.  Should read 0."""
from metrics import delta


def read(run):
    compiles = delta(run, "jax_backend_compiles")
    if compiles is None:
        return None
    return compiles - (delta(run, "jax_cache_hits") or 0)
