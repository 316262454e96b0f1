"""Canonical plan-cache hits over lookups in the window (SERVING_METRICS)."""
from metrics import delta


def read(run):
    hits = delta(run, "serving_planCacheHits")
    misses = delta(run, "serving_planCacheMisses")
    if hits is None or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
