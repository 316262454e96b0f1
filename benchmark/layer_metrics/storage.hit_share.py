"""Resident-store hits over lookups in the window (STORAGE_METRICS)."""
from metrics import delta


def read(run):
    hits, misses = delta(run, "storage_cache_hits"), \
        delta(run, "storage_cache_misses")
    if hits is None or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
