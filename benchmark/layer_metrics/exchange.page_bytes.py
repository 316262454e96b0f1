"""Uncompressed bytes of pages pulled over HTTP between stages and to the
coordinator, per query (worker/exchange.py's client counters)."""
from metrics import delta


def read(run):
    queries = sum(1 for r in run["requests"] if r["ok"])
    moved = delta(run, "exchange_uncompressed_bytes")
    if not moved or not queries:
        return None
    return moved / queries
