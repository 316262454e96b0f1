"""Queries completed over device launches (queries minus the launches
the micro-batcher saved) in the window."""
from metrics import delta


def read(run):
    saved = delta(run, "serving_servingBatchLaunchesSaved")
    queries = sum(1 for r in run["requests"] if r["ok"])
    if saved is None or not queries or queries - saved <= 0:
        return None
    return queries / (queries - saved)
