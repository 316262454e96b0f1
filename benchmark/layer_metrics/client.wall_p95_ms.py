"""95th percentile (nearest rank) of client wall, submit to last fetched
row, over every request of the whole window (not of the traced span
alone: a tail wants all the requests there are); a failed request counts
as slower than any that completed.  With eight closed-loop clients on a
busy device it swings by a sixth from run to run, so it stands here and
not among the end-to-end metrics."""
from metrics import wall_p95_ms


def read(run):
    requests = run.get("window_requests", run["requests"])
    return wall_p95_ms(requests) if requests else None
