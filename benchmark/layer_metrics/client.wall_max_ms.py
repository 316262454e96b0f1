"""The slowest request of the window, submit to last fetched row."""


def read(run):
    walls = [r["wall_s"] for r in run["requests"] if r["ok"]]
    return max(walls) * 1000 if walls else None
