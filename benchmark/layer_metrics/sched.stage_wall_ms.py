"""Mean per query of the stage walls in QueryInfo (the sum over a query's
stages of the driver wall of their tasks): host clocks of the scheduler's
side, never device time."""


def read(run):
    per_query = []
    for info in run["query_info"].values():
        stages = (info or {}).get("stages")
        if stages:
            per_query.append(sum(s.get("wallTimeInNanos", 0)
                                 for s in stages) / 1e6)
    return sum(per_query) / len(per_query) if per_query else None
