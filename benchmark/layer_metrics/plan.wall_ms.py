"""Mean per query of the host's parse, plan and optimise walls
(`queryParse`, `queryPlan`, `queryOptimize` in QueryInfo's RuntimeStats,
whichever the path records: a prepared fast-path hit records the parse
alone, a replan all three).  Only the in-process runner records them."""

PHASES = ("queryParseWallNanos", "queryPlanWallNanos",
          "queryOptimizeWallNanos")


def read(run):
    walls = []
    for info in run["query_info"].values():
        stats = (info or {}).get("runtimeStats") or {}
        found = [stats[p]["sum"] for p in PHASES if p in stats]
        if found:
            walls.append(sum(found) / 1e6)
    return sum(walls) / len(walls) if walls else None
