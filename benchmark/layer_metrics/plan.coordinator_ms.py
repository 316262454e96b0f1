"""Mean per query of the coordinator's parse, plan, optimise and fragment
walls (`queryParse`, `queryPlan`, `queryOptimize`, `queryFragment`
spans): what the HTTP runner spends before the first task is created.
`queryFragment` is plan_distributed plus the stage tree."""
from span_stats import per_query_ms

PHASES = ("queryParseWallNanos", "queryPlanWallNanos",
          "queryOptimizeWallNanos", "queryFragmentWallNanos")


def read(run):
    return per_query_ms(run, PHASES)
