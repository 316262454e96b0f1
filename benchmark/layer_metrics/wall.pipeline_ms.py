"""Mean per query of the milliseconds of its wall spent in Python between
and around launches (`queryWall.pipeline`: dispatch, build, drain, the
join / aggregation / TopN operators' own host work, tracing and loading)
while no thread of the query waited on the device."""
from wall_stats import state_ms


def read(run):
    return state_ms(run, "pipeline")
