"""Mean per query of the milliseconds of its wall in parse, plan, optimize
and fragment (`queryWall.plan`) while nothing further down the list of
states was in flight for the query."""
from wall_stats import state_ms


def read(run):
    return state_ms(run, "plan")
