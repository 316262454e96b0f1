"""Mean per query of the milliseconds of its wall in scheduling
(`queryWall.sched`: creating, queueing, rolling up and closing tasks, on
the coordinator and in the worker's handler) while nothing of the
device, the pipeline or the exchange was in flight for the query."""
from wall_stats import state_ms


def read(run):
    return state_ms(run, "sched")
