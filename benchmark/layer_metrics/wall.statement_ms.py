"""Mean per query of the milliseconds of its wall in the statement layer's
own work (`queryWall.statement`: queueing, runner lookup, the drain of
rows to the client, the QueryInfo snapshot) and nothing else."""
from wall_stats import state_ms


def read(run):
    return state_ms(run, "statement")
