"""Mean per query of the milliseconds of its wall in which a thread of
the query was blocked in `host_get` until the device handed a result back
(`queryWall.device`) and nothing else was first: the part of the wall
only a faster device program (or fewer, later syncs) shortens."""
from wall_stats import state_ms


def read(run):
    return state_ms(run, "device")
