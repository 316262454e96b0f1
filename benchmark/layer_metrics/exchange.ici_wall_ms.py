"""Mean per query of the host's wall on the ICI hash exchange: the
scheduler's dispatch of the all_to_all chunks, its one live-count sync
included (`exchangeFabricIciDispatchWallNanos`), plus the time the
consuming tasks waited for a chunk's collective to land
(`exchangeFabricIciWaitWallNanos`, summed over the tasks).  Q6 has no
hashed edge and adds 0; None in a program without the keys' spans."""
from span_stats import per_query_ms


def read(run):
    return per_query_ms(run, ("exchangeFabricIciDispatchWallNanos",
                              "exchangeFabricIciWaitWallNanos"))
