"""Mean per query of what the serving plane makes a request wait before
its launch: enqueue to launch in the micro-batcher
(`servingBatchWaitWallNanos`) plus the wait for the plan cache's lock at
checkout (`compilerCheckoutWaitWallNanos`)."""
from span_stats import per_query_ms


def read(run):
    return per_query_ms(run, ("servingBatchWaitWallNanos",
                              "compilerCheckoutWaitWallNanos"))
