"""Mean per query of the milliseconds of its wall in which every thread of
the query that recorded anything was asleep on a hand-over
(`queryWall.wait`: `exchangeClientWait`, `schedAwaitStages`,
`statementPollWait`, `servingBatchWait`, `meshGather`, ...): dead time.
Which hand-over it was is `queryWall.wait.<span>` in QueryInfo."""
from wall_stats import state_ms


def read(run):
    return state_ms(run, "wait")
