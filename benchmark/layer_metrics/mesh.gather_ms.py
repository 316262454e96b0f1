"""Mean per query of the span `meshGather`: the root's pull of what the
chips computed, from the launch of a stage's tasks pinned to the mesh's
chips to holding their output as host pages (Q6: the four partial sums;
Q1: the four chips' shares of the groups after the ICI exchange).  Like
`exchange.fetch_wait_ms` on coordinator -> worker it waits out the
producers' device work.  None where no query carries the span: a program
without a mesh, or the parent of the PR that brought it."""
from span_stats import instrumented


def read(run):
    walls = [stats["meshGatherWallNanos"]["sum"] for stats in
             instrumented(run) if "meshGatherWallNanos" in stats]
    return sum(walls) / len(walls) / 1e6 if walls else None
