"""Thread CPU time over wall of the host-work states
(`queryWallCpu.{pipeline,exchange,sched,plan,statement}` over the same
five `queryWall` keys), %, over the span's queries: what "under one GIL"
costs, measured.  Below 100 a host-work thread was runnable and not
running, or blocked inside its span."""
from wall_stats import host_cpu_share as read  # noqa: F401
