"""Share of the span's queries' wall that no record of any of their
threads covers (`queryWall.unattributed` over the eight states' sum), %:
the hole in the instrumentation, records dropped past an owner's bound
included."""
from wall_stats import unattributed_share as read  # noqa: F401
