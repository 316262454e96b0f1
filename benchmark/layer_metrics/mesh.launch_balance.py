"""How evenly the chips of the mesh were given work: over the completed
queries of the span, the fewest programs launched by the tasks pinned to
one chip over the most (`meshTaskLaunches.<ordinal>`; a pinned stage has
one task a chip of the mesh and each records its key, a task that
launched nothing a 0), in percent.  100 is even, 0 a chip that ran
nothing.  None where no query carries a key."""
from span_stats import instrumented

KEY = "meshTaskLaunches."


def read(run):
    launches = {}
    for stats in instrumented(run):
        for key, metric in stats.items():
            if key.startswith(KEY):
                launches[key] = launches.get(key, 0) + metric["sum"]
    if not launches or not max(launches.values()):
        return None
    return 100.0 * min(launches.values()) / max(launches.values())
