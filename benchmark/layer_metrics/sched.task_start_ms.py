"""Mean per query of what it costs to get tasks running: the
coordinator's POST /v1/task calls summed (`schedCreateTasks`) plus the
longest wait of any task between its creation on the worker and the
moment its thread runs (`taskQueuedWallNanos`, the merged maximum over
the query's tasks)."""
from span_stats import per_query_ms


def read(run):
    created = per_query_ms(run, ("schedCreateTasksWallNanos",))
    if created is None:
        return None
    return created + per_query_ms(run, ("taskQueuedWallNanos",), "max")
