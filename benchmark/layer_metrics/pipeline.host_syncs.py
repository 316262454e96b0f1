"""Mean per query of blocking device->host transfers (`hostSyncs`, one
per host_get call), summed over the query's tasks."""
from span_stats import per_query


def read(run):
    return per_query(run, ("hostSyncs",))
