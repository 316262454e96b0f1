"""Query event pipeline: created/completed events fanned out to pluggable
listeners.

The analog of the reference's QueryMonitor publishing QueryCreatedEvent /
QueryCompletedEvent to every registered EventListener
(presto-main-base/.../event/QueryMonitor.java:106,queryCreatedEvent and
:138,queryCompletedEvent; listener SPI at
presto-spi/.../eventlistener/EventListener.java).  Listener failures are
isolated: one broken listener must not fail the query or starve the other
listeners, matching EventListenerManager's dispatch.
"""
from __future__ import annotations

import json
import threading
import time
import traceback
from dataclasses import asdict, dataclass, field
from typing import List, Optional


@dataclass
class QueryCreatedEvent:
    """Reference QueryCreatedEvent: identity + context at intake."""
    query_id: str
    sql: str
    user: str
    source: str
    resource_group: str
    catalog: str
    schema: str
    create_time: float = field(default_factory=time.time)


@dataclass
class QueryCompletedEvent:
    """Reference QueryCompletedEvent: outcome + statistics at finish."""
    query_id: str
    sql: str
    user: str
    state: str                      # FINISHED | FAILED | CANCELED
    create_time: float
    end_time: float
    wall_time_s: float
    queued_time_s: float
    rows: int
    error: Optional[str] = None
    # rolled-up execution-wide RuntimeStats ({name: {sum, count, min, max}},
    # the reference QueryCompletedEvent's queryStats.runtimeStats) and the
    # query's peak MemoryPool reservation — both observability satellites;
    # defaulted so pre-existing listeners/tests keep constructing the event
    runtime_stats: Optional[dict] = None
    peak_memory_bytes: int = 0
    # identity context for downstream consumers (the telemetry history
    # store keys its durable records on these; the reference event carries
    # traceToken/resourceGroupId on QueryMetadata/QueryContext)
    trace_token: str = ""
    resource_group: str = ""


@dataclass
class TaskCompletedEvent:
    """Per-task terminal event from the WORKER execution path — the stats
    QueryMonitor.java:106 aggregates per task (splitCompletedEvent /
    TaskInfo final stats): identity, outcome, and the task-level counters
    the coordinator's UI drill-down reads."""
    task_id: str
    state: str                      # FINISHED | FAILED | CANCELED
    create_time: float
    end_time: float
    wall_time_s: float
    output_rows: int
    output_pages: int
    output_bytes: int
    peak_memory_bytes: int
    error: Optional[str] = None


class EventListener:
    """Listener SPI (EventListener.java): override any subset."""

    # a listener that never looks at a completed event's `runtime_stats`
    # says so: while no other is registered, a finished query's wall is
    # partitioned (telemetry/query_wall.py, a few milliseconds of
    # Python) at the first read of its QueryInfo, not behind its last
    # response
    reads_runtime_stats = True

    def query_created(self, event: QueryCreatedEvent) -> None:
        pass

    def query_completed(self, event: QueryCompletedEvent) -> None:
        pass

    def task_completed(self, event: TaskCompletedEvent) -> None:
        pass


class FileEventListener(EventListener):
    """Append events as JSON lines — the simplest useful listener (audit
    log / test fixture), analogous to the file-based event-listener
    plugins shipped around the reference."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()

    def _write(self, kind: str, event) -> None:
        line = json.dumps({"event": kind, **asdict(event)})
        with self._lock, open(self.path, "a") as f:
            f.write(line + "\n")

    def query_created(self, event: QueryCreatedEvent) -> None:
        self._write("query_created", event)

    def query_completed(self, event: QueryCompletedEvent) -> None:
        self._write("query_completed", event)

    def task_completed(self, event: TaskCompletedEvent) -> None:
        self._write("task_completed", event)


class EventListenerManager:
    """Fan events out to every registered listener, isolating failures
    (EventListenerManager.java: a throwing listener is logged and
    skipped)."""

    def __init__(self):
        self._listeners: List[EventListener] = []
        self.dispatch_errors = 0

    def register(self, listener: EventListener) -> None:
        self._listeners.append(listener)

    def unregister(self, listener: EventListener) -> None:
        """Detach a listener (server shutdown detaches its history
        bridge so a closed store never sees another event)."""
        try:
            self._listeners.remove(listener)
        except ValueError:
            pass

    def _fire(self, method: str, event) -> None:
        for listener in self._listeners:
            try:
                getattr(listener, method)(event)
            except Exception:   # noqa: BLE001 — listener isolation
                self.dispatch_errors += 1
                traceback.print_exc()

    def reads_runtime_stats(self) -> bool:
        return any(getattr(listener, "reads_runtime_stats", True)
                   for listener in self._listeners)

    def query_created(self, event: QueryCreatedEvent) -> None:
        self._fire("query_created", event)

    def query_completed(self, event: QueryCompletedEvent) -> None:
        self._fire("query_completed", event)

    def task_completed(self, event: TaskCompletedEvent) -> None:
        self._fire("task_completed", event)
