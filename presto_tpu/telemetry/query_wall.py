"""A query's wall, partitioned: every instant of it charged to one layer,
from the intervals the program's own spans record.

`RuntimeStats.close_span` (utils/runtime_stats.py) keeps every span,
launch, sync and queue wait as one record of its owner's timeline; a
task's timeline rides its TaskInfo and is kept beside the query's own.
`partition` reduces them once a query, at the query level:

  * on each thread the INNERMOST open record gives the thread's state
    (records on one thread nest; the one begun last is innermost, so a
    thread inside `exchangeClientWait` inside `joinProbe` is waiting);
  * across the query's threads the state FIRST in `STATES` wins the
    instant: a thread blocked on the device beats Python between
    launches beats the page exchange ... and `wait` wins only when no
    thread of the query does anything else;
  * an instant no record covers is `unattributed`; a stretch of it
    shorter than `GAP_FLOOR_NS` (telemetry/gaps.py's floor: the glue
    between two spans of one layer) stays with the state before it.

The extent is the query's own created .. finished, so the eight states
sum to its wall exactly.  `queryWallCpu.<state>` is the thread CPU time
of the records that won the state's instants (pro rata where a record
wins part of the time it was innermost): under concurrency the partition
is of each query's own wall by its own threads, so `device` includes
waiting behind a neighbour's programs.  Timestamps are unix nanoseconds
on each process's anchored clock; across hosts the clocks are NTP's.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..utils.runtime_stats import RECORD_WIDTH, unix_ns
from .gaps import GAP_FLOOR_NS

STATES = ("device", "pipeline", "exchange", "sched", "plan", "statement",
          "wait", "unattributed")
_SPANS_BY_STATE = {
    # a thread blocked until the device hands a result back
    "device": ("hostSync",),
    # Python between and around launches
    "pipeline": (
        "pipelineDispatch", "pipelineBuild", "pipelineDrain", "queryExecute",
        "joinBuild", "joinProbe", "aggUpdate", "aggFinalize", "topN",
        "probeCoalesce", "buildCoalesce", "outputCoalesce",
        "jaxTrace", "jaxLower", "jaxBackendCompile", "storageBuild"),
    "exchange": (
        "taskSerialize", "exchangeClientPull", "exchangeClientDecode",
        "exchangeFabricIciDispatch", "exchangeFabricIciDrain"),
    "sched": (
        "schedCreateTasks", "schedTaskEncode", "schedRollUpTasks",
        "schedCloseTasks", "taskQueued", "taskCreateDecode",
        "taskCreateStart"),
    "plan": ("queryParse", "queryPlan", "queryOptimize", "queryFragment"),
    "statement": (
        "statementQueued", "statementRunnerLookup", "statementDrain",
        "statementQueryInfoSnapshot"),
    # a thread asleep until another hands something over
    "wait": (
        "exchangeClientWait", "exchangeFabricIciWait", "schedAwaitStages",
        "meshGather", "statementPollWait", "servingBatchWait",
        "compilerCheckoutWait", "taskAwaitDynamicFilters"),
}
STATE_OF: Dict[str, str] = {name: state
                            for state, names in _SPANS_BY_STATE.items()
                            for name in names}
_RANK = {state: i for i, state in enumerate(STATES)}
_WAIT = _RANK["wait"]
_UNATTRIBUTED = _RANK["unattributed"]
_WAITS = _SPANS_BY_STATE["wait"]
_WAIT_COLUMN = {name: k for k, name in enumerate(_WAITS)}

# (thread, name, start_ns, end_ns, cpu_ns or -1, recorded by a task)
Record = Tuple[object, str, int, int, int, bool]


def records_of(timelines: Iterable[Tuple[str, dict]]
               ) -> Tuple[List[Record], int]:
    """(records, dropped) of `RuntimeStats.timelines()`: every source's
    rows as `Record`s in unix nanoseconds, a thread keyed by its source
    too (two processes may hand out one ident).  A record from a source
    with a label was recorded by a task."""
    records: List[Record] = []
    dropped = 0
    for source, line in timelines:
        names, rows = line.get("names", ()), line.get("rows", ())
        dropped += int(line.get("dropped", 0))
        for i in range(0, len(rows) - RECORD_WIDTH + 1, RECORD_WIDTH):
            tid, idx, start, wall, cpu = rows[i:i + RECORD_WIDTH]
            records.append(((source, tid), names[idx], start * 1000,
                            (start + wall) * 1000,
                            cpu * 1000 if cpu > 0 else cpu, bool(source)))
    return records, dropped


def _state_rank(name: str, on_task: bool) -> int:
    state = STATE_OF.get(name)
    if state is None:
        # not in the table (tests/test_query_wall.py fails until it is):
        # a task's thread runs the pipeline, the query's the statement
        state = "pipeline" if on_task else "statement"
    return _RANK[state]


def _innermost(items: List[Tuple[int, int, int]]):
    """`items`: (start, end, record index) of ONE thread, by start and
    the longer first.  Returns the stretches (start, end, index) in which
    each record is the innermost one open, and each record's enclosing
    record (-1: none).  Tolerant of records that overlap without nesting
    (one measured after the fact, a reused thread ident): the one begun
    last is innermost."""
    stretches: List[Tuple[int, int, int]] = []
    parent: Dict[int, int] = {}
    stack: List[Tuple[int, int, int]] = []
    cursor = 0

    def run_to(limit: Optional[int]) -> None:
        nonlocal cursor
        while stack:
            _s, end, idx = stack[-1]
            if end <= cursor:
                stack.pop()
                continue
            upto = end if limit is None else min(end, limit)
            if upto > cursor:
                stretches.append((cursor, upto, idx))
                cursor = upto
            if limit is not None and cursor >= limit:
                return

    for start, end, idx in items:
        if end <= start:
            parent[idx] = -1
            continue
        run_to(start)
        cursor = start
        while stack and stack[-1][1] <= start:
            stack.pop()
        parent[idx] = stack[-1][2] if stack else -1
        stack.append((start, end, idx))
    run_to(None)
    return stretches, parent


def partition(records: List[Record], start_ns: int, end_ns: int,
              dropped: int = 0) -> Dict[str, int]:
    """`queryWall.*` / `queryWallCpu.*` / `queryWallIntervals[Dropped]`
    of one query: its records over its extent [start_ns, end_ns).  Where
    several threads are in the state that wins an instant, the CPU time
    charged is the mean of theirs, and the wait named is the first of
    the table's that is open."""
    by_thread: Dict[object, List[Tuple[int, int, int]]] = {}
    for i, r in enumerate(records):
        by_thread.setdefault(r[0], []).append((r[2], r[3], i))
    # per record: the time it was innermost on its thread, and the CPU
    # time that is its own (its enclosed records' taken out).  A record
    # nobody measured the CPU time of (-1) shares its encloser's
    cpu_owner: Dict[int, int] = {}
    own_wall: Dict[int, int] = {}
    own_cpu: Dict[int, int] = {}
    stretches: List[Tuple[int, int, int]] = []
    for items in by_thread.values():
        items.sort(key=lambda r: (r[0], -r[1]))
        mine, parent = _innermost(items)
        for _s, _e, idx in items:
            up = parent[idx]
            outer = cpu_owner.get(up, -1) if up >= 0 else -1
            cpu = records[idx][4]
            if cpu >= 0:
                cpu_owner[idx] = idx
                own_cpu[idx] = own_cpu.get(idx, 0) + cpu
                if outer >= 0:
                    own_cpu[outer] = own_cpu.get(outer, 0) - cpu
            else:
                cpu_owner[idx] = outer
        for s, e, idx in mine:
            owner = cpu_owner[idx]
            if owner >= 0:
                own_wall[owner] = own_wall.get(owner, 0) + e - s
        stretches += mine
    # a stretch, clipped to the extent: its state, its CPU time a
    # nanosecond of wall, and (a wait) which one
    begin, end, rank, rate, wait = [], [], [], [], []
    for s, e, idx in stretches:
        s, e = max(s, start_ns), min(e, end_ns)
        if e <= s:
            continue
        _t, name, _s, _e, _c, on_task = records[idx]
        begin.append(s)
        end.append(e)
        rank.append(_state_rank(name, on_task))
        owner = cpu_owner[idx]
        rate.append(max(own_cpu[owner], 0) / own_wall[owner]
                    if owner >= 0 and own_wall.get(owner) else 0.0)
        wait.append(_WAIT_COLUMN.get(name, 0))
    n, states = len(begin), len(STATES) - 1
    # across threads, one sweep over every stretch's two edges: how many
    # stretches of each state are open between one edge and the next
    times = np.array(begin + end + [start_ns, end_ns], dtype=np.int64)
    rows = np.arange(n)
    opened = np.zeros((2 * n + 2, states), dtype=np.int64)
    rates = np.zeros((2 * n + 2, states))
    waits = np.zeros((2 * n + 2, len(_WAITS)), dtype=np.int64)
    if n:
        rank_a, rate_a = np.array(rank), np.array(rate)
        opened[rows, rank_a] = 1
        opened[rows + n, rank_a] = -1
        rates[rows, rank_a] = rate_a
        rates[rows + n, rank_a] = -rate_a
        waiting = rank_a == _WAIT
        wait_a = np.array(wait)[waiting]
        waits[rows[waiting], wait_a] = 1
        waits[rows[waiting] + n, wait_a] = -1
    order = np.argsort(times, kind="stable")
    span = np.diff(times[order])
    keep = span > 0
    span = span[keep]
    opened = np.cumsum(opened[order], axis=0)[:-1][keep]
    rates = np.cumsum(rates[order], axis=0)[:-1][keep]
    waits = np.cumsum(waits[order], axis=0)[:-1][keep]
    # the first state in STATES with a stretch open wins the instant; the
    # glue between two spans stays with the state before it
    live = opened > 0
    winner = np.where(live.any(axis=1), live.argmax(axis=1), _UNATTRIBUTED)
    glue = (winner == _UNATTRIBUTED) & (span < GAP_FLOOR_NS)
    glue[:1] = False
    winner = np.where(glue, np.roll(winner, 1), winner)
    wall = np.bincount(winner, weights=span, minlength=len(STATES))
    out = {f"queryWall.{state}": int(round(wall[i]))
           for i, state in enumerate(STATES[:-1])}
    out["queryWall.unattributed"] = max(
        0, end_ns - start_ns - sum(out.values()))
    won = (winner == _WAIT) & ~glue
    named = np.bincount((waits[won] > 0).argmax(axis=1), weights=span[won],
                        minlength=len(_WAITS))
    for k in np.argsort(-named):
        if named[k]:
            out[f"queryWall.wait.{_WAITS[k]}"] = int(round(named[k]))
    at = np.arange(len(winner))
    attributed = (winner != _UNATTRIBUTED) & ~glue
    state = np.where(attributed, winner, 0)
    charged = np.where(attributed, span * rates[at, state]
                       / np.maximum(opened[at, state], 1), 0.0)
    cpu = np.bincount(state, weights=charged, minlength=states)
    for i, name in enumerate(STATES[:-1]):
        out[f"queryWallCpu.{name}"] = int(max(cpu[i], 0.0))
    out["queryWallIntervals"] = len(records)
    out["queryWallIntervalsDropped"] = dropped
    return out


def runtime_stats_keys(timelines: Iterable[Tuple[str, dict]],
                       start_ns: int, end_ns: int) -> Dict[str, dict]:
    """The partition as `runtimeStats` entries (QueryInfo, EXPLAIN
    ANALYZE's footer, the completed event): nanoseconds but the two
    counts.  A query's alone: tasks carry none of these keys."""
    records, dropped = records_of(timelines)
    out = {}
    for key, value in partition(records, start_ns, max(start_ns, end_ns),
                                dropped).items():
        unit = "NONE" if key.startswith("queryWallIntervals") else "NANO"
        out[key] = {"unit": unit, "sum": value, "count": 1,
                    "min": value, "max": value}
    return out


def with_partition(stats, start_ns: int) -> Dict[str, dict]:
    """`stats.to_dict()` with the partition of [start_ns, now) on top:
    for a run whose RuntimeStats are its own (a runner called with no
    statement layer above it, EXPLAIN ANALYZE's analysed run), which is
    then the query level."""
    return {**stats.to_dict(),
            **runtime_stats_keys(stats.timelines(), start_ns, unix_ns())}
