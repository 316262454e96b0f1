"""Where the device idled, by what the program was doing: a profiler
capture reduced over the program's own `presto:` spans.

`RuntimeStats.span`, `host_get` and `named_jit` open
`jax.profiler.TraceAnnotation("presto:<name>")`, so a capture (the
`profile` session property, or any `jax.profiler.trace`) holds them on
the host threads' lines of the SAME timeline as the device's `XLA Ops`.
This module reads such a capture and answers, for every stretch in which
the device ran nothing, which `presto:` span was innermost on a host
thread at that moment:

    python -m presto_tpu.telemetry.gaps <trace_dir> [--top 12]

prints one JSON object: the window, the device's busy seconds, device
seconds per program (`XLA Modules`), the idle seconds per innermost span,
and how much of the idle time no span covered.  Threads are ranked by
depth: the deepest open span wins (a task thread inside
`pipelineDispatch` says more than the coordinator inside
`schedAwaitStages`), and at equal depth the shorter one.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Tuple

SPAN_PREFIX = "presto:"
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
GAP_FLOOR_NS = 100_000      # shorter gaps are launch latency, not idling

Span = Tuple[str, int, int, str]        # (thread, start_ns, end_ns, name)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: str):
    """(device op intervals, program events, presto: spans) of a capture.
    Without a device plane (a CPU capture) the first two are empty."""
    from jax.profiler import ProfileData
    ops: List[Tuple[int, int]] = []
    programs: List[Tuple[int, int, str]] = []
    spans: List[Span] = []
    for plane in ProfileData.from_file(find_xplane(trace_dir)).planes:
        on_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if on_device:
                if line.name == OPS_LINE:
                    ops.extend((int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in line.events)
                elif line.name == PROGRAMS_LINE:
                    programs.extend((int(e.start_ns),
                                     int(e.start_ns + e.duration_ns),
                                     e.name) for e in line.events)
                continue
            thread = f"{plane.name}|{line.name}"
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((thread, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns),
                                  e.name[len(SPAN_PREFIX):]))
    return ops, programs, spans


def nesting(spans: List[Span]) -> List[Tuple[str, str]]:
    """(child, parent) names for every span that lies inside another on
    its own thread (the innermost enclosing one)."""
    pairs = []
    by_thread: Dict[str, List[Span]] = {}
    for s in spans:
        by_thread.setdefault(s[0], []).append(s)
    for items in by_thread.values():
        items.sort(key=lambda s: (s[1], -s[2]))
        stack: List[Span] = []
        for s in items:
            while stack and stack[-1][2] < s[2]:
                stack.pop()
            if stack:
                pairs.append((s[3], stack[-1][3]))
            stack.append(s)
    return pairs


def pair_records(spans: List[Span], records, tolerance_ns: int = 200_000,
                 window_ns: int = 1_000_000) -> dict:
    """Lay a query's own records (telemetry/query_wall.py `records_of`:
    unix nanoseconds) on a capture's axis, which counts from its
    session's start.  The bridge is the span itself: every record of a
    `RuntimeStats.span`, a launch or a host sync has a `presto:<name>`
    annotation of the same extent.  The longest annotation proposes the
    offset (against each record of its name and duration); the proposal
    that places most annotations on a record of their name, duration
    (within `tolerance_ns`) and start (within `window_ns`) wins, and each
    annotation is paired with the nearest such record, in time order.
    Returns `offset_ns` (records minus capture, the median over the
    pairs), `spread_ns` (greatest minus least offset of a pair), `pairs`
    as (span, record, offset) and the annotations left `unpaired`:
    add `offset_ns` to the capture's `XLA Ops` and they are on the
    partition's axis."""
    by_name: Dict[str, list] = {}
    for r in records:
        by_name.setdefault(r[1], []).append(r)
    for items in by_name.values():
        items.sort(key=lambda r: r[2])
    spans = sorted((s for s in spans if s[3] in by_name),
                   key=lambda s: s[1])

    def place(offset: int, keep: bool):
        pairs, used = [], set()
        for s in spans:
            length, best = s[2] - s[1], None
            for r in by_name[s[3]]:
                off = r[2] - s[1]
                if id(r) in used or abs(off - offset) > window_ns \
                        or abs((r[3] - r[2]) - length) > tolerance_ns:
                    continue
                if best is None or abs(off - offset) < abs(best[2] - offset):
                    best = (s, r, off)
            if best is not None:
                used.add(id(best[1]))
                pairs.append(best)
        return pairs if keep else len(pairs)

    if not spans:
        return {"offset_ns": None, "spread_ns": 0, "pairs": [],
                "unpaired": 0}
    longest = max(spans, key=lambda s: s[2] - s[1])
    proposals = [r[2] - longest[1] for r in by_name[longest[3]]
                 if abs((r[3] - r[2]) - (longest[2] - longest[1]))
                 <= tolerance_ns]
    if not proposals:
        return {"offset_ns": None, "spread_ns": 0, "pairs": [],
                "unpaired": len(spans)}
    pairs = place(max(proposals, key=lambda o: place(o, False)), True)
    offsets = sorted(p[2] for p in pairs)
    return {"offset_ns": offsets[len(offsets) // 2],
            "spread_ns": offsets[-1] - offsets[0], "pairs": pairs,
            "unpaired": len(spans) - len(pairs)}


def _union(intervals):
    merged: List[List[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _innermost(spans: List[Span], at_ns: int) -> str:
    """Name of the deepest span open at `at_ns` on any thread."""
    best, best_rank = "", (0, 0)
    by_thread: Dict[str, List[Span]] = {}
    for s in spans:
        if s[1] <= at_ns < s[2]:
            by_thread.setdefault(s[0], []).append(s)
    for items in by_thread.values():
        inner = min(items, key=lambda s: s[2] - s[1])
        # deepest wins; at equal depth the shorter (more specific) span
        rank = (len(items), -(inner[2] - inner[1]))
        if rank > best_rank or not best:
            best, best_rank = inner[3], rank
    return best


def reduce(ops, programs, spans, top: int = 12) -> dict:
    """The window is the extent of the `presto:` spans (the query)."""
    if not spans:
        return {"window_s": 0.0, "spans": 0}
    w0 = min(s[1] for s in spans)
    w1 = max(s[2] for s in spans)
    busy = _union((max(s, w0), min(e, w1)) for s, e in ops
                  if e > w0 and s < w1)
    by_program: Dict[str, int] = {}
    for s, e, name in programs:
        if e > w0 and s < w1:
            by_program[name] = by_program.get(name, 0) \
                + min(e, w1) - max(s, w0)
    gaps: Dict[str, int] = {}
    cursor = w0
    for s, e in busy + [[w1, w1]]:
        if s - cursor >= GAP_FLOOR_NS:
            what = _innermost(spans, (cursor + s) // 2) or "(no span)"
            gaps[what] = gaps.get(what, 0) + s - cursor
        cursor = max(cursor, e)
    span_s: Dict[str, int] = {}
    for _t, s, e, name in spans:
        span_s[name] = span_s.get(name, 0) + e - s

    def ranked(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    idle = sum(gaps.values())
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "idle_s": idle / 1e9,
            "idle_uncovered_s": gaps.get("(no span)", 0) / 1e9,
            "spans": len(spans),
            "device_programs": ranked(by_program),
            "idle_by_span": ranked(gaps),
            "span_seconds": ranked(span_s)}


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if not args:
        print(__doc__.split("\n\n")[2], file=sys.stderr)
        return 2
    top = int(args[args.index("--top") + 1]) if "--top" in args else 12
    print(json.dumps(reduce(*load(args[0]), top=top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
