"""From a profiler trace to busy and idle time, per-program device time
and what the host was doing in the idle gaps.

Two steps, so that the arithmetic can be tested without a chip:
`extract` flattens an .xplane.pb into plain events, `reduce` turns events
into the numbers.  An event is (plane, line, name, start_ns, duration_ns).
"""
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"            # one event per executed HLO op
PROGRAMS_LINE = "XLA Modules"   # one event per executed program (jit name)
SPAN_PREFIX = "bench:"          # the harness's own TraceAnnotation spans
GAP_FLOOR_NS = 100_000          # shorter gaps are launch latency, not idling


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def extract(xplane_path: str, rehearsal: bool = False):
    """Events of the device planes' ops and programs lines and of the
    harness's spans on the host planes; and every (plane, line) seen with
    its event count, for the log.  A rehearsal has no device plane: there
    the CPU client's executor threads stand in for one, so that the
    reduction and the readers run; its numbers mean nothing."""
    from jax.profiler import ProfileData
    events, seen = [], {}
    for plane in ProfileData.from_file(xplane_path).planes:
        on_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            n = 0
            stand_in = rehearsal and line.name.startswith("tf_XLAEigen")
            if on_device and line.name not in (OPS_LINE, PROGRAMS_LINE):
                continue
            # an op's name is not read: a million of them a traced minute,
            # and the breakdown names programs
            named = line.name != OPS_LINE
            for ev in line.events:
                n += 1
                if on_device:
                    events.append((plane.name, line.name,
                                   ev.name if named else "",
                                   int(ev.start_ns), int(ev.duration_ns)))
                elif stand_in:
                    events.append((DEVICE_PLANE_PREFIX + "rehearsal",
                                   OPS_LINE, ev.name,
                                   int(ev.start_ns), int(ev.duration_ns)))
                elif ev.name.startswith(SPAN_PREFIX):
                    events.append((plane.name, "spans", ev.name,
                                   int(ev.start_ns), int(ev.duration_ns)))
            seen[f"{plane.name}|{line.name}"] = n
    return events, seen


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _top(totals: dict, n: int = 10):
    return [[name, ns / 1e9] for name, ns in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def reduce(events, window_s=None):
    """busy_s (union of the device's op intervals, averaged over the
    device planes), window_s, the programs that took most device time, and the idle gaps summed by what the harness's spans say the
    host was doing.  The window starts with the first of the harness's
    spans (the first request) and lasts `window_s`; without that it is the
    extent of the spans, or of the device's events where there are none."""
    by_plane, spans = {}, []
    for plane, line, name, start, dur in events:
        if line == "spans":
            spans.append((start, start + dur, name[len(SPAN_PREFIX):]))
        elif plane.startswith(DEVICE_PLANE_PREFIX):
            by_plane.setdefault(plane, {}).setdefault(line, []).append(
                (start, start + dur, name))
    if not by_plane:
        return None
    edges = [(s, e) for s, e, _ in spans] or [
        (s, e) for lines in by_plane.values()
        for evs in lines.values() for s, e, _ in evs]
    w0 = min(s for s, _ in edges)
    w1 = max(e for _, e in edges) if window_s is None \
        else w0 + int(window_s * 1e9)
    busy_ns, programs, gaps = 0, {}, {}
    for lines in by_plane.values():
        timeline = lines.get(OPS_LINE) or lines.get(PROGRAMS_LINE) or []
        merged = union((max(s, w0), min(e, w1)) for s, e, _ in timeline
                       if e > w0 and s < w1)
        busy_ns += sum(e - s for s, e in merged)
        for s, e, name in lines.get(PROGRAMS_LINE, []):
            if e > w0 and s < w1:
                programs[name] = programs.get(name, 0) \
                    + min(e, w1) - max(s, w0)
        cursor = w0
        for s, e in merged + [[w1, w1]]:
            if s - cursor >= GAP_FLOOR_NS:
                what = _host_was(spans, (cursor + s) // 2)
                gaps[what] = gaps.get(what, 0) + s - cursor
            cursor = max(cursor, e)
    n = len(by_plane)
    return {"busy_s": busy_ns / n / 1e9, "window_s": (w1 - w0) / 1e9,
            "device_planes": n,
            "program_s": {k: v / n / 1e9 for k, v in programs.items()},
            "device_ops": _top(programs),
            "idle_gaps": _top({k: v / n for k, v in gaps.items()})}


def _host_was(spans, at_ns):
    inside = sorted({name for s, e, name in spans if s <= at_ns < e})
    return "in " + "+".join(inside) if inside else "between requests"
