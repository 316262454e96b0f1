"""The reduction from trace events to busy and idle time, per-program
device time and the gaps' attribution, on a small recorded trace."""
import json
import os

import pytest

import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = T.DEVICE_PLANE_PREFIX + "0"


def test_union_merges_overlaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def synthetic():
    ms = 1_000_000
    return [
        ("/host:CPU", "spans", "bench:tpch/q6", 0, 40 * ms),
        ("/host:CPU", "spans", "bench:tpch/q1", 60 * ms, 40 * ms),
        (DEV, T.PROGRAMS_LINE, "jit_scan(1)", 10 * ms, 12 * ms),
        (DEV, T.OPS_LINE, "fusion.1", 10 * ms, 5 * ms),
        (DEV, T.OPS_LINE, "fusion.2", 14 * ms, 6 * ms),     # overlaps fusion.1
        (DEV, T.PROGRAMS_LINE, "jit_agg(2)", 70 * ms, 10 * ms),
        (DEV, T.OPS_LINE, "fusion.1", 70 * ms, 10 * ms),
        (DEV, "Steps", "0", 0, 100 * ms),                   # not an op line
    ]


def test_busy_union_idle_share_programs_and_gaps():
    r = T.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.100)            # the spans' extent
    assert r["busy_s"] == pytest.approx(0.020)              # 10 (union) + 10
    assert r["program_s"] == {"jit_scan(1)": pytest.approx(0.012),
                              "jit_agg(2)": pytest.approx(0.010)}
    assert r["device_ops"][0] == ["jit_scan(1)", pytest.approx(0.012)]
    # a window given in seconds starts with the first span
    assert T.reduce(synthetic(), window_s=0.05)["busy_s"] == pytest.approx(0.010)
    gaps = dict(map(tuple, r["idle_gaps"]))
    # 0-10 and 20-40 inside q6's span (gap 20-70 is split by its midpoint
    # rule: one gap, attributed to what the host did at its middle)
    assert gaps["in tpch/q6"] == pytest.approx(0.010)
    assert gaps["between requests"] == pytest.approx(0.050)
    assert gaps["in tpch/q1"] == pytest.approx(0.020)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_no_device_plane_reduces_to_nothing():
    assert T.reduce([e for e in synthetic() if not e[0].startswith(DEV)]) is None


def test_recorded_trace_from_the_chip():
    """A slice of a --trace 1 run of tpch10-cluster.scan-power on the v5e
    (PR 25), as `extract` flattened it."""
    path = os.path.join(HERE, "data", "trace_small.json")
    with open(path) as f:
        recorded = json.load(f)
    events = [tuple(e) for e in recorded["events"]]
    r = T.reduce(events)
    want = recorded["expected"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"][0][0] == want["top_program"]
    assert sum(s for _n, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9
    # the busy time is the union, never more than the programs' sum
    assert r["busy_s"] <= sum(r["program_s"].values()) * (1 + 1e-9) \
        or not r["program_s"]
