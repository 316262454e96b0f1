"""Window-function conformance bank (VERDICT item 7): ranking, value
functions (lag/lead/first_value/last_value/nth_value), ntile,
percent_rank/cume_dist, and explicit ROWS/RANGE frames — engine
(exec/operators.py window_batch) vs the independent numpy oracle
(exec/reference.py), per the reference's AbstractTestWindowQueries
differential strategy (SURVEY.md §4.3).

Reference semantics fixture: presto-main-base/.../operator/window/
(frames), WindowOperator.java:69.
"""
import numpy as np
import pytest

from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner("sf0.01", config=ExecutionConfig(
        batch_rows=1 << 14, join_out_capacity=1 << 16))


SHAPES = {
    "row_number": """
        SELECT custkey, orderkey,
               row_number() OVER (PARTITION BY custkey ORDER BY orderkey)
        FROM orders WHERE orderkey < 2000""",
    "rank_dense": """
        SELECT orderkey, rank() OVER (ORDER BY orderpriority),
               dense_rank() OVER (ORDER BY orderpriority)
        FROM orders WHERE orderkey < 400""",
    "running_sum": """
        SELECT custkey, orderkey,
               sum(totalprice) OVER (PARTITION BY custkey ORDER BY orderkey)
        FROM orders WHERE orderkey < 4000""",
    "global_agg": """
        SELECT orderkey, avg(totalprice) OVER () FROM orders
        WHERE orderkey < 500""",
    "lag_default": """
        SELECT custkey, orderkey,
               lag(orderkey) OVER (PARTITION BY custkey ORDER BY orderkey),
               lag(orderkey, 2, -1) OVER (PARTITION BY custkey
                                          ORDER BY orderkey)
        FROM orders WHERE orderkey < 4000""",
    "lead": """
        SELECT custkey, orderkey,
               lead(totalprice) OVER (PARTITION BY custkey ORDER BY orderkey)
        FROM orders WHERE orderkey < 4000""",
    "first_last_value": """
        SELECT custkey, orderkey,
               first_value(orderkey) OVER (PARTITION BY custkey
                                           ORDER BY orderkey),
               last_value(orderkey) OVER (PARTITION BY custkey
                                          ORDER BY orderkey)
        FROM orders WHERE orderkey < 4000""",
    "last_value_full_frame": """
        SELECT custkey, orderkey,
               last_value(orderkey) OVER (
                   PARTITION BY custkey ORDER BY orderkey
                   RANGE BETWEEN UNBOUNDED PRECEDING
                             AND UNBOUNDED FOLLOWING)
        FROM orders WHERE orderkey < 4000""",
    "nth_value": """
        SELECT custkey, orderkey,
               nth_value(orderkey, 2) OVER (
                   PARTITION BY custkey ORDER BY orderkey
                   ROWS BETWEEN UNBOUNDED PRECEDING
                            AND UNBOUNDED FOLLOWING)
        FROM orders WHERE orderkey < 4000""",
    "ntile": """
        SELECT orderkey, ntile(4) OVER (ORDER BY totalprice)
        FROM orders WHERE orderkey < 800""",
    "percent_rank": """
        SELECT orderkey, percent_rank() OVER (ORDER BY orderpriority),
               cume_dist() OVER (ORDER BY orderpriority)
        FROM orders WHERE orderkey < 400""",
    "rows_preceding": """
        SELECT custkey, orderkey,
               sum(totalprice) OVER (PARTITION BY custkey ORDER BY orderkey
                                     ROWS 2 PRECEDING)
        FROM orders WHERE orderkey < 4000""",
    "rows_between": """
        SELECT custkey, orderkey,
               sum(totalprice) OVER (
                   PARTITION BY custkey ORDER BY orderkey
                   ROWS BETWEEN 1 PRECEDING AND 1 FOLLOWING)
        FROM orders WHERE orderkey < 4000""",
    "rows_moving_min_max": """
        SELECT custkey, orderkey,
               min(totalprice) OVER (PARTITION BY custkey ORDER BY orderkey
                                     ROWS BETWEEN 2 PRECEDING
                                              AND CURRENT ROW),
               max(totalprice) OVER (PARTITION BY custkey ORDER BY orderkey
                                     ROWS BETWEEN 2 PRECEDING
                                              AND CURRENT ROW)
        FROM orders WHERE orderkey < 4000""",
    "rows_following_only": """
        SELECT custkey, orderkey,
               count(*) OVER (PARTITION BY custkey ORDER BY orderkey
                              ROWS BETWEEN 1 FOLLOWING AND 2 FOLLOWING)
        FROM orders WHERE orderkey < 4000""",
    "rows_unbounded_following": """
        SELECT custkey, orderkey,
               sum(totalprice) OVER (
                   PARTITION BY custkey ORDER BY orderkey
                   ROWS BETWEEN CURRENT ROW AND UNBOUNDED FOLLOWING)
        FROM orders WHERE orderkey < 4000""",
    "range_unbounded_both": """
        SELECT custkey, orderkey,
               count(*) OVER (PARTITION BY custkey ORDER BY orderkey
                              RANGE BETWEEN UNBOUNDED PRECEDING
                                        AND UNBOUNDED FOLLOWING)
        FROM orders WHERE orderkey < 4000""",
    "min_max_string": """
        SELECT orderkey,
               max(orderpriority) OVER (ORDER BY orderkey
                                        ROWS 3 PRECEDING)
        FROM orders WHERE orderkey < 800""",
    "window_over_join": """
        SELECT o.orderkey,
               rank() OVER (PARTITION BY o.custkey ORDER BY o.totalprice)
        FROM orders o JOIN customer c ON o.custkey = c.custkey
        WHERE c.nationkey < 5 AND o.orderkey < 4000""",
    "multi_specs": """
        SELECT orderkey,
               row_number() OVER (ORDER BY totalprice),
               sum(totalprice) OVER (PARTITION BY orderpriority
                                     ORDER BY orderkey)
        FROM orders WHERE orderkey < 800""",
    "empty_input": """
        SELECT orderkey, lag(totalprice) OVER (ORDER BY orderkey)
        FROM orders WHERE orderkey < 0""",
    "same_spec_different_frames": """
        SELECT custkey, orderkey,
               sum(totalprice) OVER (PARTITION BY custkey ORDER BY orderkey
                                     ROWS 1 PRECEDING),
               sum(totalprice) OVER (PARTITION BY custkey ORDER BY orderkey
                                     ROWS 3 PRECEDING)
        FROM orders WHERE orderkey < 4000""",
}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_window_shape(runner, name):
    runner.assert_same_as_reference(SHAPES[name])


def test_frames_not_deduped(runner):
    """Two window calls that differ ONLY in frame must produce distinct
    columns (the planner dedups by canonical text — the frame is part of
    it).  Hand-checked because the oracle runs the same planned IR and
    would inherit a planner-side dedup bug."""
    r = runner.execute("""
        SELECT orderkey,
               sum(orderkey) OVER (ORDER BY orderkey ROWS 1 PRECEDING),
               sum(orderkey) OVER (ORDER BY orderkey ROWS 3 PRECEDING)
        FROM orders WHERE orderkey IN (1, 2, 3, 4, 5, 6, 7)
    """)
    got = {int(a): (int(b), int(c)) for a, b, c in r.rows}
    keys = sorted(got)
    for i, k in enumerate(keys):
        want1 = sum(keys[max(0, i - 1):i + 1])
        want3 = sum(keys[max(0, i - 3):i + 1])
        assert got[k] == (want1, want3), (k, got[k], (want1, want3))


def test_hand_checked_frames(runner):
    """Anchor both implementations to hand-computed values (guards against
    a shared misunderstanding of frame semantics)."""
    r = runner.execute("""
        SELECT orderkey,
               sum(orderkey) OVER (ORDER BY orderkey
                                   ROWS BETWEEN 1 PRECEDING AND 1 FOLLOWING)
        FROM orders WHERE orderkey IN (1, 2, 3, 4, 5, 6)
    """)
    got = {int(a): int(b) for a, b in r.rows}
    # rows present: orderkeys 1..6 that exist in tpch data
    keys = sorted(got)
    for i, k in enumerate(keys):
        lo = max(0, i - 1)
        hi = min(len(keys) - 1, i + 1)
        assert got[k] == sum(keys[lo:hi + 1]), (k, got[k])


def test_ntile_hand_checked(runner):
    r = runner.execute("""
        SELECT orderkey, ntile(3) OVER (ORDER BY orderkey)
        FROM orders WHERE orderkey < 30
    """)
    rows = sorted((int(a), int(b)) for a, b in r.rows)
    n = len(rows)
    q, rem = divmod(n, 3)
    sizes = [q + 1] * rem + [q] * (3 - rem)
    want = []
    for b, sz in enumerate(sizes, 1):
        want += [b] * sz
    assert [b for _, b in rows] == want


# ---------------------------------------------------------------------------
# window_batch under the DEFAULT config (what a served query runs under).
# Everything but float_sum is integer/decimal arithmetic, so the comparison
# with the oracle is exact equality.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def default_runner():
    return LocalQueryRunner("sf0.01")


RUNNING_SUM = """
    select custkey, orderkey,
           sum(totalprice) over (partition by custkey
                                 order by orderkey) as running
    from orders where orderkey < 4000
"""

DEFAULT_CONFIG_SHAPES = {
    "running_sum": RUNNING_SUM,
    # row_number / rank / dense_rank share one (partition, order) spec
    "ranking": "select custkey, orderkey, "
               "row_number() over (partition by custkey order by orderdate, "
               "orderkey) as rn, "
               "rank() over (partition by custkey order by orderdate, "
               "orderkey) as rk, "
               "dense_rank() over (partition by custkey order by orderdate, "
               "orderkey) as dr "
               "from orders where orderkey < 4000",
    "count_avg": "select custkey, orderkey, "
                 "count(*) over (partition by custkey order by orderkey) "
                 "as c, avg(totalprice) over (partition by custkey "
                 "order by orderkey) as a "
                 "from orders where orderkey < 4000",
    # every partition has exactly one row: the frame is the row itself
    "single_row_partitions":
        "select orderkey, sum(totalprice) over (partition by orderkey "
        "order by orderkey) as s, count(*) over (partition by orderkey "
        "order by orderkey) as c from orders where orderkey < 3000",
    # no PARTITION BY at all: one segment spans the whole live range
    "global_partition":
        "select orderkey, sum(totalprice) over (order by orderkey) as s, "
        "rank() over (order by orderkey) as r "
        "from orders where orderkey < 3000",
    # NULL inputs: count skips them, sum carries them as non-contributing
    # rows, empty frames are NULL
    "null_args": "select k, orderkey, sum(v) over (partition by k "
                 "order by orderkey) as s, count(v) over (partition by k "
                 "order by orderkey) as c from "
                 "(select custkey % 7 as k, orderkey, "
                 "case when orderkey % 3 = 0 then null else totalprice "
                 "end as v from orders where orderkey < 6000)",
    # a shifted gather, not a prefix scan
    "lag": "select orderkey, lag(totalprice) over (partition by custkey "
           "order by orderkey) as prev from orders where orderkey < 3000",
    "float_sum": "select orderkey, sum(cast(totalprice as double)) over "
                 "(partition by custkey order by orderkey) as s "
                 "from orders where orderkey < 3000",
    "explicit_frame":
        "select orderkey, sum(totalprice) over (partition by custkey "
        "order by orderkey rows between 1 preceding and current row) "
        "as s from orders where orderkey < 3000",
    # a late-materialized partition key whose row ids are not value
    # ordered: the host encodes it before window_batch compares keys
    "lazy_key": "select orderkey, sum(totalprice) over (partition by clerk "
                "order by orderkey) as s from orders where orderkey < 3000",
}


@pytest.mark.parametrize("name", sorted(DEFAULT_CONFIG_SHAPES))
def test_window_shape_default_config(default_runner, name):
    default_runner.assert_same_as_reference(DEFAULT_CONFIG_SHAPES[name])


# seeded fuzz: partition-key cardinality x functions x order keys.
# orderkey is unique, so every function is deterministic under the sort.
_FUNCS = ["row_number()", "rank()", "dense_rank()", "count(*)",
          "count(totalprice)", "sum(totalprice)", "avg(totalprice)"]
# multi-row partitions, single-row partitions (the unique order key), one
# global partition, and a dictionary-encoded partition key
_PARTS = ["partition by custkey", "partition by orderkey", "",
          "partition by orderpriority"]


def _window_fuzz_sql(seed: int) -> str:
    rng = np.random.default_rng(seed)
    part = _PARTS[int(rng.integers(len(_PARTS)))]
    order = ["order by orderkey",
             "order by orderdate, orderkey"][int(rng.integers(2))]
    over = f"over ({part}{' ' if part else ''}{order})"
    n = int(rng.integers(2, 5))
    funcs = [_FUNCS[i] for i in rng.choice(len(_FUNCS), n, replace=False)]
    sel = ", ".join(f"{f} {over} as w{i}" for i, f in enumerate(funcs))
    hi = int(rng.integers(2000, 12_000))
    return (f"select custkey, orderkey, {sel} "
            f"from orders where orderkey < {hi}")


@pytest.mark.parametrize("seed", range(31, 40))
def test_window_fuzz_vs_oracle(default_runner, seed):
    default_runner.assert_same_as_reference(_window_fuzz_sql(seed))
