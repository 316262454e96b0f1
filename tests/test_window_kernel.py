"""Prefix-sum window kernel (presto_tpu/exec/kernels/window.py):
engagement and parity vs the XLA segmented scans (operators.
window_batch) and the numpy reference oracle, randomized fuzz across
partition-key cardinalities (single-row and all-one-partition edges
included), and the Window* decline gates.

Everything the kernel accepts is integer/decimal arithmetic, so every
comparison is exact equality; float accumulation declines by design."""
import numpy as np
import pytest

from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner, _assert_rows_equal


def _window_programs(res) -> int:
    return int((res.runtime_stats or {}).get(
        "kernelWindowPrograms", {}).get("sum", 0))


def _declined(res) -> dict:
    return {k[len("kernelDeclined"):]: int(v.get("sum", 0))
            for k, v in (res.runtime_stats or {}).items()
            if k.startswith("kernelDeclined")}


@pytest.fixture(scope="module")
def pallas():
    return LocalQueryRunner(
        "sf0.01", config=ExecutionConfig(scan_kernel="pallas"))


@pytest.fixture(scope="module")
def xla():
    return LocalQueryRunner(
        "sf0.01", config=ExecutionConfig(scan_kernel="xla"))


RUNNING_SUM = """
    select custkey, orderkey,
           sum(totalprice) over (partition by custkey
                                 order by orderkey) as running
    from orders where orderkey < 4000
"""


def test_running_sum_kernel_engages(pallas, xla):
    # the acceptance shape: running SUM over sorted partitions through
    # the in-kernel pairing scan, bit-identical to the XLA path
    pres = pallas.execute(RUNNING_SUM)
    assert _window_programs(pres) >= 1, _declined(pres)
    assert not _declined(pres)
    xres = xla.execute(RUNNING_SUM)
    assert _window_programs(xres) == 0
    assert _declined(xres).get("Disabled", 0) >= 1
    _assert_rows_equal(pres, xres, ordered=False)
    _assert_rows_equal(pres, pallas.execute_reference(RUNNING_SUM),
                       ordered=False)


def test_ranking_functions_in_kernel(pallas, xla):
    # row_number / rank / dense_rank share one (partition, order) spec:
    # one launch computes all three
    sql = ("select custkey, orderkey, "
           "row_number() over (partition by custkey order by orderdate, "
           "orderkey) as rn, "
           "rank() over (partition by custkey order by orderdate, "
           "orderkey) as rk, "
           "dense_rank() over (partition by custkey order by orderdate, "
           "orderkey) as dr "
           "from orders where orderkey < 4000")
    pres = pallas.execute(sql)
    assert _window_programs(pres) >= 1, _declined(pres)
    _assert_rows_equal(pres, xla.execute(sql), ordered=False)
    _assert_rows_equal(pres, pallas.execute_reference(sql), ordered=False)


def test_count_avg_in_kernel(pallas, xla):
    sql = ("select custkey, orderkey, "
           "count(*) over (partition by custkey order by orderkey) as c, "
           "avg(totalprice) over (partition by custkey "
           "order by orderkey) as a "
           "from orders where orderkey < 4000")
    pres = pallas.execute(sql)
    assert _window_programs(pres) >= 1, _declined(pres)
    _assert_rows_equal(pres, xla.execute(sql), ordered=False)
    _assert_rows_equal(pres, pallas.execute_reference(sql), ordered=False)


# ---------------------------------------------------------------------------
# randomized fuzz: partition-key cardinality x functions x order keys,
# pallas vs xla vs oracle.  orderkey is unique, so every function is
# deterministic under the shared sort.
# ---------------------------------------------------------------------------

_FUNCS = ["row_number()", "rank()", "dense_rank()", "count(*)",
          "count(totalprice)", "sum(totalprice)", "avg(totalprice)"]
# cardinality sweep: multi-row partitions, single-row partitions
# (partition key = the unique order key), one global partition, and a
# dictionary-encoded partition key
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


@pytest.mark.parametrize("seed", [31, 32, 33, 34, 35])
def test_window_parity_fuzz(pallas, xla, seed):
    sql = _window_fuzz_sql(seed)
    pres = pallas.execute(sql)
    xres = xla.execute(sql)
    _assert_rows_equal(pres, xres, ordered=False)
    assert _window_programs(pres) >= 1, (sql, _declined(pres))
    assert _window_programs(xres) == 0
    _assert_rows_equal(pres, pallas.execute_reference(sql), ordered=False)


def test_single_row_and_global_partition_edges(pallas, xla):
    # both edges in one query batch: every partition has exactly one
    # row (frame == the row itself), then no PARTITION BY at all (one
    # segment spans the whole live range)
    for sql in (
        "select orderkey, sum(totalprice) over (partition by orderkey "
        "order by orderkey) as s, count(*) over (partition by orderkey "
        "order by orderkey) as c from orders where orderkey < 3000",
        "select orderkey, sum(totalprice) over (order by orderkey) as s, "
        "rank() over (order by orderkey) as r "
        "from orders where orderkey < 3000",
    ):
        pres = pallas.execute(sql)
        assert _window_programs(pres) >= 1, (sql, _declined(pres))
        _assert_rows_equal(pres, xla.execute(sql), ordered=False)
        _assert_rows_equal(pres, pallas.execute_reference(sql),
                           ordered=False)


def test_null_arg_running_aggregates(pallas, xla):
    # NULL inputs: count skips them, sum carries them as non-contrib
    # rows, empty frames are NULL — the contrib mask in-kernel must
    # match window_batch exactly
    sql = ("select k, orderkey, sum(v) over (partition by k "
           "order by orderkey) as s, count(v) over (partition by k "
           "order by orderkey) as c from "
           "(select custkey % 7 as k, orderkey, "
           "case when orderkey % 3 = 0 then null else totalprice end as v "
           "from orders where orderkey < 6000)")
    pres = pallas.execute(sql)
    assert _window_programs(pres) >= 1, _declined(pres)
    _assert_rows_equal(pres, xla.execute(sql), ordered=False)
    _assert_rows_equal(pres, pallas.execute_reference(sql), ordered=False)


# ---------------------------------------------------------------------------
# Window* decline gates
# ---------------------------------------------------------------------------

def test_unsupported_function_declines(pallas, xla):
    # lag needs a shifted gather, not a prefix scan: stays on XLA
    sql = ("select orderkey, lag(totalprice) over (partition by custkey "
           "order by orderkey) as prev from orders where orderkey < 3000")
    pres = pallas.execute(sql)
    assert _window_programs(pres) == 0
    assert _declined(pres).get("WindowFunctionShape", 0) >= 1
    _assert_rows_equal(pres, xla.execute(sql), ordered=False)


def test_float_sum_declines(pallas):
    # float cumsum re-associates the reduction tree: bit-identity would
    # break, so float accumulation declines by design
    sql = ("select orderkey, sum(cast(totalprice as double)) over "
           "(partition by custkey order by orderkey) as s "
           "from orders where orderkey < 3000")
    res = pallas.execute(sql)
    assert _window_programs(res) == 0
    assert _declined(res).get("WindowFunctionShape", 0) >= 1
    pallas.assert_same_as_reference(sql)


def test_explicit_frame_declines(pallas, xla):
    sql = ("select orderkey, sum(totalprice) over (partition by custkey "
           "order by orderkey rows between 1 preceding and current row) "
           "as s from orders where orderkey < 3000")
    pres = pallas.execute(sql)
    assert _window_programs(pres) == 0
    assert _declined(pres).get("WindowFunctionShape", 0) >= 1
    _assert_rows_equal(pres, xla.execute(sql), ordered=False)


def test_lazy_key_declines_window_key_shape():
    # a late-materialized key column cannot feed in-kernel peer
    # detection: the row-id indirection would compare ids, not values
    import jax.numpy as jnp

    from presto_tpu.exec.batch import Batch, Column
    from presto_tpu.exec.kernels.window import try_window_kernel
    from presto_tpu.exec.operators import WindowSpec

    n = 8
    cols = {
        "k": Column(jnp.arange(n, dtype=jnp.int64), None, None,
                    ("rowid", "orders", "clerk", 1.0)),
        "v": Column(jnp.arange(n, dtype=jnp.int64), None),
    }
    batch = Batch(cols, jnp.ones(n, dtype=bool))
    reasons = []
    out = try_window_kernel(
        batch, ("k",), (("v", "ASC_NULLS_LAST"),),
        (WindowSpec("sum", "s", "v"),), declined=reasons.append)
    assert out is None and reasons == ["WindowKeyShape"]


def test_input_size_gate_declines(pallas, monkeypatch):
    from presto_tpu.exec.kernels import window as wk
    monkeypatch.setattr(wk, "KERNEL_WINDOW_MAX_BYTES", 64)
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        scan_kernel="pallas"))
    res = r.execute(RUNNING_SUM)
    assert _window_programs(res) == 0
    assert _declined(res).get("WindowInputSize", 0) >= 1
    _assert_rows_equal(res, pallas.execute(RUNNING_SUM), ordered=False)


@pytest.mark.parametrize("compiles,reason", [
    (False, "CompilerRefused"),   # today's table: refused on every backend
    (True, "Backend"),            # a family that compiles: auto off-TPU
])
def test_auto_declines(monkeypatch, compiles, reason):
    from presto_tpu.exec.kernels import KERNEL_FAMILY_COMPILES
    monkeypatch.setitem(KERNEL_FAMILY_COMPILES, "window", compiles)
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        scan_kernel="auto"))
    res = r.execute(RUNNING_SUM)
    assert _window_programs(res) == 0
    assert _declined(res) == {reason: 1}


def test_explain_analyze_reports_window_kernel(pallas):
    text = pallas.execute(
        "EXPLAIN ANALYZE " + RUNNING_SUM.strip()).rows[0][0]
    assert "Pallas window kernels: 1" in text
