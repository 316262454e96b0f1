"""Pallas fused scan kernel (presto_tpu/exec/kernels): parity fuzz vs
the XLA fused chain and the numpy reference oracle, decline-reason
coverage for the kernelDeclined{reason} counters, and operator-stats
fidelity on the kernel path.

The kernel runs through kernels/shim.py, which flips interpret=True
off-TPU, so these tests execute the REAL kernel body (late decode ->
predicate -> Blelloch prefix-sum compaction -> subtile partial agg)
on CPU.  Integer aggregates and row counters must match the XLA chain
exactly; TPC-H money columns are unscaled int64 decimals, so the money
sums and averages are exact too, not merely close."""
import numpy as np
import pytest

from presto_tpu.exec.kernels import KERNEL_DECLINE_REASONS
from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner, _assert_rows_equal

Q6 = """
    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= date '1994-01-01'
      and l_shipdate < date '1995-01-01'
      and l_discount between 0.05 and 0.07 and l_quantity < 24
"""

Q1 = """
    select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
           sum(l_extendedprice) as sum_base_price,
           sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
           avg(l_quantity) as avg_qty, min(l_quantity) as min_qty,
           max(l_extendedprice) as max_price, count(*) as count_order
    from lineitem where l_shipdate <= date '1998-09-02'
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus
"""


def _kernel_programs(res) -> int:
    return int((res.runtime_stats or {}).get(
        "kernelScanPrograms", {}).get("sum", 0))


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


# ---------------------------------------------------------------------------
# the kernel actually runs, and matches the oracle
# ---------------------------------------------------------------------------

def test_q6_kernel_engages_and_matches_oracle(pallas):
    res = pallas.assert_same_as_reference(Q6)
    assert _kernel_programs(res) >= 1, _declined(res)


def test_q1_grouped_kernel_matches_oracle(pallas):
    # dict-encoded group keys (returnflag/linestatus) through the
    # in-kernel stride-code accumulators, incl. min/max/avg/count(*)
    res = pallas.assert_same_as_reference(Q1, ordered=True)
    assert _kernel_programs(res) >= 1, _declined(res)


def test_rle_decode_path_matches_oracle(pallas):
    # l_orderkey is monotone -> RLE resident encoding: the predicate
    # forces the kernel's binary-search run decode (and zone pruning
    # folded into the aligned grid)
    sql = ("select count(*), sum(l_extendedprice), max(l_orderkey) "
           "from lineitem where l_orderkey < 150")
    res = pallas.assert_same_as_reference(sql)
    assert _kernel_programs(res) >= 1, _declined(res)
    from presto_tpu.storage.store import get_store
    kinds = {k[2]: e.column.kind for k, e in get_store().entries.items()
             if k[1] == "lineitem"}
    assert kinds.get("orderkey") == "rle", kinds


# ---------------------------------------------------------------------------
# parity fuzz: randomized predicates x encodings x agg shapes, Pallas
# output vs the XLA chain (and, each seed, vs the reference oracle)
# ---------------------------------------------------------------------------

_AGGS = ["count(*)", "sum(l_quantity)", "sum(l_extendedprice)",
         "sum(l_extendedprice * l_discount)", "min(l_quantity)",
         "max(l_extendedprice)", "avg(l_discount)"]
_GROUPS = ["", "l_returnflag", "l_returnflag, l_linestatus"]


def _fuzz_sql(seed: int) -> str:
    rng = np.random.default_rng(seed)
    conj = [f"l_quantity < {int(rng.integers(5, 45))}"]
    if rng.integers(2):
        lo = int(rng.integers(0, 7)) / 100.0
        hi = lo + int(rng.integers(1, 4)) / 100.0
        conj.append(f"l_discount between {lo:.2f} and {hi:.2f}")
    if rng.integers(2):
        y = int(rng.integers(1992, 1998))
        conj.append(f"l_shipdate >= date '{y}-01-01' "
                    f"and l_shipdate < date '{y + 1}-07-01'")
    if rng.integers(2):
        # RLE column + zone pruning on the kernel's aligned grid
        conj.append(f"l_orderkey < {int(rng.integers(100, 20_000))}")
    n_aggs = int(rng.integers(2, 5))
    aggs = [_AGGS[i] for i in rng.choice(len(_AGGS), n_aggs,
                                         replace=False)]
    group = _GROUPS[int(rng.integers(len(_GROUPS)))]
    sql = (f"select {group + ', ' if group else ''}{', '.join(aggs)} "
           f"from lineitem where {' and '.join(conj)}")
    if group:
        sql += f" group by {group}"
    return sql


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_parity_fuzz_pallas_vs_xla_vs_oracle(pallas, xla, seed):
    sql = _fuzz_sql(seed)
    pres = pallas.execute(sql)
    xres = xla.execute(sql)
    _assert_rows_equal(pres, xres, ordered=False)
    assert _kernel_programs(pres) >= 1, (sql, _declined(pres))
    assert _kernel_programs(xres) == 0
    assert _declined(xres).get("Disabled", 0) >= 1
    # reference oracle on the same query (row-at-a-time numpy engine)
    _assert_rows_equal(pres, pallas.execute_reference(sql), ordered=False)


# grouped fuzz: G randomized across the direct/span/hash slot-mode
# boundaries (6 direct, 168 span, open-domain + computed-modulus hash),
# same encodings and predicate shapes as the direct fuzz
_GROUPED_KEYS = [
    "l_returnflag, l_linestatus",                           # direct, G=6
    "l_returnflag, l_linestatus, l_shipmode, l_shipinstruct",  # span, G=168
    "l_orderkey",                                           # hash, open int
]


@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_grouped_parity_fuzz(pallas, xla, seed):
    rng = np.random.default_rng(seed)
    n_aggs = int(rng.integers(2, 5))
    aggs = [_AGGS[i] for i in rng.choice(len(_AGGS), n_aggs,
                                         replace=False)]
    qty = int(rng.integers(10, 45))
    if seed % 2:
        group = _GROUPED_KEYS[int(rng.integers(len(_GROUPED_KEYS)))]
        sql = (f"select {group}, {', '.join(aggs)} from lineitem "
               f"where l_quantity < {qty} group by {group}")
    else:
        # randomized G through a computed modulus key: always an open
        # int64 domain, so the hashed slot mode carries it
        g = int(rng.integers(65, 20_000))
        aggs = [a.replace("l_", "") for a in aggs]
        sql = (f"select gkey, {', '.join(aggs)} from "
               f"(select orderkey % {g} as gkey, quantity, "
               f"extendedprice, discount from lineitem) "
               f"where quantity < {qty} group by gkey")
    pres = pallas.execute(sql)
    xres = xla.execute(sql)
    _assert_rows_equal(pres, xres, ordered=False)
    assert _kernel_programs(pres) >= 1, (sql, _declined(pres))
    assert _kernel_programs(xres) == 0
    assert _declined(xres).get("Disabled", 0) >= 1
    _assert_rows_equal(pres, pallas.execute_reference(sql), ordered=False)


def test_dma_double_buffer_parity():
    # scan.kernel-dma = double stages block k+1's slabs into the
    # alternate VMEM buffer while block k computes: identical results,
    # plus the overlap-fraction stat (absent in single mode)
    import dataclasses
    base = ExecutionConfig(scan_kernel="pallas", batch_rows=8192)
    sql = ("select l_orderkey, sum(l_quantity), count(*) from lineitem "
           "where l_orderkey < 3000 group by l_orderkey")
    single = LocalQueryRunner("sf0.01", config=base)
    double = LocalQueryRunner("sf0.01", config=dataclasses.replace(
        base, scan_kernel_dma="double"))
    res_s = single.execute(sql)
    res_d = double.execute(sql)
    _assert_rows_equal(res_s, res_d, ordered=False)
    assert _kernel_programs(res_s) >= 1, _declined(res_s)
    assert _kernel_programs(res_d) >= 1, _declined(res_d)
    ov = (res_d.runtime_stats or {}).get("kernelDmaOverlapFraction")
    assert ov and ov["count"] >= 1
    # batch_rows=8192 splits sf0.01 lineitem into a multi-block grid:
    # every block after the first was prefetched
    assert 0.0 < ov["max"] <= 1.0
    assert "kernelDmaOverlapFraction" not in (res_s.runtime_stats or {})
    _assert_rows_equal(res_d, double.execute_reference(sql),
                       ordered=False)


def test_row_counters_match_xla_chain(pallas, xla):
    # the device-side counters feed the operator-stats spine: rows per
    # plan node (scan -> filter -> agg) must be identical across the
    # two scan implementations, not just the final result rows
    sql = "EXPLAIN ANALYZE " + Q6.strip()
    pallas.execute(sql)
    xla.execute(sql)
    prows = {nid: s.get("rows")
             for nid, s in (pallas.last_operator_stats or {}).items()}
    xrows = {nid: s.get("rows")
             for nid, s in (xla.last_operator_stats or {}).items()}
    assert prows and prows == xrows


# ---------------------------------------------------------------------------
# decline reasons: every ineligible shape is metered, never mis-run
# ---------------------------------------------------------------------------

def test_decline_disabled(xla):
    res = xla.assert_same_as_reference(Q6)
    assert _kernel_programs(res) == 0
    assert _declined(res).get("Disabled", 0) >= 1


def test_grouped_hash_kernel_engages(pallas):
    # high-cardinality open-domain group key: runs in-kernel via the
    # hashed open-addressing slot mode (kernels/grouped.py) — the shape
    # that used to decline as AggShape
    res = pallas.assert_same_as_reference(
        "select l_orderkey, count(*) from lineitem group by l_orderkey")
    assert _kernel_programs(res) >= 1, _declined(res)
    assert not _declined(res)


def test_grouped_span_kernel_engages(pallas):
    # 3*2*7*4 = 168 groups: over the direct accumulator grid (G <= 64)
    # but inside the span gate, so the combined stride code addresses
    # the accumulator stacks directly in-kernel
    res = pallas.assert_same_as_reference(
        "select l_returnflag, l_linestatus, l_shipmode, l_shipinstruct, "
        "sum(l_quantity), avg(l_discount), count(*) from lineitem "
        "group by 1, 2, 3, 4")
    assert _kernel_programs(res) >= 1, _declined(res)
    assert not _declined(res)


def test_decline_agg_function_shape(pallas):
    # moment aggregates have no in-kernel accumulator shape: the miss
    # is metered under the split vocabulary (was AggShape)
    res = pallas.execute(
        "select l_returnflag, stddev(l_quantity) from lineitem "
        "group by l_returnflag")
    assert _kernel_programs(res) == 0
    assert _declined(res).get("AggFunctionShape", 0) >= 1


def test_decline_agg_group_cardinality(monkeypatch):
    # the capacity gate declines only truly huge G: shrink the slot cap
    # so the optimizer's group estimate overflows it
    from presto_tpu.exec.kernels import grouped as gk
    monkeypatch.setattr(gk, "KERNEL_HASH_MAX_SLOTS", 16)
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        scan_kernel="pallas"))
    res = r.assert_same_as_reference(
        "select l_orderkey, count(*) from lineitem group by l_orderkey")
    assert _kernel_programs(res) == 0
    assert _declined(res).get("AggGroupCardinality", 0) >= 1


def test_join_chain_runs_in_kernel(pallas):
    # PR 16: probe-side joins lower into the kernel body
    # (kernels/join.py) instead of declining as PlanShape — the shape
    # that used to be this file's PlanShape fixture now engages
    res = pallas.assert_same_as_reference(
        "select count(*) from lineitem, orders "
        "where l_orderkey = o_orderkey")
    assert _kernel_programs(res) >= 1, _declined(res)
    assert not _declined(res)


def test_decline_plan_shape():
    # uid steps (count(distinct)-style rewrites) stay outside the
    # kernel's step vocabulary even with joins allowed
    from presto_tpu.exec.kernels.scan_kernel import chain_eligible

    class _Chain:
        steps = [("uid", None)]
        scan_meta: dict = {}
    reasons = []
    assert not chain_eligible(_Chain(), (None,), reasons.append,
                              allow_joins=True)
    assert reasons == ["PlanShape"]


def test_decline_columns_not_resident():
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        scan_kernel="pallas", storage_enabled=False))
    res = r.assert_same_as_reference(Q6)
    assert _kernel_programs(res) == 0
    assert _declined(res).get("ColumnsNotResident", 0) >= 1


def test_misaligned_chunk_tail_padded():
    # non-power-of-two chunk capacities are padded up to the pow2 block
    # (tail lanes masked dead by the [lo, hi) live window) instead of
    # declining the whole scan; no decline of any kind may fire
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        scan_kernel="pallas", batch_rows=5000))
    res = r.assert_same_as_reference(Q6)
    assert _kernel_programs(res) >= 1, _declined(res)
    assert _declined(res) == {}


def test_decline_auto_compiler_refused():
    # the chip's compiler refuses the direct family (KERNEL_FAMILY_COMPILES,
    # held to the compiler by tests/test_chip_compile.py), so auto keeps
    # the XLA chain on every backend and meters CompilerRefused -- never
    # Backend, which would be false on a TPU; explicit scan_kernel="pallas"
    # pins the kernel (the other fixtures in this file) so CI still
    # executes the real kernel body
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        scan_kernel="auto"))
    res = r.assert_same_as_reference(Q6)
    assert _kernel_programs(res) == 0
    assert _declined(res) == {"CompilerRefused": 1}


def test_decline_backend_auto_off_tpu(monkeypatch):
    # once a family compiles, auto is a performance decision: off-TPU the
    # kernel only runs in interpret-mode emulation, so auto takes the XLA
    # chain and meters Backend
    from presto_tpu.exec.kernels import KERNEL_FAMILY_COMPILES
    monkeypatch.setitem(KERNEL_FAMILY_COMPILES, "direct", True)
    r = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        scan_kernel="auto"))
    res = r.assert_same_as_reference(Q6)
    assert _kernel_programs(res) == 0
    assert _declined(res).get("Backend", 0) >= 1


@pytest.mark.parametrize("mode,families,compiles,reason", [
    ("xla", ("direct",), True, "Disabled"),
    ("auto", ("direct",), False, "CompilerRefused"),
    ("auto", ("span", "join"), False, "CompilerRefused"),
    ("auto", ("direct",), True, "Backend"),
    ("pallas", ("direct",), False, None),
    ("pallas", ("window",), True, None),
])
def test_kernel_gate(monkeypatch, mode, families, compiles, reason):
    # the one static scan.kernel decision every call site shares:
    # a join chain needs BOTH its aggregation family and the probe
    from presto_tpu.exec.kernels import KERNEL_FAMILY_COMPILES, kernel_gate
    monkeypatch.setitem(KERNEL_FAMILY_COMPILES, families[0], compiles)
    assert kernel_gate(mode, *families) == reason


def test_decline_reasons_are_closed():
    # the reason vocabulary is the EXPLAIN ANALYZE contract: keep it
    # closed
    assert set(KERNEL_DECLINE_REASONS) == {
        "Disabled", "AggFunctionShape", "AggGroupCardinality",
        "CompilerRefused", "Backend", "PlanShape", "ColumnsNotResident",
        "JoinShape", "JoinBuildSize",
        "WindowFunctionShape", "WindowKeyShape", "WindowInputSize"}


# ---------------------------------------------------------------------------
# observability on the kernel path
# ---------------------------------------------------------------------------

def test_explain_analyze_footer_reports_kernel(pallas, xla):
    text = pallas.execute("EXPLAIN ANALYZE " + Q6.strip()).rows[0][0]
    assert "Pallas scan kernels: 1" in text
    ops = pallas.last_operator_stats or {}
    scan = [s for nid, s in ops.items() if nid.startswith("scan")]
    aggs = [s for nid, s in ops.items() if nid.startswith("agg")]
    assert scan and scan[0]["rows"] > 0
    assert aggs and aggs[-1]["rows"] >= 1
    xtext = xla.execute("EXPLAIN ANALYZE " + Q6.strip()).rows[0][0]
    assert "Scan kernel declined" in xtext and "Disabled" in xtext
