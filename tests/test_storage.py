"""HBM-resident columnar storage (presto_tpu/storage): encoding
round-trips, zone-map construction, conservative chunk pruning, LRU
eviction under a tight budget, and end-to-end result identity vs the
numpy reference oracle with pruning active.

The correctness obligations tested here mirror the design contract:
encodings are EXACT (late decode reproduces the plain column bit-for-
bit), pruning is CONSERVATIVE (a skipped chunk provably holds no
passing row), and the storage budget degrades throughput only — a
column that cannot fit is regenerated on the fly, never
MemoryExceededError."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.common.types import (BIGINT, BOOLEAN, DATE, DOUBLE,
                                     DecimalType)
from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner
from presto_tpu.spi.expr import (VariableReferenceExpression, call, constant,
                                 special)
from presto_tpu.storage import (STORAGE_METRICS, ResidentColumn,
                                ResidentStore, build_zone_maps, encode_column,
                                entry_unsatisfiable, extract_pushdown,
                                prune_chunks)


def _padded(body, pad=64):
    body = jnp.asarray(body)
    return jnp.concatenate([body, jnp.zeros(pad, dtype=body.dtype)])


def _np(x):
    return np.asarray(jax.device_get(x))


# ---------------------------------------------------------------------------
# encoding round-trips (late decode must be exact)
# ---------------------------------------------------------------------------

def test_plain_roundtrip():
    rng = np.random.default_rng(0)
    body = rng.standard_normal(1000)
    col = encode_column(_padded(body), 1000)
    assert col.kind == "plain"
    np.testing.assert_array_equal(_np(col.decode_full())[:1000], body)


def test_dict_roundtrip_int8_codes():
    rng = np.random.default_rng(1)
    body = rng.integers(0, 11, size=1 << 14, dtype=np.int64)
    col = encode_column(_padded(body), len(body))
    assert col.kind == "dict"
    codes, values = col.arrays
    assert codes.dtype == jnp.int8          # ndv 11 <= 127
    assert int(values.shape[0]) == 11
    np.testing.assert_array_equal(_np(col.decode_full())[:len(body)], body)
    # chunk decode at an unaligned offset
    got = _np(col.slice_decode(jnp.int64(1234), 512))
    np.testing.assert_array_equal(got, body[1234:1234 + 512])
    assert col.nbytes < col.logical_nbytes


# a dictionary only where its decode is a select chain on the chip: NDV
# at most DICT_MAX_NDV, int8 codes; more distinct values stay plain in the
# logical dtype (2375 int32: l_shipdate).  (ndv, dtype, kind)
SELECTION_CASES = [
    (2, np.int64, "dict"), (64, np.int64, "dict"), (64, np.int32, "dict"),
    (65, np.int64, "plain"), (65, np.int32, "plain"),
    (127, np.int64, "plain"), (128, np.int64, "plain"),
    (2375, np.int32, "plain"), (40000, np.int64, "plain"),
]


@pytest.mark.parametrize("on_host", [False, True], ids=["device", "host"])
@pytest.mark.parametrize(
    "ndv,dtype,kind", SELECTION_CASES,
    ids=[f"ndv{n}-{np.dtype(d).name}" for n, d, _k in SELECTION_CASES])
def test_encoding_selection_by_ndv(ndv, dtype, kind, on_host):
    from presto_tpu.storage.encodings import DICT_MAX_NDV
    assert (ndv <= DICT_MAX_NDV) == (kind == "dict")
    n = 1 << 17
    rng = np.random.default_rng(ndv)
    # every value present, spread so that they are not runs
    body = (rng.permutation(n) % ndv).astype(dtype) * 3 + 8000
    arr = _padded(body)
    col = encode_column(arr, n, host=_np(arr) if on_host else None)
    assert col.kind == kind
    assert col.dtype == np.dtype(dtype)
    if kind == "dict":
        codes, values = col.arrays
        assert codes.dtype == jnp.int8
        assert int(values.shape[0]) == ndv
        assert col.nbytes < col.logical_nbytes
    else:
        assert col.arrays[0].dtype == np.dtype(dtype)   # the logical dtype
        assert col.nbytes == col.logical_nbytes
    np.testing.assert_array_equal(_np(col.decode_full())[:n], body)
    # as a mesh shard: table positions from `base`, an unaligned chunk
    col.base = jnp.int64(7_000_000)
    got = _np(col.slice_decode(jnp.int64(7_000_000 + 1234), 512))
    np.testing.assert_array_equal(got, body[1234:1234 + 512])


@pytest.mark.parametrize("ndv,dtype,kind", SELECTION_CASES[1:5],
                         ids=["ndv64-int64", "ndv64-int32",
                              "ndv65-int64", "ndv65-int32"])
def test_host_and_device_encoders_agree(ndv, dtype, kind):
    n = 1 << 14
    body = (np.random.default_rng(5).permutation(n) % ndv).astype(dtype)
    arr = _padded(body)
    dev, host = encode_column(arr, n), encode_column(arr, n, host=_np(arr))
    assert dev.kind == host.kind == kind
    for a, b in zip(dev.arrays, host.arrays):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(_np(a), _np(b))


@pytest.mark.parametrize("late_values", [False, True])
def test_dict_values_from_probe(monkeypatch, late_values):
    # the distinct set comes from the host-sorted probe when every row is
    # one of its values; a column that goes on to new values after the
    # probed rows falls back to the full-column sort -- same dictionary
    from presto_tpu.storage import encodings
    monkeypatch.setattr(encodings, "DICT_PROBE_ROWS", 1024)
    sorts = []
    unique = jnp.unique
    monkeypatch.setattr(encodings.jnp, "unique",
                        lambda a: sorts.append(a.shape) or unique(a))
    rng = np.random.default_rng(3)
    body = rng.integers(0, 11, size=1 << 13, dtype=np.int64)
    if late_values:
        body[4096:] += 11
    col = encode_column(_padded(body), len(body))
    assert col.kind == "dict"
    assert int(col.arrays[1].shape[0]) == (22 if late_values else 11)
    assert bool(sorts) == late_values
    np.testing.assert_array_equal(_np(col.decode_full())[:len(body)], body)


def test_rle_roundtrip_monotone():
    n = 1 << 14
    body = (np.arange(n, dtype=np.int64) // 64) + 1   # 256 runs of 64
    col = encode_column(_padded(body), n)
    assert col.kind == "rle"
    run_values, run_starts = col.arrays
    # 256 runs + the zero-valued sentinel run covering the tail padding
    assert int(run_starts.shape[0]) == 257
    assert int(run_starts[0]) == 0 and int(run_starts[-1]) == n
    np.testing.assert_array_equal(_np(col.decode_full())[:n], body)
    got = _np(col.slice_decode(jnp.int64(63), 130))   # spans 3 runs
    np.testing.assert_array_equal(got, body[63:63 + 130])
    assert col.nbytes < col.logical_nbytes


def test_rle_hint_lowers_the_compression_bar():
    n = 1 << 14
    body = (np.arange(n, dtype=np.int64) // 8) + 1    # 2048 runs: only ~8x
    unhinted = encode_column(_padded(body), n)
    hinted = encode_column(_padded(body), n, hint="rle")
    assert unhinted.kind != "rle"   # 8x < RLE_MIN_COMPRESSION
    assert hinted.kind == "rle"     # >= RLE_HINT_COMPRESSION
    np.testing.assert_array_equal(_np(hinted.decode_full())[:n], body)


def test_encodings_disabled_forces_plain():
    body = np.zeros(1 << 12, dtype=np.int64)   # trivially compressible
    col = encode_column(_padded(body), len(body), encodings=False)
    assert col.kind == "plain"


def test_resident_column_is_a_pytree():
    body = np.arange(1 << 12, dtype=np.int64) // 64
    col = encode_column(_padded(body), len(body))
    leaves, treedef = jax.tree_util.tree_flatten(col)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.kind == col.kind and back.n_rows == col.n_rows
    np.testing.assert_array_equal(_np(back.decode_full()),
                                  _np(col.decode_full()))


# ---------------------------------------------------------------------------
# zone maps
# ---------------------------------------------------------------------------

def test_zone_map_bounds_exact_with_ragged_tail():
    body = np.arange(100, dtype=np.int64)
    zm = build_zone_maps(_padded(body), 100, zone_rows=16)
    assert len(zm.zmin) == 7                      # ceil(100 / 16)
    np.testing.assert_array_equal(zm.zmin, np.arange(7) * 16)
    # ragged last zone covers rows 96..99 only; the identity padding
    # must not leak the zero tail into its min
    assert zm.zmax[-1] == 99 and zm.zmin[-1] == 96
    assert zm.chunk_bounds(32, 20) == (32, 63)    # zones 2..3
    assert zm.chunk_bounds(0, 100) == (0, 99)


def test_zone_map_float_identity_padding():
    body = np.full(10, -5.0)
    zm = build_zone_maps(_padded(body), 10, zone_rows=16)
    assert zm.zmin[0] == -5.0 and zm.zmax[0] == -5.0


# ---------------------------------------------------------------------------
# pruning: conservative vs a brute-force oracle
# ---------------------------------------------------------------------------

_OPS = {"eq": np.equal, "lt": np.less, "lte": np.less_equal,
        "gt": np.greater, "gte": np.greater_equal}


@pytest.mark.parametrize("layout", ["sorted", "random", "clustered"])
def test_prune_chunks_never_skips_a_passing_row(layout):
    rng = np.random.default_rng(hash(layout) % (1 << 31))
    n = 2000
    if layout == "sorted":
        vals = np.sort(rng.integers(0, 1000, size=n))
    elif layout == "clustered":
        vals = (np.arange(n) // 250) * 100 + rng.integers(0, 40, size=n)
    else:
        vals = rng.integers(0, 1000, size=n)
    zm = build_zone_maps(jnp.asarray(vals), n, zone_rows=64)
    chunks = [(p, min(128, n - p)) for p in range(0, n, 128)]
    for _ in range(40):
        k = int(rng.integers(1, 4))
        pd = [{"column": "c",
               "op": str(rng.choice(list(_OPS))),
               "value": int(rng.integers(-50, 1100))} for _ in range(k)]
        kept, skipped = prune_chunks(chunks, {"c": zm}, pd)
        assert len(kept) + skipped == len(chunks)
        assert kept                                # never empties the scan
        kept_set = set(kept)
        for pos, count in chunks:
            if (pos, count) in kept_set:
                continue
            seg = vals[pos:pos + count]
            mask = np.ones(len(seg), dtype=bool)
            for e in pd:
                mask &= _OPS[e["op"]](seg, e["value"])
            assert not mask.any(), \
                f"pruned a chunk with passing rows: {pd}"


def test_entry_unsatisfiable_edges():
    # zone holds [10, 20]
    assert entry_unsatisfiable("eq", 9, 10, 20)
    assert not entry_unsatisfiable("eq", 10, 10, 20)
    assert entry_unsatisfiable("lt", 10, 10, 20)
    assert not entry_unsatisfiable("lte", 10, 10, 20)
    assert entry_unsatisfiable("gt", 20, 10, 20)
    assert not entry_unsatisfiable("gte", 20, 10, 20)
    # all-null zone carries identity bounds (min > max): any comparison
    # is unsatisfiable, matching NULL-never-passes filter semantics
    assert entry_unsatisfiable("lte", 1 << 60, 10, -10)


# ---------------------------------------------------------------------------
# pushdown extraction: unit-safe literal handling
# ---------------------------------------------------------------------------

_V2C = {"x_0": "x", "d_1": "d", "q_2": "q"}


def test_extract_plain_comparison_and_flip():
    x = VariableReferenceExpression("x_0", BIGINT)
    lt = call("lt", BOOLEAN, x, constant(5, BIGINT))
    assert extract_pushdown(lt, _V2C) == [
        {"column": "x", "op": "lt", "value": 5}]
    flipped = call("gt", BOOLEAN, constant(5, BIGINT), x)   # 5 > x == x < 5
    assert extract_pushdown(flipped, _V2C) == [
        {"column": "x", "op": "lt", "value": 5}]


def test_extract_between_and_conjunction():
    x = VariableReferenceExpression("x_0", DOUBLE)
    bt = call("between", BOOLEAN, x, constant(1.5, DOUBLE),
              constant(2.5, DOUBLE))
    ge = call("gte", BOOLEAN, x, constant(0.0, DOUBLE))
    both = special("AND", BOOLEAN, bt, ge)
    assert extract_pushdown(both, _V2C) == [
        {"column": "x", "op": "gte", "value": 1.5},
        {"column": "x", "op": "lte", "value": 2.5},
        {"column": "x", "op": "gte", "value": 0.0}]


def test_extract_date_constant_becomes_epoch_days():
    d = VariableReferenceExpression("d_1", DATE)
    ge = call("gte", BOOLEAN, d, constant("1994-01-01", DATE))
    assert extract_pushdown(ge, _V2C) == [
        {"column": "d", "op": "gte", "value": 8766}]


def test_extract_decimal_requires_matching_scale():
    from decimal import Decimal
    q = VariableReferenceExpression("q_2", DecimalType(12, 2))
    ok = call("lt", BOOLEAN, q, constant(Decimal("24"), DecimalType(38, 2)))
    # stored columns are UNSCALED at the column's scale: 24.00 -> 2400
    assert extract_pushdown(ok, _V2C) == [
        {"column": "q", "op": "lt", "value": 2400}]
    # scale mismatch would be a silent 10x unit error: must NOT extract
    bad = call("lt", BOOLEAN, q, constant(Decimal("24"), DecimalType(38, 3)))
    assert extract_pushdown(bad, _V2C) == []
    # a raw int against an unscaled decimal column is off by 10^scale
    raw = call("lt", BOOLEAN, q, constant(24, BIGINT))
    assert extract_pushdown(raw, _V2C) == []


def test_extract_rejects_non_range_shapes():
    x = VariableReferenceExpression("x_0", BIGINT)
    y = VariableReferenceExpression("y_9", BIGINT)
    assert extract_pushdown(call("lt", BOOLEAN, x, y), _V2C) == []
    assert extract_pushdown(
        call("eq", BOOLEAN, x, constant(True, BOOLEAN)), _V2C) == []
    assert extract_pushdown(
        call("neq", BOOLEAN, x, constant(5, BIGINT)), _V2C) == []
    # unmapped variable (not a bare scan column)
    assert extract_pushdown(
        call("lt", BOOLEAN, VariableReferenceExpression("expr_3", BIGINT),
             constant(5, BIGINT)), _V2C) == []


# ---------------------------------------------------------------------------
# resident store: LRU eviction, budget rejection
# ---------------------------------------------------------------------------

def _metrics_snapshot():
    return dict(STORAGE_METRICS)


def _metric_delta(before, key):
    return STORAGE_METRICS[key] - before[key]


def test_store_lru_evicts_under_tight_budget():
    # measure the two columns' encoded sizes, then size the budget so
    # they provably cannot coexist: the second build must evict the
    # first, and re-requesting the first must rebuild it (miss, not an
    # error)
    probe = ResidentStore(budget=1 << 30, max_column_bytes=1 << 30)
    pa = probe.get_or_build("tpch", "lineitem", "quantity", 0.01,
                            10_000, 256, False)
    pb = probe.get_or_build("tpch", "lineitem", "extendedprice", 0.01,
                            10_000, 256, False)
    st = ResidentStore(budget=pa.nbytes + pb.nbytes - 1,
                       max_column_bytes=1 << 30)
    before = _metrics_snapshot()
    a = st.get_or_build("tpch", "lineitem", "quantity", 0.01,
                        10_000, 256, False)
    assert a is not None
    b = st.get_or_build("tpch", "lineitem", "extendedprice", 0.01,
                        10_000, 256, False)
    assert b is not None
    assert _metric_delta(before, "evictions") == 1
    assert len(st.entries) == 1
    a2 = st.get_or_build("tpch", "lineitem", "quantity", 0.01,
                         10_000, 256, False)
    assert a2 is not None
    assert _metric_delta(before, "cache_hits") == 0


def test_store_rejects_oversized_column_gracefully():
    st = ResidentStore(budget=1 << 20, max_column_bytes=1 << 10)
    before = _metrics_snapshot()
    ent = st.get_or_build("tpch", "lineitem", "quantity", 0.01,
                          10_000, 256, False)
    assert ent is None                       # too big to ever cache
    assert _metric_delta(before, "build_rejected") == 1
    assert not st.entries


def test_store_hit_reuses_entry():
    st = ResidentStore(budget=1 << 24, max_column_bytes=1 << 30)
    before = _metrics_snapshot()
    e1 = st.get_or_build("tpch", "lineitem", "quantity", 0.01,
                         10_000, 256, False)
    e2 = st.get_or_build("tpch", "lineitem", "quantity", 0.01,
                         10_000, 128, False)   # smaller pad: still a hit
    assert e1 is e2
    assert _metric_delta(before, "cache_hits") == 1
    # a LARGER pad must rebuild (chunk slices may not clamp)
    e3 = st.get_or_build("tpch", "lineitem", "quantity", 0.01,
                         10_000, 512, False)
    assert e3 is not e1 and e3.pad == 512


def test_store_build_counts_the_chosen_encoding():
    from presto_tpu.utils.runtime_stats import RuntimeStats
    st = ResidentStore(budget=1 << 24, max_column_bytes=1 << 30)
    stats = RuntimeStats()
    with stats.activate():
        qty = st.get_or_build("tpch", "lineitem", "quantity", 0.01,
                              10_000, 256, False)
        ship = st.get_or_build("tpch", "lineitem", "shipdate", 0.01,
                               10_000, 256, True)
        st.get_or_build("tpch", "lineitem", "quantity", 0.01,
                        10_000, 256, False)             # a hit counts none
    # 50 quantities: a select chain; ~2400 ship dates: plain int32
    assert qty.kinds == ("dict",) and ship.kinds == ("plain",)
    assert ship.column.dtype == jnp.int32
    assert stats.get("storageEncoding.dict").sum == 1
    assert stats.get("storageEncoding.plain").sum == 1
    assert stats.get("storageEncoding.rle") is None
    assert stats.get("storageBuilds").sum == 2


# ---------------------------------------------------------------------------
# end-to-end: results identical to the oracle with storage active
# ---------------------------------------------------------------------------

Q6 = """
    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01'
      and l_discount between 0.05 and 0.07 and l_quantity < 24
"""

Q1 = """
    select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
           sum(l_extendedprice) as sum_base_price,
           sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
           avg(l_quantity) as avg_qty, count(*) as count_order
    from lineitem where l_shipdate <= date '1998-09-02'
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus
"""


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner("sf0.01")


def test_q1_matches_oracle_with_resident_storage(runner):
    runner.assert_same_as_reference(Q1, ordered=True)


def test_q6_matches_oracle_with_resident_storage(runner):
    before = _metrics_snapshot()
    runner.assert_same_as_reference(Q6)
    # the date/decimal conjuncts must have reached the pruning path
    assert _metric_delta(before, "chunks_total") > 0


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_randomized_range_constants_match_oracle(runner, seed):
    rng = np.random.default_rng(seed)
    cutoff = int(rng.integers(50, 15_000))
    lo = rng.integers(0, 6) / 100.0
    hi = lo + rng.integers(1, 4) / 100.0
    sql = (f"select count(*), sum(l_quantity) from lineitem "
           f"where l_orderkey < {cutoff} "
           f"and l_discount between {lo:.2f} and {hi:.2f}")
    runner.assert_same_as_reference(sql)


def test_selective_orderkey_predicate_skips_chunks():
    # dedicated store (distinct budget => distinct registry key) with
    # fine zones so the sf0.01 table spans many zones; l_orderkey is
    # monotone (RLE-hinted), so a low cutoff makes later chunks provably
    # unsatisfiable
    cfg = ExecutionConfig(storage_budget_bytes=(6 << 30) + 4096,
                          storage_zone_rows=1 << 10)
    r = LocalQueryRunner("sf0.01", config=cfg)
    before = _metrics_snapshot()
    r.assert_same_as_reference(
        "select count(*), sum(l_extendedprice) from lineitem "
        "where l_orderkey < 150")
    assert _metric_delta(before, "chunks_skipped") > 0


def test_tiny_storage_budget_falls_back_without_error():
    # every column is larger than the whole budget: nothing caches, the
    # scan regenerates on the fly, and the query still matches the
    # oracle — MemoryExceededError must never surface from storage
    cfg = ExecutionConfig(storage_budget_bytes=1 << 12)
    r = LocalQueryRunner("sf0.01", config=cfg)
    before = _metrics_snapshot()
    r.assert_same_as_reference(Q6)
    assert _metric_delta(before, "build_rejected") > 0
    assert _metric_delta(before, "columns_built") == 0


def test_storage_disabled_still_matches_oracle():
    r = LocalQueryRunner("sf0.01",
                         config=ExecutionConfig(storage_enabled=False))
    r.assert_same_as_reference(Q6)


def test_encodings_disabled_still_matches_oracle():
    cfg = ExecutionConfig(storage_budget_bytes=(6 << 30) + 8192,
                          storage_encodings=False)
    r = LocalQueryRunner("sf0.01", config=cfg)
    r.assert_same_as_reference(Q6)
