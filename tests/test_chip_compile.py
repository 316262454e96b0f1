"""Ask the chip's compiler, without the chip: the programs of the main
path lowered and compiled for a DESCRIBED TPU v5e (2x2), on the CPU-only
test host.  Nothing runs, so these cases say "the compiler accepts it and
it fits", never a result or a time.

This is the only file that describes the chip.  The topology is described
inside a module-scoped fixture -- never at import, in a skipif or in a
parametrize argument -- because only one process may load the TPU library
at a time and every xdist worker imports every test file.

Each program is CAPTURED from the engine, not rebuilt here: a query runs at
sf0.01 with batch_rows = 1 << 20 up to the first call of the jitted launcher
(the Pallas launcher a kernels.build_* function returns, or the fused
fori_loop program of exec/pipeline.py), the call is aborted, and the
launcher is lowered with the captured argument shapes placed on the
described device.  Code that asks jax.default_backend() still sees the CPU
here, so shim.kernel_interpret is steered by the test (never by an option
of the program).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from presto_tpu.exec.kernels import (KERNEL_FAMILY_COMPILES, grouped, shim,
                                     window)
from presto_tpu.exec.kernels import scan_kernel as sk
from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner
from presto_tpu.serving.cache import PlanCache

from test_join_kernel import Q3_SHAPE
from test_queries import TPCH_Q1, TPCH_Q6
from test_window_kernel import RUNNING_SUM

BATCH_ROWS = 1 << 20
HBM_BYTES = 16 * 10**9          # one v5e chip

# one query per kernel family: the shape that makes the engine build that
# family's launcher under scan_kernel="pallas"
FAMILY_SQL = {
    "direct": TPCH_Q6,
    "span": "select l_returnflag, l_linestatus, l_shipmode, l_shipinstruct, "
            "sum(l_quantity), avg(l_discount), count(*) from lineitem "
            "group by 1, 2, 3, 4",
    "hash": "select l_orderkey, count(*) from lineitem group by l_orderkey",
    "join": Q3_SHAPE,
    "window": RUNNING_SUM,
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 -- whatever libtpu raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep it off around them
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


class _Captured(Exception):
    def __init__(self, fn, args):
        super().__init__("launcher captured")
        self.fn = fn
        self.args = args


def _capturing(fn):
    """Stand-in for a jitted launcher: aborts the query at its first call,
    BEFORE it is traced, carrying the launcher and its arguments."""
    def stop(*args):
        raise _Captured(fn, args)
    return stop


def _capture(sql, schema="sf0.01", batch_rows=BATCH_ROWS, **config):
    runner = LocalQueryRunner(
        schema, plan_cache=PlanCache(),
        config=ExecutionConfig(batch_rows=batch_rows,
                               join_out_capacity=1 << 21, **config))
    # the launcher has to be BUILT here to be captured: an earlier test of
    # this process may have left the program in the process-wide cache,
    # and the stand-in must not stay there for a later one
    from presto_tpu.serving import FRAGMENT_JIT_CACHE
    FRAGMENT_JIT_CACHE.invalidate_all()
    try:
        with pytest.raises(_Captured) as cap:
            runner.execute(sql)
    finally:
        FRAGMENT_JIT_CACHE.invalidate_all()
    return cap.value.fn, cap.value.args


def _on(sharding, args):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding), args)


# ---------------------------------------------------------------------------
# Pallas kernel families vs the static table beside KERNEL_DECLINE_REASONS
# ---------------------------------------------------------------------------

def _capture_kernel(monkeypatch, family):
    """The jitted Pallas launcher the engine builds for `family`, with the
    arguments of its first call at BATCH_ROWS."""
    real_direct, real_hash = sk.build_direct_runner, grouped.build_hash_runner
    real_window = window._build_runner

    def build_direct(*a, **k):
        r = real_direct(*a, **k)
        return r._replace(fn=_capturing(r.fn))

    def build_hash(*a, **k):
        run, names = real_hash(*a, **k)
        return _capturing(run), names

    # both the defining module and the importing module hold the name
    monkeypatch.setattr(sk, "build_direct_runner", build_direct)
    monkeypatch.setattr(grouped, "build_direct_runner", build_direct)
    monkeypatch.setattr(grouped, "build_hash_runner", build_hash)
    monkeypatch.setattr(window, "_build_runner",
                        lambda *a, **k: _capturing(real_window(*a, **k)))
    monkeypatch.setattr(window, "_RUNNER_CACHE", {})
    return _capture(FAMILY_SQL[family], scan_kernel="pallas")


@pytest.mark.parametrize("family", sorted(KERNEL_FAMILY_COMPILES))
def test_kernel_family_matches_static_table(monkeypatch, one_chip, family):
    """Every family the table accepts compiles for the v5e; every family it
    refuses still raises -- so the PR that makes one compile must flip the
    table (and `auto` starts selecting it), and a JAX upgrade that breaks
    an accepted family fails here, not on the chip."""
    assert set(FAMILY_SQL) == set(KERNEL_FAMILY_COMPILES)
    fn, args = _capture_kernel(monkeypatch, family)
    monkeypatch.setattr(shim, "kernel_interpret", lambda: False)
    lower = lambda: fn.lower(*_on(one_chip, args)).compile()  # noqa: E731
    if KERNEL_FAMILY_COMPILES[family]:
        assert "tpu_custom_call" in lower().as_text()
    else:
        with pytest.raises(Exception) as refused:
            lower()
        # the compiler's own refusal, not a capture or placement slip
        assert "mosaic" in str(refused.traceback[-1].path), refused.value


# ---------------------------------------------------------------------------
# what `auto` runs today: the fused XLA chain programs
# ---------------------------------------------------------------------------

def _capture_fused(monkeypatch, sql, **capture):
    """The jitted fused loop of `sql` (exec/pipeline.py `scan_agg_*`) and
    the arguments of its first call."""
    real_jit = jax.jit

    def recording_jit(fun, *a, **k):
        jitted = real_jit(fun, *a, **k)
        # named_jit names the fused loop by its mode: scan_agg_direct, ...
        if getattr(fun, "__name__", "").startswith("scan_agg_"):
            return _capturing(jitted)
        return jitted

    monkeypatch.setattr(jax, "jit", recording_jit)
    fn, args = _capture(sql, **capture)
    monkeypatch.undo()
    return fn, args


@pytest.mark.parametrize("sql", [TPCH_Q6, TPCH_Q1], ids=["q6", "q1"])
def test_fused_xla_step_compiles_and_fits(monkeypatch, one_chip, sql):
    """The fused scan -> filter -> project -> agg fori_loop program the
    default config runs for Q6 / Q1 compiles for the v5e at BATCH_ROWS and
    fits its 16 GB.  And at the encodings the served store holds (sf0.1 at
    the served 64K-row chunk is the least size that takes them: under a
    1M-row pad every sf0.01 column is plain) no column is decoded by a
    per-row gather, 7 ns a row on the chip (storage/encodings.py)."""
    from presto_tpu.storage import ResidentColumn
    fn, args = _capture_fused(monkeypatch, sql)
    compiled = fn.lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" not in compiled.as_text()   # no Pallas in auto
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < need < HBM_BYTES, mem

    fn, args = _capture_fused(monkeypatch, sql, schema="sf0.1",
                              batch_rows=1 << 16)
    kinds = [(c.kind, c.dtype) for c in jax.tree_util.tree_leaves(
        args, is_leaf=lambda x: isinstance(x, ResidentColumn))
        if isinstance(c, ResidentColumn)]
    # l_quantity, l_discount, ... as dictionaries; l_shipdate's ~2400 days
    # and l_extendedprice plain
    assert ("dict", jnp.int64) in kinds and ("plain", jnp.int32) in kinds
    assert " gather(" not in fn.lower(*_on(one_chip, args)).compile().as_text()


@pytest.mark.parametrize("values_dtype", [jnp.int64, jnp.int32],
                         ids=["int64", "int32"])
def test_largest_dictionary_decodes_without_a_gather(one_chip, values_dtype):
    """DICT_MAX_NDV is the compiler's threshold, not ours: a `dict` column
    of that many values, decoded chunk by chunk through `slice_decode` as
    the scan loop does, compiles for the v5e into selects.  A JAX / libtpu
    upgrade that lowers the threshold fails here, not on the chip."""
    from presto_tpu.storage.encodings import DICT_MAX_NDV, ResidentColumn
    chunk, rows = 1 << 16, 1 << 20
    col = ResidentColumn(
        "dict", (jnp.zeros(rows + chunk, jnp.int8),
                 jnp.arange(DICT_MAX_NDV, dtype=values_dtype)), rows,
        base=jnp.int64(0))

    def scan(col):
        return jax.lax.fori_loop(
            0, rows // chunk,
            lambda i, acc: acc + col.slice_decode(i * chunk, chunk).sum(),
            jnp.zeros((), jnp.int64))

    compiled = jax.jit(scan).lower(_on(one_chip, col)).compile()
    assert " gather(" not in compiled.as_text()


# ---------------------------------------------------------------------------
# the ICI shuffle on the four-chip mesh
# ---------------------------------------------------------------------------

def test_ici_exchange_compiles_on_four_chip_mesh(topo):
    """parallel/exchange.py's partitioned shuffle, one chunk of
    4 x BATCH_ROWS rows row-sharded over the described 2x2 mesh: it
    compiles, and the compiler kept the collective as an all-to-all."""
    from presto_tpu.exec.batch import Batch, Column
    from presto_tpu.parallel.exchange import make_partitioned_exchange
    from presto_tpu.parallel.mesh import make_mesh, row_sharding
    mesh = make_mesh(devices=topo.devices)
    n = mesh.devices.size
    assert n == 4
    rows = row_sharding(mesh)

    def col(dtype):
        return Column(jax.ShapeDtypeStruct((n * BATCH_ROWS,), dtype,
                                           sharding=rows), None)

    batch = Batch({"l_orderkey": col(jnp.int64), "revenue": col(jnp.int64),
                   "o_orderdate": col(jnp.int32)},
                  jax.ShapeDtypeStruct((n * BATCH_ROWS,), jnp.bool_,
                                       sharding=rows))
    shuffle = make_partitioned_exchange(mesh, ("l_orderkey",), BATCH_ROWS)
    compiled = shuffle.lower(batch).compile()
    assert "all-to-all" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
