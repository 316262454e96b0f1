"""Ask the chip's compiler, without the chip: the programs of the main
path lowered and compiled for a DESCRIBED TPU v5e (2x2), on the CPU-only
test host.  Nothing runs, so these cases say "the compiler accepts it and
it fits", never a result or a time.

This is the only file that describes the chip.  The topology is described
inside a module-scoped fixture -- never at import, in a skipif or in a
parametrize argument -- because only one process may load the TPU library
at a time and every xdist worker imports every test file.

Each program is CAPTURED from the engine, not rebuilt here: a query runs at
sf0.01 with batch_rows = 1 << 20 up to the first call of the named program
(a fused fori_loop program of exec/pipeline.py, or window_batch), the call
is aborted, and the program is lowered with the captured argument shapes
placed on the described device.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from presto_tpu.exec import pipeline
from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import LocalQueryRunner
from presto_tpu.serving.cache import PlanCache

from test_fused import MODULUS_KEY, ORDERKEY_COUNT, Q3_SHAPE, SPAN_4KEYS
from test_queries import TPCH_Q1, TPCH_Q6
from test_window import RUNNING_SUM

BATCH_ROWS = 1 << 20
HBM_BYTES = 16 * 10**9          # one v5e chip


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
# the scan path: the fused XLA chain programs, and window_batch
# ---------------------------------------------------------------------------

def _capture_program(monkeypatch, sql, program, **capture):
    """The jitted program `program` (the name named_jit gave it) that
    `sql` builds and the arguments of its first call; whatever the query
    launches before it (build tables, the span probe) runs for real."""
    real_jit = jax.jit

    def recording_jit(fun, *a, **k):
        jitted = real_jit(fun, *a, **k)
        if getattr(fun, "__name__", "") == program:
            return _capturing(jitted)
        return jitted

    monkeypatch.setattr(jax, "jit", recording_jit)
    # sort_batch / build_table / window_batch are jitted once a process
    # (pipeline._jits): have them jitted again, under the recorder
    for once in ("_jit_sort", "_jit_build", "_jit_window"):
        monkeypatch.setattr(pipeline, once, None)
    fn, args = _capture(sql, **capture)
    monkeypatch.undo()
    return fn, args


@pytest.mark.parametrize("sql,program,n_static,sort_budget", [
    (TPCH_Q6, "scan_agg_direct", 0, None),
    (TPCH_Q1, "scan_agg_direct", 0, None),
    (SPAN_4KEYS, "scan_agg_static_span", 0, None),
    (ORDERKEY_COUNT, "scan_agg_runtime_span", 0, None),
    (Q3_SHAPE, "scan_agg_runtime_span", 0, None),
    # over the sort budgets (the fused one's bytes and the stream's rows):
    # the scatter hash table's update (its growth: the case below this
    # function).  (scan_agg_sort itself compiles
    # too, in 89 s on this host: too long for this file)
    (MODULUS_KEY, "agg_upd", 0, 0),
    # window_batch(batch, part_names, orderings, specs): three static
    (RUNNING_SUM, "window_batch", 3, None),
], ids=["q6", "q1", "static_span", "anchored_span", "join", "hash",
        "window"])
def test_fused_xla_step_compiles_and_fits(monkeypatch, one_chip, sql,
                                          program, n_static, sort_budget):
    """The program the default config runs for each shape -- the fused
    scan -> filter -> project [-> probe] -> agg fori_loop by aggregation
    strategy at BATCH_ROWS, and the window's segmented scans at the
    capacity the query materializes -- compiles for the v5e and fits its
    16 GB, with no hand-written kernel in it."""
    config = {}
    if sort_budget is not None:
        monkeypatch.setattr(pipeline, "SORT_AGG_MAX_BYTES", sort_budget)
        monkeypatch.setattr(pipeline, "SORT_STREAM_MAX_ROWS", sort_budget)
        config["agg_slots"] = 8
    fn, args = _capture_program(monkeypatch, sql, program, **config)
    n = len(args) - n_static
    compiled = fn.lower(*_on(one_chip, args[:n]), *args[n:]).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < need < HBM_BYTES, mem


@pytest.mark.parametrize("sql", [TPCH_Q6, TPCH_Q1], ids=["q6", "q1"])
def test_served_encodings_decode_without_a_gather(monkeypatch, one_chip,
                                                  sql):
    """At the encodings the served store holds (sf0.1 at the served
    64K-row chunk is the least size that takes them: under a 1M-row pad
    every sf0.01 column is plain) no column of Q6 / Q1 is decoded by a
    per-row gather, 7 ns a row on the chip (storage/encodings.py)."""
    from presto_tpu.storage import ResidentColumn
    fn, args = _capture_program(monkeypatch, sql, "scan_agg_direct",
                                schema="sf0.1", batch_rows=1 << 16)
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


# ---------------------------------------------------------------------------
# the join path of tpch10-joins: the programs PR 32 brought
# ---------------------------------------------------------------------------

SPARSE_FILTER = ("select l_orderkey, l_partkey, l_extendedprice from lineitem "
                 "where l_shipdate = date '1996-03-13'")
def test_hash_table_growth_compiles(one_chip):
    """`hash_aggregate`'s growth step (PR 34): a checked table of 1 M
    slots rehashed into one of 4 M by its stored key hashes."""
    from presto_tpu.exec import operators as ops
    specs = (ops.AggSpec("sum", "revenue", False, None),
             ops.AggSpec("count_star", "n", False, None))
    names, dtypes = ("k", "day"), (jnp.int64, jnp.int32)
    old, new = 1 << 20, 1 << 22

    def grow(state):
        return ops.agg_merge(ops.agg_init(new, specs, names, dtypes), state,
                             specs, names, new)
    state = _on(one_chip, ops.agg_init(old, specs, names, dtypes))
    mem = jax.jit(grow).lower(state).compile().memory_analysis()
    assert 0 < mem.temp_size_in_bytes + mem.output_size_in_bytes < HBM_BYTES


FULL_JOIN = ("select l_orderkey, o_custkey from lineitem full join orders "
             "on l_orderkey = o_orderkey")
TPCH_Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10"""


@pytest.mark.parametrize("sql,program,config", [
    # a selective chain as dense batches: the count pass and the write
    (SPARSE_FILTER, "chain_dense_counts", {}),
    (SPARSE_FILTER, "chain_dense_write", {}),
    # the unfused join against a direct-address table, its row counters
    # carried on the device
    (FULL_JOIN, "join_direct", {}),
    # the sorted-hash probe step with its live count
    ("select o_orderkey, l_linenumber from orders full join lineitem "
     "on o_orderkey = l_orderkey", "join_step", {}),
    # TPC-H Q3 (PR 34): a chain cut at its join's lookup (the windowed
    # gather of `ops.gather_near` in both passes), the build columns
    # gathered for the dense rows alone, the held rows grouped by one
    # sort, the first ten picked without one
    (TPCH_Q3, "chain_dense_counts", {}),
    (TPCH_Q3, "chain_dense_write", {}),
    (TPCH_Q3, "chain_dense_finish", {}),
    (TPCH_Q3, "agg_sort", {}),
    (TPCH_Q3, "topn_first", {}),
], ids=["dense_counts", "dense_write", "join_direct", "join_step",
        "q3_dense_counts", "q3_dense_write", "q3_dense_finish",
        "q3_agg_sort", "q3_topn"])
def test_join_path_program_compiles_and_fits(monkeypatch, one_chip, sql,
                                             program, config):
    """At the served chunk (64K rows) over sf0.1's resident columns."""
    fn, args = _capture_program(monkeypatch, sql, program, schema="sf0.1",
                                batch_rows=1 << 16, **config)
    compiled = fn.lower(*_on(one_chip, args)).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < need < HBM_BYTES, mem


def test_block_local_lookup_compiles_and_fits(one_chip):
    """`ops.gather_near` as Q3's count pass runs it at SF10: 64K-row
    batches of l_orderkey against the 15M-entry direct-address table of
    orders, its way returned with the sums.  The block-local read's int8
    product lands on the matrix unit (a `convolution`)."""
    from presto_tpu.exec import operators as ops
    rows, chunks, size = 1 << 16, 4, 15_000_000

    def count(table, idx, live):
        def step(chunk):
            with ops.lookup_paths() as paths:
                found = ops.gather_near(table, *chunk)
            return jnp.sum(found, dtype=jnp.int32), paths[0]
        return jax.lax.map(step, (idx, live))
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    compiled = jax.jit(count).lower(
        shape((size,), jnp.int32), shape((chunks, rows), jnp.int32),
        shape((chunks, rows), jnp.bool_)).compile()
    assert "convolution" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < HBM_BYTES


def test_a_cached_build_side_brings_no_program_of_its_own(monkeypatch):
    """The door that remembers build sides (`PlanCompiler.shared_build`)
    adds no program family: an execution that takes its build side from
    the process-wide cache launches a subset of what the first one
    launched, and none of the build's programs (so nothing new is there
    to compile for the chip)."""
    from presto_tpu.serving import FRAGMENT_JIT_CACHE
    from presto_tpu.serving.builds import JOIN_BUILD_CACHE
    from presto_tpu.utils.runtime_stats import NamedJit
    launched = []
    real_call = NamedJit.__call__

    def recording(self, *args, **kwargs):
        launched.append(self.name)
        return real_call(self, *args, **kwargs)
    monkeypatch.setattr(NamedJit, "__call__", recording)
    FRAGMENT_JIT_CACHE.invalidate_all()
    JOIN_BUILD_CACHE.invalidate_all()
    runs = []
    for _ in range(2):
        del launched[:]
        runner = LocalQueryRunner(
            "sf0.01", plan_cache=PlanCache(),
            config=ExecutionConfig(batch_rows=1 << 14,
                                   join_out_capacity=1 << 16))
        res = runner.execute(FULL_JOIN)
        runs.append((set(launched), res.runtime_stats, len(res.rows)))
    (first, cold, n_first), (second, warm, n_second) = runs
    assert cold["joinBuildCacheMisses"]["sum"] == 1
    assert warm["joinBuildCacheHits"]["sum"] == 1
    assert n_first == n_second
    build_family = {"chain_materialize", "chain_counts", "key_stats",
                    "direct_table_build", "build_table", "max_run"}
    assert first & build_family
    assert not second & build_family
    assert second <= first, second - first


def test_stream_coalescer_programs_compile(one_chip):
    """`dense_batches`' append step, the count behind it and the window
    cut of a dense buffer, for a batch of an int64, a decimal, a date and
    a dictionary column."""
    from presto_tpu.exec.batch import Batch, Column
    cap = 1 << 16

    def batch(rows):
        def col(dtype, nulls=False, dictionary=None):
            return Column(jnp.zeros(rows, dtype),
                          jnp.zeros(rows, bool) if nulls else None,
                          dictionary)
        return Batch({"k": col(jnp.int64), "price": col(jnp.int64, True),
                      "day": col(jnp.int32),
                      "mode": col(jnp.int32, False, ("AIR", "MAIL"))},
                     jnp.zeros(rows, bool))

    carry, piece = _on(one_chip, batch(2 * cap)), _on(one_chip, batch(cap))
    fill = _on(one_chip, jnp.int32(0))
    pipeline._jit_coalesce_start.lower(piece).compile()
    text = pipeline._jit_coalesce.lower(carry, piece, fill).compile().as_text()
    # rows move by shifts and selects: no scatter, no gather
    assert " scatter(" not in text and " gather(" not in text
    pipeline._jit_count_live.lower(piece.mask).compile()
    pipeline._jit_count_live.lower(piece.mask,
                                   _on(one_chip, jnp.int64(0))).compile()
    pipeline._jit_rows_at.lower(_on(one_chip, batch(8 * cap)), fill,
                                cap).compile()
    pipeline._jit_prefix.lower(_on(one_chip, batch(4 * cap)), cap).compile()


@pytest.mark.parametrize("values_dtype", [jnp.int64, jnp.int32],
                         ids=["int64", "int32"])
def test_run_length_chunk_decode_compiles_without_a_search_a_row(
        one_chip, values_dtype):
    """l_orderkey's 15M runs at SF10: a chunk's decode is one scalar
    search, a slice of run starts, one scatter-add and a running sum --
    no `while` (the per-row binary search) and no per-row gather of the
    run table in the scan loop."""
    from presto_tpu.storage.encodings import ResidentColumn
    chunk, runs, rows = 1 << 16, 15_000_000, 60_000_000
    col = ResidentColumn(
        "rle", (jnp.zeros(runs + 1, values_dtype),
                jnp.zeros(runs + 1, jnp.int64)), rows, base=jnp.int64(0))

    def decode(col, pos):
        return col.slice_decode(pos, chunk)

    text = jax.jit(decode).lower(
        _on(one_chip, col), _on(one_chip, jnp.int64(0))).compile().as_text()
    # the one search is a loop of its own; nothing else may loop or gather
    # `chunk` rows from the 15M-entry run table
    assert text.count(" while(") <= 1
    assert f"[{chunk}]" in text
