"""Fragment plan -> executable pipelines.

The TPU analog of the reference LocalExecutionPlanner
(presto-main-base/.../sql/planner/LocalExecutionPlanner.java:363: visitTableScan
:1612, visitAggregation :1360, visitJoin :1934) plus the Driver page loop
(operator/Driver.java:303,421-451).  Differences forced by XLA:

- Linear Filter/Project chains above a leaf are FUSED into one jitted function
  per batch (XLA fuses the elementwise work into one kernel), instead of an
  operator chain passing pages.
- Aggregation is a jitted scatter-update per batch over a persistent device
  table (operators.agg_update) with host-side salt retry on slot collisions.
- Joins materialize the build side on device, then stream probe batches
  through a jitted searchsorted probe with a static output capacity; probe
  overflow splits the probe batch and retries.
- All shapes static: (capacity, agg slots, join capacity) come from the
  ExecutionConfig, and jit caching is keyed by them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common.page import Page
from ..common.types import (BIGINT, BOOLEAN, DOUBLE, DecimalType, DoubleType,
                            RealType, Type, VarcharType, CharType)
from ..connectors import catalog, tpch
from ..spi.expr import (CallExpression, ConstantExpression, RowExpression,
                        VariableReferenceExpression)
from ..spi import plan as P
from .batch import (Batch, Column, batch_to_page, page_to_batch,
                    pages_to_batches)
from . import operators as ops
from .lowering import Lowering, canonical_name, expr_has_params
from .memory import (MemoryContext, MemoryExceededError, MemoryPool,
                     PartitionedSpillStore, QueryMemoryLimitExceededError,
                     batch_bytes)
from ..utils.runtime_stats import host_get, jit_as, named_jit

DEFAULT_CAPACITY = 1 << 20
# a grouped aggregation of a stream holds up to this many rows (by its
# batches' capacities) and groups them by one sort; a longer stream goes
# through the scatter hash table (164 ms a 64K-row batch on the v5e
# against 31 ms a 256K-row sort: PERF.md, PR 34)
SORT_STREAM_MAX_ROWS = 1 << 22


def _sort_bucket(rows: int) -> int:
    """The capacity a held input is sorted at: powers of four from 4096,
    so that a sort program (43-60 s to compile on the v5e) is shared by
    every input of its size class."""
    bucket = 1 << 12
    while bucket < rows:
        bucket *= 4
    return bucket


class GrowthRefused(Exception):
    """A hash aggregation's table could not be grown within its budget."""


def hash_aggregate(state, window, num_slots, batches, update, grow,
                   reserve=None, rs=None):
    """THE loop over a scatter hash table: `batches` folded into `state`
    (a table of `num_slots` slots, to which the rows of `window` are
    still to be added) once through, the table grown IN PLACE where rows
    found no slot.  The collision flag comes back once a window of
    batches (1, 2, 4 .. 64); on a collision the last checked table is
    rehashed into one four times the size (`ops.agg_merge`: the stored
    key hashes place the groups, no input row is read again) and the
    window's batches, which are still held, are folded into that.
    Neither the source nor anything below it runs a second time.
    `update(num_slots)` and `grow(old_slots, new_slots)` give the jitted
    steps; `reserve(num_slots)` -> bool accounts for a grown table and
    may refuse it (GrowthRefused: the caller splits its input).
    Returns (state, num_slots)."""
    checked, limit = state, 1
    for b in window:
        state = update(num_slots)(state, b)

    def collided(st):
        return bool(host_get(st["__collision"], "agg_hash_collision"))

    def settle(state, checked, window, num_slots):
        checked_slots = num_slots
        while collided(state):
            num_slots *= 4
            if reserve is not None and not reserve(num_slots):
                raise GrowthRefused(num_slots)
            state = grow(checked_slots, num_slots)(checked)
            if rs is not None:
                rs.add("aggTableGrowths", 1)
            # (a rehash that itself collides leaves the flag set and the
            # table grows again before the window is folded: `checked`
            # is never a table with lost rows)
            if not collided(state):
                checked, checked_slots = state, num_slots
                for wb in window:
                    state = update(num_slots)(state, wb)
        return state, num_slots

    for b in batches:
        state = update(num_slots)(state, b)
        window.append(b)
        if len(window) >= limit:
            state, num_slots = settle(state, checked, window, num_slots)
            checked, window = state, []
            limit = min(64, limit * 2)
    return settle(state, checked, window, num_slots)


# ceiling on the materialized (keys + agg inputs) bytes for sort-based
# grouped aggregation; beyond it the scatter hash table takes over
SORT_AGG_MAX_BYTES = 6 << 30

# module-level jitted singletons: compiled once per process/shape, reused by
# every query (the compile-once/execute-many property that makes repeated
# queries cheap — the analog of the reference's reusable DriverFactories)
_jit_concat = named_jit("concat_batches",
                        lambda batches: _concat_batches(batches))
_jit_compact = named_jit("compact", ops.compact, static_argnums=1)
_jit_prefix = named_jit(
    "batch_prefix",
    lambda batch, n: jax.tree_util.tree_map(lambda a: a[:n], batch),
    static_argnums=1)
_jit_rows_at = named_jit(
    "batch_rows_at",
    lambda batch, at, n: jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, at, n), batch),
    static_argnums=2)
_jit_pad_rows = named_jit(
    "batch_pad_rows",
    lambda batch, n: jax.tree_util.tree_map(
        lambda a: jnp.concatenate(
            [a, jnp.zeros((n - a.shape[0],) + a.shape[1:], a.dtype)]), batch),
    static_argnums=1)
# live rows of a mask, on top of a running total where one is carried on
# the device (operator statistics: summed a batch, fetched once a stream)
_jit_count_live = named_jit(
    "count_live",
    lambda mask, total=0: total + jnp.sum(mask, dtype=jnp.int64))
_jit_count_dropped = named_jit(
    "count_dropped", lambda before, after, total=0: total
    + jnp.sum(before, dtype=jnp.int64) - jnp.sum(after, dtype=jnp.int64))


def _span(rs, name: str):
    """`rs.span(name)`, or nothing to enter where no RuntimeStats is."""
    return rs.span(name) if rs is not None else contextlib.nullcontext()


def _compact_concat(batches: List[Batch]) -> Batch:
    """Concatenate batches, dropping masked-out padding when it dominates.

    Operators that materialize their whole input (sort, window, join build)
    compile per merged shape; concatenating full-capacity padded batches
    after a selective filter yields huge mostly-dead arrays (e.g. 8M-row
    merges holding 80k live rows) whose sort kernels take ~50s to compile
    and dominate execution.  When under 1/4 of the merged rows are live,
    each batch is compacted (fixed per-capacity shapes, compiled once) and
    sliced to a power-of-two bucket, so downstream sorts compile at a small
    bucketed capacity shared across queries."""
    if len(batches) == 1:
        return batches[0]
    total_cap = sum(b.capacity for b in batches)
    counts = [int(c) for c in host_get(
        [b.mask.sum() for b in batches], "compact_concat_live")]
    if sum(counts) * 4 >= total_cap:
        return _jit_concat(batches)
    out = []
    for b, n in zip(batches, counts):
        if n == 0:
            continue
        bucket = _bucket_for(n) or 1 << (int(n) - 1).bit_length()
        out.append(b if bucket >= b.capacity
                   else _jit_compact(b, bucket))
    if not out:
        return batches[0]      # all rows masked: keep an all-dead batch
    if len(out) == 1:
        return out[0]
    return _jit_concat(out)
# coarse bucket set bounds the number of compiled shape variants for
# compacted batches (shared by every compaction site)
_COMPACT_BUCKETS = (1 << 12, 1 << 16, 1 << 18, 1 << 20)


def _bucket_for(live: int):
    """Smallest standard bucket holding `live` rows (None above the
    largest bucket)."""
    return next((s for s in _COMPACT_BUCKETS if s >= live), None)


def _maybe_compact(batch: Batch) -> Batch:
    """Compact a single mostly-dead batch (e.g. a sparse aggregation table)
    to a bucketed capacity so downstream sorts/joins/probes don't pay
    full-capacity costs.  One host sync for the live count."""
    live = int(host_get(batch.mask.sum(), "maybe_compact_live"))
    if live * 4 >= batch.capacity:
        return batch
    bucket = _bucket_for(live)
    if bucket is None or bucket >= batch.capacity:
        return batch
    return _jit_compact(batch, bucket)


def _coalesce_start(batch: Batch) -> Batch:
    """A carry of twice `batch`'s capacity that holds its live rows as a
    prefix: room for `capacity` live rows and, behind them, for the whole
    of any batch written there (its dead tail is overwritten by the next
    one or cut off)."""
    front = ops.compact_front(batch)
    return jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a, jnp.zeros_like(a)]), front)


def _coalesce_append(carry: Batch, batch: Batch, fill) -> Batch:
    """`carry`, its first `fill` rows live, with the live rows of `batch`
    written behind them in order (the caller knows they fit into the
    carry's first half)."""
    front = ops.compact_front(batch)
    return jax.tree_util.tree_map(
        lambda dst, src: jax.lax.dynamic_update_slice_in_dim(
            dst, src, fill, axis=0), carry, front)


_jit_coalesce_start = named_jit("coalesce_start", _coalesce_start)
_jit_coalesce = named_jit("coalesce_append", _coalesce_append)
# how many batches of a stream are pulled before their live counts come
# back in ONE host sync: short first, so that a short stream waits for
# little, then doubling, so that a long one syncs O(log n) + n/128 times
_DENSE_WINDOWS = (16, 32, 64, 128)


def dense_batches(batches, rs=None, key: str = "probeCoalesce"):
    """The same rows in the same order, in fewer batches where the stream
    is sparse: a selective filter leaves 64K-row batches with a few
    hundred live rows each, and everything downstream of it (a probe step
    at full capacity, a page fetch, a launch an operator) is paid per
    BATCH.  Chosen by what the stream shows, a window at a time: a batch
    at least half live passes through untouched (a dense stream takes no
    coalescing step and pays one count launch a batch and one sync a
    window), an empty one is dropped, the others' live rows are gathered
    on the device behind one another (`ops.compact_front` and a slice
    update: no scatter) into batches of the stream's capacity.  A stream
    of one batch is handed on as it is, unseen.
    `<key>WallNanos` is the time spent here (not in the pulls),
    `<key>dBatches` the batches folded away."""
    it = iter(batches)
    first = next(it, None)
    if first is None:
        return
    second = next(it, None)
    if second is None:
        yield first
        return
    pending = [first, second]
    # batches taken into carries or dropped empty, and carries handed on
    carry, fill, yielded, absorbed, carries = None, 0, False, 0, 0
    windows = iter(_DENSE_WINDOWS)
    width = next(windows)
    exhausted = False
    while not exhausted:
        while len(pending) < width:
            b = next(it, None)
            if b is None:
                exhausted = True
                break
            pending.append(b)
        width = next(windows, width)
        out = []
        with _span(rs, key):
            lives = host_get([_jit_count_live(b.mask) for b in pending],
                             "dense_batches_live")
            for b, k in zip(pending, lives):
                k = int(k)
                fits = carry is not None and _same_layout(carry, b) \
                    and max(fill + k, b.capacity) <= carry.capacity // 2
                if k == 0:
                    absorbed += 1
                elif fits and k * 2 < b.capacity:
                    carry = _jit_coalesce(carry, b, jnp.int32(fill))
                    fill += k
                    absorbed += 1
                else:
                    if carry is not None:
                        out.append(_jit_prefix(carry, carry.capacity // 2))
                        carry, fill, carries = None, 0, carries + 1
                    if k * 2 >= b.capacity:
                        out.append(b)
                    else:
                        carry, fill = _jit_coalesce_start(b), k
                        absorbed += 1
            pending = []
        for b in out:
            yielded = True
            yield b
    if carry is not None:
        carries += 1
        yield _jit_prefix(carry, carry.capacity // 2)
    elif not yielded:
        absorbed -= 1
        yield first             # every row dead: one all-dead batch
    if rs is not None and absorbed > carries:
        rs.add(key + "dBatches", absorbed - carries)


def _same_layout(a: Batch, b: Batch) -> bool:
    """Whether `b`'s live rows can be written into `a`: the same columns
    with the same dictionaries, null masks and dtypes."""
    if a.columns.keys() != b.columns.keys():
        return False
    for name, x in a.columns.items():
        y = b.columns[name]
        if (x.dictionary != y.dictionary or x.lazy != y.lazy
                or (x.nulls is None) != (y.nulls is None)
                or (x.lengths is None) != (y.lengths is None)
                or x.values.dtype != y.values.dtype
                or x.values.shape[1:] != y.values.shape[1:]):
            return False
    return True


_jit_sort = None
_jit_build = None
_jit_window = None


def _jits():
    global _jit_sort, _jit_build, _jit_window
    if _jit_sort is None:
        _jit_build = named_jit("build_table", ops.build_table,
                               static_argnums=(1,))
        _jit_window = named_jit("window_batch", ops.window_batch,
                                static_argnums=(1, 2, 3))
        # the guard LAST: a task thread that finds it set finds all three
        _jit_sort = named_jit("sort_batch", ops.sort_batch,
                              static_argnums=1)
    return _jit_sort, _jit_build, _jit_window


@dataclass
class ExecutionConfig:
    batch_rows: int = DEFAULT_CAPACITY      # scan page/batch capacity
    # the scatter hash table's FIRST size where a stream is aggregated
    # through one: it grows in place (hash_aggregate), so no answer
    # depends on it
    agg_slots: int = 4096
    join_out_capacity: int = 1 << 21        # probe output capacity
    splits_per_scan: int = 4
    # HBM accounting / spill (reference MemoryPool + spiller, exec/memory.py)
    memory_budget_bytes: Optional[int] = None   # None = unlimited
    spill_enabled: bool = True
    spill_partitions: int = 8
    # host-RAM ceiling for spill staging (None = unlimited); past it
    # whole buckets overflow to LZ4-compressed disk files (the second
    # spill tier) — config key spill.host-budget-bytes
    spill_budget_bytes: Optional[int] = None
    # directory for tier-2 spill files (config key spill.path); None =
    # the system temp dir.  Real deployments pin this to fast local SSD
    spill_path: Optional[str] = None
    # stage device->host spill transfers on a double-buffered background
    # thread so eviction overlaps the operator's continuing compute
    # (spillOverlapFraction meters the achieved overlap); False runs the
    # old synchronous staging.  Config key spill.async-staging
    spill_async_staging: bool = True
    # query-level memory ceiling (reference query.max-memory /
    # EXCEEDED_MEMORY_LIMIT): exceeding it is a TYPED USER error that
    # fails fast, unlike pool pressure which spill/arbitration absorb.
    # Revocable (spillable) reservations are exempt.  None = unlimited
    memory_max_query_bytes: Optional[int] = None
    # compile scan→filter/project→direct-agg chains into ONE XLA program
    # (fori_loop over split chunks): eliminates per-batch dispatch overhead
    fuse_pipelines: bool = True
    # EXPLAIN ANALYZE profiles the FUSED execution by default (chains emit
    # device-side row counters as extra jit outputs); True restores the
    # old behavior of disabling fusion so every operator streams through
    # its instrumented BatchSource (session property analyze_unfused)
    analyze_unfused: bool = False
    # compress exchange pages on the wire (SerializedPage COMPRESSED
    # marker; opt-in like the reference's exchange.compression-enabled —
    # same-host exchanges have no bandwidth to save, cross-host ones do)
    exchange_compression: bool = False
    # codec for COMPRESSED pages (reference exchange.compression-codec /
    # PagesSerdeFactory.java:69-80): LZ4 | SNAPPY | ZSTD | GZIP | ZLIB | NONE
    exchange_compression_codec: str = "LZ4"
    # grouped (lifespan) execution over connector co-bucketed tables
    # (reference Lifespan.java:30-37 / GroupedExecutionTagger /
    # session grouped_execution; exec/grouped.py): 0 = auto (engage when
    # the anchor keyspace exceeds AUTO_SPAN_THRESHOLD — the SF100-class
    # joins whose whole-table builds exceed HBM), 1 = off, N>=2 = force N
    # bucket lifespans
    grouped_lifespans: int = 0
    # lifespans staged AHEAD of the one the device is computing: bucket
    # k+1's split reads / on-the-fly column generation and host->HBM
    # transfers dispatch while bucket k's program runs (JAX async
    # dispatch keeps the device queue full).  0 = strictly serial — each
    # bucket's host work blocks on the previous bucket's consumption
    grouped_prefetch_depth: int = 1
    # distributed grouped stages: when a source stage is grouped-eligible
    # (exec/grouped.py stage_shards_lifespans), give every task the FULL
    # split set plus a disjoint round-robin subset of the bucket layout
    # (task i runs lifespans i, i+N, ...) — K lifespans spread across N
    # tasks/chips instead of replayed per task; per-bucket partial
    # aggregates merge at the FINAL stage exactly as same-task buckets do
    grouped_lifespan_sharding: bool = True
    # intra-task driver concurrency (reference task_concurrency /
    # driver-per-split, SqlTaskExecution.java:548): leaf scans drain
    # splits on this many threads through exec/local_exchange.py, and the
    # worker task overlaps pipeline drain with page serialization.  >1
    # overlaps HOST work with DEVICE dispatch; the chip itself serializes
    # kernels either way.  NOTE: a pipeline the whole-program fuser
    # accepts (fuse_pipelines=True, all-device scan chain) runs as ONE
    # XLA program with no per-batch host work to overlap — driver threads
    # apply to the STREAMING paths (host columns, windows, sorts, spills).
    # Measured on chip (round 5): a single-chip streaming group-by showed
    # no wall-clock win at 4 drivers (5.50s vs 5.56s) because the device
    # serializes kernels; the default stays 1, and >1 remains for
    # multi-core HOST work (spill IO, page serde, host-generated columns)
    task_concurrency: int = 1
    # -- fault tolerance (distributed HTTP runtime) -----------------------
    # per-lineage retry attempts for FAILED/lost remote tasks (reference
    # presto-spark ErrorClassifier retries; 0 = fail-fast streaming MPP).
    # >0 additionally makes worker output buffers RETAIN acknowledged
    # pages until task teardown, so a restarted consumer replays its
    # input from token 0 — memory-for-replayability; the durable
    # alternative is the batch scheduler's shuffle staging
    remote_task_retry_attempts: int = 2
    # how long an exchange client keeps retrying an unreachable source
    # (exponential backoff + jitter) before declaring the producer lost
    # (reference exchange.max-error-duration, Configs.h)
    exchange_max_error_duration_s: float = 60.0
    # concurrent pullers per ExchangeClient (reference
    # exchange.client-threads, ExchangeClientConfig.java): each upstream
    # location gets its own puller (capped here), so pulls + LZ4 decode
    # parallelize across producers and the consuming pipeline computes
    # while pages stream in
    exchange_client_threads: int = 4
    # bound on bytes buffered inside one ExchangeClient (reference
    # exchange.max-buffer-size): pullers park when the arrival queue holds
    # this much decoded data — producer backpressure end to end
    exchange_max_buffer_bytes: int = 32 << 20
    # target response size for the results endpoint (reference
    # exchange.max-response-size): producers coalesce small serialized
    # pages up to ~this many bytes per pull round, and the client sends it
    # as an X-Presto-Max-Size cap, so tiny-page stages stop paying a
    # request round trip per page
    exchange_max_response_bytes: int = 1 << 20
    # retry policy (reference retry-policy=QUERY|TASK, fault-tolerant
    # execution over a spooled exchange): "query" keeps the streaming
    # restart-with-ancestors behavior over retained in-memory buffers;
    # "task" spools every stage's output pages durably through
    # worker/spooling.py (host-RAM staging -> LZ4 block files under
    # spool.path/spill.path, charged revocable, retained past task
    # completion) so a failed task is retried ALONE on a surviving
    # worker with no ancestor-stage restart.  Config key retry-policy /
    # session retry_policy
    retry_policy: str = "query"
    # durable spool directory under retry-policy=task (config key
    # spool.path); None falls back to spill_path, then the system temp
    # dir.  Spool block files survive a graceful worker exit
    spool_path: Optional[str] = None
    # host-RAM ceiling for spool staging per task; past it (or under
    # memory-pool revocation) staged pages overflow to the LZ4 block
    # file.  Config key spool.staging-budget-bytes
    spool_staging_budget_bytes: int = 16 << 20
    # query wall-clock budget (reference query.max-execution-time /
    # QueryTracker.enforceTimeLimits): the coordinator mints the typed
    # non-retryable EXCEEDED_TIME_LIMIT user error when it elapses and
    # forwards each task's remaining budget via the
    # X-Presto-Task-Deadline header, which the TaskManager reaper and
    # the pipeline drain loops enforce.  0 = no deadline
    query_max_execution_time_s: float = 0.0
    # coordinator worker-loss trigger on heartbeat AGE (config key
    # failure-detector.heartbeat-timeout): a worker whose last
    # successful probe is older than this is dropped from scheduling
    # even if its transport streak has not tripped.  0 = streak-only
    failure_detector_heartbeat_timeout_s: float = 0.0
    # chaos hook: probability a task fails at start.  The roll is
    # deterministic per task id, so a retry (new attempt id) rolls
    # independently and chaos tests replay exactly
    fault_injection_probability: float = 0.0
    # plan sanity/type validation (presto_tpu/analysis, the reference
    # PlanChecker analog): "on" validates post-plan / post-optimize /
    # post-fragment; "strict" additionally validates after every
    # optimizer-rule firing; "off" disables.  Violations raise the
    # non-retryable PLAN_VALIDATION error
    plan_validation: str = "on"
    # runtime lock-order validation (common/locks.py, the dynamic half of
    # analysis/concurrency.py): task driver threads record per-thread
    # acquisition stacks, raise LockOrderError on rank inversion, and
    # meter hold/contention into /v1/metrics presto_tpu_lock_*.  Worker
    # property debug.lock-validation; session key lock_validation
    lock_validation: bool = False
    # -- HBM-resident columnar storage (presto_tpu/storage) ---------------
    # scans materialize device-generated columns once per process into an
    # encoded resident cache with zone maps; False = regenerate per chunk
    storage_enabled: bool = True
    # LRU budget for resident encoded bytes (charged to the store's
    # MemoryPool; over-budget columns fall back to on-the-fly generation)
    storage_budget_bytes: Optional[int] = 6 << 30
    # a column whose PLAIN bytes exceed this is never materialized (the
    # build transiently holds ~2x plain bytes)
    storage_max_column_bytes: int = 1 << 30
    # zone-map granularity in rows: chunk pruning aggregates the zones
    # covering each scan chunk, so finer zones prune better and cost
    # (n_rows / zone_rows) host floats per column
    storage_zone_rows: int = 1 << 16
    # dictionary/RLE encodings for resident columns; False = plain only
    storage_encodings: bool = True
    # -- exchange fabric (parallel/fabric.py) -----------------------------
    # which fabric hashed remote-exchange edges ride (reference analog:
    # a per-edge shuffle-transport choice): "auto" picks the ICI
    # all_to_all whenever producer+consumer stages can be pinned 1:1 to
    # one mesh (the scheduler CHOOSES task counts to fit), "http" forces
    # the PR 4 ExchangeClient page path, "ici" requests ICI and falls
    # back to http (with a recorded fallback) when the edge is
    # ineligible.  Config key exchange.fabric / session exchange_fabric
    exchange_fabric: str = "auto"
    # chunk granularity of the chunked ICI exchange (exchange.ici-chunk-rows):
    # each producer's rows split into fixed-size chunks whose collectives
    # dispatch back-to-back with NO host sync between them, so chunk k+1's
    # all_to_all is in flight while the consumer computes on chunk k.
    # Fixed chunk shapes also mean ONE compiled exchange program reused
    # across stages (no re-padding to a fresh per-stage global max).
    # 0 = auto-tune: the scheduler picks the next run's chunk size from
    # the observed compute/collective overlap_fraction in FabricMetrics
    # (parallel/fabric.py IciChunkTuner, multiplicative feedback)
    ici_chunk_rows: int = 0
    # -- per-query device profiler capture (telemetry/profiler.py) --------
    # session property `profile = true` wraps THIS query's execution in
    # jax.profiler.trace() writing a TensorBoard-loadable trace dir under
    # profile_dir; the path lands on QueryInfo and the EXPLAIN ANALYZE
    # footer.  Best-effort: profiler failures never fail the query.
    profile: bool = False
    # Config key telemetry.profile-dir; "" disables capture entirely
    profile_dir: str = "/tmp/presto_tpu_profiles"
    # -- adaptive query execution (exec/adaptive.py) ----------------------
    # master switch for runtime dynamic filters (config key
    # optimizer.dynamic-filtering / session dynamic_filtering): completed
    # build-side stages publish key-domain summaries that prune
    # downstream scans at the zone-map level and through a traced row
    # filter (bounds ride as jit args — no recompile on arrival);
    # False = intra-task probe-side narrowing only
    dynamic_filtering: bool = True
    # bounded wall a remote scan task waits for an expected summary
    # before proceeding unfiltered (dynamic-filtering.wait-timeout); a
    # late or lost filter costs pruning opportunity, never a deadlock
    dynamic_filtering_wait_timeout_s: float = 0.5
    # distinct-value cap for exact set summaries
    # (dynamic-filtering.max-distinct-values); past it a summary carries
    # min/max bounds only
    dynamic_filtering_max_distinct: int = 256
    # re-decide broadcast-vs-partitioned exchange (and INNER join sides)
    # at stage boundaries from OBSERVED build cardinality (config key
    # adaptive.exchange / session adaptive_exchange)
    adaptive_exchange: bool = True
    # seed task counts, agg slot sizing, and admission memory estimates
    # from matching query-history records keyed on the canonical plan
    # template (adaptive.history-sizing / session adaptive_history_sizing)
    adaptive_history_sizing: bool = False
    # observed group count from a prior run of the same plan template
    # (set by the runner's history-sizing pass, never by hand): when
    # present it REPLACES the optimizer's group estimate for aggregation
    # table sizing.  A dataclass field so the plan-cache config
    # fingerprint re-keys compiled plans on a changed hint.
    history_agg_groups: Optional[int] = None
    # -- serving plane (presto_tpu/serving) -------------------------------
    # take every jitted program from the process-wide cache
    # (serving/fragments.py) under its subtree's structural key: a
    # worker's tasks, the next query with the same text, rebuilt pooled
    # compilers and different plans sharing a scan→filter→agg subchain
    # reuse one jax.jit object (PlanCompiler.shared_jit).  Off: a fresh
    # jit per compiler.  The in-process batch scheduler's stage cache
    # keeps its node-id keys either way; a fingerprinted field, so
    # flipping it re-keys the canonical plan cache
    fragment_share: bool = True


# legal retry-policy / retry_policy values (worker/properties.py and the
# session-property validation both check against this)
RETRY_POLICY_MODES = ("query", "task")


def tuned_config(**overrides) -> "ExecutionConfig":
    """The server/runner default ExecutionConfig: 64K-row scan batches and
    256K-row join output keep HBM footprint and dispatch count balanced on
    one chip.  Single source of truth — WorkerServer, LocalQueryRunner,
    TaskManager, and the etc-dir properties loader all start from this."""
    return ExecutionConfig(batch_rows=1 << 16, join_out_capacity=1 << 18,
                           **overrides)


@dataclass
class TaskContext:
    """Execution context for one task: configuration + split assignment."""
    config: ExecutionConfig = field(default_factory=ExecutionConfig)
    # table-scan node id -> list of splits this task owns
    splits: Dict[str, List[tpch.TpchSplit]] = field(default_factory=dict)
    # remote-source node id -> iterator of host Pages (exchange input)
    remote_pages: Dict[str, Callable[[], Iterator[Tuple[Page, List[str], List[Type]]]]] = field(default_factory=dict)
    # remote-source node id -> iterator of DEVICE Batches (ICI exchange
    # input: rows arrived via all_to_all, no host round-trip); wins over
    # remote_pages when both are present
    remote_batches: Dict[str, Callable[[], Iterator["Batch"]]] = field(default_factory=dict)
    # this task's index in its stage: namespaces AssignUniqueId across tasks
    task_index: int = 0
    # the mesh's devices when this task is one of a source stage pinned
    # task i -> device i (exec/scheduler.py): its scans then read shard
    # `task_index` of the resident columns, which holds exactly the rows
    # of its splits (storage/store.py)
    mesh_devices: Optional[tuple] = None
    # per-STAGE shared jitted-program cache (scheduler-provided): the N
    # tasks of a stage compile byte-identical step closures, and Python
    # tracing is GIL-serialized — without sharing, an N-task stage pays
    # N traces on one core (measured 8x the single-task wall on the
    # 8-device dryrun).  The reference analog: tasks share the
    # coordinator-shipped plan; here they share the XLA trace.
    shared_jits: Optional[Dict] = None
    # HBM byte accounting for this task (created by PlanCompiler if absent)
    memory: Optional[MemoryPool] = None
    # EXPLAIN ANALYZE: node id -> {rows, wall_s, batches} (None = disabled)
    stats: Optional[Dict[str, dict]] = None
    # node id -> [first, last] unix seconds at which _instrument saw the
    # node hand a batch up: the real interval of its exported operator
    # span (kept beside `stats`, whose keys QueryInfo serves as they are)
    operator_times: Dict[str, List[float]] = field(default_factory=dict)
    # lifespan sharding (exec/grouped.py stage_shards_lifespans): when set
    # to (shard_index, shard_count), this task owns bucket lifespans
    # shard_index, shard_index+shard_count, ... of the grouped layout;
    # its scans hold the FULL split set, and if grouped execution fails
    # to engage at runtime only shard 0 runs the compiled fallback (the
    # aggregation gen() guard) so no rows are duplicated
    grouped_shard: Optional[Tuple[int, int]] = None
    # runner-provided RuntimeStats sink (utils/runtime_stats.py): grouped
    # execution records per-bucket generation/compute walls here
    runtime_stats: Optional[object] = None
    # serving tier (sql/canonical.py): the bound-parameter vector for this
    # execution.  `params` holds device scalars that ride parameterized
    # steps as jit arguments (so one executable serves every binding);
    # `params_fingerprint` holds the host values, appended to
    # value-sensitive result-cache keys (materialized builds) whenever the
    # cached subtree contains parameter leaves
    params: Optional[Tuple] = None
    params_fingerprint: Optional[Tuple] = None
    # runtime dynamic-filter summaries delivered by the scheduler (or
    # the worker task-update channel): filter id -> DynamicFilterSummary
    # wire dict (exec/adaptive.py).  The dict object is SHARED and
    # mutated in place on delivery; scans read it lazily at split drain
    # time, so a summary landing before a split's chunk list resolves
    # still prunes (late binding, no recompile)
    dynamic_filters: Dict[str, dict] = field(default_factory=dict)


def _var_types(variables) -> List[Type]:
    return [v.type for v in variables]


def output_schema(node: P.PlanNode) -> Tuple[List[str], List[Type]]:
    vs = node.output_variables
    return [v.name for v in vs], [v.type for v in vs]


# ---------------------------------------------------------------------------
# batch-source compilation (recursive)
# ---------------------------------------------------------------------------

class BatchSource:
    """A compiled sub-pipeline that can be iterated (possibly repeatedly)."""

    def __init__(self, fn: Callable[[], Iterator[Batch]],
                 names: List[str], types: List[Type]):
        self._fn = fn
        self.names = names
        self.types = types

    def batches(self) -> Iterator[Batch]:
        return self._fn()


class _RevocableBuildBuffer:
    """Join build-side staging whose reservation is REVOCABLE: under
    memory pressure the arbitrator converts the collected device batches
    into the partitioned host spill store (the grace-join input) via the
    registered callback instead of the query failing (reference:
    HashBuilderOperator's revocable memory + MemoryRevokingScheduler).

    Locking discipline — the two rules that keep arbitration deadlock-
    free: (1) `add` reserves BEFORE taking the buffer lock, because the
    arbitrator may pick this very holder as its victim while the
    reservation waits; (2) the revoke callback never blocks — if the
    buffer is mid-mutation it declines (returns 0) and the arbitrator
    moves to the next victim."""

    def __init__(self, compiler: "PlanCompiler", keys, spill_enabled: bool):
        self._compiler = compiler
        self._pool = compiler.ctx.memory
        self._keys = list(keys)
        self._spill_enabled = spill_enabled
        self._lock = threading.Lock()
        self._finished = False
        self.collected: List[Batch] = []
        self.spill = None
        self._reserved = 0
        self._table_bytes = 0
        self._holder = self._pool.register_revocable(
            "join-build", self._revoke)

    # -- arbitrator-facing -------------------------------------------------
    def _revoke(self) -> int:
        if not self._spill_enabled:
            return 0
        if not self._lock.acquire(blocking=False):
            return 0   # mid-mutation: decline, never block
        try:
            if self._finished or not self._reserved:
                return 0
            return self._spill_locked()
        finally:
            self._lock.release()

    def _spill_locked(self) -> int:
        freed = self._reserved
        if self.spill is None:
            self.spill = self._compiler._new_spill_store()
        for cb in self.collected:
            self.spill.add(cb, self._keys)
        self.collected = []
        if freed:
            self._holder.free(freed)
            self._reserved = 0
        return freed

    # -- build-loop-facing -------------------------------------------------
    def add(self, b: Batch) -> None:
        nb = batch_bytes(b)
        ok = self.spill is None and self._holder.try_reserve(nb)
        with self._lock:
            if ok and self.spill is None:
                self.collected.append(b)
                self._reserved += nb
                return
            if ok:
                # revoked between the reservation and the lock: the
                # batch is headed for the store, give the bytes back
                self._holder.free(nb)
            if self.spill is None:
                if not self._spill_enabled:
                    raise MemoryExceededError(
                        f"join build side exceeds memory budget "
                        f"{self._pool.budget} bytes and spill is disabled")
                self._spill_locked()
            self.spill.add(b, self._keys)

    def finish(self):
        """-> (collected, spill).  Stops revocation: past this point the
        batches feed the device hash table, which spilling the staging
        copy cannot shrink — so the bytes stop being revocable and are
        re-charged as plain user memory (covering the table until
        close()).  The re-charge is where the `query.max-memory` ceiling
        fires (typed, fail-fast; reference: revocable memory converts to
        user memory when HashBuilder finishes revoking); plain pool
        pressure at the handoff instead converts the build into a grace
        hash join spill."""
        with self._lock:
            if self._reserved and self.spill is None:
                n = self._reserved
                self._holder.free(n)
                self._reserved = 0
                if self._pool.try_reserve(n):
                    self._table_bytes = n
                elif self._spill_enabled:
                    self._spill_locked()
                else:
                    self._finished = True
                    raise MemoryExceededError(
                        f"join build table of {n} bytes exceeds memory "
                        f"budget {self._pool.budget} bytes and spill is "
                        f"disabled")
            self._finished = True
            return self.collected, self.spill

    def close(self) -> None:
        with self._lock:
            self._finished = True
            self._holder.close()   # frees whatever is still reserved
            if self._table_bytes:
                self._pool.free(self._table_bytes)
                self._table_bytes = 0
            self.collected = []
            self._reserved = 0


def _stats_delta(before: dict, after: Optional[dict]) -> Optional[dict]:
    """What one operator's statistics entry gained while a build side
    was made: counts as differences, flags and names as they stand; the
    wall is left out (a task that takes the build from the cache spent
    none)."""
    if not after:
        return None
    out = {}
    for k, v in after.items():
        if k == "wall_s":
            continue
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            v = v - before.get(k, 0)
        out[k] = v
    return out


def _stats_replay(stats: dict, node_id: str, delta: Optional[dict]) -> None:
    """`_stats_delta`'s record into this task's entry of the node."""
    if delta is None:
        return
    ent = stats.setdefault(node_id, {"rows": 0, "wall_s": 0.0, "batches": 0})
    for k, v in delta.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            ent[k] = ent.get(k, 0) + v
        else:
            ent[k] = v


def _fragment_batch_sig(batch: Batch) -> tuple:
    """Hashable digest of the first-batch column structure a step's
    expression resolution depends on (laziness, dictionary presence,
    dtypes) — part of the shared_jit cache key, so structurally equal
    subtrees whose resolution would differ never share a callable.
    Shape is deliberately EXCLUDED: jax.jit retraces per aval."""
    out = []
    for n in sorted(batch.columns):
        c = batch.columns[n]
        out.append((n, str(c.values.dtype), c.values.ndim,
                    None if c.dictionary is None else len(c.dictionary),
                    c.lazy, c.nulls is not None, c.lengths is not None))
    return tuple(out)


class PlanCompiler:
    def __init__(self, ctx: TaskContext):
        if ctx.memory is None:
            # a fresh query-level context over its own pool: the
            # query.max-memory ceiling applies even when nobody handed us
            # a worker-shared pool (LocalQueryRunner, EXPLAIN ANALYZE)
            ctx.memory = MemoryContext(
                MemoryPool(ctx.config.memory_budget_bytes), "query",
                max_bytes=ctx.config.memory_max_query_bytes)
        self.ctx = ctx
        self._sources: Dict[str, BatchSource] = {}
        self.lowering = Lowering()
        self._jit_cache: Dict = {}
        # shared_jit's memos: id(node) -> (node, named structural key),
        # and the config's fingerprint
        self._structures: Dict[int, tuple] = {}
        self._config_fp: Optional[str] = None
        # batch buffers of shared (multi-consumer) sources; cleared per
        # execution (see _share)
        self._shared_states: List[dict] = []

    def shared_jit(self, node, purpose: str, fn, extra=(), **kw):
        """`named_jit(purpose, fn)` for a program compiled from `node`,
        shared with everyone who compiles the same thing: the
        `shared_entry` whose build is the jit.  `fn` must reach nothing
        of this task: no `self`, no `self.ctx` (the cache outlives the
        task and must neither pin it nor write into it)."""
        return self.shared_entry(
            node, purpose, lambda: named_jit(purpose, fn, **kw), extra)

    def shared_entry(self, node, purpose: str, build, extra=()):
        """What `build()` makes for `node` -- a jitted program, a fused
        chain's shape probe -- shared with everyone who compiles the
        same thing.

        In the in-process batch scheduler the tasks of one stage share
        ONE entry per (node id, purpose, extra) through the stage's
        `TaskContext.shared_jits`.  Everywhere else -- a worker task (a
        new PlanCompiler every task), the single-node runner's pooled
        compilers -- the entry comes from the process-wide cache
        (serving/fragments.py) under the STRUCTURAL key `(purpose,
        subtree with node ids blanked, its real variable names, extra,
        config fingerprint)`: a second task of the stage, or the next
        query with the same text, gets the same object and traces,
        lowers and loads nothing.  The `fragment_share` knob off means a
        fresh build per call.

        `extra` must carry every host constant the entry bakes in
        beyond (subtree, names, config) -- chunk capacity, first-batch
        laziness/dictionary signature, join fanouts, the direct mode's
        G and strides, `ctx.task_index` where the program reads it, the
        operator-stats variant -- since a false share would execute the
        wrong program, while a missed share only costs one retrace."""
        cache = self.ctx.shared_jits
        if cache is not None:
            key = (node.id, purpose) + tuple(extra)
            ent = cache.get(key)
            if ent is None:
                ent = cache.setdefault(key, build())
            return ent
        cfg = self.ctx.config
        if not cfg.fragment_share:
            return build()
        from ..serving.fragments import FRAGMENT_JIT_CACHE
        if self._config_fp is None:
            from ..sql.canonical import config_fingerprint
            self._config_fp = config_fingerprint(cfg)
        structure = self._structures.get(id(node))
        if structure is None:
            # the node rides along so its id() cannot be reused
            structure = self._structures[id(node)] = \
                (node, P.named_structural_key(node))
        key = (purpose,) + structure[1] + (tuple(extra), self._config_fp)
        return FRAGMENT_JIT_CACHE.get_or_build(key, build)

    def shared_build(self, node, keys, for_join: bool, build):
        """The join build side `build()` makes for the subtree `node` (a
        `JoinBuild`, exec/fused.py): THE place a build side is
        remembered.  Where the plan says the subtree's result is fixed by
        what a key can hold (`_build_share_key`), the entry comes from the
        process-wide cache (serving/builds.py) -- a worker task's new
        PlanCompiler finds what the last task with the same subtree and
        the same splits built, launches nothing and fetches nothing --
        and the lookup counts `joinBuildCacheHits` / `...Misses` /
        `...Bytes` on this task.  Everything else is built for this
        execution alone and counts neither.  Either way the operator
        statistics of the subtree's nodes read as if it had run here."""
        names = tuple(v.name for v in node.output_variables)
        key = self._build_share_key(node, names, keys, for_join)
        if key is None:
            return build()
        from ..serving.builds import JOIN_BUILD_CACHE
        stats = self.ctx.stats
        ids = [n.id for n in P.walk_plan(node)] if stats is not None else ()

        def entry():
            before = {i: dict(stats.get(i) or ()) for i in ids}
            jb = build()
            jb.names = names
            jb.op_stats = tuple(_stats_delta(before[i], stats.get(i))
                                for i in ids)
            return jb
        jb, hit = JOIN_BUILD_CACHE.get_or_build(key, entry,
                                                self.ctx.runtime_stats)
        if hit and stats is not None:
            for i, delta in zip(ids, jb.op_stats):
                _stats_replay(stats, i, delta)
        return jb.renamed(names)

    def _build_share_key(self, node, names, keys, for_join: bool):
        """The process-wide key of `node`'s build side, or None where
        something that shapes it is in no key: decided from the plan and
        the task's context, by no knob and no table's name.  Shareable:
        every leaf a scan of a generated table (a counter-hash function
        of the row id, immutable: `device_gen`'s registry; a stored table
        can change under DDL, and what flows through a RemoteSourceNode
        is this execution's alone), no dynamic filter pruning a scan's
        chunks, no local exchange merging inputs, and no memory budget
        (budgeted runs keep the accounted streaming path and the
        revocable build buffer).  The engine lowers no non-deterministic
        function, so every expression is a function of its row."""
        cfg = self.ctx.config
        if self.ctx.memory.limited or any(k not in names for k in keys):
            return None
        from ..connectors import device_gen
        assigns_ids = False
        for n in P.walk_plan(node):
            if isinstance(n, P.TableScanNode):
                if not device_gen.generated(n.table.connector_id,
                                            n.table.table_name):
                    return None
                if cfg.dynamic_filtering \
                        and getattr(n, "runtime_filters", ()):
                    return None
            elif not n.sources:
                return None     # RemoteSource, Values: not in the key
            elif isinstance(n, P.ExchangeNode) and n.inputs:
                return None
            elif isinstance(n, P.AssignUniqueIdNode):
                assigns_ids = True
        if self._config_fp is None:
            from ..sql.canonical import config_fingerprint
            self._config_fp = config_fingerprint(cfg)
        sk = P.structural_key(node)
        # a pinned task's arrays live on its own chip
        device = jax.config.jax_default_device
        return (sk, self._splits_fingerprint(node),
                self.ctx.params_fingerprint
                if '"@type": "parameter"' in sk else None,
                self._config_fp, tuple(names.index(k) for k in keys),
                bool(for_join), self.ctx.stats is not None,
                self.ctx.task_index if assigns_ids else None,
                getattr(device, "id", device))

    def _dense(self, batches, key: str):
        """`dense_batches` into this task's RuntimeStats; under a memory
        budget the stream as it is (the window of batches it looks at is
        not reserved)."""
        if self.ctx.memory.limited:
            return batches
        return dense_batches(batches, self.ctx.runtime_stats, key)

    def _new_spill_store(self, salt: Optional[int] = None
                         ) -> PartitionedSpillStore:
        """One place wires the two-tier + async-staging spill config into
        every operator's store, so spill bytes/walls always land in this
        query's RuntimeStats and memory context."""
        cfg = self.ctx.config
        kw = {} if salt is None else {"salt": salt}
        return PartitionedSpillStore(
            cfg.spill_partitions, budget_bytes=cfg.spill_budget_bytes,
            spill_path=cfg.spill_path, stats=self.ctx.runtime_stats,
            async_staging=cfg.spill_async_staging, pool=self.ctx.memory,
            **kw)

    # -- public -----------------------------------------------------------
    def compile(self, root: P.PlanNode) -> BatchSource:
        return self._compile(root)

    def compile_root(self, root: P.PlanNode) -> BatchSource:
        """`compile` for one execution of the whole fragment: the host's
        share of a run before the first batch is pulled (the span
        `pipelineBuild` of the task and the runners)."""
        for st in self._shared_states:
            st.update(buf=[], it=None, done=False)
        return self.compile(root)

    def source_to_pages(self, src: BatchSource) -> Iterator[Page]:
        for batch in src.batches():
            page = batch_to_page(batch, src.names, src.types)
            if page.position_count:
                yield page

    def run_to_pages(self, root: P.PlanNode) -> Iterator[Page]:
        yield from self.source_to_pages(self.compile_root(root))

    # -- dispatch ---------------------------------------------------------
    def _compile(self, node: P.PlanNode) -> BatchSource:
        # memoized per node id: replayed subtrees (decorrelation deep
        # copies share ids) and re-executions reuse the same BatchSource,
        # so its cached jitted steps stay warm
        cached = self._sources.get(node.id)
        if cached is not None:
            # a second consumer of the same subtree: tee its batches so the
            # subtree executes ONCE per query (decorrelated plans replay
            # whole join chains several times — TPC-H Q2/Q21 shape; the
            # reference gets this for free from its CTE materialization)
            self._share(cached)
            return cached
        m = getattr(self, "_compile_" + type(node).__name__, None)
        if m is None:
            raise NotImplementedError(f"no compiler for {type(node).__name__}")
        src = m(node)
        if self.ctx.stats is not None:
            src = self._instrument(node, src)
        self._sources[node.id] = src
        return src

    def _share(self, src: BatchSource) -> None:
        """Convert a BatchSource into a teeing source: the first consumer's
        batches are buffered (device-resident) and replayed to later — or
        interleaved — consumers, so multi-consumer subtrees execute once."""
        if getattr(src, "_shared", False):
            return
        src._shared = True
        inner_fn = src._fn
        state = {"buf": [], "it": None, "done": False}
        self._shared_states.append(state)

        def shared_fn():
            i = 0
            while True:
                if i < len(state["buf"]):
                    yield state["buf"][i]
                    i += 1
                    continue
                if state["done"]:
                    return
                if state["it"] is None:
                    state["it"] = iter(inner_fn())
                try:
                    b = next(state["it"])
                except StopIteration:
                    state["done"] = True
                    continue
                state["buf"].append(b)
                yield b
                i += 1
        src._fn = shared_fn

    def _instrument(self, node: P.PlanNode, src: BatchSource) -> BatchSource:
        """EXPLAIN ANALYZE wrapper: cumulative wall time (includes
        children, like the reference's operator getOutput accounting),
        output row counts, and estimated output bytes per plan node."""
        stats = self.ctx.stats
        times = self.ctx.operator_times
        # 8 value bytes + 1 null byte per column: an ESTIMATE (dictionary
        # and lazy columns are cheaper on device), stable across paths so
        # fused/unfused byte counts compare
        row_bytes = 9 * max(1, len(node.output_variables))

        def gen():
            import time
            ent = stats.setdefault(
                node.id, {"rows": 0, "wall_s": 0.0, "batches": 0})
            ent.setdefault("bytes", 0)
            ent.setdefault("operatorType", type(node).__name__)
            # first pull .. last batch handed up: the operator span's
            # real interval
            now = time.time()  # lint: allow-wall-clock
            span = times.setdefault(node.id, [now, now])
            it = src.batches()
            # rows are summed on the device, one launch a batch, and
            # fetched once when the stream ends (or its consumer stops
            # pulling): no host sync a batch
            live = None
            try:
                while True:
                    t0 = time.perf_counter()  # lint: allow-wall-clock
                    try:
                        b = next(it)
                    except StopIteration:
                        ent["wall_s"] += time.perf_counter() - t0  # lint: allow-wall-clock
                        return
                    ent["wall_s"] += time.perf_counter() - t0  # lint: allow-wall-clock
                    span[1] = time.time()  # lint: allow-wall-clock
                    live = _jit_count_live(b.mask) if live is None \
                        else _jit_count_live(b.mask, live)
                    ent["batches"] += 1
                    yield b
            finally:
                if live is not None:
                    rows = int(host_get(live, "operator_stats_rows"))
                    ent["rows"] += rows
                    ent["bytes"] += rows * row_bytes
        out = BatchSource(gen, src.names, src.types)
        # the fused-chain assembler reads scan metadata off the compiled
        # source (assemble_chain); the wrapper must not hide it, or
        # ANALYZE would silently decline fusion at every scan
        meta = getattr(src, "fused_scan", None)
        if meta is not None:
            out.fused_scan = meta
        return out

    # -- leaves -----------------------------------------------------------
    # HBM-resident storage of device-generated columns lives in
    # presto_tpu/storage: generating a column is a uint64 splitmix hash
    # per row — 64-bit integer multiplies are EMULATED on the TPU vector
    # unit and dominate fused-scan wall clock — so whole-table columns
    # materialize ONCE into an encoded LRU cache with zone maps, and
    # every scan chunk becomes a slice_decode.

    def _compile_TableScanNode(self, node: P.TableScanNode) -> BatchSource:
        names = [v.name for v in node.outputs]
        types = [v.type for v in node.outputs]
        columns = [node.assignments[v].name for v in node.outputs]
        th = node.table
        sf = dict(th.extra).get("scaleFactor", 0.01)
        splits = self.ctx.splits.get(node.id)
        if splits is None:
            splits = catalog.make_splits(th.table_name, sf,
                                         self.ctx.config.splits_per_scan,
                                         th.connector_id)
        cap = self.ctx.config.batch_rows
        table = th.table_name
        cid = th.connector_id
        from ..connectors import device_gen

        # split columns into device-generated (a jitted counter-hash kernel
        # materializes them straight into HBM — no host generation, no
        # host->device transfer) and host-generated (strings, small dims)
        dev: List[Tuple[str, str, str]] = []   # (out name, column, kind)
        host: List[Tuple[str, str]] = []
        for name, colname in zip(names, columns):
            if (table, colname) in catalog.OPEN_DOMAIN:
                dev.append((name, colname, "lazy"))
            elif device_gen.supported(cid, table, colname):
                dev.append((name, colname, "gen"))
            else:
                host.append((name, colname))

        i32 = {colname: (colname.endswith("date")
                         or catalog.column_type(table, colname, cid).storage
                         == "INT_ARRAY")
               for _n, colname, kind in dev if kind == "gen"}

        # HBM-resident whole-table columns (presto_tpu/storage): the
        # decision is made at trace time, so cache eligible columns BEFORE
        # the kernels compile.  Budgeted runs keep the pure-kernel path
        # (cache residency is outside their accounting).  A column the
        # store cannot fit (tight storage budget, SF100-class size) comes
        # back None and stays on-the-fly — graceful degradation, never
        # MemoryExceededError.
        cfg = self.ctx.config
        cached_cols: Dict[str, object] = {}
        zone_maps: Dict[str, object] = {}
        if not self.ctx.memory.limited and dev and cfg.storage_enabled:
            from ..storage import get_store
            store = get_store(cfg.storage_budget_bytes,
                              cfg.storage_max_column_bytes)
            n_rows = catalog.table_row_count(table, sf, cid)
            mesh_devices = self.ctx.mesh_devices
            shard = self.ctx.task_index if mesh_devices else 0
            wanted = [colname for _name, colname, kind in dev
                      if kind == "gen"]
            # each task of a pinned stage starts at another column, so a
            # cold store builds (and compiles the generators of) as many
            # columns at once as there are tasks
            for colname in wanted[shard:] + wanted[:shard]:
                ent = store.get_or_build(
                    cid, table, colname, sf, n_rows, cap, i32[colname],
                    zone_rows=cfg.storage_zone_rows,
                    encodings=cfg.storage_encodings, devices=mesh_devices)
                if ent is None:
                    continue
                col, zones = ent.shards[shard]
                if all(zones.base <= s.start
                       and s.end <= zones.base + col.n_rows
                       for s in splits):
                    cached_cols[colname] = col
                    zone_maps[colname] = zones
        # advisory chunk-skip metadata: conjuncts the optimizer pushed
        # down (plan_scan_pushdown) — the parent FilterNode still runs,
        # so pruning only has to be conservative, not exact
        pushdown = [dict(e) for e in getattr(node, "pushdown", ())]
        # runtime dynamic filters this scan may consume
        # (plan_runtime_filter_pushdown); summaries land in
        # ctx.dynamic_filters and are read LAZILY at drain time
        runtime_filters = ([dict(e) for e in
                            getattr(node, "runtime_filters", ())]
                           if cfg.dynamic_filtering else [])

        def dyn_summaries():
            if not runtime_filters:
                return None
            return self.ctx.dynamic_filters or None

        def make_factory(cap2):
            """Pure scan kernel at an arbitrary chunk capacity (fused join
            chains shrink the chunk so in-loop fanout expansion stays within
            the configured batch footprint)."""
            def make(pos, valid, cached):
                # `cached` carries the HBM-resident whole-table columns AS
                # AN ARGUMENT pytree: closing over the arrays would embed
                # hundreds of MB as XLA literal constants and blow up
                # compilation
                idx0 = jnp.arange(cap2, dtype=jnp.int64)
                live = idx0 < valid
                idx = pos + idx0
                outs = {}
                for name, colname, kind in dev:
                    if kind == "lazy":
                        # padding must hold a valid row id (materializers
                        # run over the full capacity)
                        outs[name] = jnp.where(live, idx, 0)
                        continue
                    arr = cached.get(colname)
                    if arr is not None:
                        # ResidentColumn: encoded HBM bytes stream out,
                        # decode (dict gather / RLE searchsorted) runs in
                        # vector registers — late materialization
                        v = arr.slice_decode(pos, cap2)
                    else:
                        v = device_gen.column(cid, table, colname, sf, idx)
                        if v.dtype == jnp.int64 and i32[colname]:
                            v = v.astype(jnp.int32)
                    outs[name] = jnp.where(live, v, jnp.zeros((), v.dtype))
                return outs, live
            return make

        make = make_factory(cap)
        # the scan kernel is a pure function of (table identity incl.
        # scale factor — all inside the node's structural key — chunk
        # capacity, config); resident columns ride as an argument pytree,
        # so plans sharing this scan share one compiled program.  The
        # ACTUAL output variable names are baked into the closure but
        # canonicalized away by the structural key, so they join the key
        dev_make = self.shared_jit(node, "scan_make", make,
                                     extra=(cap, tuple(names)))

        def split_chunks(split):
            out = []
            p = split.start
            while p < split.end:
                out.append((p, min(cap, split.end - p)))
                p += cap
            if zone_maps and pushdown:
                # zone-map chunk skipping (host numpy over build-time
                # stats); the FilterNode above re-filters survivors, so
                # skipping is free of correctness burden beyond the
                # conservative unsatisfiability rules
                from ..storage import prune_chunks
                out, _skipped = prune_chunks(out, zone_maps, pushdown,
                                             self.ctx.params_fingerprint,
                                             dyn_summaries(),
                                             keep_one=False)
            return out

        # traced row-level runtime filter: summary bounds ride the jitted
        # step as SCALAR ARGUMENTS (the PR 7 parameterization idiom), so
        # one compiled program serves every bound and a summary arriving
        # between splits engages without a recompile.  Only plain integer
        # device columns qualify — dict codes and lazy row ids are not in
        # stored key units.  A dropped row is one the annotated join
        # would drop anyway (plan_runtime_filter_pushdown's guarantee).
        rf_cols = []
        if runtime_filters:
            for e in runtime_filters:
                for v, ch in node.assignments.items():
                    if ch.name == e["column"]:
                        rf_cols.append((e["id"], v.name))

        def make_rf_step(name):
            def _step(batch, lo, hi):
                c = batch.columns[name]
                keep = batch.mask & (c.values >= lo) & (c.values <= hi)
                return batch.with_mask(keep), keep.sum(), batch.mask.sum()
            return self.shared_jit(node, "rf", _step, extra=(name,))

        def apply_runtime_filters(batches):
            engaged = False
            rows_in = rows_out = None
            for b in batches:
                dyn = dyn_summaries()
                if dyn:
                    for fid, vname in rf_cols:
                        s = dyn.get(fid)
                        if not (isinstance(s, dict)
                                and isinstance(s.get("min"), int)
                                and isinstance(s.get("max"), int)):
                            continue
                        c = b.columns.get(vname)
                        if c is None or c.dictionary is not None \
                                or c.lazy is not None \
                                or not jnp.issubdtype(c.values.dtype,
                                                      jnp.integer):
                            continue
                        step = make_rf_step(vname)
                        b, kept, inn = step(b, jnp.asarray(
                            s["min"], c.values.dtype),
                            jnp.asarray(s["max"], c.values.dtype))
                        if not engaged:
                            engaged = True
                            from .adaptive import ADAPTIVE_METRICS
                            ADAPTIVE_METRICS.incr("filters_applied")
                        rows_in = inn if rows_in is None else rows_in + inn
                        rows_out = (kept if rows_out is None
                                    else rows_out + kept)
                yield b
            if engaged and rows_in is not None:
                inn, out = host_get((rows_in, rows_out),
                                    "runtime_filter_rows")
                from .adaptive import ADAPTIVE_METRICS
                ADAPTIVE_METRICS.incr("filter_rows_in", int(inn))
                ADAPTIVE_METRICS.incr("filter_rows_pruned",
                                      int(inn) - int(out))
                rs = self.ctx.runtime_stats
                if rs is not None:
                    rs.add("dynamicFilterRowsIn", int(inn))
                    rs.add("dynamicFilterRowsPruned", int(inn) - int(out))

        def split_gen(split):
                for pos, n in split_chunks(split):
                    cols = {}
                    if dev:
                        douts, dmask = dev_make(jnp.int64(pos),
                                                jnp.int64(n), cached_cols)
                        for name, colname, kind in dev:
                            if kind == "lazy":
                                cols[name] = Column(
                                    douts[name], None, None,
                                    (split.connector, table, colname,
                                     split.sf))
                            else:
                                cols[name] = Column(
                                    douts[name], None,
                                    device_gen.dictionary(cid, table,
                                                          colname))
                    for name, colname in host:
                        raw = catalog.generate_column(
                            table, colname, split.sf, pos, n,
                            split.connector)
                        nulls = None
                        if isinstance(raw, catalog.HostColumn):
                            if raw.nulls is not None:
                                nbuf = np.zeros(cap, dtype=bool)
                                nbuf[:n] = raw.nulls
                                nulls = jnp.asarray(nbuf)
                            raw = raw.values
                        if isinstance(raw, tuple):
                            codes, values = raw
                            buf = np.zeros(cap, dtype=np.int32)
                            buf[:n] = codes
                            cols[name] = Column(jnp.asarray(buf), nulls,
                                                tuple(values))
                        else:
                            if raw.dtype == np.bool_:
                                dtype = np.bool_
                            elif raw.dtype in (np.float64, np.float32):
                                dtype = np.float64
                            elif (raw.dtype == np.int32
                                  or colname.endswith("date")
                                  or catalog.column_type(
                                      table, colname,
                                      split.connector).storage
                                  == "INT_ARRAY"):
                                dtype = np.int32
                            else:
                                dtype = np.int64
                            buf = np.zeros(cap, dtype=dtype)
                            buf[:n] = raw
                            cols[name] = Column(jnp.asarray(buf), nulls)
                    if dev:
                        mask = dmask
                    else:
                        m = np.zeros(cap, dtype=bool)
                        m[:n] = True
                        mask = jnp.asarray(m)
                    yield Batch(cols, mask)

        def gen():
            tc = self.ctx.config.task_concurrency
            if tc > 1 and len(splits) > 1:
                # driver-per-split leaf parallelism (LocalExchange +
                # task_concurrency): split drains overlap host-side work;
                # driver walls land in EXPLAIN ANALYZE stats
                from .local_exchange import parallel_drain
                dstats = None
                if self.ctx.stats is not None:
                    dstats = self.ctx.stats.setdefault(
                        node.id, {"rows": 0, "wall_s": 0.0, "batches": 0})
                yield from parallel_drain(
                    [lambda s=s: split_gen(s) for s in splits], tc, dstats)
                return
            for split in splits:
                yield from split_gen(split)

        def gen_filtered():
            yield from apply_runtime_filters(gen())
        src = BatchSource(gen_filtered if rf_cols else gen, names, types)
        if not host and all(kind == "gen" for _n, _c, kind in dev):
            # whole-pipeline fusion metadata (see _fuse_scan_chain): the scan
            # is a pure jax function of (pos, valid) — an aggregation above a
            # Filter/Project chain over this scan can run as ONE compiled
            # program with a fori_loop over split chunks, eliminating the
            # per-batch dispatch round-trips that dominate wall-clock
            src.fused_scan = {
                "make": make, "make_factory": make_factory,
                "splits": splits, "cap": cap, "cached_cols": cached_cols,
                "dicts": {name: device_gen.dictionary(cid, table, colname)
                          for name, colname, _k in dev},
                # lineage metadata for grouped (lifespan) execution
                "table": table, "cid": cid, "sf": sf,
                "colmap": {name: colname for name, colname, _k in dev},
                # zone-map chunk skipping inside FusedChain.chunks_for:
                # host-side stats keyed by connector column name, matched
                # against the scan's pushed-down conjuncts
                "zone_maps": zone_maps, "pushdown": pushdown,
                # runtime dynamic-filter summaries, read lazily so fused
                # chunk pruning sees filters that arrive pre-drain
                "dyn_summaries": dyn_summaries,
            }
        return src

    def _compile_TableWriterNode(self, node: P.TableWriterNode) -> BatchSource:
        """Stream source batches into a connector write handle (reference
        TableWriterOperator.java:78): pages are staged, not visible until
        TableFinish commits.  Emits one row (rows-written, staging token)."""
        src = self._compile(node.source)
        names = [v.name for v in node.outputs]
        types = [v.type for v in node.outputs]

        def gen():
            conn = catalog.module(node.connector_id)
            # parquet fields carry the SQL-visible column names, not the
            # planner's internal variable names
            handle = conn.begin_write(node.table_name,
                                      list(node.column_names),
                                      list(src.types))
            rows = 0
            wrote = False
            try:
                for b in src.batches():
                    page = batch_to_page(b, src.names, src.types)
                    if page.position_count:
                        rows += handle.write_page(page)
                        wrote = True
                if not wrote:
                    # an empty result still defines the table's schema:
                    # stage one zero-row part so scans of the empty table
                    # see real columns (matches reference CTAS semantics)
                    from ..common.block import block_from_values
                    handle.write_page(Page(
                        [block_from_values(t, []) for t in src.types], 0))
            except BaseException:
                handle.abort()
                raise
            rv, fv = node.outputs[:2]
            cols = {rv.name: Column(jnp.asarray(np.array([rows],
                                                         dtype=np.int64))),
                    fv.name: Column(jnp.asarray(np.zeros(1, np.int32)), None,
                                    (handle.staging_id,))}
            if len(node.outputs) > 2:
                # coordinator-shaped fragments carry a third
                # tableCommitContext output (TableCommitContext.java); a
                # task-wide single-commit context is constant
                cols[node.outputs[2].name] = Column(
                    jnp.asarray(np.zeros(1, np.int32)), None,
                    ('{"lifespan":"TaskWide","pageSinkCommitStrategy":'
                     '"NO_COMMIT"}',))
            yield Batch(cols, jnp.asarray(np.array([True])))
        return BatchSource(gen, names, types)

    def _compile_TableFinishNode(self, node: P.TableFinishNode) -> BatchSource:
        """Commit every staged fragment from the writer(s) and emit the total
        row count (reference TableFinishOperator.java)."""
        src = self._compile(node.source)
        names = [v.name for v in node.outputs]
        types = [v.type for v in node.outputs]

        def gen():
            from ..common.block import block_to_values
            conn = catalog.module(node.connector_id)
            total = 0
            for b in src.batches():
                page = batch_to_page(b, src.names, src.types)
                rows = block_to_values(src.types[0], page.blocks[0])
                frags = block_to_values(src.types[1], page.blocks[1])
                for r, f in zip(rows, frags):
                    total += int(r)
                    conn.staged(f).commit()
            # the table changed under every cached program that was
            # probed against its old contents, and under every kept build
            # side: on a worker this commit is the DDL (the runner's own
            # is _invalidate_plans)
            from ..serving.builds import invalidate_compiled
            invalidate_compiled()
            cols = {node.outputs[0].name:
                    Column(jnp.asarray(np.array([total], dtype=np.int64)))}
            yield Batch(cols, jnp.asarray(np.array([True])))
        return BatchSource(gen, names, types)

    def _compile_ValuesNode(self, node: P.ValuesNode) -> BatchSource:
        names = [v.name for v in node.outputs]
        types = [v.type for v in node.outputs]
        from ..common.block import block_from_values
        from .lowering import constant_device_value

        def gen():
            n = len(node.rows)
            cap = max(n, 1)
            cols = {}
            for i, (name, typ) in enumerate(zip(names, types)):
                vals = [constant_device_value(r[i].value, typ)
                        for r in node.rows]
                blk = block_from_values(
                    typ, [None if v is None else v for v in vals]
                    if not isinstance(typ, (VarcharType, CharType))
                    else [None if v is None else str(v) for v in vals])
                from .batch import block_to_column
                cols[name] = block_to_column(typ, blk, cap)
            mask = np.zeros(cap, dtype=bool)
            mask[:n] = True
            yield Batch(cols, jnp.asarray(mask))
        return BatchSource(gen, names, types)

    def _compile_RemoteSourceNode(self, node: P.RemoteSourceNode) -> BatchSource:
        names = [v.name for v in node.outputs]
        types = [v.type for v in node.outputs]
        cap = self.ctx.config.batch_rows
        ctx = self.ctx

        def gen():
            dev = ctx.remote_batches.get(node.id)
            if dev is not None:
                # ICI path: batches arrive device-resident from the
                # all_to_all exchange (parallel/exchange.py)
                yield from dev()
                return
            # HTTP/host path: string columns are materialized + remapped
            # to a union dictionary (producer tasks ship independent
            # dictionaries; jitted consumers need one per column)
            yield from pages_to_batches(ctx.remote_pages[node.id](),
                                        names, types, cap)
        return BatchSource(gen, names, types)

    # -- streaming transforms --------------------------------------------
    def _compile_FilterNode(self, node: P.FilterNode) -> BatchSource:
        src = self._compile(node.source)
        low = self.lowering
        hoister = _StringHoister([node.predicate])
        cache: dict = {}  # resolution is laziness-dependent only: jit once

        def gen():
            from .fused import fused_dense_stream
            dense = fused_dense_stream(self, node)
            if dense is not None:
                yield from dense
                return
            it = iter(src.batches())
            first = next(it, None)
            if first is None:
                return
            if "step" not in cache:
                (pred,), hoisted = hoister.resolve(first)
                sig = _fragment_batch_sig(first)
                if expr_has_params(pred):
                    # bound parameters ride as an explicit jit argument so
                    # the trace is reused across constant bindings
                    def pstep(batch, params, _pred=pred):
                        return ops.apply_filter(
                            batch, low.eval(_pred, batch.with_params(params)))
                    jitted = self.shared_jit(node, "filter_p", pstep,
                                             extra=(sig,))
                    cache["step"] = \
                        lambda b, _j=jitted: _j(b, self.ctx.params)
                else:
                    def step(batch, _pred=pred):
                        return ops.apply_filter(batch, low.eval(_pred, batch))
                    cache["step"] = self.shared_jit(node, "filter", step,
                                                    extra=(sig,))
                cache["hoisted"] = hoisted
            step, hoisted = cache["step"], cache["hoisted"]
            for b in itertools.chain([first], it):
                yield step(_add_hoisted(b, hoisted))
        return BatchSource(gen, src.names, src.types)

    def _compile_ProjectNode(self, node: P.ProjectNode) -> BatchSource:
        src = self._compile(node.source)
        names = [v.name for v in node.assignments]
        types = [v.type for v in node.assignments]
        items = list(node.assignments.items())
        low = self.lowering
        hoister = _StringHoister([e for _, e in items])
        cache: dict = {}

        def gen():
            it = iter(src.batches())
            first = next(it, None)
            if first is None:
                return
            if "step" not in cache:
                exprs, hoisted = hoister.resolve(first)
                sig = _fragment_batch_sig(first)
                if any(expr_has_params(e) for e in exprs):
                    def pstep(batch, params, _exprs=exprs):
                        pb = batch.with_params(params)
                        cols = {v.name: low.eval(e, pb)
                                for (v, _), e in zip(items, _exprs)}
                        return Batch(cols, batch.mask)
                    jitted = self.shared_jit(node, "project_p", pstep,
                                             extra=(sig, tuple(names)))
                    cache["step"] = \
                        lambda b, _j=jitted: _j(b, self.ctx.params)
                else:
                    def step(batch, _exprs=exprs):
                        cols = {v.name: low.eval(e, batch)
                                for (v, _), e in zip(items, _exprs)}
                        return Batch(cols, batch.mask)
                    cache["step"] = self.shared_jit(
                        node, "project", step, extra=(sig, tuple(names)))
                cache["hoisted"] = hoisted
            step, hoisted = cache["step"], cache["hoisted"]
            for b in itertools.chain([first], it):
                yield step(_add_hoisted(b, hoisted))
        return BatchSource(gen, names, types)

    def _compile_OutputNode(self, node: P.OutputNode) -> BatchSource:
        src = self._compile(node.source)
        # OutputNode renames columns positionally
        inner = [v.name for v in node.source.output_variables]
        outer = [v.name for v in node.outputs]
        types = [v.type for v in node.outputs]
        if inner == outer:
            return BatchSource(src.batches, outer, types)

        def gen():
            for b in src.batches():
                cols = {o: b.columns[i] for i, o in zip(inner, outer)}
                yield Batch(cols, b.mask)
        return BatchSource(gen, outer, types)

    def _compile_UnnestNode(self, node: P.UnnestNode) -> BatchSource:
        """One output row per array element; source columns replicated
        (reference UnnestOperator.java).  With the fixed-width (cap, W)
        array layout this is the same shape transform as the fused join
        fanout expansion: output capacity = cap * W, slot i*W + j = (source
        row i, element j); multiple arrays zip by position, shorter ones
        null-padded (SQL UNNEST semantics)."""
        src = self._compile(node.source)
        names = [v.name for v in node.output_variables]
        types = [v.type for v in node.output_variables]
        rep_names = [v.name for v in node.replicate_variables]
        pairs = [(av.name, elems[0].name)
                 for av, elems in node.unnest_variables]
        ord_name = (None if node.ordinality_variable is None
                    else node.ordinality_variable.name)

        def step(batch):
            cap = batch.capacity
            arrs = {an: batch.columns[an] for an, _en in pairs}
            W = max([a.values.shape[1] for a in arrs.values()] + [1])
            # rows per source row = max of the zipped arrays' lengths
            rowlen = None
            for a in arrs.values():
                ln = jnp.where(a.null_mask(), 0, a.lengths)
                rowlen = ln if rowlen is None else jnp.maximum(rowlen, ln)
            j = jnp.arange(W, dtype=jnp.int32)
            cols = {}
            for rn in rep_names:
                c = batch.columns[rn]
                if c.lengths is not None:
                    vals = jnp.repeat(c.values, W, axis=0)
                else:
                    vals = jnp.repeat(c.values, W)
                cols[rn] = Column(
                    vals,
                    None if c.nulls is None else jnp.repeat(c.nulls, W),
                    c.dictionary, c.lazy,
                    None if c.lengths is None
                    else jnp.repeat(c.lengths, W))
            for an, en in pairs:
                a = arrs[an]
                aw = a.values.shape[1]
                padded = (a.values if aw == W else jnp.pad(
                    a.values, ((0, 0), (0, W - aw))))
                vals = padded.reshape(cap * W)
                ln = jnp.where(a.null_mask(), 0, a.lengths)
                valid = (j[None, :] < ln[:, None]).reshape(cap * W)
                cols[en] = Column(vals, ~valid)
            if ord_name is not None:
                cols[ord_name] = Column(
                    jnp.tile(j.astype(jnp.int64) + 1, cap))
            mask = (batch.mask[:, None]
                    & (j[None, :] < rowlen[:, None])).reshape(cap * W)
            return Batch(cols, mask)

        step = self.shared_jit(node, "unnest", step)

        def gen():
            for b in src.batches():
                out = step(b)
                yield out.select(names)
        return BatchSource(gen, names, types)

    # -- limit / topn / sort ---------------------------------------------
    def _compile_LimitNode(self, node: P.LimitNode) -> BatchSource:
        src = self._compile(node.source)
        n = node.count

        step = self.shared_jit(
            node, "limit",
            lambda batch, consumed: ops.limit(batch, n, consumed))

        def gen():
            consumed = jnp.zeros((), dtype=jnp.int64)
            for b in src.batches():
                out, consumed = step(b, consumed)
                yield out
                if int(consumed) >= n:
                    break
        return BatchSource(gen, src.names, src.types)

    def _compile_TopNNode(self, node: P.TopNNode) -> BatchSource:
        src = self._compile(node.source)
        keys = [(v.name, order) for v, order in node.ordering_scheme.orderings]
        n = node.count

        def _step(buffer, batch):
            merged = _concat_batches([buffer, batch])
            return ops.topn(merged, keys, n)

        step = self.shared_jit(node, "topn_step", _step)
        first = self.shared_jit(node, "topn_first",
                                lambda batch: ops.topn(batch, keys, n))

        rs = self.ctx.runtime_stats

        def gen():
            key_names = [k for k, _o in keys]
            buf, rows = None, jnp.zeros((), dtype=jnp.int64)
            with _span(rs, "topN"):
                for b in src.batches():
                    if rs is not None:
                        rows = _jit_count_live(b.mask, rows)
                    b = _encode_unordered_lazy_keys(b, key_names)
                    buf = first(b) if buf is None else step(buf, b)
            if rs is not None:
                # (rows in, summed on the device: fetched once a stream)
                rs.add("topNRowsIn", int(host_get(rows, "topn_rows_in")))
            if buf is not None:
                yield buf
        return BatchSource(gen, src.names, src.types)

    def _compile_SortNode(self, node: P.SortNode) -> BatchSource:
        names, types = output_schema(node.source)
        keys = [(v.name, order) for v, order in node.ordering_scheme.orderings]

        def gen():
            merged = self._materialize_node(node.source)
            if merged is None:
                return
            merged = _encode_unordered_lazy_keys(
                merged, [k for k, _o in keys])
            yield _jits()[0](merged, tuple(keys))
        return BatchSource(gen, names, types)

    def _compile_UnionNode(self, node: P.UnionNode) -> BatchSource:
        """UNION ALL: concatenate the source streams.  Numeric/date columns
        stream straight through; string columns must first be re-encoded to
        one shared dictionary (downstream operators assume a batch-stable
        dictionary per column), which makes union a materialization point
        only when strings are involved."""
        srcs = [self._compile(s) for s in node.inputs]
        out_names = [v.name for v in node.outputs]
        out_types = [v.type for v in node.outputs]
        string_cols = [n for n, t in zip(out_names, out_types)
                       if isinstance(t, (VarcharType, CharType))]

        def gen():
            if not string_cols:
                for s in srcs:
                    yield from s.batches()
                return
            all_b = [b for s in srcs for b in s.batches()]
            if not all_b:
                return
            merged_dicts: Dict[str, list] = {n: [] for n in string_cols}
            index: Dict[str, dict] = {n: {} for n in string_cols}
            recoded = []
            for b in all_b:
                new_cols = {}
                for n in string_cols:
                    col = b.columns[n]
                    md, idx = merged_dicts[n], index[n]
                    if col.dictionary is not None:
                        lut = np.empty(len(col.dictionary), dtype=np.int64)
                        for i, sv in enumerate(col.dictionary):
                            if sv not in idx:
                                idx[sv] = len(md)
                                md.append(sv)
                            lut[i] = idx[sv]
                        newv = lut[np.asarray(col.values)]
                    elif col.lazy is not None:
                        cid, tbl, coln, sf = col.lazy
                        strings = catalog.generate_values_at(
                            tbl, coln, sf, np.asarray(col.values), cid)
                        newv = np.empty(len(strings), dtype=np.int64)
                        for i, sv in enumerate(strings):
                            if sv not in idx:
                                idx[sv] = len(md)
                                md.append(sv)
                            newv[i] = idx[sv]
                    else:
                        raise NotImplementedError(
                            f"varchar column {n} without dictionary")
                    new_cols[n] = Column(jnp.asarray(newv), col.nulls, None)
                recoded.append(b.with_columns(new_cols))
            final = []
            for b in recoded:
                cols = {n: (Column(c.values, c.nulls,
                                   tuple(merged_dicts[n]))
                            if n in string_cols else c)
                        for n, c in b.columns.items()}
                final.append(Batch(cols, b.mask))
            yield final[0] if len(final) == 1 \
                else _jit_concat(final)
        return BatchSource(gen, out_names, out_types)

    def _compile_WindowNode(self, node: P.WindowNode) -> BatchSource:
        """Materialize + one jitted segmented-scan pass (operators.window_batch);
        the reference streams partition-at-a-time (WindowOperator.java:69) but
        a single static-shape sort+scan is the XLA-friendly formulation."""
        src_names, src_types = output_schema(node.source)
        part_names = tuple(v.name for v in node.partition_by)
        orderings = tuple((v.name, o) for v, o in
                          node.ordering_scheme.orderings) \
            if node.ordering_scheme else ()
        from .lowering import constant_device_value
        specs = []
        for v, wf in node.window_functions.items():
            fname = canonical_name(wf.call.display_name)
            args = wf.call.arguments
            arg = None
            extra = ()
            if fname == "count" and not args:
                fname = "count_star"
            elif fname == "ntile":
                extra = (int(args[0].value),)
            elif args:
                arg = args[0].name
                consts = []
                for a in args[1:]:
                    consts.append(constant_device_value(a.value, a.type))
                extra = tuple(consts)
            frame = None
            if wf.frame:
                f = wf.frame
                frame = (f["type"], f["startKind"], f["startOffset"],
                         f["endKind"], f["endOffset"])
            is_float = isinstance(v.type, (DoubleType, RealType))
            specs.append(ops.WindowSpec(fname, v.name, arg, is_float,
                                        frame, extra))
        specs = tuple(specs)
        out_names = src_names + [v.name for v in node.window_functions]
        out_types = src_types + [v.type for v in node.window_functions]

        def gen():
            merged = self._materialize_node(node.source)
            if merged is None:
                return
            # late-materialized string keys: window_batch both SORTS by and
            # compares (partition identity / peer detection) every key, so a
            # lazy column's row ids must match the value order AND be
            # distinct per value; otherwise encode to whole-column
            # dictionaries on the host
            encode = []
            minmax_args = {s.arg for s in specs
                           if s.name in ("min", "max") and s.arg}
            key_cols = set(part_names) | {k for k, _ in orderings}
            for k in sorted(key_cols | minmax_args):
                col = merged.columns[k]
                if col.lazy is None:
                    continue
                _, tbl, coln, _sf = col.lazy
                # keys need row ids that sort like values AND are distinct
                # per value; min/max args only need the sort property
                ok = (tbl, coln) in catalog.ROWID_ORDERED and (
                    k not in key_cols
                    or (tbl, coln) in catalog.ROWID_DISTINCT)
                if not ok:
                    encode.append(k)
            if encode:
                merged = _encode_lazy_keys(merged, encode)
            yield _jits()[2](merged, part_names, orderings, specs)
        return BatchSource(gen, out_names, out_types)

    def _compile_DistinctLimitNode(self, node: P.DistinctLimitNode) -> BatchSource:
        agg = P.AggregationNode(node.id + ".agg", node.source, {},
                                node.distinct_variables, P.SINGLE)
        lim = P.LimitNode(node.id + ".limit", agg, node.count)
        return self._compile(lim)

    def _compile_GroupIdNode(self, node: P.GroupIdNode) -> BatchSource:
        """Grouping-set expansion (reference GroupIdOperator.java): lower
        to one ProjectNode per grouping set over the shared source (the
        compiler memoizes by node id, so the source executes once and its
        batches are teed), unioned.  The downstream aggregation groups by
        (grouping columns..., group_id), exactly the reference pairing."""
        from ..spi.expr import constant
        branches = []
        for i, gset in enumerate(node.grouping_sets):
            in_set = {v.name for v in gset}
            assigns = {}
            for out_v, in_v in node.grouping_columns.items():
                assigns[out_v] = (in_v if out_v.name in in_set
                                  else constant(None, out_v.type))
            for v in node.aggregation_arguments:
                assigns[v] = v
            assigns[node.group_id_variable] = \
                constant(i, node.group_id_variable.type)
            branches.append(P.ProjectNode(f"{node.id}.gid{i}", node.source,
                                          assigns))
        union = P.UnionNode(node.id + ".union", branches,
                            list(node.output_variables))
        return self._compile(union)

    def _compile_MarkDistinctNode(self, node: P.MarkDistinctNode) -> BatchSource:
        """Marker = first row of its distinct-key group (reference
        MarkDistinctOperator/MarkDistinctHash): row_number() partitioned by
        the distinct keys, marker = (rn == 1)."""
        from ..spi.expr import call, constant
        rn = VariableReferenceExpression(f"{node.marker.name}__rn", BIGINT)
        win = P.WindowNode(
            node.id + ".rn", node.source, list(node.distinct_variables),
            None, {rn: P.WindowFunction(
                CallExpression("row_number", BIGINT, []), None)})
        assigns = {v: v for v in node.source.output_variables}
        assigns[node.marker] = call("eq", BOOLEAN, rn, constant(1, BIGINT))
        proj = P.ProjectNode(node.id + ".mark", win, assigns)
        return self._compile(proj)

    # -- aggregation ------------------------------------------------------
    def _compile_AggregationNode(self, node: P.AggregationNode) -> BatchSource:
        node = _rewrite_agg_masks(node)
        src_node = node.source
        key_vars = node.grouping_keys
        key_names = tuple(v.name for v in key_vars)
        out_names = [v.name for v in key_vars] + [v.name for v in node.aggregations]
        out_types = ([v.type for v in key_vars]
                     + [v.type for v in node.aggregations])
        low = self.lowering

        specs = []
        input_exprs: Dict[str, Optional[RowExpression]] = {}
        input_exprs2: Dict[str, RowExpression] = {}
        for v, agg in node.aggregations.items():
            fname = canonical_name(agg.call.display_name)
            args = agg.call.arguments
            if fname == "count" and not args:
                fname = "count_star"
            is_float = isinstance(v.type, (DoubleType, RealType)) or (
                fname == "avg" and isinstance(v.type, (DoubleType,
                                                       RealType)))
            param = None
            if fname == "approx_percentile" and len(args) > 1:
                param = float(args[1].value)
                is_float = isinstance(args[0].type, (DoubleType, RealType))
            if fname in ops.HLL_AGGS:
                # optional max standard error -> register count (reference
                # approx_distinct(x, e), ApproximateCountDistinct
                # Aggregations.java)
                param = (ops.hll_buckets_for_error(float(args[1].value))
                         if len(args) > 1 else ops.HLL_DEFAULT_BUCKETS)

            if fname in ops.CORR_AGGS and len(args) > 1:
                input_exprs2[v.name] = args[1]
            specs.append(ops.AggSpec(fname, v.name, is_float, param))
            input_exprs[v.name] = args[0] if args else None
        specs = tuple(specs)
        basic_specs = all(s.name in ops.BASIC_AGGS for s in specs)
        sort_only_specs = any(s.name in ops.SORT_ONLY_AGGS for s in specs)

        cfg = self.ctx.config

        update_cache: Dict[Tuple, Callable] = {}

        def make_direct_update(G: int, strides: Tuple[int, ...]):
            fn = update_cache.get(("direct", G, strides))
            if fn is None:
                def fn(state, batch):
                    codes = None
                    for k, stride in zip(key_names, strides):
                        c = batch.columns[k].values.astype(jnp.int64)
                        codes = c * stride if codes is None \
                            else codes + c * stride
                    if codes is None:    # global aggregation: one group
                        codes = jnp.zeros(batch.capacity, dtype=jnp.int64)
                    agg_cols = {}
                    for out, expr in input_exprs.items():
                        agg_cols[out] = (low.eval(expr, batch)
                                         if expr is not None else None)
                    return ops.agg_direct_update(state, batch, codes,
                                                 agg_cols, specs, G)
                fn = self.shared_jit(node, "agg_direct", fn,
                                     extra=(G, strides))
                update_cache[("direct", G, strides)] = fn
            return fn

        def make_update(num_slots: int):
            fn = update_cache.get(num_slots)
            if fn is None:
                def fn(state, batch):
                    key_cols = [batch.columns[k] for k in key_names]
                    agg_cols = {}
                    for out, expr in input_exprs.items():
                        agg_cols[out] = (low.eval(expr, batch)
                                         if expr is not None else None)
                    agg_cols2 = {out: low.eval(expr, batch)
                                 for out, expr in input_exprs2.items()}
                    return ops.agg_update(state, batch, key_cols, agg_cols,
                                          specs, num_slots, 0, key_names,
                                          agg_cols2)
                fn = update_cache[num_slots] = self.shared_jit(
                    node, "agg_upd", fn, extra=(num_slots,))
            return fn

        fused_cache: dict = {}

        def _fusion_declined(reason: str) -> None:
            """The silent fusion refusals become per-scan RuntimeStats
            counters (fusionDeclined{Reason}), printed by EXPLAIN
            ANALYZE so an un-fused plan is diagnosable."""
            rs = self.ctx.runtime_stats
            if rs is not None:
                rs.add(f"fusionDeclined{reason}", 1)

        def get_fused():
            """Whole-pipeline fusion: when the source is a
            (Filter|Project|Join|SemiJoin)* chain over a device-generated
            TableScan (exec/fused.py), compile scan → chain → agg-update
            into ONE jitted program with a fori_loop over split chunks.
            One dispatch per task instead of O(batches × operators) — on
            TPU the per-dispatch round-trip dominates wall-clock for these
            pipelines (all of TPC-H's heavy shapes).  Returns the compiled
            FusedChain or None; decision is cached.  EXPLAIN ANALYZE runs
            the fused chain too (per-operator row counters ride the jitted
            program) unless the analyze_unfused session knob asks for the
            old streaming profile."""
            if "chain" in fused_cache:
                return fused_cache["chain"]
            fused_cache["chain"] = None
            if not cfg.fuse_pipelines:
                _fusion_declined("Disabled")
                return None
            if self.ctx.stats is not None and cfg.analyze_unfused:
                _fusion_declined("AnalyzeUnfused")
                return None
            # masks were already lowered to IF-inputs by _rewrite_agg_masks
            if any(a.distinct for a in node.aggregations.values()):
                _fusion_declined("DistinctAgg")
                return None
            if any(s.name in ops.HLL_AGGS for s in specs):
                # HLL registers live in the scatter-hash table only; the
                # fused sort path has no register file
                _fusion_declined("HllAgg")
                return None
            from .fused import assemble_chain
            chain = assemble_chain(self, src_node)
            if chain is None:
                _fusion_declined("PlanShape")
            elif not chain.chunks:
                _fusion_declined("NoChunks")
                chain = None
            fused_cache["chain"] = chain
            return chain

        def _agg_exprs(b):
            return {out: (low.eval(expr, b) if expr is not None else None)
                    for out, expr in input_exprs.items()}

        def _agg_exprs2(b):
            return {out: low.eval(expr, b)
                    for out, expr in input_exprs2.items()}

        def run_fused(chain):
            """Analyze-aware front door for _run_fused_inner: under
            EXPLAIN ANALYZE it measures the REAL fused program's wall
            (block_until_ready on the finalized output) and folds the
            device-side per-operator row counters into ctx.stats."""
            analyzing = self.ctx.stats is not None
            counts_out: dict = {}
            if not analyzing:
                return _run_fused_inner(chain, counts_out)
            import time
            t0 = time.perf_counter()  # lint: allow-wall-clock
            out = _run_fused_inner(chain, counts_out)
            if not isinstance(out, Batch):
                return out      # nothing, or the chain's dense stream
            out = jax.block_until_ready(out)
            wall = time.perf_counter() - t0  # lint: allow-wall-clock
            counts = counts_out.get("counts")
            if counts is None and "probe_args" in counts_out:
                # modes whose program cannot carry the counters in its
                # loop state (runtime span, sort-agg): one extra counting
                # dispatch over the same chain
                from .fused import chain_counts_fn
                p_arr, c_arr, p_aux, p_exp, p_cap = counts_out["probe_args"]
                counts = chain_counts_fn(
                    chain, p_exp, p_cap, fused_cache,
                    ("analyze_counts", p_exp))(p_arr, c_arr, p_aux)
            from .fused import record_chain_stats
            record_chain_stats(self.ctx.stats, chain, counts,
                               counts_out.get("n_chunks", 0), wall_s=wall)
            if self.ctx.runtime_stats is not None:
                self.ctx.runtime_stats.add("fusedProgramWallNanos",
                                           wall * 1e9, "NANO")
            return out

        def _run_fused_inner(chain, counts_out):
            """Execute a fused chain to a finalized output Batch, or hand
            back its rows as a dense stream (an iterator of batches) for
            `aggregate_stream`, or None to fall back to the streaming
            executor.  By group-key shape: one-hot grid (G<=64,
            MXU-friendly), static span (closed dictionary domains); then,
            where a join leaves under a quarter of the scanned rows, the
            dense stream; else runtime span (single integer key — probe
            min/max, then collision-free scatter-direct) or the sort of
            the stacked chain output."""
            analyzing = self.ctx.stats is not None
            pool = self.ctx.memory
            if pool.limited:
                # budgeted (or query.max-memory-limited) execution keeps
                # the streaming path: its build reservation / grace-spill
                # machinery owns memory discipline
                _fusion_declined("BudgetedPool")
                return None
            # build tables are deterministic per plan (generated connectors
            # are immutable; writes clear the runner's plan cache), so prep
            # results persist across re-executions — the warm path costs
            # zero host syncs for builds.  Parameterized BUILD subtrees are
            # the exception: their tables are a function of the bound
            # constants, so prep re-runs when the fingerprint moved.
            pfp = (self.ctx.params_fingerprint
                   if (chain.has_params or chain.build_params
                       or chain.params_pushdown) else None)
            prep_res = fused_cache.get("prep")
            if prep_res is not None and chain.build_params \
                    and fused_cache.get("prep_fp") != pfp:
                prep_res = None
            if prep_res is None:
                try:
                    prep_res = chain.prep()
                except QueryMemoryLimitExceededError:
                    raise   # typed user error: fail fast, never fall back
                except (NotImplementedError, MemoryExceededError):
                    _fusion_declined("PrepUnsupported")
                    return None
                if prep_res is None:
                    _fusion_declined("PrepFanout")
                    return None
                fused_cache["prep"] = prep_res
                fused_cache["prep_fp"] = pfp
            aux, expands, _deferred = prep_res
            if chain.has_params:
                # cached prep carries the FIRST execution's parameter
                # vector in the last aux slot — swap in the current one
                # (traced argument: no retrace)
                aux = aux[:-1] + (self.ctx.params,)
            leaf_cap = chain.leaf_cap(expands)
            chunks = chain.chunks_for(expands, meter=True)
            try:
                probe = chain.shape_probe(aux, expands, leaf_cap)
            except NotImplementedError:
                _fusion_declined("ProbeUnsupported")
                return None
            key_cols = [probe.columns.get(k) for k in key_names]
            if any(c is None for c in key_cols):
                _fusion_declined("KeyMissing")
                return None
            key_lazy: Dict[str, Tuple] = {}
            for k, c in zip(key_names, key_cols):
                if c.lazy is not None:
                    _, tbl, coln, _sf = c.lazy
                    if (tbl, coln) not in catalog.ROWID_DISTINCT:
                        _fusion_declined("KeyEncoding")
                        return None    # needs host dictionary encoding
                    key_lazy[k] = c.lazy
            key_dicts = {k: c.dictionary
                         for k, c in zip(key_names, key_cols)
                         if c.dictionary is not None}
            key_dtypes = tuple(c.values.dtype for c in key_cols)
            pos_arr = jnp.asarray([c0 for c0, _ in chunks],
                                  dtype=jnp.int64)
            cnt_arr = jnp.asarray([c1 for _, c1 in chunks],
                                  dtype=jnp.int64)
            counts_out["probe_args"] = (pos_arr, cnt_arr, aux, expands,
                                        leaf_cap)
            counts_out["n_chunks"] = len(chunks)

            prog = chain.program
            # what every program below bakes in beyond the aggregation's
            # subtree and the config: the chain's run-time constants and
            # what the probe said of the columns it produces
            chain_sig = prog.signature(expands, leaf_cap) \
                + (_fragment_batch_sig(probe),)

            def loop(mode, update, init_state, *agg_sig):
                """fori_loop over scan chunks.  The jitted program comes
                from shared_jit -- so a second task, or the next query with
                this text, traces nothing -- and is remembered here so
                re-executions of this plan skip even the lookup.  `agg_sig`
                is what `update` bakes in (slot counts, strides).  Under
                EXPLAIN ANALYZE the per-operator row counters ride the
                SAME program as an extra loop-carry output."""
                key = (mode, expands, analyzing) + agg_sig
                run_all = fused_cache.get(key)
                if run_all is None:
                    # scan_agg_direct | _static_span | _hash, and the
                    # EXPLAIN ANALYZE variant `<...>_counted`
                    program = "scan_agg_" + mode
                    if analyzing:
                        program += "_counted"

                        def run_all(pos_arr, cnt_arr, state, aux):
                            def body(i, carry):
                                st, cnts = carry
                                b, c = prog.make(
                                    pos_arr[i], cnt_arr[i], aux, expands,
                                    leaf_cap, with_counts=True)
                                with jax.named_scope("agg"):
                                    return update(st, b), cnts + c
                            return jax.lax.fori_loop(
                                0, pos_arr.shape[0], body,
                                (state, jnp.zeros(1 + len(prog.steps),
                                                  dtype=jnp.int64)))
                    else:
                        def run_all(pos_arr, cnt_arr, state, aux):
                            def body(i, st):
                                b = prog.make(pos_arr[i], cnt_arr[i], aux,
                                              expands, leaf_cap)
                                with jax.named_scope("agg"):
                                    return update(st, b)
                            # chunk count from the traced shape, NOT a
                            # closure constant: param-aware pruning may
                            # change it between executions, and the two
                            # tasks of a stage own different splits
                            # (shape change -> retrace in the same jit)
                            return jax.lax.fori_loop(0, pos_arr.shape[0],
                                                     body, state)
                    run_all = fused_cache[key] = self.shared_jit(
                        node, program, run_all,
                        extra=chain_sig + agg_sig)
                out = run_all(pos_arr, cnt_arr, init_state, aux)
                if analyzing:
                    out, counts_out["counts"] = out
                return out

            def stride_codes(b, strides, G):
                codes = None
                for k, stride in zip(key_names, strides):
                    c = b.columns[k].values.astype(jnp.int64)
                    codes = (c * stride if codes is None
                             else codes + c * stride)
                if codes is None:
                    codes = jnp.zeros(b.capacity, dtype=jnp.int64)
                return codes

            basic = basic_specs
            sort_only = sort_only_specs
            # direct: closed key domains of at most 64 groups -- the
            # one-hot grid
            info = (_direct_mode_info(key_names, key_cols)
                    if basic else None)
            if info is not None:
                doms, G, strides, kdts, kdicts = info

                def update(st, b):
                    return ops.agg_direct_update(
                        st, b, stride_codes(b, strides, G),
                        _agg_exprs(b), specs, G)
                state = loop("direct", update,
                             ops.agg_direct_init(G, specs), G, strides)
                return ops.agg_direct_finalize(
                    state, specs, key_names, doms, kdts, kdicts,
                    force_row=not key_names)

            # static span: closed dictionary/bool domains beyond the grid
            # limit — combined stride code indexes accumulators directly
            info = (_direct_mode_info(key_names, key_cols,
                                      gmax=ops.SPAN_AGG_MAX_GROUPS)
                    if basic else None)
            if info is not None:
                doms, G, strides, kdts, kdicts = info
                if not pool.try_reserve(G * 24 * max(1, len(specs))):
                    return None
                try:
                    def update(st, b):
                        return ops.agg_span_update(
                            st, b, stride_codes(b, strides, G),
                            _agg_exprs(b), specs, G)
                    state = loop("static_span", update,
                                 ops.agg_span_init(G, specs), G, strides)
                    slot = jnp.arange(G, dtype=jnp.int64)
                    key_arrays = {}
                    stride = G
                    for k, dom, dt in zip(key_names, doms, kdts):
                        stride //= dom
                        key_arrays[k] = ((slot // stride) % dom).astype(dt)
                    return _maybe_compact(ops.agg_span_finalize(
                        state, specs, key_names, key_arrays, kdicts,
                        key_lazy))
                finally:
                    pool.free(G * 24 * max(1, len(specs)))

            # open key domains over a chain that a join leaves sparse: a
            # scatter or a gather an accumulator costs an index whether
            # its row is live or not (PERF.md, PR 34), so the few live
            # rows are made dense first -- by the chain's own count pass,
            # which also says whether it is worth it -- and aggregated as
            # a stream.  A chain without a join keeps the strategies
            # below: its scan's key order is what they use.
            if key_names and any(s[0] in ("join", "semi")
                                 for s in chain.steps):
                from .fused import fused_dense_stream
                dense = fused_dense_stream(self, src_node, chain=chain,
                                           prep=prep_res, skip_root=False)
                if dense is not None:
                    return dense

            # runtime span: one integer ANCHOR key indexes the
            # accumulators directly (collision-free scatter-direct); any
            # OTHER grouping keys must be functionally dependent on the
            # anchor — verified at runtime by per-group min==max (+ null
            # uniformity), the TPC-H Q3/Q10/Q18 shape where order/customer
            # attributes are grouped alongside their key.  On violation
            # the run is discarded and the sort path below takes over.
            candidates = [i for i, c in enumerate(key_cols)
                          if c.nulls is None and c.values.dtype in
                          (jnp.int64, jnp.int32, jnp.int16)]
            if basic and candidates \
                    and all(c.values.ndim == 1 for c in key_cols):
                cand_names = tuple(key_names[i] for i in candidates)
                spanp = fused_cache.get(("span_probe", cand_names, expands))
                if spanp is None:
                    def spanp(pos_arr, cnt_arr, aux):
                        def body(i, mm):
                            b = prog.make(pos_arr[i], cnt_arr[i], aux,
                                          expands, leaf_cap)
                            los, his = mm
                            vs = jnp.stack(
                                [b.columns[k].values.astype(jnp.int64)
                                 for k in cand_names])
                            los = jnp.minimum(los, jnp.min(jnp.where(
                                b.mask[None, :], vs, ops.INT64_MAX),
                                axis=1))
                            his = jnp.maximum(his, jnp.max(jnp.where(
                                b.mask[None, :], vs, ops.INT64_MIN),
                                axis=1))
                            return (los, his)
                        k = len(cand_names)
                        return jax.lax.fori_loop(
                            0, pos_arr.shape[0], body,
                            (jnp.full(k, ops.INT64_MAX, dtype=jnp.int64),
                             jnp.full(k, ops.INT64_MIN, dtype=jnp.int64)))
                    spanp = fused_cache[
                        ("span_probe", cand_names, expands)] = \
                        self.shared_jit(node, "scan_agg_span_probe", spanp,
                                        extra=chain_sig + (cand_names,))
                # data-dependent (not shape-only) results are a function
                # of the bound parameters: key them by fingerprint
                span_key = ("span_range", cand_names, expands, pfp)
                if span_key in fused_cache:
                    ranges = fused_cache[span_key]
                else:
                    los, his = host_get(spanp(pos_arr, cnt_arr, aux),
                                        "agg_span_probe")
                    ranges = [(int(l), int(h)) for l, h in zip(los, his)]
                    fused_cache[span_key] = ranges
                # the anchor must be unique per group (verified below by
                # the dependency check).  Heuristic order: "key"-named
                # columns widest-span first (PK/FK naming convention, the
                # finest key is the likeliest group identity), then lazy
                # row-ids (row identity), then the rest; the first anchor
                # that verifies is cached for re-executions.
                viable = []
                for ci, (lo, hi) in zip(candidates, ranges):
                    span = hi - lo + 1
                    if hi >= lo and span <= ops.SPAN_AGG_MAX_GROUPS:
                        nm = key_names[ci].lower()
                        rank = (0 if "key" in nm
                                else 1 if key_cols[ci].lazy is not None
                                else 2)
                        viable.append((rank, -span, ci, span, lo))
                viable.sort()
                anchor_key = ("span_anchor", cand_names, expands, pfp)
                cached_anchor = fused_cache.get(anchor_key)
                if cached_anchor is not None:
                    # -1 = every candidate failed once; don't re-pay the
                    # wasted verification passes on re-execution
                    viable = [v for v in viable if v[2] == cached_anchor]
                attempts = [(v[2], v[3], v[4]) for v in viable[:2]]
                if not attempts and cached_anchor is None:
                    fused_cache[anchor_key] = -1
                for ci, span, lo in attempts:
                    dep_idx = [i for i in range(len(key_names)) if i != ci]
                    dep_names = tuple(key_names[i] for i in dep_idx)
                    kname = key_names[ci]
                    G = 1 << (span - 1).bit_length()
                    nacc = max(1, len(specs)) + len(dep_names)
                    if not pool.try_reserve(G * 24 * nacc):
                        return None
                    try:
                        base = jnp.int64(lo)

                        run = fused_cache.get(
                            ("span", G, kname, dep_names, expands))
                        if run is None:
                            def run(pos_arr, cnt_arr, state, aux, base):
                                def body(i, st):
                                    b = prog.make(pos_arr[i], cnt_arr[i],
                                                  aux, expands, leaf_cap)
                                    codes = b.columns[kname].values \
                                        .astype(jnp.int64) - base
                                    st = ops.agg_span_update(
                                        st, b, codes, _agg_exprs(b),
                                        specs, G)
                                    return ops.depkey_update(
                                        st, b, codes,
                                        {k: b.columns[k]
                                         for k in dep_names}, G)
                                state = jax.lax.fori_loop(
                                    0, pos_arr.shape[0], body, state)
                                dep_ok = ops.depkey_verify(
                                    state, state["__seen"], dep_names)
                                return state, dep_ok
                            run = fused_cache[
                                ("span", G, kname, dep_names, expands)] = \
                                self.shared_jit(
                                    node, "scan_agg_runtime_span", run,
                                    extra=chain_sig + (G, kname, dep_names))
                        init = {**ops.agg_span_init(G, specs),
                                **ops.depkey_init(G, dep_names)}
                        state, dep_ok = run(pos_arr, cnt_arr, init,
                                            aux, base)
                        if dep_names and not bool(
                                host_get(dep_ok, "agg_span_dep_ok")):
                            # a grouping key varies within an anchor
                            # group: this anchor was not unique — try the
                            # next candidate, else the sort path below
                            continue
                        fused_cache[anchor_key] = ci
                        key_arrays = {kname: (
                            base + jnp.arange(G, dtype=jnp.int64))
                            .astype(key_dtypes[ci])}
                        key_nulls = {}
                        for i in dep_idx:
                            k = key_names[i]
                            key_arrays[k] = ops._depkey_restore(
                                state[f"__dep_{k}$min"], key_dtypes[i])
                            key_nulls[k] = state[f"__dep_{k}$nulls"] > 0
                        return _maybe_compact(ops.agg_span_finalize(
                            state, specs, key_names, key_arrays,
                            key_dicts, key_lazy, key_nulls))
                    finally:
                        pool.free(G * 24 * nacc)
                else:
                    if attempts and cached_anchor is None:
                        fused_cache[anchor_key] = -1

            # high-cardinality keys: SORT-based grouping (argsort +
            # segmented scans — no scatters, which cost ~100ms/M rows on
            # TPU) over the stacked chain output, when it fits in memory
            total = chain.total_rows
            kprod = 1
            for k in expands:
                kprod *= k
            width = len(key_names) + sum(
                1 for e in input_exprs.values() if e is not None)
            est_mat = total * kprod * width * 9
            if (est_mat <= SORT_AGG_MAX_BYTES or sort_only) \
                    and pool.try_reserve(est_mat):
                run = fused_cache.get(("sortagg", expands))
                if run is None:
                    def run(pos_arr, cnt_arr, aux):
                        def step(pc):
                            b = prog.make(pc[0], pc[1], aux, expands,
                                          leaf_cap)
                            cols = {k: b.columns[k] for k in key_names}
                            for out, col in _agg_exprs(b).items():
                                if col is not None:
                                    cols["$in_" + out] = col
                            for out, col in _agg_exprs2(b).items():
                                cols["$in2_" + out] = col
                            return Batch(cols, b.mask)
                        stacked = jax.lax.map(step, (pos_arr, cnt_arr))
                        flat = jax.tree_util.tree_map(
                            lambda a: a.reshape((-1,) + a.shape[2:]),
                            stacked)
                        inputs = {s.output: flat.columns.get(
                            "$in_" + s.output) for s in specs}
                        inputs2 = {s.output: flat.columns["$in2_"
                                                          + s.output]
                                   for s in specs
                                   if s.name in ops.CORR_AGGS}
                        return ops.sort_group_aggregate(
                            Batch({k: flat.columns[k] for k in key_names},
                                  flat.mask),
                            key_names, inputs, specs, inputs2)
                    run = fused_cache[("sortagg", expands)] = \
                        self.shared_jit(node, "scan_agg_sort", run,
                                        extra=chain_sig)
                try:
                    return _maybe_compact(run(pos_arr, cnt_arr, aux))
                finally:
                    pool.free(est_mat)

            if sort_only:
                # percentile-class aggregates need value-ordered
                # segments; over the sort budget the streaming summary /
                # spilled-bucket paths in gen() take over
                return None

            # no fused strategy holds these keys: the chain streams, and
            # the stream is aggregated once through (aggregate_stream)
            return None

        rs = self.ctx.runtime_stats

        def note(name, value):
            if rs is not None:
                rs.add(name, value)

        def note_table(out, num_slots):
            """The groups of a finalized table (one fetch) and its slots
            (0: grouped by a sort)."""
            if rs is not None:
                rs.add("aggGroups", int(host_get(
                    _jit_count_live(out.mask), "agg_groups")))
                rs.add("aggTableSlots", num_slots)

        def make_grow(old_slots: int, new_slots: int):
            key = ("grow", old_slots, new_slots)
            fn = update_cache.get(key)
            if fn is None:
                def fn(state):
                    key_dtypes = [state[f"__key_{k}"].dtype
                                  for k in key_names]
                    return ops.agg_merge(
                        ops.agg_init(new_slots, specs, key_names,
                                     key_dtypes),
                        state, specs, key_names, new_slots)
                fn = update_cache[key] = self.shared_jit(
                    node, "agg_grow", fn, extra=(old_slots, new_slots))
            return fn

        def aggregate_stream(batches, start_slots, reserve=None,
                             allow_direct=True, restream=None):
            """One grouped aggregation of a stream of batches, the stream
            read ONCE: the rule for every aggregation that is not fused
            with its scan (above a join, above an exchange, over a spill
            bucket).  Closed small key domains take the code grid; else
            an input of up to SORT_STREAM_MAX_ROWS rows (by the batches'
            capacities: no sync) is held and grouped by one sort, which
            has no table to size; a longer one goes through the scatter
            hash table, sized from the rows already held and grown in
            place (`hash_aggregate`).  Yields the finalized batches.
            `restream`: the one case that reads its input again, a NULL
            key that turns up after the code grid was chosen on a batch
            without one (counted as `aggRestreams`)."""
            note("aggRestreams", 0)
            with _span(rs, "aggUpdate"):
                it = iter(batches)
                first = next(it, None)
            key_dicts: Dict[str, Tuple[str, ...]] = {}
            key_lazy: Dict[str, Tuple] = {}
            encode_keys: List[str] = []
            hll_outs = {s.output for s in specs if s.name in ops.HLL_AGGS}
            if first is None:
                state = ops.agg_init(start_slots, specs, key_names,
                                     [jnp.int64] * len(key_names))
                yield finalize_hash(state, key_dicts, key_lazy,
                                    start_slots)
                return
            for k in key_names:
                col = first.columns[k]
                if col.lazy is not None:
                    _, tbl, coln, _sf = col.lazy
                    if (tbl, coln) in catalog.ROWID_DISTINCT:
                        # row id IS the group identity; keep lazy tag
                        key_lazy[k] = col.lazy
                    else:
                        # small-pool column (orders.clerk): grouping
                        # by row id would split groups — encode to a
                        # real whole-column dictionary on the host
                        encode_keys.append(k)
            # HLL sketches hash the device values: a lazy column's
            # row ids are only distinct-faithful when the row id is
            # unique per VALUE; otherwise encode to dictionary codes
            for out in hll_outs:
                expr = input_exprs[out]
                if isinstance(expr, VariableReferenceExpression):
                    col = first.columns.get(expr.name)
                    if col is not None and col.lazy is not None:
                        _, tbl, coln, _sf = col.lazy
                        if (tbl, coln) not in catalog.ROWID_DISTINCT \
                                and expr.name not in encode_keys:
                            encode_keys.append(expr.name)

            if encode_keys:
                first = _encode_lazy_keys(first, encode_keys)
                it = (_encode_lazy_keys(b, encode_keys) for b in it)
            key_cols = [first.columns[k] for k in key_names]
            key_dtypes = [c.values.dtype for c in key_cols]
            for k, c in zip(key_names, key_cols):
                if c.dictionary is not None:
                    key_dicts[k] = c.dictionary
            # closed small domains: combined code IS the slot index
            info = (_direct_mode_info(key_names, key_cols)
                    if basic_specs and allow_direct else None)
            if info is not None:
                doms, G, strides, kdts, _kd = info
                update = make_direct_update(G, strides)
                with _span(rs, "aggUpdate"):
                    state = ops.agg_direct_init(G, specs)
                    batch = first
                    while batch is not None:
                        if any(batch.columns[k].nulls is not None
                               for k in key_names):
                            # the code grid has no NULL slot and was
                            # chosen on a null-free first batch (nullable
                            # storage connectors): the input is read
                            # again for the hash path.  Close the
                            # abandoned iterator FIRST — source
                            # generators release pool reservations in
                            # finally blocks.
                            if hasattr(batches, "close"):
                                batches.close()
                            if restream is None:
                                raise RuntimeError(
                                    "a NULL group key after the code "
                                    "grid was chosen, and an input that "
                                    "cannot be read again")
                            note("aggRestreams", 1)
                            state = None
                            break
                        state = update(state, batch)
                        batch = next(it, None)
                if state is None:
                    yield from aggregate_stream(
                        restream(), start_slots, reserve,
                        allow_direct=False)
                    return
                with _span(rs, "aggFinalize"):
                    out = ops.agg_direct_finalize(
                        state, specs, key_names, doms, kdts, key_dicts,
                        force_row=not key_names)
                note("aggTableSlots", G)
                yield out
                return
            held, rows = [first], first.capacity
            sortable = bool(key_names) and not hll_outs \
                and reserve is None and rows <= SORT_STREAM_MAX_ROWS
            with _span(rs, "aggUpdate"):
                if sortable:
                    for b in it:
                        held.append(b)
                        rows += b.capacity
                        if rows > SORT_STREAM_MAX_ROWS:
                            sortable = False
                            break
                if sortable:
                    merged = _compact_concat(held)
                    bucket = _sort_bucket(merged.capacity)
                    if bucket > merged.capacity:
                        merged = _jit_pad_rows(merged, bucket)
                    out = sort_aggregate()(merged)
            if sortable:
                with _span(rs, "aggFinalize"):
                    out = _maybe_compact(out)
                note_table(out, 0)
                yield out
                return
            # a table for the rows held so far, at half load or less
            num_slots = max(start_slots,
                            1 << max(0, 2 * rows - 1).bit_length()
                            if len(held) > 1 else 0)
            if reserve is not None and num_slots > start_slots \
                    and not reserve(num_slots):
                num_slots = start_slots
            with _span(rs, "aggUpdate"):
                state, num_slots = hash_aggregate(
                    ops.agg_init(num_slots, specs, key_names, key_dtypes),
                    held, num_slots, it,
                    make_update, make_grow, reserve, rs)
            yield finalize_hash(state, key_dicts, key_lazy, num_slots)

        def finalize_hash(state, key_dicts, key_lazy, num_slots):
            with _span(rs, "aggFinalize"):
                if not key_names and not bool(host_get(
                        jnp.any(state["__occupied"]), "agg_occupied")):
                    # global aggregation over empty input: one row
                    state["__occupied"] = \
                        state["__occupied"].at[0].set(True)
                out = ops.agg_finalize(state, specs, key_names, key_dicts,
                                       key_lazy)
            note_table(out, num_slots)
            return out

        def sort_aggregate():
            low2 = self.lowering
            fn = update_cache.get("sort")
            if fn is None:
                def fn(b):
                    inputs = {out: (low2.eval(e, b) if e is not None
                                    else None)
                              for out, e in input_exprs.items()}
                    inputs2 = {out: low2.eval(e, b)
                               for out, e in input_exprs2.items()}
                    return ops.sort_group_aggregate(b, key_names, inputs,
                                                    specs, inputs2)
                fn = update_cache["sort"] = self.shared_jit(
                    node, "agg_sort", fn)
            return fn

        # size the scatter table from the optimizer's group-count estimate
        # so the common case never pays a collision retry (each retry
        # re-streams the ENTIRE source — 3 full passes for a 10k-group
        # aggregate started at 4096 slots, the q21 shape).  ~2x headroom
        # for probing; clamped so a wild overestimate cannot blow HBM.
        initial_slots = cfg.agg_slots
        if key_names and cfg.history_agg_groups:
            # history-based sizing (adaptive.history-sizing): the OBSERVED
            # group count from a prior run of this plan template beats any
            # estimate, and — being a measurement, not a guess — may size
            # BELOW agg_slots too (floored so a tiny group count cannot
            # degenerate the probe sequence)
            hist_based = 1 << max(0, (int(2 * cfg.history_agg_groups)
                                      - 1).bit_length())
            initial_slots = max(256, min(hist_based, 1 << 20))
        elif key_names:
            try:
                from ..sql.stats import StatsCalculator
                est_groups = StatsCalculator().rows(node)
            except Exception:   # noqa: BLE001 — estimate only
                est_groups = None
            if est_groups:
                # clamp only the ESTIMATE term: a user-configured
                # agg_slots above the clamp must never be reduced
                est_based = 1 << max(0, (int(2 * est_groups)
                                         - 1).bit_length())
                initial_slots = max(initial_slots,
                                    min(est_based, 1 << 20))

        # rough accumulator footprint for the budget check (hash + occupied
        # + per-key value/null + per-aggregate state columns)
        est_state_bytes = _agg_state_bytes(initial_slots, key_names, specs)

        def drain_sort_input(source=None):
            """Drain the source (`source`, where the caller already holds
            its stream) once under per-batch reservation.
            Returns (merged, None) when the whole input fit the budget;
            else (None, stream) where the stream replays the collected
            (still-reserved) batches and then continues the SAME source
            iterator — the over-budget paths never re-execute the source
            and device bytes stay accounted until consumed."""
            pool = self.ctx.memory
            collected, reserved = [], 0
            it = source if source is not None \
                else self._compile(src_node).batches()
            over_batch = None
            for b in it:
                nb = batch_bytes(b)
                if pool.try_reserve(nb):
                    collected.append(b)
                    reserved += nb
                else:
                    over_batch = b
                    break
            if over_batch is None:
                merged = (_compact_concat(collected) if collected
                          else None)
                pool.free(reserved)
                if merged is None:
                    # zero-batch source: an all-masked schema-shaped
                    # batch so a global aggregate still yields its row
                    from .fused import _empty_build_batch
                    merged = _empty_build_batch(src_node)
                return merged, None

            def stream():
                try:
                    yield from collected
                    yield over_batch
                    yield from it
                finally:
                    pool.free(reserved)
            return None, stream()

        def run_global_percentile_stream(batches):
            """Global approx_percentile over a budget-exceeding input:
            one streaming pass keeping only an m-point mergeable quantile
            summary per batch (operators.percentile_batch_summary — the
            t-digest-state analog of
            ApproximateLongPercentileAggregations.java), plus the running
            scatter state for any sibling aggregates.  Rank error <=
            1/(2m) (m=8192 -> 0.006%); memory = O(batches * m) floats on
            the host, never the input."""
            m = ops.PERCENTILE_SKETCH_POINTS
            pct_specs = tuple(s for s in specs
                              if s.name == "approx_percentile")
            other_specs = tuple(s for s in specs
                                if s.name != "approx_percentile")
            low2 = self.lowering
            key = ("pctsketch", node.id)
            fns = self._jit_cache.get(key)
            if fns is None:
                @jit_as("agg_percentile_summarize")
                def summarize(b):
                    out = {}
                    for s in pct_specs:
                        col = low2.eval(input_exprs[s.output], b)
                        alive = b.mask & ~col.null_mask()
                        out[s.output] = ops.percentile_batch_summary(
                            col.values, alive, m)
                    return out

                @jit_as("agg_percentile_update_others")
                def update_others(state, b):
                    agg_cols = {s.output: low2.eval(
                        input_exprs[s.output], b)
                        if input_exprs[s.output] is not None else None
                        for s in other_specs}
                    agg_cols2 = {s.output: low2.eval(
                        input_exprs2[s.output], b)
                        for s in other_specs if s.name in ops.CORR_AGGS}
                    return ops.agg_update(state, b, [], agg_cols,
                                          other_specs, 256, 0, (),
                                          agg_cols2)
                self._jit_cache[key] = fns = (summarize, update_others)
            summarize, update_others = fns
            state = (ops.agg_init(256, other_specs, (), ())
                     if other_specs else None)
            summaries = {s.output: [] for s in pct_specs}
            for b in batches:
                for out, (pts, cnt) in summarize(b).items():
                    summaries[out].append((pts, cnt))
                if state is not None:
                    state = update_others(state, b)
            if state is not None:
                if not bool(host_get(jnp.any(state["__occupied"]),
                                     "agg_occupied")):
                    state["__occupied"] = \
                        state["__occupied"].at[0].set(True)
                row = ops.agg_finalize(state, other_specs, (), {}, {})
            else:
                row = Batch({}, jnp.ones(1, dtype=bool))
            cols = dict(row.columns)
            for s in pct_specs:
                chunks = summaries[s.output]
                if chunks:
                    pts = jnp.stack([c[0] for c in chunks])
                    cnts = jnp.stack([c[1] for c in chunks])
                else:
                    pts = jnp.full((1, m), jnp.nan)
                    cnts = jnp.zeros(1, dtype=jnp.int64)
                p = float(s.param if s.param is not None else 0.5)
                val, is_null = ops.percentile_union_value(pts, cnts, p)
                if not s.is_float:
                    val = val.astype(jnp.int64)
                # broadcast to the finalize batch's capacity: every
                # column of a Batch must share one shape (the sibling
                # aggregate columns are full hash-table slots)
                cap = row.capacity
                cols[s.output] = Column(
                    jnp.broadcast_to(val[None], (cap,)),
                    jnp.broadcast_to(is_null[None], (cap,)))
            order = [v.name for v in node.aggregations]
            return Batch({o: cols[o] for o in order}, row.mask)

        def subdivide_bucket(bstore, p, depth, work):
            """K-way sub-partition of an over-budget bucket with a fresh
            salt (recursive grouped execution, same shape as the grace
            join's re-partition), shared by the sorted- and hash-spill
            paths.  The callers' depth caps differ DELIBERATELY: the
            sort path stops at 2 — beyond that only single-key skew
            remains, handled by the per-key summary path — while the
            hash path splits to 4 because its per-KEY state always
            shrinks with more partitions."""
            salt2 = bstore.salt * 33 + 0x9E37
            sub = self._new_spill_store(salt2)
            for bb in bstore.bucket_batches(p, cfg.batch_rows):
                sub.add(bb, list(key_names))
            work.extend((sub, q, depth + 1)
                        for q in range(cfg.spill_partitions))

        def fill_spill_store(batches=None):
            """Stream the source into a key-partitioned host store.
            Lazy open-domain key columns are whole-column encoded FIRST
            (row ids for non-ROWID_DISTINCT columns would split value
            groups across buckets) — shared by the hash-spill and
            sorted-spill paths."""
            store = self._new_spill_store()
            encode_keys = None
            if batches is None:
                batches = self._compile(src_node).batches()
            for batch in batches:
                if encode_keys is None:
                    encode_keys = []
                    for k in key_names:
                        col = batch.columns[k]
                        if col.lazy is not None:
                            _, tbl, coln, _sf = col.lazy
                            if (tbl, coln) not in catalog.ROWID_DISTINCT:
                                encode_keys.append(k)
                if encode_keys:
                    batch = _encode_lazy_keys(batch, encode_keys)
                store.add(batch, list(key_names))
            return store

        def run_sorted_spilled(batches):
            """Grouped percentile-class aggregation over budget: hash-
            partition rows by group key into host buckets (disjoint key
            sets), then run the exact sort aggregation bucket-by-bucket —
            the grouped-execution Lifespan model, same store the hash
            path spills through."""
            store = fill_spill_store(batches)
            fn = sort_aggregate()
            pool = self.ctx.memory
            work = [(store, p, 0) for p in range(cfg.spill_partitions)]
            while work:
                bstore, p, depth = work.pop()
                rows_p = bstore.bucket_rows(p)
                if rows_p == 0:
                    continue
                bcap = 1 << max(0, rows_p - 1).bit_length()
                nb = bstore.bucket_bytes(p) * bcap // max(1, rows_p)
                if not pool.try_reserve(nb):
                    if depth >= 2:
                        # the bucket stopped shrinking: one (or a few)
                        # keys own more rows than the budget — no
                        # partitioning can split a single key's rows for
                        # the sort.  Per-key streaming summaries instead.
                        yield self._skewed_percentile_bucket(
                            bstore, p, key_names, specs, input_exprs,
                            input_exprs2)
                        continue
                    subdivide_bucket(bstore, p, depth, work)
                    continue
                try:
                    bucket = list(bstore.bucket_batches(p, bcap))[0]
                    yield _maybe_compact(fn(bucket))
                finally:
                    pool.free(nb)

        def gen():
            pool = self.ctx.memory
            fused = get_fused()
            grouped = None
            # EXPLAIN ANALYZE keeps the single-program fused path (its
            # row counters are per plan node); the grouped runner's
            # per-lifespan walls already land in runtime_stats
            if fused is not None and self.ctx.stats is None:
                grouped = fused_cache.get("grouped", False)
                if grouped is not False and grouped is not None \
                        and fused.build_params \
                        and grouped.params_fp != self.ctx.params_fingerprint:
                    # parameterized build tables (shared builds, bucket-0
                    # fanout probe) were sized under the old constants —
                    # rebuild the runner for this fingerprint
                    grouped = False
                if grouped is False:
                    from .grouped import make_grouped_runner
                    grouped = make_grouped_runner(
                        self, node, fused, key_names, specs, _agg_exprs,
                        basic_specs, bool(input_exprs2), cfg)
                    fused_cache["grouped"] = grouped
                if grouped is not None:
                    yield from grouped.run()
                    return
            shard = self.ctx.grouped_shard
            if shard is not None and shard[0] != 0:
                # the scheduler promised this stage disjoint lifespan
                # subsets over FULL splits, but grouped execution did not
                # engage at runtime: shard 0 alone runs the fallback over
                # everything; the other shards contribute nothing, so no
                # group is double-counted
                return
            source = None       # the stream to aggregate, where not src_node's
            if fused is not None:
                out = run_fused(fused)
                if isinstance(out, Batch):
                    yield out
                    return
                source = out        # a chain's dense stream, or None
            if sort_only_specs:
                if any(s.name in ops.HLL_AGGS for s in specs):
                    # percentile needs value-ordered segments (sort path),
                    # HLL needs the register file (hash path) — one
                    # aggregation node can't run both executors
                    raise NotImplementedError(
                        "approx_percentile and approx_distinct in the "
                        "same aggregation are not supported; split the "
                        "query into two aggregations")
                merged, stream = drain_sort_input(source)
                if stream is None:
                    yield _maybe_compact(sort_aggregate()(merged))
                    return
                if not cfg.spill_enabled:
                    raise MemoryExceededError(
                        f"sort-aggregation input exceeds memory budget "
                        f"{pool.budget} bytes and spill is disabled")
                if key_names:
                    yield from run_sorted_spilled(stream)
                else:
                    yield run_global_percentile_stream(stream)
                return

            def source_batches():
                return self._compile(src_node).batches()
            # grouped aggregation state is registered as a revocable
            # holder so arbitration/admission see it, but its callback
            # DECLINES (returns 0): a device hash table mid-scatter cannot
            # be spilled consistently, so the arbitrator moves on to the
            # next-largest victim and this operator self-spills below only
            # when its own reservation misses
            agg_holder = (pool.register_revocable("agg-state", lambda: 0)
                          if key_names else None)
            got = agg_holder is None \
                or agg_holder.try_reserve(est_state_bytes)
            if not got:
                agg_holder.close()
            if got:
                try:
                    # (a budgeted pool sees every growth of the table; an
                    # unlimited one has nothing to refuse it with)
                    reserve = None if agg_holder is None \
                        or not pool.limited else (
                            lambda n: agg_holder.try_reserve(
                                _agg_state_bytes(n, key_names, specs)))
                    try:
                        yield from aggregate_stream(
                            source if source is not None
                            else source_batches(), initial_slots, reserve,
                            restream=source_batches)
                    except GrowthRefused as e:
                        raise MemoryExceededError(
                            f"aggregation table of {e.args[0]} slots "
                            f"exceeds memory budget {pool.budget} bytes")
                finally:
                    if agg_holder is not None:
                        agg_holder.close()
                return
            if not cfg.spill_enabled:
                raise MemoryExceededError(
                    f"aggregation table exceeds memory budget "
                    f"{pool.budget} bytes and spill is disabled")
            # budget too small for one table: hash-partition the input by
            # group keys into host-staged buckets and aggregate per bucket
            # (buckets hold disjoint key sets, so each finalize is exact)
            store = fill_spill_store(source)
            # each bucket sees ~1/K of the keys: start with a
            # proportionally smaller table, and account for it.  A bucket
            # never holds more distinct keys than rows, so cap by the
            # bucket's actual row count; if even that over-runs the pool,
            # halve the table until the reservation fits (a table that
            # grows in place instead of failure, mirroring the reference's
            # spill-don't-throw behavior, HashBuilderOperator.java:56).
            # Only when even the 256-slot minimum exceeds the remaining
            # budget does reserve() raise — no smaller table exists.
            per_slot = max(1, est_state_bytes // max(1, initial_slots))
            work = [(store, pp, 0) for pp in range(cfg.spill_partitions)]
            while work:
                bstore, p, depth = work.pop()
                rows_p = bstore.bucket_rows(p)
                if rows_p == 0:
                    continue

                bucket_slots = max(
                    256, min(initial_slots // cfg.spill_partitions,
                             1 << (2 * rows_p - 1).bit_length()))
                held = [0]
                while True:
                    bucket_bytes = bucket_slots * per_slot
                    if pool.try_reserve(bucket_bytes):
                        held[0] = bucket_bytes
                        break
                    if bucket_slots <= 256:
                        break
                    bucket_slots = max(256, bucket_slots // 2)
                if not held[0]:
                    if depth < 4:
                        subdivide_bucket(bstore, p, depth, work)
                        continue
                    # even the minimum table exceeds the remaining
                    # budget after 4 re-partitions: raise the engine's
                    # exceeded-limit error
                    pool.reserve(bucket_bytes)

                def regrow(n, held=held):
                    # each growth of the table is re-reserved so device
                    # bytes never silently exceed the budget
                    pool.free(held[0])
                    held[0] = 0
                    if not pool.try_reserve(n * per_slot):
                        return False
                    held[0] = n * per_slot
                    return True

                def bucket_batches(b=bstore, p=p):
                    return b.bucket_batches(p, cfg.batch_rows)
                try:
                    # (list: a refused growth must not leave half a
                    # bucket's groups handed up before its sub-buckets'
                    # are)
                    done = list(aggregate_stream(
                        bucket_batches(), bucket_slots, regrow,
                        restream=bucket_batches))
                except GrowthRefused as e:
                    # the needed table cannot fit: sub-partition instead
                    # of over-reserving
                    if depth >= 4:
                        raise MemoryExceededError(
                            f"aggregation table of "
                            f"{e.args[0] * per_slot} bytes "
                            f"exceeds memory budget {pool.budget} "
                            f"after {depth} re-partitions")
                    subdivide_bucket(bstore, p, depth, work)
                    done = []
                finally:
                    pool.free(held[0])
                yield from done
        return BatchSource(gen, out_names, out_types)

    def _skewed_percentile_bucket(self, bstore, p, key_names, specs,
                                  input_exprs, input_exprs2) -> Batch:
        """Percentile aggregation over a spill bucket whose rows exceed
        the memory budget even after re-partitioning — i.e. single keys
        own more rows than fit (no key-hash split can help a sort).

        Split the work: percentile outputs come from per-key mergeable
        quantile summaries computed chunk-by-chunk over the HOST-resident
        spill rows (the summaries are the same m-point construction as
        operators.percentile_batch_summary, so rank error <= 1/(2m));
        every other aggregate runs exactly through the engine's scatter
        hash path over the same bucket (its state is per-KEY, tiny under
        skew).  The two result sets join on the grouping keys."""
        cfg = self.ctx.config
        pool = self.ctx.memory
        low = self.lowering
        pct_specs = [s for s in specs if s.name == "approx_percentile"]
        other_specs = tuple(s for s in specs
                            if s.name != "approx_percentile")
        for s in pct_specs:
            if not isinstance(input_exprs[s.output],
                              VariableReferenceExpression):
                raise NotImplementedError(
                    "approx_percentile over a computed expression on a "
                    "skew-spilled bucket")

        # --- per-key percentile summaries over host chunks (numpy,
        # vectorized grouping; summaries carry min(m, cnt) points so a
        # key contributing few rows to a chunk costs those rows only) ---
        m = ops.PERCENTILE_SKETCH_POINTS
        per_key: Dict[tuple, Dict[str, list]] = {}

        for rows in bstore.buckets[p]:
            n = len(next(iter(rows.values()))[0])
            arrs = []
            for k in key_names:
                vals, nulls = rows[k]
                arrs.append(vals)
                arrs.append(nulls if nulls is not None
                            else np.zeros(n, dtype=bool))
            rec = np.rec.fromarrays(arrs)
            uniq, inverse = np.unique(rec, return_inverse=True)
            order = np.argsort(inverse, kind="stable")
            bounds = np.searchsorted(inverse[order],
                                     np.arange(len(uniq) + 1))
            for g in range(len(uniq)):
                t = tuple(None if uniq[g][2 * j + 1] else
                          uniq[g][2 * j].tolist()
                          for j in range(len(key_names)))
                idxs = order[bounds[g]:bounds[g + 1]]
                ent = per_key.setdefault(
                    t, {s.output: [] for s in pct_specs})
                for s in pct_specs:
                    arg = input_exprs[s.output].name
                    vals, nulls = rows[arg]
                    v = vals[idxs]
                    if nulls is not None:
                        v = v[~nulls[idxs]]
                    cnt = len(v)
                    if cnt == 0:
                        continue
                    v = np.sort(v.astype(np.float64))
                    k_pts = min(m, cnt)
                    if k_pts < cnt:
                        pos = np.floor(np.arange(k_pts) * (cnt - 1)
                                       / (k_pts - 1) + 0.5) \
                            .astype(np.int64)
                        v = v[np.clip(pos, 0, cnt - 1)]
                    ent[s.output].append((v, cnt))

        def _pct_value(chunks, frac):
            if not chunks:
                return 0.0, True
            pts = np.concatenate([c[0] for c in chunks])
            w = np.concatenate([np.full(len(c[0]), c[1] / len(c[0]))
                                for c in chunks])
            order = np.argsort(pts, kind="stable")
            cum = np.cumsum(w[order])
            total = sum(c[1] for c in chunks)
            target = np.floor(frac * max(total - 1, 0) + 0.5)
            idx = int(np.searchsorted(cum, target, side="right"))
            return float(pts[order][min(idx, len(pts) - 1)]), False

        # --- non-percentile aggregates: exact scatter hash over the
        # bucket (keys are few, so a small table suffices) ---
        key_batch0 = next(iter(bstore.bucket_batches(p, cfg.batch_rows)))
        key_dtypes = [key_batch0.columns[k].values.dtype
                      for k in key_names]
        key_dicts = {k: key_batch0.columns[k].dictionary
                     for k in key_names
                     if key_batch0.columns[k].dictionary is not None}
        key_lazy = {k: key_batch0.columns[k].lazy for k in key_names
                    if key_batch0.columns[k].lazy is not None}
        out_batch = None
        if other_specs:
            names = tuple(key_names)

            def update(num_slots):
                jk = ("skewagg", names, other_specs, num_slots)
                upd = self._jit_cache.get(jk)
                if upd is None:
                    @jit_as("agg_skew_update")
                    def upd(state, b):
                        kc = [b.columns[k] for k in key_names]
                        ac = {s.output: (low.eval(
                            input_exprs[s.output], b)
                            if input_exprs[s.output] is not None
                            else None) for s in other_specs}
                        ac2 = {s.output: low.eval(
                            input_exprs2[s.output], b)
                            for s in other_specs
                            if s.name in ops.CORR_AGGS}
                        return ops.agg_update(
                            state, b, kc, ac, other_specs,
                            num_slots, 0, names, ac2)
                    self._jit_cache[jk] = upd
                return upd

            def grow(_old_slots, new_slots):
                return jit_as("agg_skew_grow")(lambda state: ops.agg_merge(
                    ops.agg_init(new_slots, other_specs, names, key_dtypes),
                    state, other_specs, names, new_slots))

            held = [_agg_state_bytes(256, key_names, other_specs)]
            pool.reserve(held[0])

            def regrow(n):
                # (raises where the budget cannot hold the grown table)
                est = _agg_state_bytes(n, key_names, other_specs)
                pool.reserve(est)
                pool.free(held[0])
                held[0] = est
                return True
            try:
                state, _slots = hash_aggregate(
                    ops.agg_init(256, other_specs, names, key_dtypes), [],
                    256, bstore.bucket_batches(p, cfg.batch_rows), update,
                    grow, regrow)
                out_batch = ops.agg_finalize(
                    state, other_specs, names, key_dicts, key_lazy)
            finally:
                pool.free(held[0])
            # attach percentile columns by key lookup on the host
            kcols = [np.asarray(out_batch.columns[k].values)
                     for k in key_names]
            knulls = [None if out_batch.columns[k].nulls is None
                      else np.asarray(out_batch.columns[k].nulls)
                      for k in key_names]
            mask = np.asarray(out_batch.mask)
            cap = out_batch.capacity
            new_cols = dict(out_batch.columns)
            for s in pct_specs:
                vals = np.zeros(cap, dtype=np.float64)
                nulls = np.ones(cap, dtype=bool)
                for i in range(cap):
                    if not mask[i]:
                        continue
                    t = tuple(
                        (None if (knulls[j] is not None and knulls[j][i])
                         else kcols[j].item(i))
                        for j in range(len(key_names)))
                    ent = per_key.get(t)
                    if ent is None:
                        continue
                    frac = float(s.param if s.param is not None else 0.5)
                    v, isnull = _pct_value(ent[s.output], frac)
                    vals[i], nulls[i] = v, isnull
                arr = (jnp.asarray(vals) if s.is_float
                       else jnp.asarray(vals).astype(jnp.int64))
                new_cols[s.output] = Column(arr, jnp.asarray(nulls))
            return Batch(new_cols, out_batch.mask)

        # percentile-only aggregation: build the output from the host map
        keys = sorted(per_key, key=lambda t: tuple(
            (v is None, v) for v in t))
        cap = max(1, len(keys))
        cols: Dict[str, Column] = {}
        for j, k in enumerate(key_names):
            kv = np.zeros(cap, dtype=key_dtypes[j])
            kn = np.zeros(cap, dtype=bool)
            for i, t in enumerate(keys):
                if t[j] is None:
                    kn[i] = True
                else:
                    kv[i] = t[j]
            cols[k] = Column(jnp.asarray(kv),
                             jnp.asarray(kn) if kn.any() else None,
                             key_dicts.get(k), key_lazy.get(k))
        for s in pct_specs:
            frac = float(s.param if s.param is not None else 0.5)
            vals = np.zeros(cap, dtype=np.float64)
            nulls = np.ones(cap, dtype=bool)
            for i, t in enumerate(keys):
                vals[i], nulls[i] = _pct_value(per_key[t][s.output], frac)
            arr = (jnp.asarray(vals) if s.is_float
                   else jnp.asarray(vals).astype(jnp.int64))
            cols[s.output] = Column(arr, jnp.asarray(nulls))
        mask = np.zeros(cap, dtype=bool)
        mask[:len(keys)] = True
        return Batch(cols, jnp.asarray(mask))

    # -- joins ------------------------------------------------------------
    def _splits_fingerprint(self, node: P.PlanNode) -> str:
        """Task-assigned splits under a subtree, in walk order — part of
        the structural result-cache key: two structurally equal subtrees
        only share data when their scans cover the same splits."""
        parts = []
        for n in P.walk_plan(node):
            if isinstance(n, P.TableScanNode):
                sp = self.ctx.splits.get(n.id)
                parts.append("-" if sp is None else json.dumps(
                    [s.to_dict() for s in sp], sort_keys=True))
        return "|".join(parts)

    def _materialize_node(self, node: P.PlanNode,
                          dense: Optional[str] = None) -> Optional[Batch]:
        """Materialize a subtree's full output as one batch, via the fused
        single-program path when the subtree is a fusible chain (zero host
        syncs), else by draining the streaming source (`dense`: through
        `dense_batches` under that key).  Nothing is kept: a join's build
        side is remembered by `shared_build`."""
        from .fused import fused_materialize
        b = fused_materialize(self, node)
        if b is not None:
            return b
        batches = self._compile(node).batches()
        batches = list(self._dense(batches, dense) if dense else batches)
        return _compact_concat(batches) if batches else None

    def _compile_JoinNode(self, node: P.JoinNode) -> BatchSource:
        if node.join_type not in (P.INNER, P.LEFT, P.FULL):
            raise NotImplementedError(f"join type {node.join_type}")
        full = node.join_type == P.FULL
        probe_src_node, build_src_node = node.left, node.right
        probe_keys = [l.name for l, r in node.criteria]
        build_keys = [r.name for l, r in node.criteria]
        out_names = [v.name for v in node.outputs]
        out_types = [v.type for v in node.outputs]
        from .fused import _join_build_cols
        build_names = [v.name for v in build_src_node.output_variables]
        # join outputs plus ON-filter-referenced build columns (pruning
        # may have dropped the latter from the output list)
        build_out = _join_build_cols(node, out_names, set(build_names))
        cfg = self.ctx.config
        low = self.lowering
        filter_expr = node.filter

        from .lowering import _jnp_dtype
        build_types = {v.name: v.type
                       for v in build_src_node.output_variables}

        def null_extended(batch):
            # LEFT join rows with no build match
            cols = dict(batch.columns)
            for name in build_out:
                t = build_types[name]
                if isinstance(t, (VarcharType, CharType)):
                    col = Column(
                        jnp.zeros(batch.capacity, dtype=jnp.int32),
                        jnp.ones(batch.capacity, dtype=bool), ("",))
                else:
                    col = Column(
                        jnp.zeros(batch.capacity, dtype=_jnp_dtype(t)),
                        jnp.ones(batch.capacity, dtype=bool))
                cols[name] = col
            return Batch(cols, batch.mask).select(out_names)

        filter_fn = (None if filter_expr is None
                     else (lambda pairs: low.eval(filter_expr, pairs)))

        def _jstep(batch, table, matched=None):
            joined, overflow, total, matched = ops.probe_join(
                batch, table, probe_keys, build_out,
                cfg.join_out_capacity,
                join_type="LEFT" if full else node.join_type,
                filter_fn=filter_fn, matched=matched)
            return (joined, overflow, total, matched,
                    jnp.sum(batch.mask, dtype=jnp.int32))

        step = self.shared_jit(node, "join_step", _jstep)
        # an INNER probe without an ON filter writes its pairs as a dense
        # prefix of the padded output (probe_join: out_mask = j < total)
        prefix_dense = node.join_type == P.INNER and filter_expr is None

        def trimmed(joined, live):
            """A joined batch whose out_capacity padding dominates, cut to
            the bucket that holds its rows: downstream per-batch work
            (hash-agg scatter rounds, further probes) scales with
            CAPACITY.  A slice, not a compaction: the probe input is dense
            (dense_batches), so only an output that is a dense prefix
            already is worth cutting."""
            bucket = _bucket_for(int(live)) if prefix_dense else None
            if bucket is None or bucket * 4 > joined.capacity:
                return joined
            return _jit_prefix(joined, bucket)

        rs = self.ctx.runtime_stats

        def count(name, value):
            if rs is not None:
                rs.add(name, value)

        def timed(name, batches):
            """`batches`, the time inside each pull under the span `name`
            (the consumer's time between pulls is not the stream's)."""
            it = iter(batches)
            while True:
                with _span(rs, name):
                    b = next(it, None)
                if b is None:
                    return
                yield b

        probe_names = [n for n in out_names if n not in build_out]

        def unmatched_build(build_batch, matched):
            """FULL: build rows no probe row matched, probe side nulled."""
            from .lowering import _jnp_dtype
            probe_types = {v.name: v.type
                           for v in node.left.output_variables}
            cap = build_batch.capacity
            cols = {}
            for name in build_out:
                cols[name] = build_batch.columns[name]
            for name in probe_names:
                t = probe_types[name]
                if isinstance(t, (VarcharType, CharType)):
                    cols[name] = Column(jnp.zeros(cap, dtype=jnp.int32),
                                        jnp.ones(cap, dtype=bool), ("",))
                else:
                    cols[name] = Column(jnp.zeros(cap, dtype=_jnp_dtype(t)),
                                        jnp.ones(cap, dtype=bool))
            return Batch(cols, build_batch.mask & ~matched) \
                .select(out_names)

        # dynamic filtering (reference DynamicFilterSourceOperator): once
        # the build side is materialized, its per-key min/max narrows the
        # probe stream before the (more expensive) probe step; counted in
        # EXPLAIN ANALYZE stats as dynamicFilterRowsDropped
        df_cache: dict = {}

        def make_dynamic_filter(jb):
            # INNER only: LEFT joins carry dynamic_filters keyed by their
            # BUILD variables (the probe is preserved and must never be
            # narrowed — see plan_dynamic_filters' direction convention)
            build_batch = jb.batch
            if node.join_type != P.INNER or not node.dynamic_filters \
                    or build_batch is None:
                return None
            pairs = [(l.name, r.name) for l, r in node.criteria]
            numeric = [(ln, rn) for ln, rn in pairs
                       if build_batch.columns[rn].dictionary is None
                       and build_batch.columns[rn].lazy is None
                       and jnp.issubdtype(
                           build_batch.columns[rn].values.dtype,
                           jnp.integer)]
            if not numeric:
                return None
            if "fn" not in df_cache:
                names = tuple(rn for _ln, rn in numeric)
                probe_names = tuple(ln for ln, _rn in numeric)

                def _bounds(bb):
                    out = []
                    for rn in names:
                        c = bb.columns[rn]
                        m = bb.mask if c.nulls is None                             else bb.mask & ~c.nulls
                        v = c.values
                        out.append((
                            jnp.min(jnp.where(m, v, jnp.iinfo(v.dtype).max)),
                            jnp.max(jnp.where(m, v, jnp.iinfo(v.dtype).min))))
                    return out

                def _apply(batch, bnds):
                    keep = batch.mask
                    for (ln, lohis) in zip(probe_names, bnds):
                        lo, hi = lohis
                        v = batch.columns[ln].values
                        keep = keep & (v >= lo) & (v <= hi)
                    return batch.with_mask(keep)

                df_cache["fn"] = (
                    self.shared_jit(node, "df_bounds", _bounds,
                                    extra=(names,)),
                    self.shared_jit(node, "df_apply", _apply,
                                    extra=(probe_names,)))
            bounds, apply = df_cache["fn"]
            # the bounds are the build side's: computed once an entry
            names = tuple(rn for _ln, rn in numeric)
            bnds = jb.memo.get(("df_bounds", names))
            if bnds is None:
                bnds = jb.memo[("df_bounds", names)] = bounds(build_batch)
            return lambda batch: apply(batch, bnds)

        def gen():
            pool = self.ctx.memory
            from .fused import fused_dense_stream, fused_stream
            # a join fused into its probe's scan chain: as dense batches
            # where the chain's own counts say it leaves few rows, else
            # chunk by chunk
            fs = fused_dense_stream(self, node) or fused_stream(self, node)
            if fs is not None:
                for b in fs:
                    yield b.select(out_names)
                return

            def probe_input(batches, dyn_filter):
                """The probe side as the join steps take it: narrowed by
                the build side's key bounds, then dense."""
                stats_ent = None
                if dyn_filter is not None and self.ctx.stats is not None:
                    stats_ent = self.ctx.stats.setdefault(
                        node.id, {"rows": 0, "wall_s": 0.0, "batches": 0})
                    stats_ent.setdefault("dynamicFilterRowsDropped", 0)
                return self._dense(
                    _apply_dyn_filter(iter(batches), dyn_filter, stats_ent),
                    "probeCoalesce")

            def probe_stream(table, batches, build_batch=None,
                             dyn_filter=None):
                yield from timed("joinProbe", _probe_stream_inner(
                    table, probe_input(batches, dyn_filter), build_batch))

            def _jdirect(batch, dt, matched, rows):
                out, matched = ops.probe_join_direct(
                    batch, dt, probe_keys[0], build_out,
                    join_type="LEFT" if full else node.join_type,
                    filter_fn=filter_fn, matched=matched)
                # rows in and out, summed on the device: fetched once
                return out, matched, rows + jnp.stack(
                    [jnp.sum(batch.mask, dtype=jnp.int64),
                     jnp.sum(out.mask, dtype=jnp.int64)])

            step_direct = self.shared_jit(node, "join_direct", _jdirect)

            def probe_stream_direct(dt, batches, build_batch,
                                    dyn_filter=None):
                yield from timed("joinProbe", _probe_stream_direct(
                    dt, probe_input(batches, dyn_filter), build_batch))

            def _probe_stream_direct(dt, batches, build_batch):
                matched = (jnp.zeros(build_batch.capacity, dtype=bool)
                           if full else None)
                rows, steps = jnp.zeros(2, dtype=jnp.int64), 0
                try:
                    for b in batches:
                        out, matched, rows = step_direct(b, dt, matched,
                                                         rows)
                        steps += 1
                        yield out.select(out_names)
                    if full:
                        yield unmatched_build(build_batch, matched)
                finally:
                    if steps and rs is not None:
                        rows_in, rows_out = host_get(rows, "join_stats")
                        count("joinProbeBatches", steps)
                        count("joinProbeRowsIn", int(rows_in))
                        count("joinOutputRows", int(rows_out))

            def _probe_stream_inner(table, batches, build_batch=None):
                # matched is threaded through for FULL joins; the build
                # rows nobody matched are emitted null-extended at the end
                matched = (jnp.zeros(build_batch.capacity, dtype=bool)
                           if full else None)
                # windowed drains: dispatch up to K probe batches, then
                # fetch ALL their (overflow, live) scalars in ONE
                # device_get — one host sync per K batches instead of
                # per batch.  K shrinks as join_out_capacity grows so the
                # in-flight padded join outputs stay bounded in HBM.
                from collections import deque
                work = deque()
                inflight = deque()   # (piece, joined, overflow, total)
                K = max(2, min(8, (1 << 22) // max(1,
                                                   cfg.join_out_capacity)))

                def submit(piece):
                    nonlocal matched
                    joined, overflow, total, matched, rows_in = step(
                        piece, table, matched)
                    count("joinProbeBatches", 1)
                    inflight.append((piece, joined, overflow, total,
                                     rows_in))

                batches = iter(batches)
                exhausted = False
                while True:
                    # overflow-split pieces (work) refill regardless of
                    # iterator exhaustion — only NEW batches stop coming
                    while len(inflight) < K:
                        if work:
                            submit(work.popleft())
                            continue
                        if exhausted:
                            break
                        nxt = next(batches, None)
                        if nxt is None:
                            exhausted = True
                            break
                        submit(nxt)
                    if not inflight:
                        break
                    metas = host_get(
                        [(ov, tot, n) for _p, _j, ov, tot, n in inflight],
                        "join_overflow")
                    window = list(inflight)
                    inflight.clear()
                    for (piece, joined, _o, _t, _n), (ovv, livev, rows_in) \
                            in zip(window, metas):
                        if bool(ovv):
                            # recursive halving on output overflow: high-
                            # fanout probes (worst case a constant-key
                            # cross join) split until each piece fits
                            if piece.capacity <= 1:
                                raise RuntimeError(
                                    "join output overflow on a single "
                                    "probe row: raise join_out_capacity")
                            work.extendleft(reversed(_split_batch(piece)))
                            continue
                        count("joinProbeRowsIn", int(rows_in))
                        count("joinOutputRows", int(livev))
                        yield trimmed(joined, livev).select(out_names)
                if full:
                    yield unmatched_build(build_batch, matched)

            # the build side: through the door that remembers build
            # sides (`shared_build`) where memory is unbudgeted; under a
            # budget staged batch by batch, and the staging reservation
            # is REVOCABLE — either this loop's own budget miss or the
            # arbitrator (another operator starving) converts it into a
            # grace hash join's partitioned host store (reference:
            # HashBuilderOperator.java:56 revocable memory + partitioned
            # spilling)
            from .fused import DirectTable, finish_join_build, join_build
            buf = (_RevocableBuildBuffer(self, build_keys, cfg.spill_enabled)
                   if pool.limited else None)
            held = 0
            try:
                with _span(rs, "joinBuild"):
                    spill = None
                    if buf is None:
                        jb = join_build(self, build_src_node,
                                        tuple(build_keys), True,
                                        dense="buildCoalesce")
                        # whoever built it, this task holds it while it
                        # probes: its bytes count toward the task's peak
                        nb = jb.nbytes
                        held = nb if pool.try_reserve(nb) else 0
                    else:
                        for b in self._compile(build_src_node).batches():
                            buf.add(b)
                        collected, spill = buf.finish()
                        if spill is None:
                            jb = finish_join_build(
                                _compact_concat(collected) if collected
                                else None, tuple(build_keys), True)
                if spill is None:
                    build_batch = jb.batch
                    probe = self._compile(probe_src_node)
                    if build_batch is None:
                        if node.join_type == P.INNER:
                            return
                        for batch in probe.batches():
                            yield null_extended(batch)
                        return
                    if rs is not None:
                        with _span(rs, "joinBuild"):
                            count("joinBuildRows", jb.rows())
                    dyn_filter = make_dynamic_filter(jb)
                    if isinstance(jb.table, DirectTable):
                        # dense unique integer key: fanout-1 direct probe,
                        # zero per-batch host syncs (no overflow/live
                        # fetch — output capacity == probe capacity)
                        yield from probe_stream_direct(
                            jb.table, probe.batches(), build_batch,
                            dyn_filter=dyn_filter)
                        return
                    yield from probe_stream(
                        jb.table, probe.batches(), build_batch,
                        dyn_filter=dyn_filter)
                    return
                # grace path: partition the probe the same way, join
                # bucket-by-bucket (each bucket is a Lifespan).  A bucket
                # whose build side still exceeds the budget is RE-partitioned
                # with a fresh hash salt (recursive grace join); only a
                # bucket that stops shrinking — single-key skew — fails.
                probe_store = self._new_spill_store()
                for b in self._compile(probe_src_node).batches():
                    probe_store.add(b, probe_keys)
                work = [(spill, probe_store, p, 0)
                        for p in range(cfg.spill_partitions)]
                while work:
                    bstore, pstore, p, depth = work.pop()
                    p_rows = pstore.bucket_rows(p)
                    b_rows = bstore.bucket_rows(p)
                    # FULL still visits probe-empty buckets: their build
                    # rows must be emitted null-extended
                    if p_rows == 0 and (not full or b_rows == 0):
                        continue
                    if b_rows == 0:
                        if node.join_type == P.INNER:
                            continue
                        yield from map(null_extended,
                                       pstore.bucket_batches(
                                           p, cfg.batch_rows))
                        continue
                    # power-of-two build capacity bounds jit recompiles;
                    # the bucket goes back on device, so account for it
                    bcap = 1 << max(0, b_rows - 1).bit_length()
                    bucket_bytes = bstore.bucket_bytes(p) * bcap \
                        // max(1, b_rows)
                    if not pool.try_reserve(bucket_bytes):
                        if depth >= 4:
                            raise MemoryExceededError(
                                f"join build bucket of {bucket_bytes} bytes "
                                f"exceeds memory budget {pool.budget} after "
                                f"{depth} re-partitions (key skew)")
                        salt2 = bstore.salt * 33 + 0x9E37
                        sub_b = self._new_spill_store(salt2)
                        for bb in bstore.bucket_batches(p, cfg.batch_rows):
                            sub_b.add(bb, build_keys)
                        sub_p = self._new_spill_store(salt2)
                        for pb in pstore.bucket_batches(p, cfg.batch_rows):
                            sub_p.add(pb, probe_keys)
                        work.extend((sub_b, sub_p, q, depth + 1)
                                    for q in range(cfg.spill_partitions))
                        continue
                    try:
                        from .fused import _drop_null_keys
                        bucket = list(bstore.bucket_batches(p, bcap))[0]
                        table = _jits()[1](
                            _drop_null_keys(bucket, tuple(build_keys)),
                            tuple(build_keys))
                        yield from probe_stream(
                            table,
                            pstore.bucket_batches(p, cfg.batch_rows),
                            bucket)
                    finally:
                        pool.free(bucket_bytes)
            finally:
                if buf is not None:
                    buf.close()
                if held:
                    pool.free(held)
        return BatchSource(gen, out_names, out_types)

    def _compile_SemiJoinNode(self, node: P.SemiJoinNode) -> BatchSource:
        src = self._compile(node.source)
        names = src.names + [node.semi_join_output.name]
        types = src.types + [BOOLEAN]
        key = node.source_join_variable.name
        fkey = node.filtering_source_join_variable.name

        marker_name = node.semi_join_output.name

        def step(batch, table, build_has_null):
            marker = ops.semi_join_mark(batch, table, [key],
                                        build_has_null=build_has_null)
            return batch.with_columns({marker_name: marker})

        def step_direct(batch, dt, build_has_null):
            marker = ops.semi_join_mark_direct(
                batch, dt, key, build_has_null=build_has_null)
            return batch.with_columns({marker_name: marker})

        step = self.shared_jit(node, "semi_join_step", step,
                               static_argnames=("build_has_null",))
        step_direct = self.shared_jit(node, "semi_join_step_direct",
                                      step_direct,
                                      static_argnames=("build_has_null",))

        def gen():
            from .fused import fused_stream
            fs = fused_stream(self, node)
            if fs is not None:
                yield from (b.select(names) for b in fs)
                return
            from .fused import DirectTable, join_build
            jb = join_build(self, node.filtering_source, (fkey,), False)
            if jb.batch is None:
                for b in src.batches():
                    yield b.with_columns({node.semi_join_output.name: Column(
                        jnp.zeros(b.capacity, dtype=bool), None)})
                return
            probe = step_direct if isinstance(jb.table, DirectTable) else step
            for b in src.batches():
                yield probe(b, jb.table, jb.had_null)
        return BatchSource(gen, names, types)

    def _compile_AssignUniqueIdNode(self, node: P.AssignUniqueIdNode) -> BatchSource:
        """Row ids unique within the query (reference
        AssignUniqueIdOperator): task index in the high bits, a running
        per-task offset below.  Deterministic for a fixed split assignment,
        so a deep-copied subtree replays identical ids (the decorrelated
        EXISTS plan relies on this)."""
        src = self._compile(node.source)
        names = src.names + [node.id_variable.name]
        types = src.types + [v.type for v in [node.id_variable]]
        base = self.ctx.task_index << 40
        id_name = node.id_variable.name

        def gen():
            offset = 0
            for b in src.batches():
                ids = jnp.arange(b.capacity, dtype=jnp.int64) + (base + offset)
                offset += b.capacity
                yield b.with_columns({id_name: Column(ids)})
        return BatchSource(gen, names, types)

    def _compile_EnforceSingleRowNode(self, node) -> BatchSource:
        src = self._compile(node.source)

        def gen():
            seen = 0
            for b in src.batches():
                seen += int(b.mask.sum())
                if seen > 1:
                    raise RuntimeError(
                        "scalar subquery produced more than one row")
                yield b
        return BatchSource(gen, src.names, src.types)

    # -- local exchange is a no-op in the single-task pipeline ------------
    def _compile_ExchangeNode(self, node: P.ExchangeNode) -> BatchSource:
        if len(node.exchange_sources) == 1 and not node.inputs:
            return self._compile(node.exchange_sources[0])
        sources = [self._compile(s) for s in node.exchange_sources]
        out_vars = node.partitioning_scheme.output_layout
        names = [v.name for v in out_vars]
        types = [v.type for v in out_vars]

        def gen():
            for i, s in enumerate(sources):
                in_names = ([v.name for v in node.inputs[i]]
                            if node.inputs else s.names)
                for b in s.batches():
                    cols = {o: b.columns[n] for o, n in zip(names, in_names)}
                    yield Batch(cols, b.mask)
        return BatchSource(gen, names, types)


# ---------------------------------------------------------------------------
# host hoisting of string functions over late-materialized columns
#
# like()/substr() over open-domain columns (tpch.OPEN_DOMAIN) cannot run
# inside jit: the column holds row ids, the strings exist only in the
# generator.  The compiler rewrites such calls into synthetic variables and
# computes them per batch on the host before the jitted step — the TPU
# analog of the reference's ScanFilterAndProjectOperator evaluating
# non-vectorizable functions row-wise during the scan.
# ---------------------------------------------------------------------------


def _agg_state_bytes(num_slots: int, key_names, specs) -> int:
    """Accumulator footprint estimate shared by every aggregation budget
    check (hash + occupied + per-key value/null + per-aggregate state
    columns) — ONE formula so a state-layout change cannot drift the
    reservation paths apart."""
    return num_slots * (16 + 12 * len(key_names)
                        + 24 * max(1, len(specs))
                        + ops.hll_state_bytes(specs))


def _rewrite_agg_masks(node: P.AggregationNode) -> P.AggregationNode:
    """Lower Aggregation.mask (the reference's FILTER-WHERE / mask channel,
    AggregationNode.java Aggregation) into masked inputs: every aggregate
    in the engine ignores NULL inputs, so  agg(x) MASK m  ==
    agg(IF(m, x, NULL))  and  count(*) MASK m == count(IF(m, 1, NULL))."""
    if not any(a.mask is not None for a in node.aggregations.values()):
        return node
    from ..spi.expr import ConstantExpression, special
    aggs = {}
    for v, a in node.aggregations.items():
        if a.mask is None:
            aggs[v] = a
            continue
        call_ = a.call
        if call_.arguments:
            arg0 = call_.arguments[0]
            masked = special("IF", arg0.type, a.mask, arg0,
                             ConstantExpression(None, arg0.type))
            call_ = CallExpression(call_.display_name, call_.type,
                                   [masked] + list(call_.arguments[1:]))
        else:                       # count(*)
            masked = special("IF", BIGINT, a.mask,
                             ConstantExpression(1, BIGINT),
                             ConstantExpression(None, BIGINT))
            call_ = CallExpression(call_.display_name, call_.type, [masked])
        aggs[v] = P.Aggregation(call_, a.distinct, None)
    return P.AggregationNode(node.id, node.source, aggs,
                             node.grouping_keys, node.step)


def _direct_mode_info(key_names, key_cols,
                      gmax: int = ops.DIRECT_AGG_MAX_GROUPS):
    """Closed-small-domain eligibility for direct aggregation, shared by the
    streaming (run_once) and fused (get_fused) paths — must stay consistent
    with ops.agg_direct_finalize's slot decode.  key_cols may be real Columns
    or jax.eval_shape results (only dtype/nulls/dictionary/lazy are read).
    Returns None when ineligible, else
    (doms, G, strides, key_dtypes, key_dicts)."""
    doms = []
    for c in key_cols:
        if c.nulls is not None or c.lazy is not None:
            return None
        if c.dictionary is not None:
            doms.append(len(c.dictionary))
        elif c.values.dtype == jnp.bool_:
            doms.append(2)
        else:
            return None
    G = 1
    for d in doms:
        G *= max(1, d)
    if key_names and G > gmax:
        return None
    G = max(1, G)
    doms = tuple(max(1, d) for d in doms)
    strides, s = [], G
    for d in doms:
        s //= d
        strides.append(s)
    key_dicts = {k: c.dictionary for k, c in zip(key_names, key_cols)
                 if c.dictionary is not None}
    return (doms, G, tuple(strides),
            tuple(c.values.dtype for c in key_cols), key_dicts)


class _StringHoister:
    """Finds like/substr calls rooted at a variable, and — once the first
    batch shows which of those variables are late-materialized — rewrites
    them into host-computed columns."""

    def __init__(self, exprs):
        self.exprs = list(exprs)
        self.candidates: Dict[str, CallExpression] = {}
        for e in self.exprs:
            _find_string_calls(e, self.candidates)

    def resolve(self, first_batch: Batch):
        active: Dict[str, Tuple] = {}
        for key, c in self.candidates.items():
            col = first_batch.columns.get(_hoistable_var(c).name)
            if col is not None and col.lazy is not None:
                var = VariableReferenceExpression(
                    f"__hoist_{len(active)}_{abs(hash(key)) % 10**8}", c.type)
                active[key] = (var, c)
        if not active:
            return self.exprs, {}
        table = {k: v for k, (v, _) in active.items()}
        rewritten = [_rewrite_expr(e, table) for e in self.exprs]
        hoisted = {v.name: c for v, c in active.values()}
        return rewritten, hoisted


def _hoist_key(e: RowExpression) -> str:
    return json.dumps(e.to_dict(), sort_keys=True, default=str)


# lazy-column-hoistable string-breadth functions: column first, constant
# extras, never-NULL results (the xform caches carry no null channel)
_HOIST_XFORM = ("regexp_replace",)
_HOIST_PRED = ("regexp_like", "starts_with", "ends_with")


def _hoistable_var(e: CallExpression):
    """The single column argument of a host-hoistable string call, or
    None.  like/substr take the column first; concat takes one column
    anywhere among constant parts."""
    name = canonical_name(e.display_name)
    if name in ("like", "substr") + _HOIST_XFORM + _HOIST_PRED \
            and e.arguments and isinstance(
                e.arguments[0], VariableReferenceExpression) \
            and all(isinstance(a, ConstantExpression)
                    for a in e.arguments[1:]):
        return e.arguments[0]
    if name == "concat":
        var_args = [a for a in e.arguments
                    if isinstance(a, VariableReferenceExpression)]
        from ..spi.expr import ConstantExpression as _CE
        # a NULL constant part makes every result NULL — not hoistable
        # as a string transform (str(None) would bake the text "None")
        if len(var_args) == 1 and all(
                isinstance(a, _CE) and a.value is not None
                for a in e.arguments
                if not isinstance(a, VariableReferenceExpression)):
            return var_args[0]
    return None


def _find_string_calls(e: RowExpression, out: Dict[str, CallExpression]):
    if isinstance(e, CallExpression) and _hoistable_var(e) is not None:
        out[_hoist_key(e)] = e
        return
    for a in getattr(e, "arguments", None) or []:
        _find_string_calls(a, out)


def _rewrite_expr(e: RowExpression, table: Dict[str, RowExpression]):
    if isinstance(e, CallExpression):
        k = _hoist_key(e)
        if k in table:
            return table[k]
        return CallExpression(e.display_name, e.type,
                              [_rewrite_expr(a, table) for a in e.arguments])
    from ..spi.expr import SpecialFormExpression
    if isinstance(e, SpecialFormExpression):
        return SpecialFormExpression(
            e.form, e.type, [_rewrite_expr(a, table) for a in e.arguments])
    return e


_SUBSTR_DICT_CACHE: Dict[Tuple, Tuple[str, ...]] = {}
# whole-column substr codes / LIKE masks, indexed by row id: computed ONCE
# per (column, call) then every batch is a vectorized gather — re-running
# the Python string generator per batch per call site dominated q22-class
# queries (three substr sites over customer.phone cost ~10s each per run)
_SUBSTR_CODES_CACHE: Dict[Tuple, np.ndarray] = {}
_LIKE_MASK_CACHE: Dict[Tuple, np.ndarray] = {}
# entries are O(table rows): bound both caches (FIFO evict) so a
# long-lived worker serving varied patterns/scale factors cannot grow
# host memory without limit
_COLUMN_CACHE_MAX_ENTRIES = 64


def _cache_put(cache: Dict[Tuple, np.ndarray], key, value) -> None:
    if len(cache) >= _COLUMN_CACHE_MAX_ENTRIES:
        cache.pop(next(iter(cache)))
    cache[key] = value


def _canonical_substr_dict(cid: str, table: str, column: str, sf: float,
                           start: int, length) -> Tuple[str, ...]:
    """Batch-independent (whole-column) dictionary for substr over an
    open-domain column, so codes are stable across batches and sorted-rank
    ordering holds for ORDER BY / GROUP BY consumers."""
    key = (cid, table, column, sf, start, length)
    if key not in _SUBSTR_DICT_CACHE:
        n = catalog.table_row_count(table, sf, cid)
        uniq = set()
        for pos in range(0, n, 1 << 18):
            cnt = min(1 << 18, n - pos)
            strings = catalog.generate_values_at(
                table, column, sf, np.arange(pos, pos + cnt, dtype=np.int64),
                cid)
            uniq.update(_py_substr(s, start, length) for s in strings)
        _SUBSTR_DICT_CACHE[key] = tuple(sorted(uniq))
    return _SUBSTR_DICT_CACHE[key]


def _column_substr_codes(cid: str, table: str, column: str, sf: float,
                         start: int, length) -> np.ndarray:
    """int32 substr dictionary codes for EVERY row of the column."""
    from .. import native
    key = (cid, table, column, sf, start, length)
    codes_all = _SUBSTR_CODES_CACHE.get(key)
    if codes_all is None:
        cdict = _canonical_substr_dict(cid, table, column, sf, start,
                                       length)
        n = catalog.table_row_count(table, sf, cid)
        codes_all = np.empty(n, dtype=np.int32)
        index = None
        for pos in range(0, n, 1 << 18):
            cnt = min(1 << 18, n - pos)
            strings = catalog.generate_values_at(
                table, column, sf,
                np.arange(pos, pos + cnt, dtype=np.int64), cid)
            chunk = native.substr_dict_encode(strings, start, length, cdict)
            if chunk is None:
                if index is None:
                    index = {s: i for i, s in enumerate(cdict)}
                chunk = np.fromiter(
                    (index[_py_substr(s, start, length)] for s in strings),
                    dtype=np.int32, count=cnt)
            codes_all[pos:pos + cnt] = chunk
        _cache_put(_SUBSTR_CODES_CACHE, key, codes_all)
    return codes_all


def _column_like_mask(cid: str, table: str, column: str, sf: float,
                      pattern: str) -> np.ndarray:
    """LIKE match results for EVERY row of the column."""
    from .lowering import like_matcher
    from .. import native
    key = (cid, table, column, sf, pattern)
    mask_all = _LIKE_MASK_CACHE.get(key)
    if mask_all is None:
        n = catalog.table_row_count(table, sf, cid)
        mask_all = np.empty(n, dtype=bool)
        match = None
        for pos in range(0, n, 1 << 18):
            cnt = min(1 << 18, n - pos)
            strings = catalog.generate_values_at(
                table, column, sf,
                np.arange(pos, pos + cnt, dtype=np.int64), cid)
            chunk = native.like_match(strings, pattern)
            if chunk is None:
                if match is None:
                    match = like_matcher(pattern)
                chunk = np.fromiter((match(s) for s in strings),
                                    dtype=bool, count=cnt)
            mask_all[pos:pos + cnt] = chunk
        _cache_put(_LIKE_MASK_CACHE, key, mask_all)
    return mask_all


def _py_substr(s: str, start: int, length) -> str:
    i = start - 1 if start > 0 else len(s) + start
    return s[i:i + length] if length is not None else s[i:]


# whole-column codes for arbitrary per-string transforms (concat with
# constant parts etc.), sharing the bounded-cache discipline
_XFORM_DICT_CACHE: Dict[Tuple, Tuple[str, ...]] = {}
_XFORM_CODES_CACHE: Dict[Tuple, np.ndarray] = {}


def _column_xform_codes(cid, table, column, sf, tag, fn):
    key = (cid, table, column, sf, tag)
    cdict = _XFORM_DICT_CACHE.get(key)
    codes_all = _XFORM_CODES_CACHE.get(key)
    if cdict is None or codes_all is None:
        n = catalog.table_row_count(table, sf, cid)
        uniq = set()
        for pos in range(0, n, 1 << 18):
            cnt = min(1 << 18, n - pos)
            strings = catalog.generate_values_at(
                table, column, sf,
                np.arange(pos, pos + cnt, dtype=np.int64), cid)
            uniq.update(fn(x) for x in strings)
        cdict = tuple(sorted(uniq))
        index = {x: i for i, x in enumerate(cdict)}
        codes_all = np.empty(n, dtype=np.int32)
        for pos in range(0, n, 1 << 18):
            cnt = min(1 << 18, n - pos)
            strings = catalog.generate_values_at(
                table, column, sf,
                np.arange(pos, pos + cnt, dtype=np.int64), cid)
            codes_all[pos:pos + cnt] = np.fromiter(
                (index[fn(x)] for x in strings), dtype=np.int32, count=cnt)
        _cache_put(_XFORM_DICT_CACHE, key, cdict)
        _cache_put(_XFORM_CODES_CACHE, key, codes_all)
    return cdict, codes_all


_PRED_VALUE_CACHE: Dict[Tuple, np.ndarray] = {}


def _column_pred_values(cid, table, column, sf, tag, fn, dtype):
    """Per-row results of a value-returning string kernel over the whole
    column (the _column_like_mask pattern, generalized)."""
    key = (cid, table, column, sf, tag)
    out = _PRED_VALUE_CACHE.get(key)
    if out is None:
        n = catalog.table_row_count(table, sf, cid)
        out = np.empty(n, dtype=dtype)
        for pos in range(0, n, 1 << 18):
            cnt = min(1 << 18, n - pos)
            strings = catalog.generate_values_at(
                table, column, sf,
                np.arange(pos, pos + cnt, dtype=np.int64), cid)
            out[pos:pos + cnt] = np.fromiter(
                (fn(x) for x in strings), dtype=dtype, count=cnt)
        _cache_put(_PRED_VALUE_CACHE, key, out)
    return out


def _host_string_column(call_expr: CallExpression, batch: Batch) -> Column:
    arg = _hoistable_var(call_expr)
    col = batch.columns[arg.name]
    cid, table, column, sf = col.lazy
    name = canonical_name(call_expr.display_name)
    from .lowering import _STRING_TO_STRING, _STRING_TO_VALUE
    if name in _HOIST_XFORM:
        extra = tuple(a.value for a in call_expr.arguments[1:])
        kern = _STRING_TO_STRING[name]
        cdict, codes_all = _column_xform_codes(
            cid, table, column, sf, (name,) + extra,
            lambda x, _k=kern, _e=extra: _k(x, *_e))
        ids = np.clip(np.asarray(col.values), 0, len(codes_all) - 1)
        return Column(jnp.asarray(codes_all[ids]), col.nulls, cdict)
    if name in _HOIST_PRED:
        extra = tuple(a.value for a in call_expr.arguments[1:])
        kern, dtype = _STRING_TO_VALUE[name]
        vals_all = _column_pred_values(
            cid, table, column, sf, (name,) + extra,
            lambda x, _k=kern, _e=extra: _k(x, *_e), dtype)
        ids = np.clip(np.asarray(col.values), 0, len(vals_all) - 1)
        return Column(jnp.asarray(vals_all[ids]), col.nulls)
    if name == "concat":
        parts = tuple(None if isinstance(a, VariableReferenceExpression)
                      else str(a.value) for a in call_expr.arguments)
        fn = (lambda x, _p=parts: "".join(
            x if p is None else p for p in _p))
        cdict, codes_all = _column_xform_codes(
            cid, table, column, sf, ("concat", parts), fn)
        ids = np.clip(np.asarray(col.values), 0, len(codes_all) - 1)
        return Column(jnp.asarray(codes_all[ids]), col.nulls, cdict)
    if name == "like":
        pattern = str(call_expr.arguments[1].value)
        mask_all = _column_like_mask(cid, table, column, sf, pattern)
        # masked-out lanes may hold arbitrary ids: clamp for the gather
        ids = np.clip(np.asarray(col.values), 0, len(mask_all) - 1)
        return Column(jnp.asarray(mask_all[ids]), col.nulls)
    start = int(call_expr.arguments[1].value)
    length = (int(call_expr.arguments[2].value)
              if len(call_expr.arguments) > 2 else None)
    cdict = _canonical_substr_dict(cid, table, column, sf, start, length)
    codes_all = _column_substr_codes(cid, table, column, sf, start, length)
    ids = np.clip(np.asarray(col.values), 0, len(codes_all) - 1)
    return Column(jnp.asarray(codes_all[ids]), col.nulls, cdict)


def _add_hoisted(batch: Batch, hoisted: Dict[str, CallExpression]) -> Batch:
    if not hoisted:
        return batch
    return batch.with_columns({name: _host_string_column(c, batch)
                               for name, c in hoisted.items()})


_DEV_CODES_CACHE: Dict[Tuple, "jnp.ndarray"] = {}


def _encode_unordered_lazy_keys(batch: Batch, keys: List[str]) -> Batch:
    """Whole-column dictionary-encode any SORT-KEY column whose lazy row
    ids do not already sort like values (sort_indices requires id order ==
    lex order; see catalog.ROWID_ORDERED) — q30/q65-class ORDER BY over
    open-domain strings.  The codes table is uploaded to the device once
    and each batch is a device gather, so a STREAMED consumer (TopN) adds
    no per-batch host sync."""
    new_cols = {}
    for k in keys:
        col = batch.columns.get(k)
        if col is None or col.lazy is None:
            continue
        cid, tbl, coln, sf = col.lazy
        if (tbl, coln) in catalog.ROWID_ORDERED:
            continue
        cdict = _canonical_substr_dict(cid, tbl, coln, sf, 1, None)
        ck = (cid, tbl, coln, sf)
        codes_dev = _DEV_CODES_CACHE.get(ck)
        if codes_dev is None:
            codes_dev = jnp.asarray(
                _column_substr_codes(cid, tbl, coln, sf, 1, None))
            _cache_put(_DEV_CODES_CACHE, ck, codes_dev)
        ids = jnp.clip(col.values, 0, codes_dev.shape[0] - 1)
        new_cols[k] = Column(codes_dev[ids], col.nulls, cdict)
    return batch.with_columns(new_cols) if new_cols else batch


def _encode_lazy_keys(batch: Batch, keys: List[str]) -> Batch:
    """Replace late-materialized key columns by whole-column dictionary
    codes (for GROUP BY on small-pool open-domain columns, where row ids
    would split value groups)."""
    new_cols = {}
    for k in keys:
        col = batch.columns[k]
        cid, table, column, sf = col.lazy
        cdict = _canonical_substr_dict(cid, table, column, sf, 1, None)
        codes_all = _column_substr_codes(cid, table, column, sf, 1, None)
        # masked-out lanes may hold arbitrary ids: clamp for the gather
        ids = np.clip(np.asarray(col.values), 0, len(codes_all) - 1)
        new_cols[k] = Column(jnp.asarray(codes_all[ids]), col.nulls, cdict)
    return batch.with_columns(new_cols)


# ---------------------------------------------------------------------------
# batch utilities
# ---------------------------------------------------------------------------

def _concat_batches(batches: List[Batch]) -> Batch:
    names = list(batches[0].columns)
    cols = {}
    for n in names:
        first = batches[0].columns[n]
        values = jnp.concatenate([b.columns[n].values for b in batches])
        if any(b.columns[n].nulls is not None for b in batches):
            nulls = jnp.concatenate([b.columns[n].null_mask() for b in batches])
        else:
            nulls = None
        # ARRAY columns: lengths ride along like nulls (all batches of a
        # stream share a column's representation, so lengths are either
        # present everywhere or nowhere)
        if first.lengths is not None:
            lengths = jnp.concatenate([b.columns[n].lengths
                                       for b in batches])
        else:
            lengths = None
        # dictionaries must agree (scan layer guarantees table-stable dicts)
        cols[n] = Column(values, nulls, first.dictionary, first.lazy,
                         lengths)
    mask = jnp.concatenate([b.mask for b in batches])
    return Batch(cols, mask)


def _apply_dyn_filter(batches, dyn_filter, stats_ent):
    """Apply a dynamic filter to a probe stream, tracking dropped rows
    when EXPLAIN ANALYZE stats are enabled."""
    if dyn_filter is None:
        yield from batches
        return
    dropped = None
    try:
        for b in batches:
            nb = dyn_filter(b)
            if stats_ent is not None:
                # summed on the device, fetched once when the stream ends
                dropped = _jit_count_dropped(b.mask, nb.mask) \
                    if dropped is None \
                    else _jit_count_dropped(b.mask, nb.mask, dropped)
            yield nb
    finally:
        if dropped is not None:
            stats_ent["dynamicFilterRowsDropped"] += int(
                host_get(dropped, "dynamic_filter_rows"))


def _split_batch(batch: Batch) -> List[Batch]:
    cap = batch.capacity
    half = cap // 2
    out = []
    for lo, hi in ((0, half), (half, cap)):
        cols = {n: c.slice_rows(lo, hi) for n, c in batch.columns.items()}
        out.append(Batch(cols, batch.mask[lo:hi]))
    return out
