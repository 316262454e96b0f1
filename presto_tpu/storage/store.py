"""Process-wide HBM-resident columnar store with LRU eviction.

Generating a connector column is a uint64 splitmix hash per row —
64-bit integer multiplies are EMULATED on the TPU vector unit and
dominate fused-scan wall clock (measured at SF10: shipdate generation
alone cost 3x the whole aggregation).  Generated connector data is
immutable, so whole-table columns are materialized into HBM ONCE,
encoded (encodings.py), zone-mapped, and every scan chunk becomes a
`slice_decode` — the reference analog is Velox reading an in-memory
columnar table instead of recomputing it.

Residency is charged to an `exec.memory.MemoryPool` (the same
accounting type task execution uses, so the cache composes with memory
arbitration/spill work):

- insertion evicts least-recently-used entries until the new column's
  encoded bytes fit the `storage` budget;
- a column that cannot fit even alone is simply NOT cached — the scan
  falls back to on-the-fly generation.  The budget degrades throughput,
  never correctness, and never raises MemoryExceededError.

Under a mesh (`devices`) a column is held as SHARDS, one per device:
shard i is the contiguous row range `catalog.make_splits(table, sf,
n)[i]` -- the rows the task pinned to device i scans -- built on that
device, with `ResidentColumn.base` turning table positions into local
ones.  The per-column size limit applies to a shard; `entries` stays
keyed by column, `nbytes` the total over its shards.  A column is built
once: a per-key build lock makes the second asker wait and hit.

Eviction releases the store's reference and accounting immediately;
the arrays themselves leave HBM when the last compiled plan holding
them is dropped (plans receive resident columns as traced arguments,
not closures, so nothing is baked into executables).
"""
from __future__ import annotations

import contextlib
import functools
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..common.locks import OrderedLock
from ..exec.memory import MemoryPool
from ..utils.runtime_stats import current_stats, host_get, named_jit
from .encodings import (ResidentColumn, ZoneMaps, build_zone_maps,
                        encode_column)

DEFAULT_STORAGE_BUDGET = 6 << 30
# building a column transiently holds ~2x its plain bytes (chunk parts
# + concatenated result), so multi-GB columns (SF100 lineitem) must stay
# on-the-fly or the build itself OOMs HBM
DEFAULT_MAX_COLUMN_BYTES = 1 << 30
DEFAULT_ZONE_ROWS = 1 << 16
# columns at or under this row count take the host-side stats path at
# build time (one device_get, numpy selection); larger columns keep all
# probes on device so a SF10+ build never round-trips gigabytes
HOST_STATS_ROWS = 1 << 20

# process-wide observability counters, consumed by bench.py and tests;
# chunks_total/chunks_skipped are bumped by pushdown.prune_chunks every
# time a chunk list is enumerated, so the skip FRACTION stays exact even
# though repeated enumerations inflate both counters proportionally
_STORAGE_COUNTERS = ("cache_hits", "cache_misses", "columns_built",
                     "build_rejected", "evictions", "resident_bytes",
                     "encoded_bytes", "plain_bytes",
                     "chunks_total", "chunks_skipped")


class StorageMetrics:
    """Locked storage-counter registry.  Replaces the bare module dict:
    concurrent scan threads bumping `d[k] += 1` lose increments, and
    /v1/metrics could read a half-updated view mid-build.  Keeps the
    dict-like read surface (`m[k]`, `sorted(m)`, `dict(m)`, `.items()`)
    the existing consumers and tests use."""

    def __init__(self):
        # rank 100: metrics registries are leaf locks
        self._lock = OrderedLock("metrics:storage", 100)  # lint: guarded-by(_lock)
        self._values: Dict[str, int] = {k: 0 for k in _STORAGE_COUNTERS}

    def reset(self) -> None:
        with self._lock:
            for k in _STORAGE_COUNTERS:
                self._values[k] = 0

    def incr(self, name: str, delta: int = 1) -> None:
        with self._lock:
            self._values[name] += delta

    def __getitem__(self, name: str) -> int:
        with self._lock:
            return self._values[name]

    def __setitem__(self, name: str, value: int) -> None:
        with self._lock:
            self._values[name] = value

    def __contains__(self, name: str) -> bool:
        with self._lock:
            return name in self._values

    def __iter__(self):
        return iter(self.keys())

    def __len__(self) -> int:
        with self._lock:
            return len(self._values)

    def keys(self):
        with self._lock:
            return list(self._values)

    def items(self):
        return self.snapshot().items()

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._values)


STORAGE_METRICS = StorageMetrics()


def reset_storage_metrics() -> None:
    STORAGE_METRICS.reset()


class ResidentEntry:
    """One cached column: per shard, encoded device arrays + host-side
    zone maps.  One shard is the whole table (`devices` None); under a
    mesh shard i lives on `devices[i]`."""

    __slots__ = ("shards", "nbytes", "pad", "devices")

    def __init__(self, shards: List[Tuple[ResidentColumn, ZoneMaps]],
                 pad: int, devices: Optional[tuple] = None):
        self.shards = shards
        self.nbytes = sum(col.nbytes for col, _zones in shards)
        self.pad = pad
        self.devices = devices

    @property
    def column(self) -> ResidentColumn:
        return self.shards[0][0]

    @property
    def zones(self) -> ZoneMaps:
        return self.shards[0][1]

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The encoding each shard's encoder chose, in shard order."""
        return tuple(col.kind for col, _zones in self.shards)


class ResidentStore:
    """LRU cache of ResidentEntry keyed (connector, table, column, sf,
    as_i32), charged to its own MemoryPool."""

    def __init__(self, budget: Optional[int] = DEFAULT_STORAGE_BUDGET,
                 max_column_bytes: int = DEFAULT_MAX_COLUMN_BYTES):
        self.pool = MemoryPool(budget)
        self.max_column_bytes = max_column_bytes
        self.entries: "OrderedDict[tuple, ResidentEntry]" = OrderedDict()
        # key -> the lock its builder holds (leaf locks: nothing else is
        # acquired under one but the metrics registries)
        self._build_locks: Dict[tuple, threading.Lock] = {}

    # -- lookup / build ---------------------------------------------------
    def get_or_build(self, cid: str, table: str, colname: str, sf: float,
                     n_rows: int, pad: int, as_i32: bool,
                     zone_rows: int = DEFAULT_ZONE_ROWS,
                     encodings: bool = True,
                     devices: Optional[tuple] = None
                     ) -> Optional[ResidentEntry]:
        """The column's entry, built on a miss: whole on the default
        device, or with `devices` (a mesh's, in task order) one shard a
        device."""
        key = (cid, table, colname, float(sf), bool(as_i32))

        def usable():
            ent = self.entries.get(key)
            # built under a smaller batch capacity (chunk slices must
            # never clamp), coarser zone maps (a session asking for finer
            # storage_zone_rows must actually get the pruning granularity
            # it asked for) or another layout: rebuild.  A finer-than-
            # requested cached entry is kept -- extra zones only sharpen
            # pruning.
            if ent is not None and ent.pad >= pad \
                    and ent.zones.zone_rows <= zone_rows \
                    and ent.devices == devices:
                self.entries.move_to_end(key)
                STORAGE_METRICS.incr("cache_hits")
                return ent
            return None

        ent = usable()
        if ent is not None:
            return ent
        with self._build_locks.setdefault(key, threading.Lock()):
            # a second asker waited out the first one's build: it hits
            ent = usable()
            if ent is not None:
                return ent
            if key in self.entries:
                self._evict(key)
            STORAGE_METRICS.incr("cache_misses")
            ranges = _shard_ranges(cid, table, sf, n_rows, devices)
            itemsize = 4 if as_i32 else 8
            if (max(hi - lo for lo, hi in ranges) + pad) * itemsize \
                    > self.max_column_bytes:
                STORAGE_METRICS.incr("build_rejected")
                return None
            # generate + encode, timed into the query or task that missed
            owner = current_stats()
            if owner is not None:
                owner.add("storageBuilds", 1)
                owner.add("storageShardBuilds", len(ranges))
            with (owner.span("storageBuild", table=table, column=colname)
                  if owner is not None else contextlib.nullcontext()):
                ent = self._build(key, cid, table, colname, sf, ranges,
                                  pad, as_i32, zone_rows, encodings,
                                  devices)
            if owner is not None and ent is not None:
                # what the encoder chose, a shard: plain / dict / rle
                for kind in ent.kinds:
                    owner.add(f"storageEncoding.{kind}", 1)
            return ent

    def _build(self, key, cid, table, colname, sf, ranges, pad, as_i32,
               zone_rows, encodings, devices) -> Optional[ResidentEntry]:
        if devices is None:
            shards = [_build_shard(cid, table, colname, sf, *ranges[0],
                                   pad, as_i32, zone_rows, encodings)]
        else:
            # every device builds its own shard at the same time
            def on_device(i):
                with jax.default_device(devices[i]):
                    return _build_shard(cid, table, colname, sf,
                                        *ranges[i], pad, as_i32,
                                        zone_rows, encodings, sharded=True)
            from concurrent.futures import ThreadPoolExecutor
            from functools import partial

            from ..utils.stack import roomy
            with ThreadPoolExecutor(len(devices)) as builders:
                # each from a roomy frame: they lower the generators
                shards = list(builders.map(partial(roomy, on_device),
                                           range(len(devices))))
        ent = ResidentEntry(shards, pad, devices)
        while not self.pool.try_reserve(ent.nbytes):
            if not self.entries:
                STORAGE_METRICS.incr("build_rejected")
                return None
            oldest = next(iter(self.entries))
            self._evict(oldest)
        self.entries[key] = ent
        STORAGE_METRICS.incr("columns_built")
        STORAGE_METRICS.incr("encoded_bytes", ent.nbytes)
        STORAGE_METRICS.incr("plain_bytes", sum(
            col.logical_nbytes for col, _zones in shards))
        STORAGE_METRICS["resident_bytes"] = self.pool.reserved
        return ent

    def _evict(self, key: tuple) -> None:
        ent = self.entries.pop(key)
        self.pool.free(ent.nbytes)
        STORAGE_METRICS.incr("evictions")
        STORAGE_METRICS["resident_bytes"] = self.pool.reserved

    def clear(self) -> None:
        """Drop every entry, all of its shards with it."""
        for key in list(self.entries):
            ent = self.entries.pop(key)
            self.pool.free(ent.nbytes)
        STORAGE_METRICS["resident_bytes"] = self.pool.reserved


@functools.lru_cache(maxsize=None)
def _gen_fn(cid: str, table: str, colname: str, sf: float, chunk: int,
            as_i32: bool):
    """Jitted whole-chunk generator, cached so pad-growth rebuilds and
    differently-budgeted stores reuse the compiled executable."""
    from ..connectors import device_gen

    def gen_chunk(pos):
        idx = pos + jnp.arange(chunk, dtype=jnp.int64)
        v = device_gen.column(cid, table, colname, sf, idx)
        return v.astype(jnp.int32) if as_i32 and v.dtype == jnp.int64 \
            else v

    return named_jit(f"gen_{table}_{colname}", gen_chunk)


def _shard_ranges(cid: str, table: str, sf: float, n_rows: int,
                  devices: Optional[tuple]) -> List[Tuple[int, int]]:
    """[start, end) of every shard: the whole table, or under a mesh the
    row ranges the scheduler hands its pinned tasks (the same call)."""
    if devices is None:
        return [(0, n_rows)]
    from ..connectors import catalog
    splits = catalog.make_splits(table, sf, len(devices), cid)
    ranges = [(s.start, s.end) for s in splits]
    # a table of fewer rows than devices leaves the last shards empty
    return ranges + [(n_rows, n_rows)] * (len(devices) - len(ranges))


def _build_shard(cid: str, table: str, colname: str, sf: float,
                 start: int, end: int, pad: int, as_i32: bool,
                 zone_rows: int, encodings: bool, sharded: bool = False
                 ) -> Tuple[ResidentColumn, ZoneMaps]:
    """Table rows [start, end) of one column on the default device:
    generated, encoded, zone-mapped."""
    n_rows = end - start
    arr = _build_rows(cid, table, colname, sf, start, n_rows, pad, as_i32)
    from ..connectors import device_gen
    hint = device_gen.encoding_hint(cid, table, colname)
    # for small columns, pull the padded column to the host once and
    # run encoding selection + zone reduction in numpy — dozens of
    # tiny per-column device programs collapse into one transfer
    host = None
    if n_rows <= HOST_STATS_ROWS:
        # build-time stat transfer, once per column per process
        host = host_get(arr, "storage_small_column")
    col = encode_column(arr, n_rows, encodings=encodings, hint=hint,
                        host=host)
    zones = build_zone_maps(arr, n_rows, zone_rows, host=host)
    if sharded:
        col.base = jnp.int64(start)
        zones.base = start
    return col, zones


def _build_rows(cid: str, table: str, colname: str, sf: float,
                start: int, n_rows: int, pad: int, as_i32: bool):
    """Materialize `n_rows` rows of a column from table row `start` on
    device via the jitted counter-hash generator, zero tail padding
    appended (chunk slices never clamp-shift at the edge — dynamic_slice
    clamping would silently misalign live rows).  The chunk is the next
    power of two covering the rows (capped at 4M rows): tiny catalog
    tables don't pay a 4M-row hash, and pow2 bucketing keeps
    compile-cache reuse across similar sizes."""
    chunk = 1 << max(10, min(22, (max(n_rows, 1) - 1).bit_length()))
    gen_chunk = _gen_fn(cid, table, colname, float(sf), chunk, bool(as_i32))
    parts = [gen_chunk(jnp.int64(start + p))
             for p in range(0, max(n_rows, 1), chunk)]
    arr = jnp.concatenate(parts)[:n_rows]
    return jnp.concatenate([arr, jnp.zeros(pad, dtype=arr.dtype)])


# ---------------------------------------------------------------------------
# store registry: one store per (budget, max_column_bytes) configuration,
# so a test running under a deliberately tiny budget never pollutes (or
# borrows from) the default 6 GiB process store
# ---------------------------------------------------------------------------

_STORES: Dict[tuple, ResidentStore] = {}


def get_store(budget: Optional[int] = DEFAULT_STORAGE_BUDGET,
              max_column_bytes: int = DEFAULT_MAX_COLUMN_BYTES
              ) -> ResidentStore:
    key = (budget, max_column_bytes)
    st = _STORES.get(key)
    if st is None:
        st = _STORES[key] = ResidentStore(budget, max_column_bytes)
    return st
