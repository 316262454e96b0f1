"""Partitioned exchange over ICI: the TPU-native replacement for the
reference's HTTP pull shuffle between hash-partitioned stages
(PartitionedOutputOperator.java:58 -> ExchangeClient.java:72; SURVEY.md §5.8).

Where both producer and consumer stages run on chips of the same pod slice,
the shuffle is a jitted `all_to_all` under shard_map: each device buckets
its rows by target partition (hash of the partition keys mod the worker
count), pads buckets to a fixed quota (static shapes for XLA), and the
collective transposes the bucket axis across the mesh.  Bucket overflow is
detected on device and surfaced to the host driver, which splits the batch
and retries — same recovery discipline as the join's output capacity.

The scheduler's chunked mode (exec/scheduler.py _ici_exchange,
exchange.ici-chunk-rows) calls the exchange once per fixed-size row chunk
with quota == chunk rows: a chunk of C rows can never put more than C rows
in one bucket, so overflow is STATICALLY impossible and the driver
dispatches every chunk's collective back-to-back with no host sync — chunk
k+1 rides the wire while the consumer computes on chunk k (JAX async
dispatch), and the fixed chunk shape means one compiled exchange program
(and its donated input staging buffers) is reused across chunks and
stages instead of re-padding to a fresh per-stage global max.

Cross-pod edges and TPU<->Java edges keep the HTTP exchange (worker/);
fabric selection lives in parallel/fabric.py.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..exec.batch import Batch, Column
from ..exec.operators import hash_columns
from ..utils.runtime_stats import named_jit
from .mesh import WORKER_AXIS


def _bucket_locally(batch: Batch, key_names: List[str], n_parts: int,
                    quota: int, salt: int):
    """Reorder local rows into n_parts buckets of `quota` rows each.

    Returns (bucketed columns dict name->(n_parts*quota,) arrays,
    bucketed mask, overflow flag)."""
    if key_names:
        h = hash_columns([batch.columns[k] for k in key_names], salt)
        target = (h % jnp.uint64(n_parts)).astype(jnp.int32)
    else:
        # round robin
        target = (jnp.cumsum(batch.mask) - 1).astype(jnp.int32) % n_parts
    target = jnp.where(batch.mask, target, n_parts)  # padding sorts last

    order = jnp.argsort(target, stable=True)          # rows grouped by target
    sorted_target = target[order]
    # position of each row within its bucket
    ranks = jnp.arange(batch.capacity) - jnp.searchsorted(
        sorted_target, sorted_target, side="left")
    dest = sorted_target * quota + ranks              # slot in bucketed layout
    valid = (sorted_target < n_parts) & (ranks < quota)
    counts = jnp.zeros(n_parts + 1, dtype=jnp.int32).at[sorted_target].add(
        jnp.where(sorted_target < n_parts, 1, 0), mode="drop")
    overflow = jnp.any(counts[:n_parts] > quota)
    dest = jnp.where(valid, dest, n_parts * quota)    # drop overflow rows

    out_cols = {}
    for name, col in batch.columns.items():
        src = col.values[order]
        buf = jnp.zeros(n_parts * quota, dtype=col.values.dtype)
        buf = buf.at[dest].set(src, mode="drop")
        nulls = None
        if col.nulls is not None:
            nbuf = jnp.zeros(n_parts * quota, dtype=bool)
            nulls = nbuf.at[dest].set(col.nulls[order], mode="drop")
        out_cols[name] = Column(buf, nulls, col.dictionary, col.lazy)
    mask = jnp.zeros(n_parts * quota, dtype=bool).at[dest].set(
        valid, mode="drop")
    return out_cols, mask, overflow


def exchange_step(batch: Batch, key_names: Tuple[str, ...], n_parts: int,
                  quota: int, salt: int = 0):
    """Device-local portion of the shuffle, to be called INSIDE shard_map.

    Returns (exchanged Batch with capacity n_parts*quota, overflow flag).
    After all_to_all, device d holds every device's bucket d."""
    cols, mask, overflow = _bucket_locally(batch, list(key_names), n_parts,
                                           quota, salt)

    def a2a(x):
        # (n_parts*quota, ...) -> (n_parts, quota, ...) -> transpose partitions
        shaped = x.reshape((n_parts, quota) + x.shape[1:])
        out = jax.lax.all_to_all(shaped, WORKER_AXIS, split_axis=0,
                                 concat_axis=0, tiled=False)
        return out.reshape((n_parts * quota,) + x.shape[1:])

    out_cols = {}
    for name, col in cols.items():
        values = a2a(col.values)
        nulls = a2a(col.nulls) if col.nulls is not None else None
        out_cols[name] = Column(values, nulls, col.dictionary, col.lazy)
    new_mask = a2a(mask)
    # overflow anywhere must stop everyone
    any_overflow = jax.lax.pmax(overflow.astype(jnp.int32), WORKER_AXIS) > 0
    return Batch(out_cols, new_mask), any_overflow


def make_partitioned_exchange(mesh, key_names: Tuple[str, ...],
                              quota: int, salt: int = 0,
                              donate: bool = False):
    """Build a jitted shard_map shuffle: Batch (row-sharded) -> Batch
    (row-sharded, rows placed on their hash-target device).

    donate=True marks the input batch's buffers donatable (the chunked
    caller's per-chunk staging slices are dead after the collective, so
    XLA may reuse their memory for the bucketed layout / output where
    layouts permit)."""
    n_parts = mesh.shape[WORKER_AXIS]

    def fn(batch: Batch):
        return exchange_step(batch, key_names, n_parts, quota, salt)

    spec = P(WORKER_AXIS)
    shmapped = shard_map(fn, mesh=mesh, in_specs=(spec,),
                         out_specs=(spec, P()))
    return named_jit("ici_exchange", shmapped,
                     donate_argnums=(0,) if donate else ())
