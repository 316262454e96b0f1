"""Lightweight columnar encodings for HBM-resident table columns.

A `ResidentColumn` is the device half of the resident storage layer
(store.py): one whole-table column materialized ONCE into HBM in an
encoded physical form, decoded per scan chunk INSIDE the fused program,
after the chunk's `dynamic_slice`.  Selection counts what the decode
costs on the chip, not only the bytes.  On the v5e the fused scan is
nowhere near HBM-bound (under 1 % of its roofline, PERF.md), and a
decode XLA leaves as a per-row gather costs 7 ns a row where the 2 bytes
it saves stream in 2.4 ps: an int16-coded `l_shipdate` was 0.43 s of the
0.51 s of a 60M-row Q6 (PR 29).  So a column is encoded only where its
decode stays elementwise, and is otherwise plain in its logical dtype.

Three encodings, mirroring the engine's host Block hierarchy
(common/block.py DictionaryBlock / RunLengthBlock / FixedWidthBlock):

- ``plain``  — the padded device array as-is.
- ``dict``   — sorted distinct values (at most DICT_MAX_NDV) + per-row
  int8 codes.  Exact: decode is ``values[codes]``, which XLA expands
  into a select chain over a table this small.
- ``rle``    — run values + run start offsets for sorted/monotone
  columns (tpcds ``ws_order_number``-style co-bucket layouts).  Decode
  is ``values[searchsorted(starts, row) - 1]`` — log2(runs) gathers per
  element (the per-row gather again: ROADMAP queue 1 item 3), so it is
  only selected when runs compress heavily or a connector hint forces it.

Zone maps (per-zone min/max/null-count at a fixed row granularity) are
built HERE, from the exact decoded values, on device, and brought to
the host once at build time — query-time chunk pruning
(pushdown.prune_chunks) is then pure host numpy and never syncs.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.runtime_stats import host_get

# the largest table whose `values[codes]` the TPU compiler expands into
# selects; one entry more and the decode is a real gather in the scan
# loop (tests/test_chip_compile.py holds the constant to the compiler)
DICT_MAX_NDV = 64
# cheap cardinality probe before paying a full-column check or sort
DICT_PROBE_ROWS = 1 << 16
# without a connector hint, RLE must compress >= this factor: decode
# pays log2(runs) gathers per element, so the run table must be small
# enough to stay cache-resident
RLE_MIN_COMPRESSION = 16.0
# with a connector "rle" hint (known-monotone layout), accept >= 2x
RLE_HINT_COMPRESSION = 2.0


def _running_sum(x):
    """`jnp.cumsum(x)` as log2(n) shifted adds: a 64-bit `cumsum` is a
    reduce-window that, inside a scan loop over 64K-row chunks, asks the
    TPU compiler for more scoped vector memory than it has
    (tests/test_chip_compile.py compiles both forms)."""
    return jax.lax.associative_scan(jnp.add, x)


class ResidentColumn:
    """One whole-table encoded column, traceable as a jit argument.

    Registered as a pytree: the device arrays are children (resident
    columns ride jit argument lists — closing over them would inline
    hundreds of MB as XLA literal constants), the encoding shape is
    static aux data (so the jit cache keys on it).

    A SHARD of a table held over a mesh (store.py) carries `base`, the
    table row of its row 0, as one more device child: scans keep table
    positions and `slice_decode` makes them local, so the shards of all
    devices share one traced program.
    """

    def __init__(self, kind: str, arrays: Tuple, n_rows: int, base=None):
        self.kind = kind          # "plain" | "dict" | "rle"
        self.arrays = tuple(arrays)
        self.n_rows = int(n_rows)
        self.base = base          # None: the whole table, row 0 first

    # -- chunk decode (traceable; pos may be a tracer) --------------------
    def slice_decode(self, pos, cap: int):
        """Decode table rows [pos, pos+cap) to logical values.  Arrays
        are tail-padded past n_rows at build time so the dynamic_slice
        never clamp-shifts at the table (or shard) edge."""
        return self._decode(pos if self.base is None else pos - self.base,
                            cap)

    def _decode(self, pos, cap: int, whole: bool = False):
        """`slice_decode` at a position local to these arrays (`whole`:
        the column at once, at build time, by a search a row)."""
        if self.kind == "plain":
            (data,) = self.arrays
            return jax.lax.dynamic_slice(data, (pos,), (cap,))
        if self.kind == "dict":
            codes, values = self.arrays
            c = jax.lax.dynamic_slice(codes, (pos,), (cap,))
            return values[c.astype(jnp.int32)]
        run_values, run_starts = self.arrays
        if whole or not jnp.issubdtype(run_values.dtype, jnp.integer):
            idx = pos + jnp.arange(cap, dtype=jnp.int64)
            ri = jnp.searchsorted(run_starts, idx, side="right") - 1
            ri = jnp.clip(ri, 0, run_values.shape[0] - 1)
            return run_values[ri]
        # a chunk is a contiguous row range, so ONE scalar search finds
        # its first run and at most `cap` further runs begin inside it:
        # their value steps, scattered to the rows where they begin and
        # summed along the chunk, are the values (no search, no gather a
        # row: those cost 37 ms a 64K chunk over l_orderkey's 15M runs)
        n = run_starts.shape[0]             # runs + the sentinel
        m = min(cap, n - 1)                 # run starts looked at
        r0 = jnp.searchsorted(run_starts, pos, side="right") - 1
        r0 = jnp.clip(r0, 0, n - 2)
        # dynamic_slice clamps at the arrays' end: start early instead
        # and drop the runs that begin at or before the chunk's first row
        lo = jnp.minimum(r0, n - 1 - m)
        starts = jax.lax.dynamic_slice(run_starts, (lo + 1,), (m,))
        values = jax.lax.dynamic_slice(run_values, (lo,), (m + 1,))
        off = starts - pos
        inside = (off > 0) & (off < cap)
        steps = jnp.zeros(cap, run_values.dtype).at[
            jnp.where(inside, off, cap).astype(jnp.int32)].add(
            values[1:] - values[:-1], mode="drop")
        return run_values[r0] + _running_sum(steps)

    def decode_full(self):
        """The full padded logical array (tests / zone-map building)."""
        if self.kind == "plain":
            return self.arrays[0]
        if self.kind == "dict":
            codes, values = self.arrays
            return values[codes.astype(jnp.int32)]
        return self._decode(jnp.int64(0), self.n_rows, whole=True)

    # -- accounting -------------------------------------------------------
    @property
    def nbytes(self) -> int:
        """Resident (encoded) device bytes — what HBM actually holds."""
        return int(sum(a.nbytes for a in self.arrays))

    @property
    def logical_nbytes(self) -> int:
        """Bytes a plain encoding of the same column would hold."""
        if self.kind == "plain":
            return int(self.arrays[0].nbytes)
        if self.kind == "dict":
            codes, values = self.arrays
            return int(codes.shape[0] * values.dtype.itemsize)
        run_values, _run_starts = self.arrays
        return self.n_rows * run_values.dtype.itemsize

    @property
    def dtype(self):
        if self.kind == "dict":
            return self.arrays[1].dtype
        return self.arrays[0].dtype

    def __repr__(self):
        return (f"ResidentColumn({self.kind}, rows={self.n_rows}, "
                f"bytes={self.nbytes})")


def _rescol_flatten(rc: ResidentColumn):
    if rc.base is None:
        return rc.arrays, (rc.kind, rc.n_rows, False)
    return rc.arrays + (rc.base,), (rc.kind, rc.n_rows, True)


def _rescol_unflatten(aux, children):
    kind, n_rows, sharded = aux
    children = tuple(children)
    if sharded:
        return ResidentColumn(kind, children[:-1], n_rows, children[-1])
    return ResidentColumn(kind, children, n_rows)


jax.tree_util.register_pytree_node(
    ResidentColumn, _rescol_flatten, _rescol_unflatten)


# ---------------------------------------------------------------------------
# encoder selection
# ---------------------------------------------------------------------------

def encode_column(arr, n_rows: int, encodings: bool = True,
                  hint: Optional[str] = None,
                  host: Optional[np.ndarray] = None) -> ResidentColumn:
    """Pick an encoding for a fully built padded device array.

    `arr` holds n_rows logical rows plus zero tail padding.  Selection
    stats (run count, cardinality) are device reductions pulled to the
    host ONCE at build time; the resulting ResidentColumn never syncs.
    When the caller already holds the padded column on the host
    (`host`), selection AND encoding run in numpy — small tables pay
    one transfer instead of a dozen tiny device programs.
    """
    if not encodings or n_rows < 2 or hint == "plain":
        return ResidentColumn("plain", (arr,), n_rows)
    if host is not None:
        return _encode_column_host(arr, host, n_rows, hint)
    body = arr[:n_rows]
    itemsize = arr.dtype.itemsize

    # --- RLE: runs of equal adjacent values -----------------------------
    changes = body[1:] != body[:-1]
    # build-time stat, one sync per column per process
    nruns = 1 + int(host_get(changes.sum(), "storage_rle_runs"))
    plain_bytes = n_rows * itemsize
    rle_bytes = nruns * (itemsize + 8)
    want = RLE_HINT_COMPRESSION if hint == "rle" else RLE_MIN_COMPRESSION
    if rle_bytes * want <= plain_bytes:
        change_mask = jnp.concatenate(
            [jnp.ones(1, dtype=bool), changes])
        starts = jnp.nonzero(change_mask, size=nruns,
                             fill_value=n_rows - 1)[0].astype(jnp.int64)
        run_values = body[starts]
        # sentinel run: zero-valued tail padding, so any in-capacity row
        # index decodes without clamping surprises
        run_starts = jnp.concatenate(
            [starts, jnp.asarray([n_rows], dtype=jnp.int64)])
        run_values = jnp.concatenate(
            [run_values, jnp.zeros(1, dtype=body.dtype)])
        return ResidentColumn("rle", (run_values, run_starts), n_rows)

    # --- dictionary: low-cardinality columns ----------------------------
    # the probe is sorted on the host: one DICT_PROBE_ROWS transfer per
    # column per process, where a device sort costs the chip's compiler
    # tens of seconds per dtype and size
    probe = np.unique(host_get(body[:DICT_PROBE_ROWS],
                               "storage_dict_probe"))
    if probe.shape[0] <= DICT_MAX_NDV:
        values = _distinct_values(body, probe)
        ndv = int(values.shape[0])
        if _dict_pays(ndv, arr.shape[0], n_rows, itemsize):
            # pad rows code to an arbitrary slot (dead rows are masked by
            # the scan's live predicate); clip keeps the decode in-bounds
            codes = jnp.clip(jnp.searchsorted(values, arr), 0, ndv - 1
                             ).astype(jnp.int8)
            return ResidentColumn("dict", (codes, values), n_rows)
    return ResidentColumn("plain", (arr,), n_rows)


def _dict_pays(ndv: int, padded_rows: int, n_rows: int,
               itemsize: int) -> bool:
    """A dictionary of `ndv` values is taken when its decode is a select
    chain and its int8 codes plus the values table (resident too: a
    near-unique tiny table can net MORE bytes) are fewer bytes."""
    return ndv <= DICT_MAX_NDV \
        and padded_rows + ndv * itemsize < n_rows * itemsize


def _distinct_values(body, probe: np.ndarray):
    """Sorted distinct values of `body`, given those of its first
    DICT_PROBE_ROWS rows.  When every row is one of the probe's values
    (a binary search and a compare, no sort) they are the distinct set;
    only a column that goes on to new values pays the full-column sort."""
    values = jnp.asarray(probe)
    slot = jnp.clip(jnp.searchsorted(values, body), 0, values.shape[0] - 1)
    # build-time stat, one sync per column per process
    if host_get((values[slot] == body).all(), "storage_dict_check"):
        return values
    return jnp.unique(body)


def _encode_column_host(arr, host: np.ndarray, n_rows: int,
                        hint: Optional[str]) -> ResidentColumn:
    """Numpy twin of the device selection path, same thresholds and
    same physical layout; only the encoded arrays go back to device."""
    body = host[:n_rows]
    itemsize = body.dtype.itemsize
    changes = body[1:] != body[:-1]
    nruns = 1 + int(np.count_nonzero(changes))
    plain_bytes = n_rows * itemsize
    rle_bytes = nruns * (itemsize + 8)
    want = RLE_HINT_COMPRESSION if hint == "rle" else RLE_MIN_COMPRESSION
    if rle_bytes * want <= plain_bytes:
        starts = np.flatnonzero(
            np.concatenate([np.ones(1, dtype=bool), changes]))
        run_values = jnp.asarray(np.concatenate(
            [body[starts], np.zeros(1, dtype=body.dtype)]))
        run_starts = jnp.asarray(np.concatenate(
            [starts, [n_rows]]).astype(np.int64))
        return ResidentColumn("rle", (run_values, run_starts), n_rows)

    values_h = np.unique(body[:DICT_PROBE_ROWS])
    if values_h.shape[0] <= DICT_MAX_NDV:
        values_h = np.unique(body)
        ndv = int(values_h.shape[0])
        if _dict_pays(ndv, host.shape[0], n_rows, itemsize):
            codes_h = np.clip(np.searchsorted(values_h, host), 0, ndv - 1
                              ).astype(np.int8)
            return ResidentColumn(
                "dict", (jnp.asarray(codes_h), jnp.asarray(values_h)),
                n_rows)
    return ResidentColumn("plain", (arr,), n_rows)


# ---------------------------------------------------------------------------
# zone maps
# ---------------------------------------------------------------------------

class ZoneMaps:
    """Host-side per-zone min/max/null-count at a fixed row granularity.

    Built once from the exact column values; consulted by
    pushdown.prune_chunks with pure numpy — pruning never touches the
    device."""

    __slots__ = ("zmin", "zmax", "null_count", "zone_rows", "base")

    def __init__(self, zmin: np.ndarray, zmax: np.ndarray,
                 null_count: np.ndarray, zone_rows: int):
        self.zmin = zmin
        self.zmax = zmax
        self.null_count = null_count
        self.zone_rows = int(zone_rows)
        # table row of zone 0's first row (a shard's: set by the store)
        self.base = 0

    def chunk_bounds(self, pos: int, count: int):
        """Aggregate (min, max) over the zones covering table rows
        [pos, pos+count)."""
        pos -= self.base
        z0 = pos // self.zone_rows
        z1 = (pos + count - 1) // self.zone_rows
        z1 = min(z1, len(self.zmin) - 1)
        if z0 > z1:
            return None
        return self.zmin[z0:z1 + 1].min(), self.zmax[z0:z1 + 1].max()


def _reduce_identities(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf, -jnp.inf
    if dtype == jnp.bool_:
        return True, False
    info = jnp.iinfo(dtype)
    return info.max, info.min


def build_zone_maps(arr, n_rows: int, zone_rows: int,
                    nulls=None, host: Optional[np.ndarray] = None
                    ) -> ZoneMaps:
    """Device reshape+reduce over the UNPADDED rows, one host pull.

    The ragged last zone is padded with reduction identities so zero
    tail padding never leaks into a zone's min.  With `host` (the
    padded column already on the host) the reduce is pure numpy."""
    if host is not None and nulls is None:
        return _build_zone_maps_host(host, n_rows, zone_rows)
    body = arr[:n_rows]
    nz = -(-n_rows // zone_rows)
    pad = nz * zone_rows - n_rows
    ident_min, ident_max = _reduce_identities(body.dtype)
    pmin = jnp.concatenate(
        [body, jnp.full(pad, ident_min, dtype=body.dtype)]) if pad \
        else body
    pmax = jnp.concatenate(
        [body, jnp.full(pad, ident_max, dtype=body.dtype)]) if pad \
        else body
    zmin = pmin.reshape(nz, zone_rows).min(axis=1)
    zmax = pmax.reshape(nz, zone_rows).max(axis=1)
    if nulls is not None:
        nbody = nulls[:n_rows]
        if pad:
            nbody = jnp.concatenate([nbody, jnp.zeros(pad, dtype=bool)])
        ncnt = nbody.reshape(nz, zone_rows).sum(axis=1)
    else:
        ncnt = jnp.zeros(nz, dtype=jnp.int32)
    # build-time stat transfer: one sync per column per process
    zmin, zmax, ncnt = host_get((zmin, zmax, ncnt), "storage_zone_maps")
    return ZoneMaps(np.asarray(zmin), np.asarray(zmax),
                    np.asarray(ncnt), zone_rows)


def _build_zone_maps_host(host: np.ndarray, n_rows: int,
                          zone_rows: int) -> ZoneMaps:
    body = host[:n_rows]
    nz = -(-n_rows // zone_rows)
    pad = nz * zone_rows - n_rows
    ident_min, ident_max = _reduce_identities(body.dtype)
    pmin = np.concatenate(
        [body, np.full(pad, ident_min, dtype=body.dtype)]) if pad \
        else body
    pmax = np.concatenate(
        [body, np.full(pad, ident_max, dtype=body.dtype)]) if pad \
        else body
    return ZoneMaps(pmin.reshape(nz, zone_rows).min(axis=1),
                    pmax.reshape(nz, zone_rows).max(axis=1),
                    np.zeros(nz, dtype=np.int32), zone_rows)
