"""Device-side columnar batch model.

The TPU analog of the reference's Page-in-the-Driver-loop (Driver.java:421-451):
a Batch is a fixed-capacity set of device arrays plus a row-validity mask.
Everything is static-shaped so XLA compiles each pipeline once per capacity
class (SURVEY.md §7 hard part 3: padded fixed-size batches + validity masks).

Columns:
  values      jnp array, logical dtype (int64 / int32 / float64 / bool)
  nulls       optional bool array (True == SQL NULL)
  dictionary  optional tuple of python strings: `values` are int32 codes into
              it.  Static metadata (pytree aux), so string predicates are
              precomputed host-side into code sets and stay out of the traced
              computation.

The row mask subsumes both selection (filters clear bits) and padding (the
tail of a partially-filled batch).  Operators never compact; aggregations and
outputs read the mask.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common.block import (DictionaryBlock, FixedWidthBlock, RunLengthBlock,
                            VariableWidthBlock, decode_to_flat)
from ..common.page import Page
from ..common.types import (BooleanType, DateType, DecimalType, DoubleType,
                            IntegerType, RealType, Type, VarcharType, CharType)
from ..utils.runtime_stats import host_get


class Column:
    def __init__(self, values, nulls=None,
                 dictionary: Optional[Tuple[str, ...]] = None,
                 lazy: Optional[Tuple] = None, lengths=None):
        self.values = values
        self.nulls = nulls
        self.dictionary = dictionary
        # late materialization: ("tpch", table, column, sf) — `values` are
        # global row indices; strings realized at output boundaries
        self.lazy = lazy
        # ARRAY columns: values has shape (capacity, W) — W the static
        # per-column element capacity — and `lengths` (capacity,) holds
        # each row's live element count.  Fixed-width padding instead of
        # offsets keeps shapes static for XLA (the ragged ArrayBlock form
        # exists only at host/page boundaries; reference Block model:
        # presto-common/.../block/ArrayBlock)
        self.lengths = lengths

    def tree_flatten(self):
        tag = ("nulls" if self.nulls is not None else "no_nulls",
               "len" if self.lengths is not None else "no_len")
        children = (self.values,)
        if self.nulls is not None:
            children += (self.nulls,)
        if self.lengths is not None:
            children += (self.lengths,)
        return children, (tag, self.dictionary, self.lazy)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (ntag, ltag), dictionary, lazy = aux
        i = 1
        nulls = None
        if ntag == "nulls":
            nulls = children[i]
            i += 1
        lengths = children[i] if ltag == "len" else None
        return cls(children[0], nulls, dictionary, lazy, lengths)

    def null_mask(self):
        if self.nulls is None:
            return jnp.zeros(self.values.shape[:1], dtype=bool)
        return self.nulls

    def gather(self, idx) -> "Column":
        """Row gather preserving dictionary/lazy metadata."""
        return Column(self.values[idx],
                      None if self.nulls is None else self.nulls[idx],
                      self.dictionary, self.lazy,
                      None if self.lengths is None else self.lengths[idx])

    def slice_rows(self, lo, hi) -> "Column":
        return Column(self.values[lo:hi],
                      None if self.nulls is None else self.nulls[lo:hi],
                      self.dictionary, self.lazy,
                      None if self.lengths is None else self.lengths[lo:hi])

    def __repr__(self):
        d = f", dict[{len(self.dictionary)}]" if self.dictionary else ""
        return f"Column({self.values.dtype}{self.values.shape}{d})"


jax.tree_util.register_pytree_node_class(Column)


class Batch:
    def __init__(self, columns: Dict[str, Column], mask):
        self.columns = columns
        self.mask = mask
        # Bound-parameter vector (serving tier): a tuple of device scalars
        # read by Lowering for BoundParameterExpression.  NOT part of the
        # pytree: parameterized steps take the vector as an explicit jit
        # argument and attach it inside the trace (Batch.with_params), so a
        # flatten/unflatten round trip intentionally drops it — params never
        # bake into a cached executable.
        self.params = None

    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        return tuple(self.columns[n] for n in names) + (self.mask,), names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(dict(zip(names, children[:-1])), children[-1])

    @property
    def capacity(self) -> int:
        return int(self.mask.shape[0])

    def column(self, name: str) -> Column:
        return self.columns[name]

    def with_columns(self, new: Dict[str, Column]) -> "Batch":
        cols = dict(self.columns)
        cols.update(new)
        return Batch(cols, self.mask)

    def select(self, names) -> "Batch":
        return Batch({n: self.columns[n] for n in names}, self.mask)

    def with_mask(self, mask) -> "Batch":
        return Batch(self.columns, mask)

    def with_params(self, params) -> "Batch":
        out = Batch(self.columns, self.mask)
        out.params = params
        return out

    def row_count(self):
        return jnp.sum(self.mask)

    def __repr__(self):
        return f"Batch({list(self.columns)}, capacity={self.capacity})"


jax.tree_util.register_pytree_node_class(Batch)


# ---------------------------------------------------------------------------
# host <-> device conversion
# ---------------------------------------------------------------------------

def _logical_np(typ: Type, values: np.ndarray) -> np.ndarray:
    """Storage-dtype numpy array -> logical-dtype numpy array."""
    if isinstance(typ, DoubleType):
        return values.view(np.float64) if values.dtype != np.float64 else values
    if isinstance(typ, RealType):
        return values.view(np.float32) if values.dtype != np.float32 else values
    if isinstance(typ, BooleanType):
        return values.astype(bool)
    return values


def block_to_column(typ: Type, block, capacity: int) -> Column:
    """Host block -> padded device column."""
    dictionary = None
    if isinstance(block, DictionaryBlock):
        flat = decode_to_flat(block.dictionary)
        if isinstance(flat, VariableWidthBlock):
            dictionary = tuple(flat.to_pylist())
            codes = np.zeros(capacity, dtype=np.int32)
            codes[:block.position_count] = block.ids
            nulls = None
            if flat.nulls is not None:
                nm = np.zeros(capacity, dtype=bool)
                nm[:block.position_count] = flat.null_mask()[block.ids]
                nulls = jnp.asarray(nm)
            return Column(jnp.asarray(codes), nulls, dictionary)
        block = decode_to_flat(block)
    else:
        block = decode_to_flat(block)

    if isinstance(block, VariableWidthBlock):
        # Dictionary-encode on the host: device sees int32 codes.
        strings = block.to_pylist()
        uniq = sorted({s for s in strings if s is not None})
        index = {s: i for i, s in enumerate(uniq)}
        codes = np.zeros(capacity, dtype=np.int32)
        codes[:len(strings)] = [0 if s is None else index[s] for s in strings]
        nulls = None
        if block.nulls is not None:
            nm = np.zeros(capacity, dtype=bool)
            nm[:len(strings)] = block.null_mask()
            nulls = jnp.asarray(nm)
        return Column(jnp.asarray(codes), nulls, tuple(uniq))

    from ..common.block import Int128Block
    if isinstance(block, Int128Block):
        # device holds long decimals narrowed to int64 (batch_to_page widens
        # on the way back out); values beyond int64 have no device form
        block = FixedWidthBlock(block.to_int64(), block.nulls)

    from ..common.block import ArrayBlock
    if isinstance(block, ArrayBlock):
        # ragged ArrayBlock -> fixed-width (capacity, W) element matrix
        from ..common.types import ArrayType
        etyp = typ.element if isinstance(typ, ArrayType) else typ
        inner = decode_to_flat(block.elements)
        if not isinstance(inner, FixedWidthBlock):
            raise NotImplementedError("nested/varchar array elements")
        flat = _logical_np(etyp, inner.values)
        offs = block.offsets.astype(np.int64)
        lens = offs[1:] - offs[:-1]
        W = max(1, 1 << int(max(1, lens.max(initial=1)) - 1).bit_length())
        mat = np.zeros((capacity, W), dtype=flat.dtype)
        nrows = len(lens)
        live = np.arange(W)[None, :] < lens[:, None]
        base = int(offs[0])                 # offsets are contiguous
        mat[:nrows][live] = flat[base:base + int(lens.sum())]
        lenbuf = np.zeros(capacity, dtype=np.int32)
        lenbuf[:len(lens)] = lens
        nulls = None
        if block.nulls is not None:
            nm = np.zeros(capacity, dtype=bool)
            nm[:block.position_count] = block.nulls
            nulls = jnp.asarray(nm)
        return Column(jnp.asarray(mat), nulls, None, None,
                      jnp.asarray(lenbuf))
    if not isinstance(block, FixedWidthBlock):
        raise NotImplementedError(
            f"device column from {type(block).__name__} not supported yet")

    logical = _logical_np(typ, block.values)
    padded = np.zeros(capacity, dtype=logical.dtype)
    padded[:len(logical)] = logical
    nulls = None
    if block.nulls is not None:
        nm = np.zeros(capacity, dtype=bool)
        nm[:block.position_count] = block.nulls
        nulls = jnp.asarray(nm)
    return Column(jnp.asarray(padded), nulls)


def _element_block(etyp: Type, flat: np.ndarray) -> FixedWidthBlock:
    """Flat array-element values -> a storage-dtype FixedWidthBlock (the
    same logical->storage rules as scalar columns in batch_to_page)."""
    if isinstance(etyp, BooleanType):
        flat = flat.astype(np.int8)
    elif isinstance(etyp, (DoubleType, RealType)):
        pass                        # float bits pass through
    elif flat.dtype not in (np.int8, np.int16, np.int32, np.int64):
        flat = flat.astype(etyp.np_dtype)
    if isinstance(etyp, (IntegerType, DateType)):
        flat = flat.astype(np.int32)
    return FixedWidthBlock(flat)


def page_to_batch(page: Page, names, types, capacity: int) -> Batch:
    """Host page -> device batch (pads to capacity)."""
    if page.position_count > capacity:
        raise ValueError(f"page of {page.position_count} rows > capacity {capacity}")
    cols = {}
    for name, typ, block in zip(names, types, page.blocks):
        cols[name] = block_to_column(typ, block, capacity)
    mask = np.zeros(capacity, dtype=bool)
    mask[:page.position_count] = True
    return Batch(cols, jnp.asarray(mask))


def batch_to_page(batch: Batch, names, types) -> Page:
    """Device batch -> host page (drops masked-out rows).

    All device->host copies are issued as ONE async batch (jax.device_get
    starts every transfer before awaiting any): per-transfer round-trip
    latency dominates serially-fetched columns by orders of magnitude when
    the device is remote.  Large batches check the mask first so fully
    filtered-out batches (common in selective streaming pipelines) don't pay
    for full-capacity column transfers; small batches take the single
    combined fetch since round-trips dominate their bytes."""
    def column_fetch():
        fetch = {}
        for name in names:
            col = batch.columns.get(name)
            if col is None:
                continue
            fetch["v." + name] = col.values
            if col.nulls is not None:
                fetch["n." + name] = col.nulls
            if col.lengths is not None:
                fetch["l." + name] = col.lengths
        return fetch

    combined = batch.capacity <= (1 << 16)
    fetch = {"__mask": batch.mask}
    if combined:
        fetch.update(column_fetch())
    host = host_get(fetch, "page_fetch")
    mask = host["__mask"]
    keep = np.flatnonzero(mask)
    if keep.size == 0:
        from ..common.block import block_from_values
        return Page([block_from_values(t, []) for t in types], 0)
    if not combined:
        if keep.size <= (1 << 16) and keep.size * 4 <= batch.capacity:
            # sparse large batch (an aggregation finalize holds a few
            # live rows in a table-capacity layout): compact ON DEVICE
            # and transfer only the live bucket — a full-capacity column
            # fetch through a remote-device link costs ~10-100x the
            # compact dispatch (this was most of TPC-H Q1's wall at SF10)
            # reuse the process-wide compact jit + coarse bucket set
            # (pipeline._COMPACT_BUCKETS) so this fetch site adds no new
            # compiled shape variants
            from .pipeline import _bucket_for, _jit_compact
            bucket = _bucket_for(keep.size) \
                or 1 << int(keep.size - 1).bit_length()
            batch = _jit_compact(batch, bucket)
            host = host_get({"__mask": batch.mask, **column_fetch()},
                            "page_fetch_compacted")
            mask = host["__mask"]
            keep = np.flatnonzero(mask)
        else:
            host.update(host_get(column_fetch(), "page_fetch_columns"))
    blocks = []
    for name, typ in zip(names, types):
        col = batch.columns[name]
        values = host["v." + name][keep]
        nulls = None if col.nulls is None else host["n." + name][keep]
        if col.lazy is not None:
            from ..connectors import catalog as _catalog
            cid, table, column, sf = col.lazy
            from ..common.block import DictionaryBlock as HB, VariableWidthBlock as VB
            coded = _catalog.generate_dictionary_at(table, column, sf,
                                                    values, cid)
            if coded is not None:
                # an enumerated column: int32 codes and its few values,
                # not a Python string a row
                ids, entries = np.asarray(coded[0], dtype=np.int32), \
                    list(coded[1])
                if nulls is not None and nulls.any():
                    ids = ids.copy()
                    ids[nulls] = len(entries)
                    entries.append(None)
                blocks.append(HB(ids, VB.from_strings(entries)))
                continue
            strings = _catalog.generate_values_at(table, column, sf, values,
                                                  cid)
            if nulls is not None:
                strings = [None if n else s for s, n in zip(strings, nulls)]
            from ..common.block import VariableWidthBlock as VB
            blocks.append(VB.from_strings(strings))
            continue
        if col.dictionary is not None:
            from ..common.block import DictionaryBlock as HB, VariableWidthBlock as VB
            ids = values.astype(np.int32)
            entries = list(col.dictionary)
            if nulls is not None and nulls.any():
                # DictionaryBlock carries nulls via its dictionary entries:
                # route NULL rows to an appended None entry.
                ids[nulls] = len(entries)
                entries.append(None)
            dict_block = VB.from_strings(entries)
            blocks.append(HB(ids, dict_block))
            continue
        if col.lengths is not None:
            # ARRAY column: (rows, W) padded element matrix + live lengths
            # -> ragged ArrayBlock (offsets into a flat element block)
            from ..common.block import ArrayBlock
            from ..common.types import ArrayType
            lens = host["l." + name][keep].astype(np.int64)
            W = values.shape[1] if values.ndim > 1 else 0
            lens = np.clip(lens, 0, W)
            if nulls is not None:
                lens = np.where(nulls, 0, lens)
            elem2d = values.reshape(len(keep), W) if W else \
                values.reshape(len(keep), 0)
            live = np.arange(W)[None, :] < lens[:, None]
            flat = elem2d[live]
            offsets = np.zeros(len(keep) + 1, dtype=np.int32)
            np.cumsum(lens, out=offsets[1:])
            etyp = typ.element if isinstance(typ, ArrayType) else typ
            blocks.append(ArrayBlock(offsets,
                                     _element_block(etyp, flat), nulls))
            continue
        if isinstance(typ, (VarcharType, CharType)):
            raise NotImplementedError("varchar column without dictionary")
        if isinstance(typ, DecimalType) and not typ.is_short:
            # device accumulates long decimals in int64; widen on the host
            from ..common.block import Int128Block
            blocks.append(Int128Block.from_int64(values, nulls))
            continue
        if isinstance(typ, BooleanType):
            values = values.astype(np.int8)
        elif isinstance(typ, (DoubleType, RealType)):
            pass  # float bits pass through FixedWidthBlock
        elif values.dtype not in (np.int8, np.int16, np.int32, np.int64):
            values = values.astype(typ.np_dtype)
        if isinstance(typ, (IntegerType, DateType)):
            values = values.astype(np.int32)
        blocks.append(FixedWidthBlock(values, nulls))
    return Page(blocks, len(keep))


def _host_column(typ: Type, block):
    """Host block -> (values, nulls or None, dictionary or None) as numpy
    arrays of the block's own length, or None for a block kind that only
    `block_to_column` knows (arrays)."""
    from ..common.block import Int128Block
    if isinstance(block, DictionaryBlock):
        flat = decode_to_flat(block.dictionary)
        if isinstance(flat, VariableWidthBlock):
            nulls = None
            if flat.nulls is not None:
                nulls = flat.null_mask()[block.ids]
            return block.ids, nulls, tuple(flat.to_pylist())
    block = decode_to_flat(block)
    if isinstance(block, VariableWidthBlock):
        strings = block.to_pylist()
        uniq = sorted({s for s in strings if s is not None})
        index = {s: i for i, s in enumerate(uniq)}
        codes = np.fromiter((index.get(s, 0) for s in strings),
                            dtype=np.int32, count=len(strings))
        nulls = block.null_mask() if block.nulls is not None else None
        return codes, nulls, tuple(uniq)
    if isinstance(block, Int128Block):
        # device holds long decimals narrowed to int64 (batch_to_page widens
        # on the way back out); values beyond int64 have no device form
        return block.to_int64(), block.nulls, None
    if not isinstance(block, FixedWidthBlock):
        return None
    return _logical_np(typ, block.values), block.nulls, None


def _padded(values: np.ndarray, capacity: int) -> np.ndarray:
    if len(values) == capacity:
        return values
    out = np.zeros((capacity,) + values.shape[1:], dtype=values.dtype)
    out[:len(values)] = values
    return out


def pages_to_batches(pages, names, types, capacity):
    """Host pages (exchange input) -> DENSE device batches with STABLE
    dictionaries.

    A producer that filters hands over many small pages (a 64K-row scan
    batch that keeps 1 % is a 700-row page): they are concatenated on the
    host, in arrival order, into batches of up to `capacity` live rows,
    so a consumer pays its per-batch work (a launch, a probe step at full
    capacity) once per `capacity` LIVE rows; a page larger than
    `capacity` is chunked.

    Pages arriving from different producer tasks carry independent
    dictionaries; jitted consumers (agg tables, concat for joins) need one
    dictionary per column across all batches, so string columns are
    remapped (one table lookup a page) to a union dictionary, which needs
    every page before the first batch; numeric-only schemas stream.
    """
    string_cols = [i for i, t in enumerate(types)
                   if isinstance(t, (VarcharType, CharType))]
    if string_cols:
        pages = [p for p in pages if p.position_count]

    def host_pages():
        for page in pages:
            if page.position_count:
                yield page, [_host_column(t, b)
                             for t, b in zip(types, page.blocks)]

    staged = host_pages()
    unions = {}
    if string_cols:
        staged = list(staged)
        for i in string_cols:
            seen = set()
            for _page, cols in staged:
                seen.update(s for s in cols[i][2] if s is not None)
            uniq = tuple(sorted(seen))
            unions[i] = (uniq, {s: j for j, s in enumerate(uniq)})

    def emit(group, rows):
        """One batch of the `rows` rows of `group`: [(page, cols, lo, hi)]."""
        out = {}
        for i, name in enumerate(names):
            if group[0][1][i] is None:
                # a block only block_to_column knows: its page is a
                # group of its own
                (page, _cols, lo, hi), = group
                out[name] = block_to_column(
                    types[i], page.blocks[i].take(np.arange(lo, hi)),
                    capacity)
                continue
            parts, null_parts, any_null = [], [], False
            for _page, cols, lo, hi in group:
                values, nulls, dictionary = cols[i]
                values = values[lo:hi]
                if i in unions:
                    index = unions[i][1]
                    lut = np.fromiter((index.get(s, 0) for s in dictionary),
                                      dtype=np.int32, count=len(dictionary))
                    values = lut[values] if len(lut) else \
                        np.zeros(hi - lo, dtype=np.int32)
                parts.append(values)
                null_parts.append(None if nulls is None else nulls[lo:hi])
                # (a string column carries nulls only where one is set,
                # a numeric one wherever its block does: as page_to_batch)
                any_null = any_null or (nulls is not None and (
                    i not in unions or bool(nulls[lo:hi].any())))
            values = parts[0] if len(parts) == 1 else np.concatenate(parts)
            nulls = None
            if any_null:
                nulls = jnp.asarray(_padded(np.concatenate(
                    [np.zeros(len(v), dtype=bool) if n is None else n
                     for v, n in zip(parts, null_parts)]), capacity))
            out[name] = Column(jnp.asarray(_padded(values, capacity)), nulls,
                               unions[i][0] if i in unions else None)
        mask = np.zeros(capacity, dtype=bool)
        mask[:rows] = True
        return Batch(out, jnp.asarray(mask))

    group, rows = [], 0
    for page, cols in staged:
        alone = any(c is None for c in cols)
        lo = 0
        while lo < page.position_count:
            if rows == capacity or (rows and alone):
                yield emit(group, rows)
                group, rows = [], 0
            hi = min(page.position_count, lo + capacity - rows)
            group.append((page, cols, lo, hi))
            rows += hi - lo
            lo = hi
            if alone:
                yield emit(group, rows)
                group, rows = [], 0
    if group:
        yield emit(group, rows)
