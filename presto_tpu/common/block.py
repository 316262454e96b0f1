"""Columnar Block hierarchy, host side, numpy-backed.

Re-implements the behavior of the reference block model
(presto-common/src/main/java/com/facebook/presto/common/block/Block.java and its
concrete classes) with vectorized numpy storage instead of per-position accessors.
The wire encodings (serde.py) are byte-compatible with the reference
*BlockEncoding.java classes; this module is the in-memory model.

Null convention: `nulls` is a bool ndarray where True == null, or None when the
block provably has no nulls (mirrors Block.mayHaveNull()).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from .types import (
    BYTE_ARRAY, SHORT_ARRAY, INT_ARRAY, LONG_ARRAY, INT128_ARRAY,
    VARIABLE_WIDTH, ARRAY, MAP, ROW, ArrayType, Type, DateType, DecimalType,
    DoubleType, RealType, BooleanType, VarcharType, CharType, VarbinaryType,
)

_WIDTH_TO_ENCODING = {1: BYTE_ARRAY, 2: SHORT_ARRAY, 4: INT_ARRAY, 8: LONG_ARRAY}


class Block:
    """Abstract block. position_count positions of one column."""

    position_count: int
    nulls: Optional[np.ndarray]  # bool array, True == null; None == no nulls

    @property
    def encoding(self) -> str:
        raise NotImplementedError

    @property
    def may_have_null(self) -> bool:
        return self.nulls is not None and bool(self.nulls.any())

    def null_mask(self) -> np.ndarray:
        if self.nulls is None:
            return np.zeros(self.position_count, dtype=bool)
        return self.nulls

    def __len__(self) -> int:
        return self.position_count

    # --- generic ops used by the engine ---------------------------------
    def take(self, positions: np.ndarray) -> "Block":
        """New block with the given positions (DictionaryBlock.getPositions analog,
        but materialized)."""
        raise NotImplementedError

    def region(self, offset: int, length: int) -> "Block":
        return self.take(np.arange(offset, offset + length))

    def to_pylist(self) -> list:
        """Decode to python objects (None for nulls) — test/debug path."""
        raise NotImplementedError


class FixedWidthBlock(Block):
    """BYTE/SHORT/INT/LONG array blocks.  `values` may be stored under any dtype
    of the right itemsize (e.g. float64 for DOUBLE — the wire just sees bits)."""

    def __init__(self, values: np.ndarray, nulls: Optional[np.ndarray] = None):
        values = np.ascontiguousarray(values)
        if values.ndim != 1:
            raise ValueError("FixedWidthBlock values must be 1-D")
        self.values = values
        self.position_count = len(values)
        self.nulls = nulls if (nulls is not None and nulls.any()) else None

    @property
    def encoding(self) -> str:
        return _WIDTH_TO_ENCODING[self.values.dtype.itemsize]

    def take(self, positions: np.ndarray) -> "FixedWidthBlock":
        return FixedWidthBlock(
            self.values[positions],
            None if self.nulls is None else self.nulls[positions],
        )

    def to_pylist(self) -> list:
        vals = self.values.tolist()
        if self.nulls is None:
            return vals
        return [None if n else v for v, n in zip(vals, self.nulls.tolist())]


def byte_array_block(values, nulls=None):
    return FixedWidthBlock(np.asarray(values, dtype=np.int8), _mask(nulls))


def short_array_block(values, nulls=None):
    return FixedWidthBlock(np.asarray(values, dtype=np.int16), _mask(nulls))


def int_array_block(values, nulls=None):
    return FixedWidthBlock(np.asarray(values, dtype=np.int32), _mask(nulls))


def long_array_block(values, nulls=None):
    return FixedWidthBlock(np.asarray(values, dtype=np.int64), _mask(nulls))


def double_block(values, nulls=None):
    return FixedWidthBlock(np.asarray(values, dtype=np.float64), _mask(nulls))


def _mask(nulls):
    if nulls is None:
        return None
    return np.asarray(nulls, dtype=bool)


class Int128Block(Block):
    """INT128_ARRAY: values shape (n, 2) int64 in wire order (first long, second
    long).  For long decimals the reference layout
    (UnscaledDecimal128Arithmetic.java:33-39) is sign-magnitude little-endian:
    word 0 = low 64 bits of |value|, word 1 = high 63 bits | sign bit in MSB."""

    def __init__(self, values: np.ndarray, nulls: Optional[np.ndarray] = None):
        values = np.ascontiguousarray(values, dtype=np.int64)
        if values.ndim != 2 or values.shape[1] != 2:
            raise ValueError("Int128Block values must be (n, 2) int64")
        self.values = values
        self.position_count = len(values)
        self.nulls = nulls if (nulls is not None and nulls.any()) else None

    @property
    def encoding(self) -> str:
        return INT128_ARRAY

    def take(self, positions):
        return Int128Block(
            self.values[positions],
            None if self.nulls is None else self.nulls[positions],
        )

    def to_pylist(self):
        """Decode as signed int128 under the reference sign-magnitude layout."""
        out = []
        for i in range(self.position_count):
            if self.nulls is not None and self.nulls[i]:
                out.append(None)
            else:
                lo = int(self.values[i, 0]) & 0xFFFFFFFFFFFFFFFF
                hi = int(self.values[i, 1]) & 0xFFFFFFFFFFFFFFFF
                negative = bool(hi >> 63)
                magnitude = ((hi & 0x7FFFFFFFFFFFFFFF) << 64) | lo
                out.append(-magnitude if negative else magnitude)
        return out

    @staticmethod
    def from_ints(values, nulls=None) -> "Int128Block":
        """Build from python ints using the reference sign-magnitude layout."""
        arr = np.zeros((len(values), 2), dtype=np.uint64)
        for i, v in enumerate(values):
            if v is None:
                continue
            magnitude = abs(int(v))
            lo = magnitude & 0xFFFFFFFFFFFFFFFF
            hi = (magnitude >> 64) & 0x7FFFFFFFFFFFFFFF
            if v < 0:
                hi |= 1 << 63
            arr[i, 0] = lo
            arr[i, 1] = hi
        return Int128Block(arr.view(np.int64), _mask(nulls))

    @staticmethod
    def from_int64(values, nulls=None) -> "Int128Block":
        """`from_ints` of an int64 array, by whole-array operations: the same
        words, a null row two zero words.  |INT64_MIN| wraps to the int64
        bit pattern of 2**63, which is its magnitude's low word."""
        values = np.asarray(values, dtype=np.int64)
        nulls = _mask(nulls)
        if nulls is not None:
            values = np.where(nulls, 0, values)
        words = np.empty((len(values), 2), dtype=np.int64)
        words[:, 0] = np.abs(values)
        words[:, 1] = np.where(values < 0, np.iinfo(np.int64).min, 0)
        return Int128Block(words, nulls)

    def to_int64(self) -> np.ndarray:
        """The values narrowed to int64 by whole-array operations, null rows
        0 (`self.nulls` says which).  Raises OverflowError where a live row's
        magnitude does not fit: word 1 holds a bit below the sign, or word 0
        is 2**63 or more, short of -2**63 exactly."""
        lo, hi = self.values[:, 0], self.values[:, 1]
        negative = hi < 0
        wide = ((hi & np.iinfo(np.int64).max) != 0) | (
            (lo < 0) & ~(negative & (lo == np.iinfo(np.int64).min)))
        if self.nulls is not None:
            wide &= ~self.nulls
        if wide.any():
            raise OverflowError(
                "long decimal beyond int64, which is its device form: "
                f"{int(wide.sum())} row(s)")
        values = np.where(negative, -lo, lo)   # -(-2**63) wraps to itself
        if self.nulls is not None:
            values[self.nulls] = 0
        return values


class VariableWidthBlock(Block):
    """VARIABLE_WIDTH: concatenated bytes + (n+1) int32 offsets."""

    def __init__(self, offsets: np.ndarray, data: np.ndarray,
                 nulls: Optional[np.ndarray] = None):
        self.offsets = np.ascontiguousarray(offsets, dtype=np.int32)
        self.data = np.ascontiguousarray(data, dtype=np.uint8)
        self.position_count = len(self.offsets) - 1
        self.nulls = nulls if (nulls is not None and nulls.any()) else None

    @property
    def encoding(self) -> str:
        return VARIABLE_WIDTH

    @staticmethod
    def from_bytes(items: Sequence[Optional[bytes]]) -> "VariableWidthBlock":
        encoded = [(b if b is not None else b"") for b in items]
        offsets = np.zeros(len(encoded) + 1, dtype=np.int32)
        np.cumsum([len(b) for b in encoded], out=offsets[1:])
        data = np.frombuffer(b"".join(encoded), dtype=np.uint8).copy()
        nulls = np.array([b is None for b in items], dtype=bool)
        return VariableWidthBlock(offsets, data, nulls if nulls.any() else None)

    @staticmethod
    def from_strings(strings: Sequence[Optional[str]]) -> "VariableWidthBlock":
        return VariableWidthBlock.from_bytes(
            [None if s is None else s.encode("utf-8") for s in strings])

    def take(self, positions) -> "VariableWidthBlock":
        positions = np.asarray(positions)
        lengths = (self.offsets[1:] - self.offsets[:-1])[positions]
        new_offsets = np.zeros(len(positions) + 1, dtype=np.int32)
        np.cumsum(lengths, out=new_offsets[1:])
        total = int(new_offsets[-1])
        if total == 0:
            out = np.empty(0, dtype=np.uint8)
        else:
            # vectorized byte gather: source index = row start + offset
            # within the row (no per-row python loop — this sits on the
            # exchange partition-split path)
            within = np.arange(total, dtype=np.int64) - np.repeat(
                new_offsets[:-1].astype(np.int64), lengths)
            src = np.repeat(self.offsets[positions].astype(np.int64),
                            lengths) + within
            out = self.data[src]
        return VariableWidthBlock(
            new_offsets, out,
            None if self.nulls is None else self.nulls[positions])

    def slice_at(self, i: int) -> bytes:
        return self.data[self.offsets[i]:self.offsets[i + 1]].tobytes()

    def to_pylist(self):
        out = []
        for i in range(self.position_count):
            if self.nulls is not None and self.nulls[i]:
                out.append(None)
            else:
                out.append(self.slice_at(i).decode("utf-8", errors="replace"))
        return out


# Sequence id for dictionary blocks written on the wire (reference DictionaryId).
_DICT_ID_COUNTER = [0]


def _next_dictionary_id():
    _DICT_ID_COUNTER[0] += 1
    # (mostSignificantBits, leastSignificantBits, sequenceId)
    return (0x7075_7470, 0x7463_6F6C, _DICT_ID_COUNTER[0])


class DictionaryBlock(Block):
    """DICTIONARY: int32 ids into a dictionary block."""

    def __init__(self, ids: np.ndarray, dictionary: Block, source_id=None):
        self.ids = np.ascontiguousarray(ids, dtype=np.int32)
        self.dictionary = dictionary
        self.position_count = len(self.ids)
        self.source_id = source_id or _next_dictionary_id()
        self.nulls = None

    @property
    def encoding(self) -> str:
        return "DICTIONARY"

    @property
    def may_have_null(self) -> bool:
        return self.dictionary.may_have_null

    def null_mask(self) -> np.ndarray:
        return self.dictionary.null_mask()[self.ids]

    def compact(self) -> "DictionaryBlock":
        """Rewrite so the dictionary contains only referenced entries
        (DictionaryBlock.compact in the reference — required before
        serializing).  An already-compact block is returned unchanged so
        its dictionary instance id survives re-serialization."""
        entries = self.dictionary.position_count
        if len(self.ids) and (self.ids.min() < 0
                              or self.ids.max() >= entries):
            raise IndexError(
                f"dictionary id outside its {entries} entries")
        # a count an entry, not a sort of the ids (np.unique: 5 ms a 64K
        # row page, once a page a string column on the exchange)
        used = np.flatnonzero(np.bincount(self.ids, minlength=entries))
        if len(used) == entries:
            return self
        remap = np.zeros(entries, dtype=np.int32)
        remap[used] = np.arange(len(used), dtype=np.int32)
        return DictionaryBlock(remap[self.ids], self.dictionary.take(used))

    def decode(self) -> Block:
        return self.dictionary.take(self.ids)

    def take(self, positions):
        return DictionaryBlock(self.ids[positions], self.dictionary)

    def to_pylist(self):
        d = self.dictionary.to_pylist()
        return [d[i] for i in self.ids.tolist()]


class RunLengthBlock(Block):
    """RLE: one value repeated position_count times."""

    def __init__(self, value: Block, position_count: int):
        if value.position_count != 1:
            raise ValueError("RLE value block must have exactly 1 position")
        self.value = value
        self.position_count = position_count
        self.nulls = None

    @property
    def encoding(self) -> str:
        return "RLE"

    @property
    def may_have_null(self) -> bool:
        return self.value.may_have_null

    def null_mask(self) -> np.ndarray:
        return np.full(self.position_count, bool(self.value.null_mask()[0]))

    def decode(self) -> Block:
        return self.value.take(np.zeros(self.position_count, dtype=np.int64))

    def take(self, positions):
        return RunLengthBlock(self.value, len(np.asarray(positions)))

    def to_pylist(self):
        return self.value.to_pylist() * self.position_count


class ArrayBlock(Block):
    """ARRAY: (n+1) int32 offsets into an elements block."""

    def __init__(self, offsets: np.ndarray, elements: Block,
                 nulls: Optional[np.ndarray] = None):
        self.offsets = np.ascontiguousarray(offsets, dtype=np.int32)
        self.elements = elements
        self.position_count = len(self.offsets) - 1
        self.nulls = nulls if (nulls is not None and nulls.any()) else None

    @property
    def encoding(self) -> str:
        return ARRAY

    def take(self, positions):
        positions = np.asarray(positions)
        lengths = (self.offsets[1:] - self.offsets[:-1])[positions]
        new_offsets = np.zeros(len(positions) + 1, dtype=np.int32)
        np.cumsum(lengths, out=new_offsets[1:])
        idx = np.concatenate(
            [np.arange(self.offsets[p], self.offsets[p + 1]) for p in positions]
        ) if len(positions) else np.array([], dtype=np.int64)
        return ArrayBlock(
            new_offsets, self.elements.take(idx.astype(np.int64)),
            None if self.nulls is None else self.nulls[positions])

    def to_pylist(self):
        elems = self.elements.to_pylist()
        out = []
        for i in range(self.position_count):
            if self.nulls is not None and self.nulls[i]:
                out.append(None)
            else:
                out.append(elems[self.offsets[i]:self.offsets[i + 1]])
        return out


class RowBlock(Block):
    """ROW: parallel field blocks + (n+1) offsets (non-null rows are dense)."""

    def __init__(self, field_blocks: List[Block], offsets: np.ndarray,
                 nulls: Optional[np.ndarray] = None):
        self.field_blocks = field_blocks
        self.offsets = np.ascontiguousarray(offsets, dtype=np.int32)
        self.position_count = len(self.offsets) - 1
        self.nulls = nulls if (nulls is not None and nulls.any()) else None

    @staticmethod
    def from_fields(field_blocks: List[Block]) -> "RowBlock":
        n = field_blocks[0].position_count
        return RowBlock(field_blocks, np.arange(n + 1, dtype=np.int32))

    @property
    def encoding(self) -> str:
        return ROW

    def take(self, positions):
        positions = np.asarray(positions)
        nulls = None if self.nulls is None else self.nulls[positions]
        # Null rows occupy no field entries in the sparse reference layout
        # (RowBlockEncoding offsets), so only gather rows for non-null positions.
        null_mask = (np.zeros(len(positions), dtype=bool)
                     if nulls is None else nulls)
        rows = self.offsets[positions][~null_mask]
        new_offsets = np.zeros(len(positions) + 1, dtype=np.int32)
        np.cumsum(~null_mask, out=new_offsets[1:])
        return RowBlock(
            [f.take(rows) for f in self.field_blocks], new_offsets, nulls)

    def to_pylist(self):
        fields = [f.to_pylist() for f in self.field_blocks]
        out = []
        for i in range(self.position_count):
            if self.nulls is not None and self.nulls[i]:
                out.append(None)
            else:
                r = int(self.offsets[i])
                out.append([f[r] for f in fields])
        return out


def decode_to_flat(block: Block) -> Block:
    """Flatten DICTIONARY/RLE wrappers to a direct block."""
    while isinstance(block, (DictionaryBlock, RunLengthBlock)):
        block = block.decode()
    return block


# ---------------------------------------------------------------------------
# Typed construction helpers: python values -> storage block for a Type
# ---------------------------------------------------------------------------

def block_from_values(typ: Type, values: Sequence) -> Block:
    """Build a block from python values (None == null) under `typ` semantics."""
    nulls = np.array([v is None for v in values], dtype=bool)
    has_null = bool(nulls.any())
    n = len(values)

    if isinstance(typ, (VarcharType, CharType)):
        return VariableWidthBlock.from_strings(values)
    if isinstance(typ, VarbinaryType):
        return VariableWidthBlock.from_bytes(values)
    if isinstance(typ, DecimalType) and not typ.is_short:
        return Int128Block.from_ints(values, nulls if has_null else None)
    if isinstance(typ, ArrayType):
        offsets = np.zeros(n + 1, dtype=np.int32)
        flat: list = []
        for i, v in enumerate(values):
            if v is not None:
                flat.extend(v)
            offsets[i + 1] = len(flat)
        return ArrayBlock(offsets, block_from_values(typ.element, flat),
                          nulls if has_null else None)

    if isinstance(typ, DoubleType):
        dtype = np.float64
    elif isinstance(typ, RealType):
        dtype = np.float32
    elif isinstance(typ, BooleanType):
        dtype = np.int8
    else:
        dtype = typ.np_dtype
    arr = np.zeros(n, dtype=dtype)
    for i, v in enumerate(values):
        if v is not None:
            arr[i] = v
    if isinstance(typ, RealType):
        # REAL stores float bits in an INT_ARRAY on the wire
        arr = arr.view(np.int32) if arr.dtype == np.float32 else arr
    return FixedWidthBlock(arr, nulls if has_null else None)


def block_to_values(typ: Type, block: Block) -> list:
    """Decode a block to python values under `typ` semantics."""
    block = decode_to_flat(block)
    if isinstance(typ, ArrayType) and isinstance(block, ArrayBlock):
        elems = block_to_values(typ.element, block.elements)
        out = []
        for i in range(block.position_count):
            if block.nulls is not None and block.nulls[i]:
                out.append(None)
            else:
                out.append(elems[block.offsets[i]:block.offsets[i + 1]])
        return out
    if isinstance(typ, (VarcharType, CharType)):
        return block.to_pylist()
    if isinstance(typ, VarbinaryType):
        return [
            None if (block.nulls is not None and block.nulls[i])
            else block.slice_at(i)
            for i in range(block.position_count)
        ]
    if isinstance(typ, DoubleType):
        vals = block.values.view(np.float64) if block.values.dtype != np.float64 else block.values
        return [None if n else float(v)
                for v, n in zip(vals, block.null_mask())]
    if isinstance(typ, RealType):
        vals = block.values.view(np.float32) if block.values.dtype != np.float32 else block.values
        return [None if n else float(v)
                for v, n in zip(vals, block.null_mask())]
    if isinstance(typ, BooleanType):
        return [None if n else bool(v)
                for v, n in zip(block.values, block.null_mask())]
    if isinstance(typ, DateType):
        return [None if n else str(np.datetime64(int(v), "D"))
                for v, n in zip(block.values, block.null_mask())]
    if isinstance(typ, DecimalType):
        raw = block.to_pylist()  # Int128Block.to_pylist handles sign-magnitude
        from decimal import Decimal
        q = Decimal(1).scaleb(-typ.scale)
        return [None if v is None else (Decimal(v) * q) for v in raw]
    return block.to_pylist()
