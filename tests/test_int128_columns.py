"""Long decimals between the device's int64 column and the wire's
INT128_ARRAY: whole-array conversions at both ends of an exchange, checked
against the per-row definition (`Int128Block.from_ints` / `to_pylist`)."""
import numpy as np
import pytest

import jax.numpy as jnp

from presto_tpu.common import (
    BIGINT, DATE, INTEGER, DecimalType, Int128Block, Page, deserialize_page,
    serialize_page,
)
from presto_tpu.exec.batch import (
    Batch, Column, batch_to_page, page_to_batch, pages_to_batches,
)

I64_MIN, I64_MAX = np.iinfo(np.int64).min, np.iinfo(np.int64).max
EDGES = [0, 1, -1, I64_MAX, I64_MIN, 10**18, -10**18]
LONG = DecimalType(38, 4)


def _draws(name, n=4096):
    if name == "empty":
        return np.zeros(0, dtype=np.int64)
    if name == "edges":
        return np.asarray(EDGES * 3, dtype=np.int64)
    rng = np.random.default_rng(17)
    return rng.integers(I64_MIN, I64_MAX, size=n, dtype=np.int64,
                        endpoint=True)


def _nulls(name, n):
    if name == "none":
        return None
    return np.random.default_rng(5).random(n) < 0.3


def _reference_ints(values, nulls):
    return [None if (nulls is not None and nulls[i]) else int(v)
            for i, v in enumerate(values)]


@pytest.mark.parametrize("nulls_kind", ["none", "some"])
@pytest.mark.parametrize("draws", ["random", "edges", "empty"])
def test_from_int64_writes_the_words_of_from_ints(draws, nulls_kind):
    values = _draws(draws)
    nulls = _nulls(nulls_kind, len(values))
    got = Int128Block.from_int64(values, nulls)
    want = Int128Block.from_ints(_reference_ints(values, nulls), nulls)
    assert got.values.dtype == np.int64 and got.values.shape == (len(values), 2)
    np.testing.assert_array_equal(got.values, want.values)
    assert (got.nulls is None) == (want.nulls is None)
    if want.nulls is not None:
        np.testing.assert_array_equal(got.nulls, want.nulls)


@pytest.mark.parametrize("nulls_kind", ["none", "some"])
@pytest.mark.parametrize("draws", ["random", "edges", "empty"])
def test_to_int64_reads_what_to_pylist_reads(draws, nulls_kind):
    values = _draws(draws)
    nulls = _nulls(nulls_kind, len(values))
    block = Int128Block.from_ints(_reference_ints(values, nulls), nulls)
    got = block.to_int64()
    assert got.dtype == np.int64
    assert [None if (block.nulls is not None and block.nulls[i]) else int(v)
            for i, v in enumerate(got)] == block.to_pylist()
    if block.nulls is not None:
        assert not got[block.nulls].any()        # a null row reads 0


def test_negative_zero_and_a_wide_null_row_read_zero():
    words = np.asarray([[0, I64_MIN], [5, 7], [3, 0]], dtype=np.int64)
    got = Int128Block(words, np.asarray([False, True, False])).to_int64()
    assert got.tolist() == [0, 0, 3]


@pytest.mark.parametrize("words", [
    [I64_MIN, 0],               # +2**63
    [-1, 0],                    # +2**64 - 1
    [-1, I64_MIN],              # -(2**64 - 1)
    [I64_MIN + 1, I64_MIN],     # -(2**63 + 1)
    [0, 1],                     # 2**64
    [5, I64_MIN | 2],           # -(2**65 + 5)
], ids=["2^63", "2^64-1", "-(2^64-1)", "-(2^63+1)", "2^64", "-(2^65+5)"])
@pytest.mark.parametrize("way", ["block_to_column", "pages_to_batches"])
def test_a_magnitude_beyond_int64_raises_on_the_way_to_the_device(words, way):
    block = Int128Block(np.asarray([[7, 0], words], dtype=np.int64))
    page = Page([block], 2)
    with pytest.raises(OverflowError, match="long decimal"):
        if way == "block_to_column":
            page_to_batch(page, ["d"], [LONG], 4)
        else:
            list(pages_to_batches([page], ["d"], [LONG], 4))


def _values_and_nulls(batches):
    values, nulls = [], []
    for b in batches:
        n = int(np.asarray(b.mask).sum())
        col = b.columns["d"]
        values.append(np.asarray(col.values)[:n])
        nulls.append(np.zeros(n, dtype=bool) if col.nulls is None
                     else np.asarray(col.nulls)[:n])
    return np.concatenate(values), np.concatenate(nulls)


def _wire(page):
    return deserialize_page(serialize_page(page))[0]


@pytest.mark.parametrize("pages", [1, 3])
def test_a_64k_row_long_decimal_column_crosses_the_exchange(pages):
    n = 1 << 16
    rng = np.random.default_rng(11)
    values = np.concatenate([
        np.asarray(EDGES, dtype=np.int64),
        rng.integers(-10**18, 10**18, size=n - len(EDGES), dtype=np.int64)])
    nulls = rng.random(n) < 0.1
    keys = np.arange(n, dtype=np.int64)
    names = ["k", "day", "qty", "d"]
    types = [BIGINT, DATE, INTEGER, LONG]
    cuts = np.linspace(0, n, pages + 1).astype(int)
    wire = []
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        live = np.zeros(n, dtype=bool)
        live[lo:hi] = True
        batch = Batch({
            "k": Column(jnp.asarray(keys)),
            "day": Column(jnp.asarray((keys % 2000).astype(np.int32))),
            "qty": Column(jnp.asarray((keys % 50).astype(np.int32))),
            "d": Column(jnp.asarray(values), jnp.asarray(nulls)),
        }, jnp.asarray(live))
        page = batch_to_page(batch, names, types)
        assert isinstance(page.blocks[3], Int128Block)
        wire.append(_wire(page))
    got_values, got_nulls = _values_and_nulls(
        pages_to_batches(wire, names, types, n))
    np.testing.assert_array_equal(got_nulls, nulls)
    np.testing.assert_array_equal(got_values[~nulls], values[~nulls])


def test_no_long_decimal_goes_through_python_ints(monkeypatch):
    """The per-row definitions raise here: every exchange path still
    carries a long-decimal page."""
    def per_row(*_args, **_kwargs):
        raise AssertionError("a long decimal converted one row at a time")

    monkeypatch.setattr(Int128Block, "from_ints", staticmethod(per_row))
    monkeypatch.setattr(Int128Block, "to_pylist", per_row)
    values = np.asarray(EDGES + [42], dtype=np.int64)
    nulls = np.zeros(len(values), dtype=bool)
    nulls[-1] = True
    batch = Batch({"d": Column(jnp.asarray(values), jnp.asarray(nulls))},
                  jnp.ones(len(values), dtype=bool))
    page = _wire(batch_to_page(batch, ["d"], [LONG]))
    for batches in ([page_to_batch(page, ["d"], [LONG], 16)],
                    list(pages_to_batches([page], ["d"], [LONG], 16))):
        got_values, got_nulls = _values_and_nulls(batches)
        np.testing.assert_array_equal(got_nulls, nulls)
        np.testing.assert_array_equal(got_values[:-1], values[:-1])
