"""`ops.gather_near`: `table[idx]` on every live row, by whichever of its
three ways the indices allow -- the gather, the GATHER_WINDOW window, or
the block-local read of LOOKUP_BLOCK rows at a time from two neighbouring
LOOKUP_TILE-entry tiles -- and which one it took."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from presto_tpu.connectors.tpch import _li_order_map
from presto_tpu.exec import operators as ops

SIZE = 1 << 19
N = 8192
BLOCKED, WINDOW, GATHER = ops.LOOKUP_BLOCKED, ops.LOOKUP_WINDOW, \
    ops.LOOKUP_GATHER


def _orderkeys(first_row, n):
    """lineitem's l_orderkey for n rows from `first_row`: sorted, dense,
    1-7 rows a key."""
    return _li_order_map(np.arange(first_row, first_row + n), 10.0)[0]


def _sparse_keys(rng, n, max_gap):
    """sorted keys, 1-7 rows a key, the gap to the next key 1..max_gap."""
    counts = rng.integers(1, 8, n)
    keys = np.cumsum(rng.integers(1, max_gap + 1, n))
    return np.repeat(keys, counts)[:n]


def _case(name, rng):
    """(table size, indices, live) of a case, and the way it must go."""
    half = rng.random(N) < 0.5
    every = np.ones(N, dtype=bool)
    if name == "clustered":         # 8192 rows over 1000 keys, sorted
        return SIZE, np.sort(rng.integers(SIZE // 2, SIZE // 2 + 1000, N)), \
            half, BLOCKED
    if name == "scattered":
        return SIZE, rng.integers(0, SIZE, N), half, GATHER
    if name == "none_live":         # every block spans nothing
        return SIZE, rng.integers(0, SIZE, N), np.zeros(N, bool), BLOCKED
    if name == "small_table":       # under 2 x GATHER_WINDOW: the gather
        size = ops.GATHER_WINDOW + 1000
        return size, np.sort(rng.integers(0, 1000, N)), half, GATHER
    if name == "dense_1_to_7":      # lineitem in l_orderkey's order
        keys = _orderkeys(20_000_000, N)
        return SIZE, keys - keys[0] + 7777, half, BLOCKED
    if name == "sparse_gaps_to_25":
        # the first half's blocks fit (gaps of 1), the second's do not
        keys = np.concatenate([_sparse_keys(rng, N // 2, 1),
                               _sparse_keys(rng, N // 2, 25) + 5000])
        return SIZE, keys + 1000, every, WINDOW
    if name in ("span_w_minus_1", "span_w"):
        # block 0 at the window's start; block 1 from a tile boundary,
        # hi - lo exactly LOOKUP_SPAN - 1 or LOOKUP_SPAN
        b = ops.LOOKUP_BLOCK
        span = ops.LOOKUP_SPAN - (name == "span_w_minus_1")
        lo = 3000 + 8 * ops.LOOKUP_TILE
        idx = np.concatenate([np.full(b, 3000),
                              np.linspace(lo, lo + span, b).astype(int)])
        return SIZE, idx, np.ones(2 * b, bool), \
            BLOCKED if name == "span_w_minus_1" else WINDOW
    if name == "table_end":         # the window clipped to the table's end
        keys = _orderkeys(0, N)
        return SIZE, keys - keys[-1] + SIZE - 1, every, BLOCKED
    if name == "dead_blocks":       # blocks with no live row among dense ones
        keys = _orderkeys(5_000_000, N)
        live = half & ((np.arange(N) // ops.LOOKUP_BLOCK) % 3 != 0)
        return SIZE, keys - keys[0], live, BLOCKED
    if name == "unsorted":          # dense keys, shuffled: no block fits
        keys = _orderkeys(0, N)
        return SIZE, rng.permutation(keys), every, WINDOW
    if name == "ragged_batch":      # rows no multiple of a block
        keys = _orderkeys(0, 1000)
        return SIZE, keys + 100, np.ones(1000, bool), WINDOW
    if name == "misses":            # slots holding -1 stay -1
        keys = _orderkeys(0, N)
        return SIZE, keys + 100, every, BLOCKED
    raise AssertionError(name)


CASES = ["clustered", "scattered", "none_live", "small_table", "dense_1_to_7",
         "sparse_gaps_to_25", "span_w_minus_1", "span_w", "table_end",
         "dead_blocks", "unsorted", "ragged_batch", "misses"]


@pytest.mark.parametrize("case", CASES)
def test_gather_near_equals_the_gather(case):
    rng = np.random.default_rng(4)
    size, idx, live, way = _case(case, rng)
    table = rng.integers(0, 1 << 31, size)
    table = np.where(rng.random(size) < 0.4, -1, table)
    table = jnp.asarray(table, dtype=jnp.int32)
    idx = jnp.asarray(np.clip(idx, 0, size - 1), dtype=jnp.int32)
    live = jnp.asarray(live)
    got, path = jax.jit(ops.gather_near_path)(table, idx, live)
    want = table[idx]
    assert int(path) == way
    # (a row that is not live may read garbage off the gather)
    assert bool(jnp.all(jnp.where(live, got == want, True)))
    assert bool(jnp.all(jax.jit(ops.gather_near)(table, idx, live) == got))
    if way == GATHER:
        assert bool(jnp.all(got == want))
    if case == "misses":
        assert bool(jnp.any(live & (want == -1)))
    if case == "sparse_gaps_to_25":     # some blocks would have fitted
        blocks = np.asarray(idx).reshape(-1, ops.LOOKUP_BLOCK)
        spans = blocks.max(axis=1) - blocks.min(axis=1)
        assert (spans < ops.LOOKUP_TILE).any() \
            and (spans >= ops.LOOKUP_SPAN).any()


def test_lookup_paths_gives_each_lookup_of_a_trace_its_way():
    """What the count pass of a dense stream returns with its counts."""
    rng = np.random.default_rng(5)
    table = jnp.asarray(rng.integers(-1, 1000, SIZE), dtype=jnp.int32)
    keys = _orderkeys(0, N)
    dense = jnp.asarray(keys, dtype=jnp.int32)
    scattered = jnp.asarray(rng.integers(0, SIZE, N), dtype=jnp.int32)
    live = jnp.ones(N, dtype=bool)

    def two_lookups(table, a, b):
        with ops.lookup_paths() as paths:
            ops.gather_near(table, a, live)
            ops.gather_near(table, b, live)
        return jnp.stack(paths)
    ways = jax.jit(two_lookups)(table, dense, scattered)
    assert ways.tolist() == [BLOCKED, GATHER]
    with ops.lookup_paths() as outside:
        pass
    assert outside == []
