"""The resident store under a mesh (storage/store.py): a column held as
one shard a device, shard i the rows of `make_splits(table, sf, n)[i]`.

The shards add up to the table: decoded and concatenated in device order
they equal the one-device column, every shard lives on its own device and
reads table positions through its `base`; a column is built once however
many tasks miss it at once; `entries` keeps the key and the total bytes
that benchmark/collect.py `resident_columns()` unpacks; and without
`devices` the store does what it did."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.connectors import catalog
from presto_tpu.storage import STORAGE_METRICS, ResidentStore
from presto_tpu.utils.runtime_stats import RuntimeStats

SF = 0.01
PAD = 256
N = 4
# the lineitem columns Q1 and Q6 touch, with the width the scan asks for
COLUMNS = {"quantity": False, "extendedprice": False, "discount": False,
           "tax": False, "returnflag": False, "linestatus": False,
           "shipdate": True}


def _devices():
    return tuple(jax.devices()[:N])


def _np(x):
    return np.asarray(jax.device_get(x))


def _build(store, table, column, as_i32, devices=None, pad=PAD):
    return store.get_or_build(
        "tpch", table, column, SF,
        catalog.table_row_count(table, SF, "tpch"), pad, as_i32,
        devices=devices)


@pytest.fixture(scope="module")
def stores():
    return ResidentStore(), ResidentStore()


@pytest.mark.parametrize("column", sorted(COLUMNS))
def test_shards_in_device_order_are_the_one_device_column(stores, column):
    sharded, whole = stores
    ent = _build(sharded, "lineitem", column, COLUMNS[column], _devices())
    one = _build(whole, "lineitem", column, COLUMNS[column])
    n_rows = catalog.table_row_count("lineitem", SF, "tpch")
    splits = catalog.make_splits("lineitem", SF, N, "tpch")
    assert ent.devices == _devices() and len(ent.shards) == N
    parts = []
    for i, (col, zones) in enumerate(ent.shards):
        # shard i is split i of the n-way split, on device i
        assert (zones.base, zones.base + col.n_rows) \
            == (splits[i].start, splits[i].end)
        assert int(col.base) == splits[i].start
        for arr in col.arrays + (col.base,):
            assert arr.devices() == {_devices()[i]}
        # no chip holds more than its share of the column (+ padding)
        assert col.arrays[0].shape[0] == col.n_rows + PAD
        parts.append(_np(col.decode_full())[:col.n_rows])
    expected = _np(one.column.decode_full())[:n_rows]
    np.testing.assert_array_equal(np.concatenate(parts), expected)
    # a scan's table positions are made local by the shard's base
    for i, (col, zones) in enumerate(ent.shards):
        pos = splits[i].start + 37
        got = _np(col.slice_decode(jnp.int64(pos), 64))
        np.testing.assert_array_equal(got, expected[pos:pos + 64])
        assert zones.chunk_bounds(pos, 64) is not None
    assert one.column.base is None and one.zones.base == 0


def test_entries_unpack_as_the_benchmark_reads_them(stores):
    sharded, whole = stores
    for column, as_i32 in COLUMNS.items():
        _build(sharded, "lineitem", column, as_i32, _devices())
    # benchmark/collect.py resident_columns(), letter for letter
    resident = {f"{table}.{column}": int(entry.nbytes)
                for (_cid, table, column, _sf, _i32), entry
                in sharded.entries.items()}
    assert sorted(resident) == sorted(f"lineitem.{c}" for c in COLUMNS)
    for key, entry in sharded.entries.items():
        assert entry.nbytes == sum(
            a.nbytes for col, _zones in entry.shards for a in col.arrays)
    assert sharded.pool.reserved == sum(resident.values())
    sharded.clear()
    assert not sharded.entries and sharded.pool.reserved == 0


def test_tasks_that_miss_a_column_at_once_build_it_once():
    store = ResidentStore()
    stats = [RuntimeStats() for _ in range(2)]
    before = STORAGE_METRICS.snapshot()
    start = threading.Barrier(2)
    got = []

    def ask(s):
        with s.activate():
            start.wait()
            got.append(_build(store, "lineitem", "quantity", False,
                              _devices()))
    threads = [threading.Thread(target=ask, args=(s,)) for s in stats]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got[0] is got[1] is not None       # the second waited and hit
    built = [s.get("storageShardBuilds") for s in stats]
    assert sorted(0 if m is None else m.sum for m in built) == [0, N]
    assert sum(s.get("storageBuilds") is not None for s in stats) == 1
    # the builder counted what each shard's encoder chose
    chose = [s.get("storageEncoding.dict") for s in stats]
    assert sorted(0 if m is None else m.sum for m in chose) == [0, N]
    assert got[0].kinds == ("dict",) * N
    after = STORAGE_METRICS.snapshot()
    assert after["columns_built"] - before["columns_built"] == 1
    assert after["cache_misses"] - before["cache_misses"] == 1
    assert after["cache_hits"] - before["cache_hits"] == 1


def test_without_devices_the_store_is_what_it_was():
    store = ResidentStore()
    before = STORAGE_METRICS.snapshot()
    ent = _build(store, "lineitem", "discount", False)
    n_rows = catalog.table_row_count("lineitem", SF, "tpch")
    assert list(store.entries) == [("tpch", "lineitem", "discount", SF,
                                    False)]
    assert ent.devices is None and len(ent.shards) == 1
    assert ent.column.n_rows == n_rows and ent.column.base is None
    assert ent.nbytes == ent.column.nbytes == store.pool.reserved
    # the pytree a scan program takes holds the arrays and nothing else
    assert len(jax.tree_util.tree_leaves(ent.column)) \
        == len(ent.column.arrays)
    assert _build(store, "lineitem", "discount", False) is ent
    after = STORAGE_METRICS.snapshot()
    delta = {k: after[k] - before[k] for k in after
             if after[k] != before[k] and k != "resident_bytes"}
    assert delta == {"cache_misses": 1, "cache_hits": 1, "columns_built": 1,
                     "encoded_bytes": ent.nbytes,
                     "plain_bytes": ent.column.logical_nbytes}


def test_a_table_of_fewer_rows_than_devices_leaves_shards_empty():
    sf = 0.0003                               # two suppliers, four devices
    n_rows = catalog.table_row_count("supplier", sf, "tpch")
    ent = ResidentStore().get_or_build(
        "tpch", "supplier", "suppkey", sf, n_rows, PAD, False,
        devices=_devices())
    rows = [col.n_rows for col, _zones in ent.shards]
    assert rows == [1, 1, 0, 0] and n_rows == 2
    one = ResidentStore().get_or_build(
        "tpch", "supplier", "suppkey", sf, n_rows, PAD, False)
    values = np.concatenate([_np(col.decode_full())[:col.n_rows]
                             for col, _zones in ent.shards])
    np.testing.assert_array_equal(
        values, _np(one.column.decode_full())[:n_rows])


def test_the_size_limit_is_a_shards_and_a_new_layout_rebuilds():
    n_rows = catalog.table_row_count("lineitem", SF, "tpch")
    # room for a quarter of the 8-byte column and its padding, not for all
    store = ResidentStore(max_column_bytes=(n_rows // N + PAD) * 8)
    assert _build(store, "lineitem", "extendedprice", False) is None
    ent = _build(store, "lineitem", "extendedprice", False, _devices())
    assert ent is not None and len(ent.shards) == N
    # asked for under another layout, the entry is built anew
    roomy = ResidentStore()
    sharded = _build(roomy, "lineitem", "tax", False, _devices())
    whole = _build(roomy, "lineitem", "tax", False)
    assert whole is not sharded and whole.devices is None
    assert list(roomy.entries.values()) == [whole]
    assert roomy.pool.reserved == whole.nbytes
