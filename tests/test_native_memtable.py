"""Differential tests: NativeMemTable (C++ arena, native/memtable_arena.cc)
must match the Python MemTable on random workloads — ordering, dict
overwrite semantics, point_get seek semantics, packed/slab exports.
ref: src/yb/rocksdb/db/memtable.cc (arena + skiplist memtable)."""

import random

import numpy as np
import pytest

from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime
from yugabyte_tpu.storage.memtable import (MemTable, NativeMemTable,
                                           make_internal_key)

pytestmark = pytest.mark.requires_native("memtable_arena")


def _dht(us, w=0):
    return DocHybridTime(HybridTime.from_micros(us), w)


def _rand_items(rng, n, key_space, with_dups=True):
    items = []
    for _ in range(n):
        k = b"Skey%06d\x00\x00!" % rng.randrange(key_space)
        ht = _dht(rng.randrange(1, 5000), rng.randrange(3))
        v = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 24)))
        items.append((k, ht, v))
    if with_dups and items:
        # exact (key, dht) duplicates across batches: latest value wins
        k, ht, _ = items[rng.randrange(len(items))]
        items.append((k, ht, b"winner"))
    return items


def _fill_both(rng, n=400):
    py, nat = MemTable(), NativeMemTable()
    for _ in range(4):
        batch = _rand_items(rng, n // 4, key_space=64)
        py.add_batch(batch)
        nat.add_batch(batch)
    one = _rand_items(rng, 1, key_space=64, with_dups=False)[0]
    py.add(*one)
    nat.add(*one)
    return py, nat


def test_iteration_matches_python():
    rng = random.Random(11)
    py, nat = _fill_both(rng)
    assert list(nat.iter_from(b"")) == list(py.iter_from(b""))
    assert nat.n_entries == py.n_entries
    # mid-stream seek
    keys = [k for k, _ in py.iter_from(b"")]
    seek = keys[len(keys) // 2]
    assert list(nat.iter_from(seek)) == list(py.iter_from(seek))


def test_point_get_matches_python():
    rng = random.Random(12)
    py, nat = _fill_both(rng)
    for i in range(64):
        prefix = b"Skey%06d\x00\x00!" % i
        seek = make_internal_key(prefix, _dht(10**9))
        assert nat.point_get(seek, prefix) == py.point_get(seek, prefix)


def test_to_packed_matches_python():
    rng = random.Random(13)
    py, nat = _fill_both(rng)
    pk, pko, pht, pwid, pv, pvo = py.to_packed()
    nk, nko, nht, nwid, nv, nvo = nat.to_packed()
    assert pk == nk and pv == nv
    np.testing.assert_array_equal(pko, nko)
    np.testing.assert_array_equal(pvo, nvo)
    np.testing.assert_array_equal(pht, nht)
    np.testing.assert_array_equal(pwid, nwid)


def test_to_slab_matches_python():
    from yugabyte_tpu.docdb.value import Value
    rng = random.Random(14)
    py, nat = MemTable(), NativeMemTable()
    for i in range(200):
        k = b"Skey%06d\x00\x00!" % rng.randrange(50)
        ht = _dht(rng.randrange(1, 3000), rng.randrange(2))
        v = Value(primitive=rng.randrange(1000)).encode() \
            if rng.random() < 0.8 else Value.tombstone().encode()
        py.add(k, ht, v)
        nat.add(k, ht, v)
    a, b = py.to_slab(), nat.to_slab()
    assert a.n == b.n
    for i in range(a.n):
        assert a.key_bytes(i) == b.key_bytes(i)
        assert a.doc_ht(i) == b.doc_ht(i)
    np.testing.assert_array_equal(a.flags, b.flags)


def test_add_columns_equals_add_batch():
    rng = random.Random(15)
    items = _rand_items(rng, 300, key_space=40)
    a, b = NativeMemTable(), NativeMemTable()
    a.add_batch(items)
    b.add_columns([k for k, _d, _v in items],
                  np.array([d.ht.value for _k, d, _v in items],
                           dtype=np.uint64),
                  np.array([d.write_id for _k, d, _v in items],
                           dtype=np.uint32),
                  [v for _k, _d, v in items])
    assert list(a.iter_from(b"")) == list(b.iter_from(b""))


def test_iteration_survives_concurrent_add():
    rng = random.Random(16)
    nat = NativeMemTable()
    nat.add_batch(_rand_items(rng, 100, key_space=50, with_dups=False))
    it = nat.iter_from(b"")
    first = [next(it) for _ in range(10)]
    nat.add_batch(_rand_items(rng, 100, key_space=50, with_dups=False))
    rest = list(it)
    got = [k for k, _ in first + rest]
    assert got == sorted(set(got)), "iterator tore under concurrent add"
