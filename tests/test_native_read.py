"""Differential tests: native read engine vs the Python oracle paths.

The native engine (native/read_engine.cc) must reproduce byte-for-byte the
Python implementations it replaces (ref parity targets:
src/yb/rocksdb/table/block_based_table_reader.cc:1144-1286 seek + bloom,
table/merger.cc:51 MergingIterator, docdb/doc_rowwise_iterator.cc RESOLVE).
Every test builds the same DB and compares the two paths directly.
"""

import os
import random

import pytest

from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime
from yugabyte_tpu.docdb.doc_key import DocKey, SubDocKey
from yugabyte_tpu.docdb.doc_rowwise_iterator import DocRowwiseIterator
from yugabyte_tpu.docdb.value import Value
from yugabyte_tpu.storage import native_read
from yugabyte_tpu.storage.db import DB, DBOptions
from yugabyte_tpu.utils import flags


pytestmark = pytest.mark.requires_native("read_engine")


def _rand_value(rng) -> Value:
    r = rng.random()
    if r < 0.1:
        return Value.tombstone()
    if r < 0.15:
        return Value(is_object=True)
    if r < 0.3:
        return Value(primitive=rng.randrange(10**6),
                     ttl_ms=rng.choice([1, 10_000, 10**9]))
    return Value(primitive="v" * rng.randrange(1, 40))


def _build_db(tmp_path, seed=7, n_docs=120, n_batches=5) -> DB:
    """Multi-SST + live-memtable DB with versions, tombstones, TTLs,
    deep subdocuments, and bare-DocKey markers."""
    rng = random.Random(seed)
    db = DB(os.path.join(str(tmp_path), f"db{seed}"),
            DBOptions(device="native", auto_compact=False))
    t = 1000
    for batch in range(n_batches):
        items = []
        for _ in range(200):
            doc = rng.randrange(n_docs)
            dk = DocKey(range_components=(f"doc{doc:04d}",))
            kind = rng.random()
            if kind < 0.15:
                key = dk.encode()  # bare DocKey: init marker / row tombstone
                val = Value(is_object=True) if rng.random() < 0.6 \
                    else Value.tombstone()
            elif kind < 0.25:
                # deep subdocument path
                key = SubDocKey(dk, (("col", rng.randrange(4)),
                                     f"elem{rng.randrange(3)}")).encode(
                    include_ht=False)
                val = _rand_value(rng)
            else:
                key = SubDocKey(dk, (("col", rng.randrange(6)),)).encode(
                    include_ht=False)
                val = _rand_value(rng)
            t += rng.randrange(1, 3)
            items.append((key, DocHybridTime(HybridTime.from_micros(t),
                                             rng.randrange(3)),
                          val.encode()))
        db.write_batch(items, op_id=(1, batch + 1))
        if batch < n_batches - 1:
            db.flush()  # last batch stays in the memtable (overlay path)
    return db


def _python_iter(db, seek=b""):
    flags.set_flag("read_native", False)
    try:
        return list(db.iter_from(seek))
    finally:
        flags.set_flag("read_native", True)


class TestIterFromEquivalence:
    def test_full_stream_matches_python_merge(self, tmp_path):
        db = _build_db(tmp_path)
        native = list(db.iter_from(b""))
        oracle = _python_iter(db)
        assert native == oracle
        assert len(native) == 1000
        db.close()

    def test_seek_with_ht_suffix(self, tmp_path):
        db = _build_db(tmp_path, seed=8)
        oracle = _python_iter(db)
        # seek to every 97th oracle position, with its full internal key
        for i in range(0, len(oracle), 97):
            seek = oracle[i][0]
            assert list(db.iter_from(seek)) == oracle[i:], f"seek at {i}"
        db.close()

    def test_seek_prefix_only(self, tmp_path):
        db = _build_db(tmp_path, seed=9)
        oracle = _python_iter(db)
        dk = DocKey(range_components=("doc0050",)).encode()
        expect = [kv for kv in oracle if kv[0] >= dk]
        assert list(db.iter_from(dk)) == expect
        db.close()


class TestPointGetEquivalence:
    def test_random_gets_match_python(self, tmp_path):
        db = _build_db(tmp_path, seed=10)
        rng = random.Random(1)
        keys = []
        for doc in range(0, 120, 3):
            dk = DocKey(range_components=(f"doc{doc:04d}",))
            keys.append(dk.encode())
            for c in range(6):
                keys.append(SubDocKey(dk, (("col", c),)).encode(
                    include_ht=False))
        for key in keys:
            for read_ht in (None, HybridTime.from_micros(1500),
                            HybridTime.from_micros(
                                1000 + rng.randrange(2000))):
                got = db.get(key, read_ht)
                flags.set_flag("read_native", False)
                want = db.get(key, read_ht)
                flags.set_flag("read_native", True)
                assert got == want, (key, read_ht)
        db.close()

    def test_missing_keys(self, tmp_path):
        db = _build_db(tmp_path, seed=11)
        for doc in range(500, 540):
            key = DocKey(range_components=(f"doc{doc:04d}",)).encode()
            assert db.get(key) is None
        db.close()


class TestVisibleScanEquivalence:
    @pytest.mark.parametrize("read_us", [1100, 1700, 10**7])
    def test_visible_matches_resolve_visible(self, tmp_path, read_us):
        db = _build_db(tmp_path, seed=12)
        read_ht = HybridTime.from_micros(read_us)
        scan = db.scan_native(visible=True, read_ht_value=read_ht.value)
        assert scan is not None
        native = [(k, v, ht) for k, v, ht, _w, _f, _d in scan.entries()]
        flags.set_flag("read_native", False)
        try:
            from yugabyte_tpu.common.schema import Schema
            it = DocRowwiseIterator.__new__(DocRowwiseIterator)
            it._db = db
            it._read_ht = read_ht
            it._lower = b""
            it._upper = None
            it._entry_stream = None
            oracle = list(it._resolve_visible())
        finally:
            flags.set_flag("read_native", True)
        assert native == oracle
        db.close()

    def test_bounded_visible_scan(self, tmp_path):
        db = _build_db(tmp_path, seed=13)
        lower = DocKey(range_components=("doc0020",)).encode()
        upper = DocKey(range_components=("doc0060",)).encode()
        read_ht = HybridTime.from_micros(10**7)
        scan = db.scan_native(lower=lower, upper=upper, visible=True,
                              read_ht_value=read_ht.value)
        native = [(k, v, ht) for k, v, ht, _w, _f, _d in scan.entries()]
        flags.set_flag("read_native", False)
        try:
            it = DocRowwiseIterator.__new__(DocRowwiseIterator)
            it._db = db
            it._read_ht = read_ht
            it._lower = lower
            it._upper = upper
            it._entry_stream = None
            oracle = list(it._resolve_visible())
        finally:
            flags.set_flag("read_native", True)
        assert native == oracle
        db.close()


class TestCompressedBlocks:
    def test_zlib_blocks_served_natively(self, tmp_path):
        flags.set_flag("sst_compression", "zlib")
        try:
            db = _build_db(tmp_path, seed=14)
        finally:
            flags.set_flag("sst_compression", "none")
        native = list(db.iter_from(b""))
        oracle = _python_iter(db)
        assert native == oracle
        db.close()


class TestNativeFlushEquivalence:
    def test_native_flush_readback_matches_python_writer(self, tmp_path):
        # same content flushed through the native packed encoder and the
        # Python SSTWriter must produce identical merged streams
        dbs = []
        for sub, native_flush in (("n", True), ("p", False)):
            db = DB(os.path.join(str(tmp_path), sub),
                    DBOptions(device="native", auto_compact=False))
            rng = random.Random(21)
            items = []
            for i in range(500):
                dk = DocKey(range_components=(f"k{rng.randrange(100):03d}",))
                key = SubDocKey(dk, (("col", rng.randrange(4)),)).encode(
                    include_ht=False)
                items.append((key,
                              DocHybridTime(
                                  HybridTime.from_micros(5000 + i), 0),
                              _rand_value(rng).encode()))
            db.write_batch(items, op_id=(1, 1))
            if not native_flush:
                # force the slab/SSTWriter path by routing through a fake
                # device cache sentinel? simpler: call the python writer
                # via the public knob — temporarily mark engine unavailable
                from yugabyte_tpu.storage import native_engine
                with pytest.MonkeyPatch.context() as mp:
                    mp.setattr(native_engine, "available", lambda: False)
                    db.flush()
            else:
                db.flush()
            dbs.append(db)
        a = _python_iter(dbs[0])
        b = _python_iter(dbs[1])
        assert a == b
        # and the props agree on the doc-aware bits
        fa = dbs[0].versions.live_files()[0]
        fb = dbs[1].versions.live_files()[0]
        assert fa.props.n_entries == fb.props.n_entries
        assert fa.props.first_key == fb.props.first_key
        assert fa.props.last_key == fb.props.last_key
        assert fa.props.has_deep == fb.props.has_deep
        assert fa.props.max_expire_us == fb.props.max_expire_us
        for db in dbs:
            db.close()


class TestIngestPacked:
    def test_unsorted_ingest_readback(self, tmp_path):
        import numpy as np
        db = DB(os.path.join(str(tmp_path), "ing"),
                DBOptions(device="native", auto_compact=False))
        rng = random.Random(31)
        rows = []
        for i in range(2000):
            dk = DocKey(range_components=(f"u{rng.randrange(1000):04d}",))
            key = SubDocKey(dk, (("col", 1),)).encode(include_ht=False)
            rows.append((key, 7000 + i, Value(primitive=i).encode()))
        rng.shuffle(rows)  # ingest handles unsorted runs
        keys_blob = b"".join(r[0] for r in rows)
        koffs = np.zeros(len(rows) + 1, dtype=np.int64)
        np.cumsum([len(r[0]) for r in rows], out=koffs[1:])
        ht = np.array([HybridTime.from_micros(r[1]).value for r in rows],
                      dtype=np.uint64)
        wid = np.zeros(len(rows), dtype=np.uint32)
        vals_blob = b"".join(r[2] for r in rows)
        voffs = np.zeros(len(rows) + 1, dtype=np.int64)
        np.cumsum([len(r[2]) for r in rows], out=voffs[1:])
        fid = db.ingest_packed(keys_blob, koffs, ht, wid, vals_blob, voffs,
                               op_id=(1, 1))
        assert fid is not None
        stream = list(db.iter_from(b""))
        assert len(stream) == 2000
        assert stream == sorted(stream), "ingest must order unsorted input"
        # point-get the newest version of one doc
        probe = rows[0][0]
        got = db.get(probe)
        assert got is not None
        db.close()


class TestConcurrentChurn:
    def test_reads_stable_under_flush_and_compaction(self, tmp_path):
        """Writers + point readers + scanners race flushes and compactions:
        the native reader-set snapshots must never serve a torn view, hide
        a committed row, or crash on a freed handle (the refcount design
        replaces the reference's Version pinning, ref db/version_set.cc)."""
        import threading

        from yugabyte_tpu.docdb.value import Value

        db = DB(os.path.join(str(tmp_path), "churn"),
                DBOptions(device="native", auto_compact=True))
        n_keys = 400
        stop = threading.Event()
        errors = []
        write_floor = [0]  # generation fully written (all keys)

        def writer():
            gen = 0
            t = 10_000
            try:
                while not stop.is_set():
                    gen += 1
                    items = []
                    for i in range(n_keys):
                        dk = DocKey(range_components=(f"w{i:04d}",))
                        key = SubDocKey(dk, (("col", 0),)).encode(
                            include_ht=False)
                        t += 1
                        items.append((key, DocHybridTime(
                            HybridTime.from_micros(t), 0),
                            Value(primitive=gen).encode()))
                    db.write_batch(items, op_id=(1, gen))
                    write_floor[0] = gen
                    if gen % 3 == 0:
                        db.flush()
            except Exception as e:  # noqa: BLE001
                errors.append(("writer", repr(e)))

        def reader():
            import random
            rng = random.Random(5)
            try:
                while not stop.is_set():
                    floor = write_floor[0]
                    if floor == 0:
                        continue
                    i = rng.randrange(n_keys)
                    dk = DocKey(range_components=(f"w{i:04d}",))
                    key = SubDocKey(dk, (("col", 0),)).encode(
                        include_ht=False)
                    got = db.get(key)
                    assert got is not None, f"key w{i:04d} vanished"
                    v = Value.decode(got[1]).primitive
                    assert v >= floor, (
                        f"stale read: saw gen {v}, floor was {floor}")
            except Exception as e:  # noqa: BLE001
                errors.append(("reader", repr(e)))

        def scanner():
            try:
                while not stop.is_set():
                    floor = write_floor[0]
                    if floor == 0:
                        continue
                    seen = 0
                    for _ikey, _v in db.iter_from(b""):
                        seen += 1
                    assert seen >= n_keys, (
                        f"scan saw {seen} < {n_keys} entries")
            except Exception as e:  # noqa: BLE001
                errors.append(("scanner", repr(e)))

        threads = [threading.Thread(target=f, daemon=True)
                   for f in (writer, reader, reader, scanner)]
        for t in threads:
            t.start()
        import time as _time
        _time.sleep(8)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        db.close()
        assert not errors, errors
