"""Device-resident write-through: compaction outputs staged from HBM.

run_compaction_job_device_native's write-through must stage the output
files by gathering the surviving columns ON DEVICE (ops/run_merge.py
_gather_staged_output) — the staged entries must be indistinguishable from
host restaging (stage_slab over SSTReader.read_all()) for everything a
later merge reads, and a chained second compaction consuming the cache
entries must keep exactly what a from-disk compaction keeps.
"""

import os

import numpy as np
import pytest

from yugabyte_tpu.ops.merge_gc import _ROW_WORDS, stage_slab
from yugabyte_tpu.ops.slabs import ValueArray
from yugabyte_tpu.storage import compaction as compaction_mod
from yugabyte_tpu.storage.device_cache import DeviceSlabCache
from yugabyte_tpu.storage.sst import Frontier, SSTReader, SSTWriter
from yugabyte_tpu.utils import flags

pytestmark = pytest.mark.requires_native("compaction_engine")


def _mk_run(rng, n, key_space, value_bytes=16, ttl_frac=0.0):
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from test_run_merge import _make_run
    slab = _make_run(rng, n, key_space, ttl_frac=ttl_frac)
    data = rng.integers(0, 256, size=n * value_bytes, dtype=np.uint8)
    offs = np.arange(n + 1, dtype=np.int64) * value_bytes
    slab.values = ValueArray(data, offs)
    return slab


def _write_runs(workdir, runs):
    readers = []
    for i, slab in enumerate(runs):
        p = os.path.join(workdir, f"in{i:03d}.sst")
        SSTWriter(p).write(slab, Frontier())
        readers.append(SSTReader(p))
    return readers


def _device():
    import jax
    return jax.devices()[0]


def _run_device_native(readers, out_dir, cutoff, cache, input_ids,
                       first_id=100):
    os.makedirs(out_dir, exist_ok=True)
    ids = iter(range(first_id, first_id + 500))
    return compaction_mod.run_compaction_job_device_native(
        readers, out_dir, lambda: next(ids), cutoff, True,
        device=_device(), device_cache=cache, input_ids=input_ids)


CUTOFF = (10_000_000 << 12)


def test_staged_output_matches_host_restage(tmp_path):
    rng = np.random.default_rng(11)
    runs = [_mk_run(rng, 800, 500) for _ in range(3)]
    readers = _write_runs(str(tmp_path), runs)
    cache = DeviceSlabCache(device=_device())
    ids = list(range(len(readers)))
    for fid, r in zip(ids, readers):
        cache.stage(fid, r.read_all())
    res = _run_device_native(readers, str(tmp_path / "out"), CUTOFF,
                            cache, ids)
    assert res.outputs, "compaction produced no outputs"
    for fid, base_path, _props in res.outputs:
        dev_staged = cache.get(fid)
        assert dev_staged is not None, "write-through missed the cache"
        rdr = SSTReader(base_path)
        host_staged = stage_slab(rdr.read_all())
        rdr.close()
        assert dev_staged.n == host_staged.n
        dev_cols = np.asarray(dev_staged.cols_dev)
        host_cols = np.asarray(host_staged.cols_dev)
        n = host_staged.n
        r_common = min(dev_cols.shape[0], host_cols.shape[0])
        np.testing.assert_array_equal(
            dev_cols[:r_common, :n], host_cols[:r_common, :n],
            err_msg="device-staged columns differ from host restage")
        # any extra device rows are key-word padding and must be zero
        if dev_cols.shape[0] > r_common:
            assert (dev_cols[r_common:, :n] == 0).all()
        # padding columns must carry the pad template (sort to tail)
        from yugabyte_tpu.ops.merge_gc import pad_template
        if dev_staged.n_pad > n:
            pt = pad_template(dev_cols.shape[0])
            np.testing.assert_array_equal(
                dev_cols[:, n:], np.tile(pt[:, None], (1, dev_staged.n_pad - n)))


def test_ttl_rewrite_flag_mirrored(tmp_path):
    """TTL-expired survivors written as tombstones must carry the
    tombstone flag in the device-staged entry too (non-major keeps them)."""
    rng = np.random.default_rng(12)
    runs = [_mk_run(rng, 600, 400, ttl_frac=0.5) for _ in range(2)]
    readers = _write_runs(str(tmp_path), runs)
    cache = DeviceSlabCache(device=_device())
    ids = list(range(len(readers)))
    for fid, r in zip(ids, readers):
        cache.stage(fid, r.read_all())
    os.makedirs(str(tmp_path / "out"), exist_ok=True)
    idgen = iter(range(10, 500))
    res = compaction_mod.run_compaction_job_device_native(
        readers, str(tmp_path / "out"), lambda: next(idgen), CUTOFF,
        False,  # non-major: TTL expiry rewrites values as tombstones
        device=_device(), device_cache=cache, input_ids=ids)
    for fid, base_path, _props in res.outputs:
        dev_cols = np.asarray(cache.get(fid).cols_dev)
        rdr = SSTReader(base_path)
        host_staged = stage_slab(rdr.read_all())
        rdr.close()
        host_cols = np.asarray(host_staged.cols_dev)
        r_common = min(dev_cols.shape[0], host_cols.shape[0])
        np.testing.assert_array_equal(dev_cols[:r_common, :host_staged.n],
                                      host_cols[:r_common, :host_staged.n])


def test_chained_compaction_from_cache(tmp_path):
    """Second compaction consuming device-staged outputs == from-disk."""
    rng = np.random.default_rng(13)
    runs_a = [_mk_run(rng, 700, 450) for _ in range(2)]
    runs_b = [_mk_run(rng, 700, 450) for _ in range(2)]
    cache = DeviceSlabCache(device=_device())

    os.makedirs(str(tmp_path / "a"))
    os.makedirs(str(tmp_path / "b"))
    readers_a = _write_runs(str(tmp_path / "a"), runs_a)
    readers_b = _write_runs(str(tmp_path / "b"), runs_b)
    for fid, r in zip((0, 1), readers_a):
        cache.stage(fid, r.read_all())
    for fid, r in zip((2, 3), readers_b):
        cache.stage(fid, r.read_all())

    res_a = _run_device_native(readers_a, str(tmp_path / "oa"), CUTOFF,
                               cache, [0, 1], first_id=100)
    res_b = _run_device_native(readers_b, str(tmp_path / "ob"), CUTOFF,
                               cache, [2, 3], first_id=200)

    # L1: compact the two outputs together, inputs from the cache
    l1_readers = [SSTReader(p) for _, p, _ in res_a.outputs + res_b.outputs]
    l1_ids = [fid for fid, _, _ in res_a.outputs + res_b.outputs]
    res_l1 = _run_device_native(l1_readers, str(tmp_path / "l1"), CUTOFF,
                                cache, l1_ids, first_id=300)

    # reference: same L1 compaction fully from disk, no cache
    os.makedirs(str(tmp_path / "l1ref"))
    ids = iter(range(400, 500))
    ref = compaction_mod.run_compaction_job(
        l1_readers, str(tmp_path / "l1ref"), lambda: next(ids), CUTOFF,
        True, device="native")
    assert res_l1.rows_out == ref.rows_out
    # outputs must be byte-identical
    for (_, b1, _), (_, b2, _) in zip(res_l1.outputs, ref.outputs):
        with open(b1 + ".sblock.0", "rb") as f1, \
                open(b2 + ".sblock.0", "rb") as f2:
            assert f1.read() == f2.read()
    for r in l1_readers + readers_a + readers_b:
        r.close()


def test_multi_file_split_ranges(tmp_path):
    """File splits: each cache entry covers exactly its file's rows."""
    rng = np.random.default_rng(14)
    runs = [_mk_run(rng, 900, 4000) for _ in range(2)]  # few dups: big out
    readers = _write_runs(str(tmp_path), runs)
    cache = DeviceSlabCache(device=_device())
    ids = [0, 1]
    for fid, r in zip(ids, readers):
        cache.stage(fid, r.read_all())
    old = flags.get_flag("compaction_max_output_entries_per_sst")
    flags.set_flag("compaction_max_output_entries_per_sst", 500)
    try:
        res = _run_device_native(readers, str(tmp_path / "out"), CUTOFF,
                                 cache, ids)
        assert len(res.outputs) >= 2, "expected a multi-file split"
        for fid, base_path, props in res.outputs:
            dev_staged = cache.get(fid)
            rdr = SSTReader(base_path)
            host_staged = stage_slab(rdr.read_all())
            rdr.close()
            assert dev_staged.n == host_staged.n == props.n_entries
            dev_cols = np.asarray(dev_staged.cols_dev)
            host_cols = np.asarray(host_staged.cols_dev)
            r_common = min(dev_cols.shape[0], host_cols.shape[0])
            np.testing.assert_array_equal(
                dev_cols[:r_common, :host_staged.n],
                host_cols[:r_common, :host_staged.n])
    finally:
        flags.set_flag("compaction_max_output_entries_per_sst", old)


def test_production_db_routes_to_combined_path(tmp_path, monkeypatch):
    """DB background compaction on a JAX device takes the flagship
    device-decisions + native-shell path (the configuration the bench
    measures), and deep-document inputs do NOT."""
    import jax
    from yugabyte_tpu.storage import compaction as comp
    from yugabyte_tpu.storage.db import DB, DBOptions
    from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime
    from yugabyte_tpu.docdb.value import Value

    calls = []
    orig = comp.run_compaction_job_device_native

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)
    monkeypatch.setattr(comp, "run_compaction_job_device_native", spy)

    from yugabyte_tpu.docdb.doc_key import DocKey, SubDocKey
    db = DB(str(tmp_path / "db"),
            DBOptions(auto_compact=False, device=jax.devices()[0]))
    for batch in range(4):
        kvs = []
        for i in range(50):
            dk = DocKey(range_components=(f"r{i:04d}",))
            key = SubDocKey(dk, (("col", 0),)).encode(include_ht=False)
            kvs.append((key, DocHybridTime(
                HybridTime.from_micros(1000 + batch * 100 + i), 0),
                Value(primitive=batch).encode()))
        db.write_batch(kvs)
        db.flush()
    assert db.n_live_files == 4
    db.compact_all()
    assert calls, "combined device+native path was not taken"
    assert db.n_live_files == 1
    db.close()

    # deep inputs: props.has_deep gates the combined path off
    calls.clear()
    from yugabyte_tpu.docdb.subdocument import subdocument_writes
    db2 = DB(str(tmp_path / "db2"),
             DBOptions(auto_compact=False, device=jax.devices()[0]))
    for batch in range(4):
        kvs = [(k, DocHybridTime(HybridTime.from_micros(1000 + batch), i), v)
               for i, (k, v) in enumerate(subdocument_writes(
                   DocKey(range_components=(f"d{batch}",)), (),
                   {"a": {"b": {"c": batch}}}))]
        db2.write_batch(kvs)
        db2.flush()
    db2.compact_all()
    assert not calls, "deep inputs must not take the depth-2 device path"
    db2.close()


def test_chunked_write_through_matches_host(tmp_path, monkeypatch):
    """Chunked subcompactions must still stage outputs into the HBM cache
    (to_parent_products rebuilds the parent-domain arrays): entries match
    a host restage of the written files byte-for-byte."""
    from yugabyte_tpu.ops import run_merge

    rng = np.random.default_rng(15)
    runs = [_mk_run(rng, 2000, 8000) for _ in range(2)]
    readers = _write_runs(str(tmp_path), runs)
    cache = DeviceSlabCache(device=_device())
    ids = [0, 1]
    for fid, r in zip(ids, readers):
        cache.stage(fid, r.read_all())

    chunked_calls = {"n": 0}
    real = run_merge._launch_chunked

    def spy(*a, **k):
        h = real(*a, **k)
        if h is not None:
            chunked_calls["n"] += 1
        return h

    monkeypatch.setattr(run_merge, "_launch_chunked", spy)
    monkeypatch.setenv("YBTPU_MERGE_CHUNK_ROWS", "2048")
    res = _run_device_native(readers, str(tmp_path / "out"), CUTOFF,
                             cache, ids)
    assert chunked_calls["n"] == 1, "chunked path did not engage"
    assert res.outputs, "no outputs written"
    for fid, base_path, props in res.outputs:
        dev_staged = cache.get(fid)
        assert dev_staged is not None, "write-through skipped"
        rdr = SSTReader(base_path)
        host_staged = stage_slab(rdr.read_all())
        rdr.close()
        assert dev_staged.n == host_staged.n == props.n_entries
        dev_cols = np.asarray(dev_staged.cols_dev)
        host_cols = np.asarray(host_staged.cols_dev)
        r_common = min(dev_cols.shape[0], host_cols.shape[0])
        np.testing.assert_array_equal(
            dev_cols[:r_common, :host_staged.n],
            host_cols[:r_common, :host_staged.n])
    for r in readers:
        r.close()


# ---------------------------------------------------------------------------
# DB.flush's write-through: the slab comes from the job that wrote the file
# ---------------------------------------------------------------------------

class _RecordingCache(DeviceSlabCache):
    """Keeps the host slab each stage() was handed."""

    def __init__(self, device=None):
        super().__init__(device=device)
        self.slabs = {}

    def stage(self, key, slab, **kw):
        self.slabs[key] = slab
        return super().stage(key, slab, **kw)


def _flush_items(n=1500, key_space=400):
    """Column writes with several versions a key, row tombstones, TTLs,
    an object marker and a deep document."""
    from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime
    from yugabyte_tpu.docdb.doc_key import DocKey, SubDocKey
    from yugabyte_tpu.docdb.value import Value
    rng = np.random.default_rng(21)
    items = []
    for i in range(n):
        dk = DocKey(range_components=("user%08d" % rng.integers(key_space),))
        kind = i % 7
        if kind == 0:
            key, val = dk.encode(), Value.tombstone()
        elif kind == 1:
            key = SubDocKey(dk, (("col", 1), "m", i % 5)).encode(
                include_ht=False)
            val = Value(primitive=i)
        elif kind == 2:
            key = SubDocKey(dk, (("col", 2),)).encode(include_ht=False)
            val = Value(primitive="t" * 20, ttl_ms=1000 + i)
        elif kind == 3:
            key = SubDocKey(dk, (("col", 1),)).encode(include_ht=False)
            val = Value(is_object=True)
        else:
            key = SubDocKey(dk, (("col", 3),)).encode(include_ht=False)
            val = Value(primitive="v%037d" % i)
        items.append((key, DocHybridTime(HybridTime((1000 + i) << 12), i % 2),
                      val.encode()))
    return items


def _flushed_db(path, items, **opts):
    from yugabyte_tpu.storage.db import DB, DBOptions
    db = DB(path, DBOptions(auto_compact=False, **opts))
    db.write_batch(items)
    fid = db.flush()
    assert fid is not None and db.background_error is None
    return db, fid


def test_flush_stages_the_slab_of_the_job_that_wrote_the_file(tmp_path):
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from test_flush_slab import assert_slabs_equal
    from yugabyte_tpu.storage.db import flush_slab_metrics
    from yugabyte_tpu.storage.memtable import MemTable
    from yugabyte_tpu.storage.sst import data_file_name
    items = _flush_items()
    meters = flush_slab_metrics()
    before = {k: c.value() for k, c in meters.items()}
    cache = _RecordingCache(device=_device())
    db, fid = _flushed_db(str(tmp_path / "cached"), items,
                          device=_device(), device_cache=cache)
    moved = {k: c.value() - before[k] for k, c in meters.items()}
    assert moved == {"native": 1, "python": 0}
    assert db._device_cache.contains(fid)
    (staged_slab,) = cache.slabs.values()
    # the Python memtable's slab over the same writes: pack_kvs entry by
    # entry, the oracle
    oracle = MemTable()
    oracle.add_batch(items)
    assert_slabs_equal(staged_slab, oracle.to_slab())
    # and what a later merge reads of the staged entry is a restage of
    # the file
    rdr = SSTReader(db.versions.files[fid].path)
    host = stage_slab(rdr.read_all())
    rdr.close()
    dev = db._device_cache.get(fid)
    assert dev.n == host.n == len(items)
    np.testing.assert_array_equal(np.asarray(dev.cols_dev),
                                  np.asarray(host.cols_dev))
    # the file is what a flush without the slab writes
    # (write_sst_from_packed alone: the parent's path), byte for byte
    plain, plain_fid = _flushed_db(str(tmp_path / "plain"), items)
    assert plain_fid == fid
    for name in (lambda p: p, data_file_name):
        with open(name(db.versions.files[fid].path), "rb") as a, \
                open(name(plain.versions.files[fid].path), "rb") as b:
            assert a.read() == b.read()
    assert {k: c.value() - before[k] for k, c in meters.items()} == \
        {"native": 1, "python": 0}, "a flush with no device cache counted"
    db.close()
    plain.close()


def test_flush_of_a_python_memtable_still_takes_the_job_s_columns(tmp_path):
    """memtable_native off: to_packed() is the Python memtable's, the slab
    still the job's; without the compaction engine the flush packs entry
    by entry and says so."""
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from test_flush_slab import assert_slabs_equal
    from yugabyte_tpu.storage import native_engine
    from yugabyte_tpu.storage.db import flush_slab_metrics
    from yugabyte_tpu.storage.memtable import MemTable
    items = _flush_items(300, 80)
    oracle = MemTable()
    oracle.add_batch(items)
    meters = flush_slab_metrics()
    old = flags.get_flag("memtable_native")
    flags.set_flag("memtable_native", False)
    try:
        before = {k: c.value() for k, c in meters.items()}
        cache = _RecordingCache(device=_device())
        db, fid = _flushed_db(str(tmp_path / "a"), items,
                              device=_device(), device_cache=cache)
        assert_slabs_equal(*cache.slabs.values(), oracle.to_slab())
        assert {k: c.value() - before[k] for k, c in meters.items()} == \
            {"native": 1, "python": 0}
        db.close()
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(native_engine, "available", lambda: False)
            cache = _RecordingCache(device=_device())
            db, fid = _flushed_db(str(tmp_path / "b"), items,
                                  device=_device(), device_cache=cache)
            assert_slabs_equal(*cache.slabs.values(), oracle.to_slab())
            db.close()
        assert {k: c.value() - before[k] for k, c in meters.items()} == \
            {"native": 1, "python": 1}
    finally:
        flags.set_flag("memtable_native", old)
