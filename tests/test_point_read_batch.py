"""Batched device point-read path (ROADMAP item 4, PR perf_opt).

DB.multi_get must be BYTE-IDENTICAL to N sequential DB.get calls — with
the SST layer resolved through the vectorized bloom/locate/gather kernels
(ops/point_read.py) over HBM-resident slab matrices, memtable probes
host-side, and every degradation path (no device, quarantined bucket,
mid-batch device fault, learned-index misprediction) falling back exactly:

  - hit + miss mixes, MVCC read_ht snapshots, tombstones, memtable
    overlay, multi-version keys;
  - bloom probe bit-identical to storage/bloom.py, false positives
    resolved by the exact locate;
  - the learned per-SST index is ADVISORY: forced mispredictions are
    detected by the search-invariant check and re-resolve exactly; a
    model-bearing SST stays readable by the pre-model reader path;
  - device-fault injection at dispatch/result falls back byte-identically
    with zero leaked pins and a quarantined shape bucket;
  - read-path Corruption containment preserved (retryable
    ServiceUnavailable, never a raw Corruption).

The tablet layer rides it: Tablet/TabletPeer/TabletService.multi_read and
client.multi_read return rows identical to per-key read_row.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime  # noqa: E402
from yugabyte_tpu.ops import device_faults  # noqa: E402
from yugabyte_tpu.storage import learned_index  # noqa: E402
from yugabyte_tpu.storage import offload_policy  # noqa: E402
from yugabyte_tpu.storage.db import DB, DBOptions  # noqa: E402
from yugabyte_tpu.storage.device_cache import DeviceSlabCache  # noqa: E402
from yugabyte_tpu.storage.sst import SSTReader  # noqa: E402
from yugabyte_tpu.utils import flags  # noqa: E402
from yugabyte_tpu.utils.env import corrupt_file_range  # noqa: E402
from yugabyte_tpu.utils.status import Code, StatusError  # noqa: E402


def _device():
    import jax
    return jax.devices()[0]


@pytest.fixture(autouse=True)
def _clean_state():
    device_faults.disarm_all()
    offload_policy.bucket_quarantine().clear()
    yield
    device_faults.disarm_all()
    offload_policy.bucket_quarantine().clear()


def _key(i: int) -> bytes:
    return b"Suser%08d\x00\x00!" % i


def _tomb() -> bytes:
    from yugabyte_tpu.docdb.value import Value
    return Value.tombstone().encode()


def _fill_db(tmp_path, n_keys=1200, n_ssts=3, device=True,
             mem_overlay=True):
    """Keys across n_ssts SSTs with 1-2 versions, some tombstones, and a
    memtable overlay — the shapes a serving tablet's regular DB holds."""
    opts = DBOptions(auto_compact=False)
    if device:
        dev = _device()
        opts = DBOptions(device=dev,
                         device_cache=DeviceSlabCache(device=dev),
                         auto_compact=False)
    db = DB(str(tmp_path / "db"), opts)
    val = b"value-" + b"x" * 26
    for f in range(n_ssts):
        items = []
        for i in range(f, n_keys, n_ssts):
            v = _tomb() if i % 17 == 0 and f == 1 else val + b"%d" % f
            items.append((_key(i),
                          DocHybridTime(
                              HybridTime.from_micros(1000 + i + 7 * f),
                              f), v))
        db.write_batch(items, op_id=(1, f + 1))
        db.flush()
    if mem_overlay:
        items = [(_key(i), DocHybridTime(HybridTime.from_micros(99_999),
                                         1), b"memval%d" % i)
                 for i in range(0, 120, 7)]
        db.write_batch(items, op_id=(1, n_ssts + 1))
    return db


def _poison_models(db):
    """Every learned index reversed with a bound of 0, and the readers
    reloaded so the poisoned models serve: the locate flags its
    mispredictions and those keys take the exact path."""
    for fid, r in list(db._readers.items()):
        m = r.props.lindex
        if m is None:
            continue
        bad = dict(m, a_hi=list(reversed(m["a_hi"])),
                   a_lo=list(reversed(m["a_lo"])), max_err=0)
        learned_index.attach_learned_index(r.base_path, bad)
        db._readers[fid] = SSTReader(r.base_path, db.opts.block_cache)
        r.close()


def _query_keys(n_keys, rng, m=400):
    # hits, misses past the range, and misses interleaved in the range
    ids = list(rng.integers(0, n_keys + 200, size=m))
    return [_key(int(i)) for i in ids]


# ---------------------------------------------------------------- identity
class TestByteIdentity:
    def test_multi_get_equals_sequential_gets(self, tmp_path):
        db = _fill_db(tmp_path)
        rng = np.random.default_rng(7)
        keys = _query_keys(1200, rng)
        try:
            for read_ht in (None, HybridTime.from_micros(1400),
                            HybridTime.from_micros(50_000),
                            HybridTime.from_micros(100_000)):
                seq = [db.get(k, read_ht) for k in keys]
                assert db.multi_get(keys, read_ht) == seq, read_ht
            # the batched path actually ran (not a silent fallback)
            from yugabyte_tpu.ops.point_read import point_read_metrics
            assert point_read_metrics()["batches"].value() > 0
        finally:
            db.close()

    def test_multi_get_native_fallback_identical(self, tmp_path):
        db = _fill_db(tmp_path)
        rng = np.random.default_rng(8)
        keys = _query_keys(1200, rng)
        try:
            dev = db.multi_get(keys)
            flags.set_flag("point_read_batched", False)
            try:
                nat = db.multi_get(keys)
            finally:
                flags.set_flag("point_read_batched", True)
            assert dev == nat == [db.get(k) for k in keys]
        finally:
            db.close()

    def test_multi_get_no_device_db(self, tmp_path):
        """A deviceless DB serves multi_get through the native per-key
        path (storage/native_read.py) — identical results."""
        db = _fill_db(tmp_path, device=False)
        rng = np.random.default_rng(9)
        keys = _query_keys(1200, rng)
        try:
            assert db.multi_get(keys) == [db.get(k) for k in keys]
        finally:
            db.close()

    def test_multi_get_edge_shapes(self, tmp_path):
        db = _fill_db(tmp_path, n_keys=400, mem_overlay=False)
        try:
            assert db.multi_get([]) == []
            # a key longer than any SST's key stride can never match
            long_key = _key(1) + b"\x00" * 64
            assert db.multi_get([long_key]) == [None]
            # read point below every write: nothing visible
            early = HybridTime.from_micros(1)
            assert db.multi_get([_key(3)], early) == [db.get(_key(3),
                                                             early)]
            # duplicate keys in one batch
            keys = [_key(5), _key(5), _key(9999), _key(5)]
            assert db.multi_get(keys) == [db.get(k) for k in keys]
        finally:
            db.close()


# ------------------------------------------------------------------ bloom
class TestBloom:
    def test_bloom_rejected_misses(self, tmp_path):
        import jax.numpy as jnp
        from yugabyte_tpu.ops import point_read as pr
        from yugabyte_tpu.ops.slabs import _doc_key_len, _pad_keys_to_words
        db = _fill_db(tmp_path, mem_overlay=False)
        try:
            from yugabyte_tpu.ops.point_read import point_read_metrics
            skips0 = point_read_metrics()["bloom_skips"].value()
            miss = [_key(5000 + i) for i in range(128)]
            # expected dispatch skips: SSTs whose bloom rejects EVERY
            # key of the batch (false positives may let a few through —
            # the exact locate resolves those to misses)
            dkls = np.asarray([_doc_key_len(k) for k in miss],
                              dtype=np.int32)
            words, _ = _pad_keys_to_words(miss, width_words=4)
            h1, h2 = pr._fnv64_fused(jnp.asarray(words),
                                     jnp.asarray(dkls), w=4)
            expected_skips = sum(
                1 for r in db._readers.values()
                if not np.asarray(pr.probe_bloom(r, h1, h2)
                                  )[:len(miss)].any())
            assert db.multi_get(miss) == [None] * len(miss)
            assert point_read_metrics()["bloom_skips"].value() \
                == skips0 + expected_skips
        finally:
            db.close()

    def test_device_probe_matches_cpu_bloom(self, tmp_path):
        """The kernel probe is bit-identical to the CPU bloom — false
        positives included (they are resolved by the exact locate)."""
        import jax.numpy as jnp
        from yugabyte_tpu.ops import point_read as pr
        from yugabyte_tpu.ops.slabs import _doc_key_len, _pad_keys_to_words
        from yugabyte_tpu.storage.bloom import fnv64_masked
        db = _fill_db(tmp_path, n_keys=600, n_ssts=1, mem_overlay=False)
        try:
            r = next(iter(db._readers.values()))
            keys = [_key(i) for i in range(0, 2000, 3)]
            dkls = np.asarray([_doc_key_len(k) for k in keys],
                              dtype=np.int64)
            w = 4
            words, _ = _pad_keys_to_words(keys, width_words=w)
            h1, h2 = pr._fnv64_fused(jnp.asarray(words),
                                     jnp.asarray(dkls.astype(np.int32)),
                                     w=w)
            dev = pr.probe_bloom(r, h1, h2)
            u8 = np.zeros((len(keys), w * 4), np.uint8)
            for i, k in enumerate(keys):
                u8[i, :len(k)] = np.frombuffer(k, np.uint8)
            cpu = r.bloom.may_contain_batch(fnv64_masked(u8, dkls))
            assert np.array_equal(dev[:len(keys)], cpu)
        finally:
            db.close()


# ---------------------------------------------------------- learned index
class TestLearnedIndex:
    def test_models_persisted_at_flush(self, tmp_path):
        db = _fill_db(tmp_path, mem_overlay=False)
        try:
            models = [r.props.lindex for r in db._readers.values()]
            assert all(m is not None for m in models), models
            for m in models:
                assert m["v"] == learned_index.MODEL_VERSION
                assert m["max_err"] <= learned_index.LINDEX_MAX_ERR
                # all-integer persistence: JSON round-trips exactly
                assert json.loads(json.dumps(m)) == m
        finally:
            db.close()

    def test_forced_mispredict_falls_back_exact(self, tmp_path):
        """A model whose anchors are garbage and whose error bound is a
        lie must change NOTHING: the search-invariant check flags every
        misprediction and those keys re-resolve exactly."""
        db = _fill_db(tmp_path)
        rng = np.random.default_rng(11)
        keys = _query_keys(1200, rng)
        try:
            expect = [db.get(k) for k in keys]
            from yugabyte_tpu.ops.point_read import point_read_metrics
            fb0 = point_read_metrics()["learned_fallbacks"].value()
            _poison_models(db)
            assert db.multi_get(keys) == expect
            assert point_read_metrics()["learned_fallbacks"].value() > fb0
        finally:
            db.close()

    def test_model_disabled_results_unchanged(self, tmp_path):
        db = _fill_db(tmp_path)
        rng = np.random.default_rng(12)
        keys = _query_keys(1200, rng)
        try:
            with_model = db.multi_get(keys)
            flags.set_flag("point_read_learned_index", False)
            try:
                without = db.multi_get(keys)
            finally:
                flags.set_flag("point_read_learned_index", True)
            assert with_model == without == [db.get(k) for k in keys]
        finally:
            db.close()

    def test_model_bearing_sst_readable_by_pre_model_path(self, tmp_path):
        """Format compatibility both ways: the lindex field is an
        OPTIONAL props key — the pre-model reader path (python
        iter_from/get, props parse) serves a model-bearing SST
        unchanged, and props without the field parse to None."""
        db = _fill_db(tmp_path, n_keys=600, n_ssts=1, mem_overlay=False)
        try:
            r = next(iter(db._readers.values()))
            assert r.props.lindex is not None
            # pre-model read paths: python merged iterator + bloom route
            flags.set_flag("read_native", False)
            flags.set_flag("point_read_batched", False)
            try:
                assert db.get(_key(3)) is not None
                assert db.get(_key(9999)) is None
                n_iter = sum(1 for _ in db.iter_from(b""))
                assert n_iter == r.props.n_entries
            finally:
                flags.set_flag("read_native", True)
                flags.set_flag("point_read_batched", True)
            # a pre-model properties dict (no lindex key) parses clean
            from yugabyte_tpu.storage.sst import SSTProps
            d = r.props.to_json()
            d.pop("lindex")
            assert SSTProps.from_json(d).lindex is None
        finally:
            db.close()

    def test_stale_model_ignored(self, tmp_path):
        """A model whose n disagrees with the file (stale/foreign) is
        advisory data — model_operands refuses it, the exact seek
        serves."""
        db = _fill_db(tmp_path, n_keys=600, n_ssts=1, mem_overlay=False)
        try:
            r = next(iter(db._readers.values()))
            m = dict(r.props.lindex)
            assert learned_index.model_operands(m,
                                               r.props.n_entries) \
                is not None
            m["n"] = m["n"] + 1
            assert learned_index.model_operands(m,
                                               r.props.n_entries) is None
            assert learned_index.model_operands(None, 100) is None
            assert learned_index.model_operands({"v": 99}, 100) is None
        finally:
            db.close()

    def test_device_and_host_fits_agree(self, tmp_path):
        """The device fit (staged cols in HBM) and the numpy twin must
        produce the SAME model for the same sorted keys."""
        from yugabyte_tpu.ops import point_read as pr
        from yugabyte_tpu.ops.merge_gc import stage_slab
        from yugabyte_tpu.ops.slabs import pack_kvs
        entries = [(_key(i), ((1000 + i) << 12 << 32), b"v%d" % i)
                   for i in range(800)]
        slab = pack_kvs(entries)
        host = learned_index.fit_from_slab(slab)
        dev = pr.fit_learned_index_device(stage_slab(slab, _device()))
        assert host == dev
        assert host["p"] >= 1  # the shared "Suser000…" prefix is skipped


# ----------------------------------------------------- fault containment
class TestDeviceFaults:
    @pytest.mark.parametrize("site", ["dispatch", "result"])
    @pytest.mark.parametrize("kind", ["compile", "oom", "runtime"])
    def test_fault_falls_back_byte_identical(self, tmp_path, site, kind):
        db = _fill_db(tmp_path)
        rng = np.random.default_rng(13)
        keys = _query_keys(1200, rng)
        try:
            expect = [db.get(k) for k in keys]
            from yugabyte_tpu.ops.point_read import point_read_metrics
            fb0 = point_read_metrics()["device_fallbacks"].value()
            device_faults.arm(kind, site, 1)
            assert db.multi_get(keys) == expect
            assert point_read_metrics()["device_fallbacks"].value() \
                == fb0 + 1
            # zero leaked pins on the fault path
            assert db._pins == {}
            # the shape bucket is parked native-only...
            snap = offload_policy.bucket_quarantine().snapshot()
            assert snap, "no bucket quarantined after a point-read fault"
            assert all(b["bucket"][0] == 1 for b in snap)
            # ...so the next batch routes native pre-dispatch (no
            # re-fault even if a fault is still armed)
            device_faults.arm(kind, site, 1)
            assert db.multi_get(keys) == expect
            assert device_faults.armed_count() == 1  # never consumed
        finally:
            device_faults.disarm_all()
            db.close()

    def test_corruption_containment(self, tmp_path):
        """A corrupt data block under the batched read parks the DB and
        surfaces RETRYABLY — never a raw Corruption (the client must
        walk to a healthy replica while the master rebuilds this one)."""
        db = _fill_db(tmp_path, mem_overlay=False)
        try:
            data_files = sorted(
                p for p in (os.path.join(db.db_dir, f)
                            for f in os.listdir(db.db_dir))
                if p.endswith(".sblock.0"))
            corrupt_file_range(data_files[0], length=64, nbits=3)
            # drop caches so the corrupt bytes are actually re-read
            for fid in list(db._readers):
                db._device_cache.drop(fid)
            keys = [_key(i) for i in range(0, 1200, 2)]
            with pytest.raises(StatusError) as ei:
                db.multi_get(keys)
            assert ei.value.status.code == Code.SERVICE_UNAVAILABLE
            assert db.background_error is not None
            assert db.background_error.code == Code.CORRUPTION
            assert db._pins == {}
        finally:
            db.close()


# ------------------------------------------- the host side, chunk by chunk
def _ht(us: int) -> HybridTime:
    return HybridTime.from_micros(us)


def _wide_key(i: int) -> bytes:
    """28 bytes: staged 8 words wide where _key's 16 bytes stage 4."""
    return _key(i) + b"K%09dxx" % i


def _zoo_db(tmp_path, n=900, block_entries=32):
    """One DB with everything the batched path merges: three SSTs of the
    narrow width holding overlapping versions (and tombstones) of the
    same keys in small blocks, one SST of the wide width, and a memtable
    that is newer than the files for some keys, OLDER for some, and
    holds tombstones."""
    dev = _device()
    db = DB(str(tmp_path / "zoo"), DBOptions(
        device=dev, device_cache=DeviceSlabCache(device=dev),
        auto_compact=False, block_entries=block_entries))
    for f in range(3):
        items = []
        for i in range(0, n, f + 1):   # file 0: every key; 1: evens; ...
            v = _tomb() if (i + f) % 23 == 0 else b"f%d-%06d-" % (f, i) \
                + b"v" * (i % 40)
            items.append((_key(i), DocHybridTime(
                _ht(1000 + 100 * f + i % 7), f), v))
        db.write_batch(items, op_id=(1, f + 1))
        db.flush()
    db.write_batch([(_wide_key(i), DocHybridTime(_ht(1500), 0),
                     b"wide-%d" % i) for i in range(0, n, 3)],
                   op_id=(1, 4))
    db.flush()
    mem = []
    for i in range(0, n, 5):
        if i % 15 == 0:      # older than every file's version: loses
            mem.append((_key(i), DocHybridTime(_ht(900), 9), b"old-mem"))
        elif i % 15 == 5:    # newest, a tombstone
            mem.append((_key(i), DocHybridTime(_ht(5000), 0), _tomb()))
        else:                # newest, a value
            mem.append((_key(i), DocHybridTime(_ht(5000), 1),
                        b"mem-%d" % i))
    db.write_batch(mem, op_id=(1, 5))
    return db


def _entry_blocks(db):
    """(key, doc hybrid time) -> (file id, block) of every SST entry."""
    where = {}
    for fid, r in db._readers.items():
        for blk in range(r.n_blocks):
            slab = r.read_block(blk)
            for i in range(slab.n):
                where[(slab.key_bytes(i), slab.doc_ht(i))] = (fid, blk)
    return where


_ZOO_CASES = {
    # (a) keys with versions in two and three files
    "overlapping_versions": lambda n: [_key(i) for i in range(0, n, 2)],
    # (b) a run of neighbours (one block) and a stride over every block
    "one_block_and_many": lambda n: (
        [_key(i) for i in range(100, 130)]
        + [_key(i) for i in range(0, n, 37)]),
    # (c) keys the memtable wins, loses, and buries
    "memtable_wins_loses_tombstones": lambda n: [
        _key(i) for i in range(0, n, 5)] + [_key(i) for i in range(1, 60)],
    # (d) nothing anywhere, inside and past the key range
    "absent": lambda n: [_key(n + i) for i in range(40)] + [
        _key(i) + b"\x01" for i in range(0, n, 50)],
    # (e) longer than the narrow files' 16 bytes, and than every file's
    "longer_than_width": lambda n: [
        _key(3) + b"\x00", _key(4) + b"Z" * 30, _wide_key(3) + b"q" * 9,
        _key(3), _wide_key(3)],
    # (f) both widths in one batch
    "two_widths": lambda n: [
        k for i in range(0, n, 9) for k in (_key(i), _wide_key(i))],
    # (g) a batch of one, a hit and a miss
    "batch_of_one": lambda n: [_key(7)],
    "batch_of_one_absent": lambda n: [_key(n + 7)],
    # (h) the learned index mispredicts: exact_fallback keys
    "exact_fallback": lambda n: [_key(i) for i in range(0, n, 3)],
}


class TestHostSideByChunk:
    @pytest.mark.parametrize("case", sorted(_ZOO_CASES))
    def test_multi_get_equals_gets(self, tmp_path, case):
        from yugabyte_tpu.ops.point_read import point_read_metrics
        n = 900
        db = _zoo_db(tmp_path, n)
        try:
            if case == "exact_fallback":
                _poison_models(db)
            keys = _ZOO_CASES[case](n)
            m = point_read_metrics()
            before = {k: m[k].value() for k in
                      ("batches", "device_fallbacks", "learned_fallbacks")}
            for read_ht in (None, _ht(950), _ht(1003), _ht(1104),
                            _ht(1500), _ht(4999)):
                want = [db.get(k, read_ht) for k in keys]
                got = db.multi_get(keys, read_ht)
                assert len(got) == len(want)
                for k, g, w in zip(keys, got, want):
                    assert g == w, (case, read_ht, k)
            # the device path answered, every time
            assert m["batches"].value() == before["batches"] + 6
            assert m["device_fallbacks"].value() \
                == before["device_fallbacks"]
            assert (m["learned_fallbacks"].value()
                    > before["learned_fallbacks"]) \
                == (case == "exact_fallback")
            if case == "memtable_wins_loses_tombstones":
                vals = [r[1] for r in db.multi_get(keys) if r is not None]
                assert any(v.startswith(b"mem-") for v in vals)
                assert _tomb() in vals
                assert b"old-mem" not in vals
                assert b"old-mem" in [
                    r[1] for r in db.multi_get(keys, _ht(950))
                    if r is not None]
        finally:
            db.close()

    def test_value_fetch_counters(self, tmp_path):
        """value_fetch_rows counts the SST winners, value_fetch_blocks
        the distinct blocks read for them: a run of neighbours costs one
        read_block, not one a winner."""
        from yugabyte_tpu.ops.point_read import (point_read_metrics,
                                                 point_read_snapshot)
        db = _fill_db(tmp_path, n_keys=1200, n_ssts=3, mem_overlay=False)
        try:
            where = _entry_blocks(db)
            assert len({b for _f, b in where.values()}) == 1  # 4096 a block
            m = point_read_metrics()
            for keys in ([_key(i) for i in range(300, 360)],
                         [_key(i) for i in range(0, 1400, 11)],
                         [_key(5000)]):
                rows0 = m["value_fetch_rows"].value()
                blocks0 = m["value_fetch_blocks"].value()
                got = db.multi_get(keys)
                winners = [(k, r[0]) for k, r in zip(keys, got)
                           if r is not None]
                assert m["value_fetch_rows"].value() - rows0 \
                    == len(winners)
                assert m["value_fetch_blocks"].value() - blocks0 \
                    == len({where[w] for w in winners})
            snap = point_read_snapshot()
            assert snap["value_fetch_rows"] == m["value_fetch_rows"].value()
            assert snap["value_fetch_blocks"] \
                == m["value_fetch_blocks"].value()
        finally:
            db.close()

    def test_value_fetch_counters_small_blocks(self, tmp_path):
        """The same two counts where the winners spread over many
        blocks of several files, some of them beaten by a memtable."""
        from yugabyte_tpu.ops.point_read import point_read_metrics
        n = 900
        db = _zoo_db(tmp_path, n)
        try:
            assert all(r.n_blocks > 5 for r in db._readers.values())
            where = _entry_blocks(db)
            m = point_read_metrics()
            keys = [_key(i) for i in range(0, n, 2)] \
                + [_wide_key(i) for i in range(0, n, 6)]
            rows0 = m["value_fetch_rows"].value()
            blocks0 = m["value_fetch_blocks"].value()
            got = db.multi_get(keys)
            assert got == [db.get(k) for k in keys]
            # a memtable's winner is in no file at its hybrid time
            winners = [(k, r[0]) for k, r in zip(keys, got)
                       if r is not None and (k, r[0]) in where]
            assert 0 < len(winners) < sum(r is not None for r in got)
            n_blocks = len({where[w] for w in winners})
            assert 5 < n_blocks < len(winners)
            assert m["value_fetch_rows"].value() - rows0 == len(winners)
            assert m["value_fetch_blocks"].value() - blocks0 == n_blocks
        finally:
            db.close()

    def test_resident_operands_go_with_the_file(self, tmp_path):
        """What is kept per file (bloom words, entry count, learned
        index on the device; the blocks' first rows) is built on the
        file's first read and dropped when a compaction replaces the
        file: the new file is served with its own."""
        db = _fill_db(tmp_path, n_keys=1200, n_ssts=3, mem_overlay=False)
        keys = [_key(i) for i in range(0, 1300, 7)]
        try:
            want = [db.get(k) for k in keys]
            assert db.multi_get(keys) == want
            old = list(db._readers.values())
            assert len(old) == 3
            for r in old:
                words, m_bits, k = r._bloom_dev
                assert int(m_bits) == r.bloom.m_bits and int(k) == r.bloom.k
                n_dev, model = r._locate_dev
                assert int(n_dev) == r.props.n_entries == 400
                assert [int(x) for x in model[0]] == r.props.lindex["a_hi"]
                assert int(model[4]) == r.props.lindex["max_err"]
                assert r._row_offs_pr.tolist() == [0, 400]
                # uncommitted, as jnp.int32(...) made them: a committed
                # operand would be another program to the compiler
                assert not any(x.committed for x in
                               (m_bits, k, n_dev) + tuple(model))
            held = old[0]._locate_dev
            assert db.multi_get(keys) == want
            assert old[0]._locate_dev is held      # built once
            db.compact_all()
            (new,) = db._readers.values()
            assert new not in old
            for r in old:
                assert r._bloom_dev is None and r._locate_dev is None \
                    and r._row_offs_pr is None
            assert new._locate_dev is None         # nothing read it yet
            assert db.multi_get(keys) == want == [db.get(k) for k in keys]
            n_dev, model = new._locate_dev
            assert int(n_dev) == new.props.n_entries
            assert new.props.n_entries != 400
            assert new._row_offs_pr[-1] == new.props.n_entries
            assert int(new._bloom_dev[1]) == new.bloom.m_bits
        finally:
            db.close()

    def test_second_batch_compiles_nothing(self, tmp_path):
        """A second multi_get of the same shape finds every program
        compiled: the operands reach the three programs with the avals
        prewarm_point_read lowers them for, call after call."""
        import jax
        from yugabyte_tpu.ops import point_read as pr
        db = _fill_db(tmp_path, n_keys=1200, n_ssts=3)
        programs = (pr._fnv64_fused, pr._bloom_probe_fused,
                    pr._locate_gather_fused)
        seen = []
        real = pr._locate_gather_fused

        def spy(*args, **statics):
            seen.append((args, statics))
            return real(*args, **statics)

        compiles = []

        def on_event(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        try:
            assert db.multi_get([_key(i) for i in range(40)]) \
                == [db.get(_key(i)) for i in range(40)]
            sizes = [f._cache_size() for f in programs]
            jax.monitoring.register_event_duration_secs_listener(on_event)
            pr._locate_gather_fused = spy
            try:
                keys = [_key(i) for i in range(500, 560)]   # same bucket
                got = db.multi_get(keys, HybridTime.from_micros(60_000))
            finally:
                pr._locate_gather_fused = real
                jax.monitoring.unregister_event_duration_listener(on_event)
            assert got == [db.get(k, HybridTime.from_micros(60_000))
                           for k in keys]
            assert compiles == []
            assert [f._cache_size() for f in programs] == sizes
            assert len(seen) == 3
            s17 = (learned_index.LINDEX_SEGMENTS + 1,)
            for args, statics in seen:
                assert statics == {"w": 4, "use_model": True}
                got_avals = [(tuple(np.shape(a)), np.dtype(a.dtype).name)
                             for a in args]
                assert got_avals == [
                    ((12, args[0].shape[1]), "uint32"), ((), "int32"),
                    ((64, 4), "uint32"), ((64,), "int32"),
                    ((), "uint32"), ((), "uint32"),
                    (s17, "uint32"), (s17, "uint32"), (s17, "int32"),
                    ((), "int32"), ((), "int32")]
                # no Python number among them: a weak-typed scalar is
                # another aval, and another executable
                assert not any(isinstance(a, (int, float)) for a in args)
        finally:
            db.close()


# ----------------------------------------------------------- tablet layer
SCHEMA = None


def _schema():
    global SCHEMA
    if SCHEMA is None:
        from yugabyte_tpu.common.schema import (ColumnSchema, DataType,
                                                Schema)
        SCHEMA = Schema(columns=[ColumnSchema("k", DataType.STRING),
                                 ColumnSchema("v", DataType.STRING),
                                 ColumnSchema("n", DataType.INT64)],
                        num_hash_key_columns=1)
    return SCHEMA


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    from yugabyte_tpu.integration.mini_cluster import (MiniCluster,
                                                       MiniClusterOptions)
    flags.set_flag("replication_factor", 1)
    c = MiniCluster(MiniClusterOptions(
        num_masters=1, num_tservers=1,
        fs_root=str(tmp_path_factory.mktemp("pr-minicluster")))).start()
    yield c
    c.shutdown()


@pytest.fixture(scope="module")
def table(cluster):
    client = cluster.new_client()
    client.create_namespace("db")
    t = client.create_table("db", "kv", _schema(), num_tablets=2)
    cluster.wait_all_replicas_running(t.table_id)
    cluster.wait_for_table_leaders("db", "kv")
    return t


def _dk(k: str):
    from yugabyte_tpu.docdb.doc_key import DocKey
    return DocKey(hash_components=(k,))


class TestMultiReadRPC:
    def _load(self, cluster, table):
        from yugabyte_tpu.docdb.doc_operations import (QLWriteOp,
                                                       WriteOpKind)
        client = cluster.new_client()
        ops = []
        for i in range(60):
            ops.append(QLWriteOp(WriteOpKind.INSERT, _dk(f"row{i:03d}"),
                                 {"v": f"val{i}", "n": i}))
        for op in ops:
            client.write(table, [op])
        # updates (newer versions), column tombstone via update-to-None,
        # and row deletes
        for i in range(0, 60, 5):
            client.write(table, [QLWriteOp(WriteOpKind.UPDATE,
                                           _dk(f"row{i:03d}"),
                                           {"v": f"val{i}-v2"})])
        for i in range(0, 60, 11):
            client.write(table, [QLWriteOp(WriteOpKind.DELETE_ROW,
                                           _dk(f"row{i:03d}"), {})])
        return client

    def test_multi_read_matches_read_row(self, cluster, table):
        client = self._load(cluster, table)
        dks = [_dk(f"row{i:03d}") for i in range(70)]  # incl. absent
        batched = client.multi_read(table, dks)
        seq = [client.read_row(table, dk) for dk in dks]
        assert len(batched) == len(seq)
        for b, s, dk in zip(batched, seq, dks):
            if s is None:
                assert b is None, dk
            else:
                assert b is not None, dk
                assert b.to_dict(_schema()) == s.to_dict(_schema()), dk

    def test_multi_read_after_flush_and_projection(self, cluster, table):
        client = cluster.new_client()
        for ts in cluster.tservers:
            for peer in ts.tablet_manager.peers():
                t = getattr(peer, "tablet", None)
                if t is not None and t.regular_db is not None:
                    t.regular_db.flush()
        dks = [_dk(f"row{i:03d}") for i in range(0, 70, 3)]
        batched = client.multi_read(table, dks, projection=["v"])
        seq = [client.read_row(table, dk, projection=["v"])
               for dk in dks]
        for b, s in zip(batched, seq):
            assert (b is None) == (s is None)
            if b is not None:
                assert b.to_dict(_schema()) == s.to_dict(_schema())

    def test_multi_read_deep_rows_fall_back(self, cluster, table):
        """Rows holding deep documents route through the exact per-row
        path (the flat fast path refuses them) — answers still match."""
        client = cluster.new_client()
        peer = None
        for ts in cluster.tservers:
            for p in ts.tablet_manager.peers():
                if getattr(p, "tablet", None) is not None \
                        and p.raft.is_leader():
                    peer = p
                    break
            if peer is not None:
                break
        assert peer is not None
        schema = peer.tablet.schema
        cid = schema.column_id("v")
        dk = None
        # find a doc key this tablet owns
        for i in range(60):
            cand = _dk(f"row{i:03d}")
            enc = cand.encode()
            lo = peer.tablet.opts.lower_bound_key
            hi = peer.tablet.opts.upper_bound_key
            if (not lo or enc >= lo) and (hi is None or enc < hi):
                dk = cand
                break
        assert dk is not None
        peer.tablet.write_subdocument(dk, (("col", cid), "deepkey"),
                                      {"a": 1})
        rows = peer.multi_read([dk])
        direct = peer.read_row(dk)
        assert (rows[0] is None) == (direct is None)
        if direct is not None:
            assert rows[0].to_dict(schema) == direct.to_dict(schema)
