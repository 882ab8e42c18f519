"""Native run cache: zero-decode steady-state compaction inputs.

storage/run_cache.py + ce_runcache_* (native/compaction_engine.cc): a
flush/compaction output exported into the cache must be byte-equivalent
to re-decoding the file that was written for the same survivor range —
a job ingesting cached runs (prepare_cached) must produce outputs
byte-identical to one decoding the same inputs from disk, including
rewritten-as-tombstone survivors. The cache is an LRU over immutable
C++-side entries; Python's accounting must track the native registry.

ref (what the fast path skips): rocksdb/db/compaction_job.cc:442 input
iteration + table/block-based reader decode per job.
"""

import glob
import os

import numpy as np
import pytest

from yugabyte_tpu.ops.slabs import ValueArray
from yugabyte_tpu.storage import compaction as compaction_mod
from yugabyte_tpu.storage import native_engine
from yugabyte_tpu.storage.device_cache import DeviceSlabCache
from yugabyte_tpu.storage.run_cache import (NamespacedRunCache,
                                            NativeRunCache)
from yugabyte_tpu.storage.sst import Frontier, SSTReader, SSTWriter

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


def _export_inputs(rc, input_ids, readers):
    """What flush write-through does: retain each input decoded."""
    from yugabyte_tpu.storage.run_cache import export_reader
    for fid, r in zip(input_ids, readers):
        export_reader(rc, fid, r)


def _device():
    import jax
    return jax.devices()[0]


@pytest.fixture
def workload(tmp_path):
    rng = np.random.default_rng(7)
    runs = [_mk_run(rng, 800, 500, ttl_frac=0.3) for _ in range(4)]
    readers = _write_runs(str(tmp_path), runs)
    yield str(tmp_path), readers
    for r in readers:
        r.close()


def _run_job(readers, out_dir, cutoff, first_id, *, is_major=True,
             cache=None, input_ids=None, run_cache=None):
    os.makedirs(out_dir, exist_ok=True)
    ids = iter(range(first_id, first_id + 500))
    return compaction_mod.run_compaction_job_device_native(
        readers, out_dir, lambda: next(ids), cutoff, is_major,
        device=_device(), device_cache=cache, input_ids=input_ids,
        run_cache=run_cache)


def _data_bytes(out_dir):
    return [open(p, "rb").read()
            for p in sorted(glob.glob(os.path.join(out_dir, "*.data")))]


def test_cached_job_matches_decode_job(workload):
    """All-cached input path == from-disk path, byte for byte."""
    workdir, readers = workload
    cutoff = 1 << 60
    cache = DeviceSlabCache(device=_device())
    input_ids = [10**9 + i for i in range(len(readers))]
    for fid, r in zip(input_ids, readers):
        cache.stage(fid, r.read_all())
    rc = NamespacedRunCache(NativeRunCache(capacity_bytes=1 << 30), "t")
    _export_inputs(rc, input_ids, readers)

    res_rc = _run_job(readers, os.path.join(workdir, "a"), cutoff, 100,
                      cache=cache, input_ids=input_ids, run_cache=rc)
    res_no = _run_job(readers, os.path.join(workdir, "b"), cutoff, 600,
                      cache=cache, input_ids=input_ids, run_cache=None)
    assert res_rc.rows_out == res_no.rows_out
    assert _data_bytes(os.path.join(workdir, "a")) == \
        _data_bytes(os.path.join(workdir, "b"))
    assert rc.hits >= len(readers)


def test_tombstone_rewrite_survives_chain(workload):
    """Survivors rewritten as tombstones (TTL-expired, non-major) must
    round-trip the cache as tombstones: a chained second compaction from
    cached outputs equals one from decoded outputs."""
    workdir, readers = workload
    cutoff = 1 << 62  # far future: TTLs expire -> mk rewrites on non-major
    cache = DeviceSlabCache(device=_device())
    input_ids = [10**9 + i for i in range(len(readers))]
    for fid, r in zip(input_ids, readers):
        cache.stage(fid, r.read_all())
    rc = NamespacedRunCache(NativeRunCache(capacity_bytes=1 << 30), "t")
    _export_inputs(rc, input_ids, readers)

    out1 = os.path.join(workdir, "chain1")
    res1 = _run_job(readers, out1, cutoff, 100, is_major=False,
                    cache=cache, input_ids=input_ids, run_cache=rc)
    outs1 = sorted(glob.glob(os.path.join(out1, "*.sst")))
    assert outs1 and res1.rows_out
    out_ids = [fid for fid, _b, _p in res1.outputs]
    assert all(rc.contains(fid) for fid in out_ids), \
        "compaction outputs must be exported to the run cache"

    # chained second job: cached outputs vs re-decoded outputs
    readers1 = [SSTReader(p) for p in outs1]
    res_c = _run_job(readers1, os.path.join(workdir, "chain2c"), cutoff,
                     300, is_major=True, cache=cache, input_ids=out_ids,
                     run_cache=rc)
    res_d = _run_job(readers1, os.path.join(workdir, "chain2d"), cutoff,
                     700, is_major=True, cache=cache, input_ids=out_ids,
                     run_cache=None)
    for r in readers1:
        r.close()
    assert res_c.rows_out == res_d.rows_out
    assert _data_bytes(os.path.join(workdir, "chain2c")) == \
        _data_bytes(os.path.join(workdir, "chain2d"))


def test_partial_hit_falls_back_to_decode(workload):
    """A single missing input drops the whole job to the file path (run
    order could not otherwise match the device's run-major indexes)."""
    workdir, readers = workload
    cutoff = 1 << 60
    cache = DeviceSlabCache(device=_device())
    input_ids = [10**9 + i for i in range(len(readers))]
    for fid, r in zip(input_ids, readers):
        cache.stage(fid, r.read_all())
    rc = NamespacedRunCache(NativeRunCache(capacity_bytes=1 << 30), "t")
    _export_inputs(rc, input_ids[:-1], readers[:-1])  # one input missing

    res = _run_job(readers, os.path.join(workdir, "p"), cutoff, 100,
                   cache=cache, input_ids=input_ids, run_cache=rc)
    res_no = _run_job(readers, os.path.join(workdir, "q"), cutoff, 600,
                      cache=cache, input_ids=input_ids, run_cache=None)
    assert res.rows_out == res_no.rows_out
    assert _data_bytes(os.path.join(workdir, "p")) == \
        _data_bytes(os.path.join(workdir, "q"))


def test_lru_eviction_and_native_accounting():
    """Eviction keeps Python and C++ byte accounting in step; dropped ids
    are gone from the native registry."""
    rng = np.random.default_rng(3)
    import tempfile
    workdir = tempfile.mkdtemp()
    runs = [_mk_run(rng, 300, 200) for _ in range(3)]
    readers = _write_runs(workdir, runs)
    ids = []
    sizes = []
    for r in readers:
        with native_engine.NativeCompactionJob() as j:
            with open(r.data_path, "rb") as f:
                j.add_input(f.read(), r.block_handles)
            n = j.prepare()
            j.sort_all()
            rid = j.export_run(0, n, b"X")
            ids.append(rid)
            sizes.append(native_engine.runcache_entry_bytes(rid))
    base = native_engine.runcache_bytes()
    # capacity for ~2 entries: inserting all 3 evicts the oldest
    rc = NativeRunCache(capacity_bytes=sizes[0] + sizes[1] + 1)
    for i, (rid, nb) in enumerate(zip(ids, sizes)):
        rc.put(("t", i), rid, nb)
    assert not rc.contains(("t", 0)) and rc.contains(("t", 2))
    assert rc.used_bytes <= rc.capacity
    assert native_engine.runcache_entry_bytes(ids[0]) == -1  # dropped
    rc.drop_namespace("t")
    assert rc.used_bytes == 0
    assert native_engine.runcache_bytes() == base - sum(sizes)
    # an entry larger than the whole budget is evicted immediately — the
    # cache never pins RAM past its cap
    rc2 = NativeRunCache(capacity_bytes=sizes[2] - 1)
    rc2.put(("t", 9), ids[2], sizes[2])
    assert not rc2.contains(("t", 9)) and rc2.used_bytes == 0
    for r in readers:
        r.close()


def test_db_flush_exports_and_compaction_hits(tmp_path):
    """DB integration: flushes export to the run cache, the compaction
    over them starts all-cached (hits == input count), its outputs are
    re-exported, and reads stay correct afterwards."""
    from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime
    from yugabyte_tpu.storage.db import DB, DBOptions

    opts = DBOptions(auto_compact=False, device=_device(),
                     device_cache=DeviceSlabCache(device=_device()))
    db = DB(str(tmp_path / "db"), opts)
    if db._run_cache is None:
        db.close()
        pytest.skip("run cache disabled in this configuration")
    n_flushes = 4  # >= universal_compaction_min_merge_width
    expected = {}
    ht = 1000
    for batch in range(n_flushes):
        items = []
        for i in range(200):
            k = b"k%04d" % ((batch * 150 + i) % 400)
            v = b"v%d-%d" % (batch, i)
            items.append((k, DocHybridTime(HybridTime(ht << 12), 0), v))
            expected[k] = v
            ht += 1
        db.write_batch(items)
        fid = db.flush()
        assert db._run_cache.contains(fid), \
            "flush must write through to the run cache"
    hits0 = db._run_cache.hits
    assert db.maybe_schedule_compaction()
    assert db._run_cache.hits >= hits0 + n_flushes, \
        "compaction over flushed SSTs must take the all-cached path"
    live = list(db.versions.files)
    assert all(db._run_cache.contains(fid) for fid in live), \
        "compaction outputs must be re-exported"
    for k, v in list(expected.items())[::17]:
        got = db.get(k)
        assert got is not None and got[1] == v, k
    db.close()
