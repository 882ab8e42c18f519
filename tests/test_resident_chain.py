"""Device-resident LSM chain: compaction outputs feed the next level
from HBM with zero re-decode.

The chained L0->L1->L2 path must (a) produce SSTs byte-identical to the
sequential native path with the decode counters FLAT across the warm
chain (run-cache ingest + resident slabs mean no SST byte is re-read),
(b) install each output's cache entry under the output file id AS its
span completes, at one residency level below the deepest input, (c)
never let capacity eviction touch a pinned in-flight input, (d) drop
slabs when their files become obsolete (and on DB close), and (e) fall
back natively under an injected device fault with the cache left
coherent and zero leaked pins.
"""

import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_run_merge import _make_run  # noqa: E402

from yugabyte_tpu.ops import device_faults  # noqa: E402
from yugabyte_tpu.ops.slabs import ValueArray  # noqa: E402
from yugabyte_tpu.storage import compaction as compaction_mod  # noqa: E402
from yugabyte_tpu.storage import integrity  # noqa: F401,E402 (registers
#   shadow_verify_sample — without it the file only passes when another
#   test module imported integrity first)
from yugabyte_tpu.storage import offload_policy  # noqa: E402
from yugabyte_tpu.storage.device_cache import DeviceSlabCache  # noqa: E402
from yugabyte_tpu.storage.run_cache import (NamespacedRunCache,  # noqa: E402
                                            NativeRunCache)
from yugabyte_tpu.storage.sst import (Frontier, SSTReader,  # noqa: E402
                                      SSTWriter, _block_decode_counter)
from yugabyte_tpu.utils import flags  # noqa: E402

pytestmark = pytest.mark.requires_native("compaction_engine")

CUTOFF = (10_000_000 << 12)


@pytest.fixture(autouse=True)
def _clean_state():
    device_faults.disarm_all()
    offload_policy.bucket_quarantine().clear()
    yield
    device_faults.disarm_all()
    offload_policy.bucket_quarantine().clear()


def _device():
    import jax
    return jax.devices()[0]


def _mk_run(rng, n, key_space, value_bytes=16):
    slab = _make_run(rng, n, key_space)
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


def _run_chain_job(readers, out_dir, cache, input_ids, run_cache=None,
                   first_id=100, is_major=True):
    os.makedirs(out_dir, exist_ok=True)
    ids = iter(range(first_id, first_id + 500))
    return compaction_mod.run_compaction_job_device_native(
        readers, out_dir, lambda: next(ids), CUTOFF, is_major,
        device=_device(), device_cache=cache, input_ids=input_ids,
        run_cache=run_cache)


def _ingest_counter():
    return compaction_mod._ingest_decode_counter()


def _sst_bytes(outputs):
    out = []
    for _fid, base_path, _props in outputs:
        with open(base_path + ".sblock.0", "rb") as f:
            out.append(f.read())
    return out


# ---------------------------------------------------------------------------
# the chain itself


def test_chained_l0_l1_l2_byte_identical_zero_decode(tmp_path):
    """L0->L1->L2 through the resident chain == the sequential native
    path, and the WARM chained jobs re-decode nothing: both the block
    decode counter and the native-shell ingest counter stay flat."""
    rng = np.random.default_rng(21)
    runs_a = [_mk_run(rng, 700, 450) for _ in range(2)]
    runs_b = [_mk_run(rng, 700, 450) for _ in range(2)]
    cache = DeviceSlabCache(device=_device())
    rc = NamespacedRunCache(NativeRunCache(capacity_bytes=1 << 30), "t")

    os.makedirs(str(tmp_path / "a"))
    os.makedirs(str(tmp_path / "b"))
    readers_a = _write_runs(str(tmp_path / "a"), runs_a)
    readers_b = _write_runs(str(tmp_path / "b"), runs_b)
    # steady state: flush write-through staged the inputs (level 0) and
    # retained the packed runs, exactly as DB.flush does
    for fid, r in zip((0, 1), readers_a):
        cache.stage(fid, r.read_all(), level=0)
    for fid, r in zip((2, 3), readers_b):
        cache.stage(fid, r.read_all(), level=0)
    from yugabyte_tpu.storage.run_cache import export_reader
    for fid, r in zip((0, 1), readers_a):
        export_reader(rc, fid, r)
    for fid, r in zip((2, 3), readers_b):
        export_reader(rc, fid, r)

    # the decode/ingest counters are PROCESS-global: daemon threads a
    # prior suite leaked (remote-bootstrap readers, CDC pollers winding
    # down) can still be decoding blocks when this test starts. Open the
    # flat-counter window only after one quiet 250ms interval.
    deadline = time.monotonic() + 10.0
    blocks0 = _block_decode_counter().value()
    ingest0 = _ingest_counter().value()
    while time.monotonic() < deadline:
        time.sleep(0.25)
        cur = (_block_decode_counter().value(), _ingest_counter().value())
        if cur == (blocks0, ingest0):
            break
        blocks0, ingest0 = cur

    # deflake: the SAMPLED shadow verifier's oracle legitimately decodes
    # the inputs when a job is drawn (default 2%/job) — pin sampling off
    # so the flat-counter assertion only sees real leaks
    old_shadow = flags.get_flag("shadow_verify_sample")
    flags.set_flag("shadow_verify_sample", 0.0)
    try:
        # L0 -> L1 (two jobs), chained straight into L1 -> L2
        res_a = _run_chain_job(readers_a, str(tmp_path / "oa"), cache,
                               [0, 1], run_cache=rc, first_id=100)
        res_b = _run_chain_job(readers_b, str(tmp_path / "ob"), cache,
                               [2, 3], run_cache=rc, first_id=200)
        l1_outputs = res_a.outputs + res_b.outputs
        l1_readers = [SSTReader(p) for _, p, _ in l1_outputs]
        l1_ids = [fid for fid, _, _ in l1_outputs]
        res_l2 = _run_chain_job(l1_readers, str(tmp_path / "l2"), cache,
                                l1_ids, run_cache=rc, first_id=300)
    finally:
        flags.set_flag("shadow_verify_sample", old_shadow)

    # zero re-decode across the whole warm chain: every input came from
    # the HBM slab cache (decisions) + the packed-run cache (bytes)
    assert _block_decode_counter().value() == blocks0, \
        "warm chained compaction decoded SST blocks"
    assert _ingest_counter().value() == ingest0, \
        "warm chained compaction re-ingested SST files"

    # residency levels: L1 outputs sit one above their L0 inputs, the
    # L2 output one above those
    for fid in l1_ids:
        assert cache.level_of(fid) == 1
    for fid, _p, _props in res_l2.outputs:
        assert cache.level_of(fid) == 2

    # byte-identity vs the sequential native path over the same L1 files
    os.makedirs(str(tmp_path / "ref"))
    ids = iter(range(400, 500))
    ref = compaction_mod.run_compaction_job(
        l1_readers, str(tmp_path / "ref"), lambda: next(ids), CUTOFF,
        True, device="native")
    assert res_l2.rows_out == ref.rows_out
    assert _sst_bytes(res_l2.outputs) == _sst_bytes(ref.outputs)
    for r in l1_readers + readers_a + readers_b:
        r.close()


def test_per_span_install_as_spans_complete(tmp_path, monkeypatch):
    """Each output file's cache entry is installed the moment its span's
    SST exists — observed from inside the writer callback, before the
    job finishes."""
    rng = np.random.default_rng(22)
    runs = [_mk_run(rng, 900, 4000) for _ in range(2)]  # few dups: big out
    readers = _write_runs(str(tmp_path), runs)
    cache = DeviceSlabCache(device=_device())
    for fid, r in zip((0, 1), readers):
        cache.stage(fid, r.read_all())

    seen = []
    orig = compaction_mod._ResidentSpanInstaller.on_span

    def spy(self, fid, base_path, start, end):
        orig(self, fid, base_path, start, end)
        seen.append((fid, cache.contains(fid)))

    monkeypatch.setattr(compaction_mod._ResidentSpanInstaller, "on_span",
                        spy)
    old = flags.get_flag("compaction_max_output_entries_per_sst")
    flags.set_flag("compaction_max_output_entries_per_sst", 500)
    try:
        res = _run_chain_job(readers, str(tmp_path / "out"), cache, [0, 1])
    finally:
        flags.set_flag("compaction_max_output_entries_per_sst", old)
    assert len(res.outputs) >= 2, "expected a multi-file split"
    assert len(seen) == len(res.outputs)
    assert all(installed for _fid, installed in seen), \
        "a span completed without its cache entry installed"
    for r in readers:
        r.close()


def test_digest_mismatch_drops_entry(tmp_path, monkeypatch):
    """A write-through entry that fails the sampled digest check is
    dropped, never installed — the job itself still succeeds (the file
    bytes are host truth)."""
    from yugabyte_tpu.storage import integrity

    rng = np.random.default_rng(23)
    runs = [_mk_run(rng, 600, 400) for _ in range(2)]
    readers = _write_runs(str(tmp_path), runs)
    cache = DeviceSlabCache(device=_device())
    for fid, r in zip((0, 1), readers):
        cache.stage(fid, r.read_all())

    flags.set_flag("resident_digest_sample", 1.0)
    mm0 = integrity.resident_digest_mismatch_counter().value()
    real_verify = integrity.verify_resident_entry

    def broken_verify(staged, base_path):
        errs = real_verify(staged, base_path)
        return errs + ["synthetic divergence"]

    monkeypatch.setattr(integrity, "verify_resident_entry", broken_verify)
    try:
        res = _run_chain_job(readers, str(tmp_path / "out"), cache, [0, 1])
    finally:
        flags.set_flag("resident_digest_sample", 0.02)
    assert res.outputs
    for fid, _p, _props in res.outputs:
        assert not cache.contains(fid), \
            "digest-mismatched entry was installed anyway"
    assert integrity.resident_digest_mismatch_counter().value() > mm0
    for r in readers:
        r.close()


def test_digest_check_passes_clean_entries(tmp_path):
    """With sampling forced on, clean write-through entries verify and
    install (the check against real decoded bytes holds)."""
    from yugabyte_tpu.storage import integrity

    rng = np.random.default_rng(24)
    runs = [_mk_run(rng, 600, 400) for _ in range(2)]
    readers = _write_runs(str(tmp_path), runs)
    cache = DeviceSlabCache(device=_device())
    for fid, r in zip((0, 1), readers):
        cache.stage(fid, r.read_all())
    flags.set_flag("resident_digest_sample", 1.0)
    checked0 = integrity.resident_digest_snapshot()["checked"]
    mm0 = integrity.resident_digest_snapshot()["mismatches"]
    try:
        res = _run_chain_job(readers, str(tmp_path / "out"), cache, [0, 1])
    finally:
        flags.set_flag("resident_digest_sample", 0.02)
    assert res.outputs
    for fid, _p, _props in res.outputs:
        assert cache.contains(fid)
    snap = integrity.resident_digest_snapshot()
    assert snap["checked"] > checked0
    assert snap["mismatches"] == mm0


def test_cold_chain_flat_decode_counters_with_device_codec(tmp_path):
    """A COLD L0->L1->L2 chain (empty device cache, empty run cache)
    with the device codec enabled: neither sst_block_decode_total nor
    compaction_ingest_decode_total moves at any point — the initial
    ingest is a raw-byte upload + device decode (block_decode_fused),
    not a host decode — and the L2 output is byte-identical to the
    sequential native path (the ISSUE-14 acceptance criterion; the warm
    test above proves the run-cache/shell flavor)."""
    assert os.environ.get("YBTPU_DEVICE_CODEC", "1") not in ("0", "false")
    rng = np.random.default_rng(26)
    runs_a = [_mk_run(rng, 700, 450) for _ in range(2)]
    runs_b = [_mk_run(rng, 700, 450) for _ in range(2)]
    cache = DeviceSlabCache(device=_device())   # EMPTY: nothing pre-staged
    os.makedirs(str(tmp_path / "a"))
    os.makedirs(str(tmp_path / "b"))
    readers_a = _write_runs(str(tmp_path / "a"), runs_a)
    readers_b = _write_runs(str(tmp_path / "b"), runs_b)

    # determinism: the SAMPLED shadow/digest checks legitimately decode
    # host blocks when they fire — pin them off so any counter movement
    # is a real codec leak
    old_shadow = flags.get_flag("shadow_verify_sample")
    old_digest = flags.get_flag("resident_digest_sample")
    flags.set_flag("shadow_verify_sample", 0.0)
    flags.set_flag("resident_digest_sample", 0.0)
    blocks0 = _block_decode_counter().value()
    ingest0 = _ingest_counter().value()
    from yugabyte_tpu.ops.block_codec import codec_metrics
    dev_decode0 = codec_metrics()["decode_blocks"].value()
    dev_encode0 = codec_metrics()["encode_blocks"].value()
    try:
        res_a = _run_chain_job(readers_a, str(tmp_path / "oa"), cache,
                               [0, 1], first_id=100)
        res_b = _run_chain_job(readers_b, str(tmp_path / "ob"), cache,
                               [2, 3], first_id=200)
        l1_outputs = res_a.outputs + res_b.outputs
        l1_readers = [SSTReader(p) for _, p, _ in l1_outputs]
        l1_ids = [fid for fid, _, _ in l1_outputs]
        res_l2 = _run_chain_job(l1_readers, str(tmp_path / "l2"), cache,
                                l1_ids, first_id=300)
    finally:
        flags.set_flag("shadow_verify_sample", old_shadow)
        flags.set_flag("resident_digest_sample", old_digest)

    # flat across the WHOLE cold chain, including the initial raw-byte
    # upload: the device codec never routes bytes through decode_block
    # or the native shell ingest
    assert _block_decode_counter().value() == blocks0, \
        "cold chained compaction decoded SST blocks on the host"
    assert _ingest_counter().value() == ingest0, \
        "cold chained compaction ingested through the native shell"
    # the L0 ingest ran on the decode family; outputs on the encode one
    assert codec_metrics()["decode_blocks"].value() > dev_decode0
    assert codec_metrics()["encode_blocks"].value() > dev_encode0
    # the L1->L2 job found its inputs resident (write-through): only the
    # four L0 files ever paid a decode dispatch
    assert codec_metrics()["decode_blocks"].value() - dev_decode0 == 4

    for fid in l1_ids:
        assert cache.level_of(fid) == 1
    for fid, _p, _props in res_l2.outputs:
        assert cache.level_of(fid) == 2

    os.makedirs(str(tmp_path / "ref"))
    ids = iter(range(400, 500))
    ref = compaction_mod.run_compaction_job(
        l1_readers, str(tmp_path / "ref"), lambda: next(ids), CUTOFF,
        True, device="native")
    assert res_l2.rows_out == ref.rows_out
    assert _sst_bytes(res_l2.outputs) == _sst_bytes(ref.outputs)
    for r in l1_readers + readers_a + readers_b:
        r.close()


# ---------------------------------------------------------------------------
# residency policy: pins + levels + gauge


def test_eviction_never_evicts_pinned():
    from tests.test_storage import make_slab
    cache = DeviceSlabCache(capacity_bytes=1)  # evict aggressively
    cache.stage(1, make_slab(100))
    assert cache.pin(1)
    cache.stage(2, make_slab(100))
    cache.stage(3, make_slab(100))
    # pinned entry survives every eviction pass; unpinned ones go
    assert cache.contains(1)
    cache.unpin(1)
    assert cache.pinned_count() == 0
    cache.stage(4, make_slab(100))
    assert not cache.contains(1)  # unpinned: evictable again


def test_eviction_prefers_shallow_levels():
    from tests.test_storage import make_slab
    big = make_slab(200)
    cache = DeviceSlabCache(capacity_bytes=1 << 62)
    cache.stage(10, big, level=2)          # oldest, deep
    cache.stage(11, make_slab(200), level=0)
    cache.stage(12, make_slab(200), level=1)
    cache.capacity = cache.snapshot()["used_bytes"] - 1
    cache.stage(13, make_slab(50), level=0)
    # L0 entries evict before the (older) L2 base run
    assert cache.contains(10), "deep entry evicted before shallow ones"
    assert not cache.contains(11)


def test_pin_miss_returns_false():
    cache = DeviceSlabCache()
    assert not cache.pin(999)
    cache.unpin(999)  # no-op, never raises
    assert cache.pinned_count() == 0


def test_used_gauge_tracks_every_mutation():
    """drop/drop_namespace/eviction must update the used-bytes gauge,
    not just put (the stale-gauge satellite fix)."""
    from tests.test_storage import make_slab
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    g = ROOT_REGISTRY.entity("server", "device_cache").gauge(
        "device_cache_used_bytes", "")
    cache = DeviceSlabCache()
    cache.stage(("ns", 1), make_slab(100))
    cache.stage(("ns", 2), make_slab(100))
    cache.stage(("other", 3), make_slab(100))
    assert g.value() == cache.snapshot()["used_bytes"] > 0
    cache.drop(("ns", 1))
    assert g.value() == cache.snapshot()["used_bytes"]
    cache.drop_namespace("ns")
    assert g.value() == cache.snapshot()["used_bytes"]
    cache.drop_namespace("other")
    assert g.value() == 0
    # eviction path: shrink capacity and re-stage
    cache.capacity = 1
    cache.stage(("ns", 4), make_slab(100))
    cache.stage(("ns", 5), make_slab(100))
    assert g.value() == cache.snapshot()["used_bytes"]
    assert cache.evictions > 0


def test_snapshot_levels_block():
    from tests.test_storage import make_slab
    cache = DeviceSlabCache()
    cache.stage(1, make_slab(50), level=0)
    cache.stage(2, make_slab(50), level=1)
    cache.pin(2)
    snap = cache.snapshot()
    assert snap["entries"] == 2 and snap["pinned"] == 1
    assert snap["levels"]["L0"]["entries"] == 1
    assert snap["levels"]["L1"]["pinned"] == 1
    cache.unpin(2)


# ---------------------------------------------------------------------------
# lifecycle: obsolete files + close drop slabs


def test_obsolete_and_close_drop_slabs(tmp_path):
    from yugabyte_tpu.common.hybrid_time import HybridTime
    from yugabyte_tpu.docdb.value import Value
    from yugabyte_tpu.storage.db import DB, DBOptions
    from tests.test_storage import key_for, ht

    cache = DeviceSlabCache()
    ns = os.path.abspath(str(tmp_path / "db"))
    db = DB(str(tmp_path / "db"),
            DBOptions(block_entries=128, auto_compact=False,
                      device_cache=cache,
                      retention_policy=lambda: HybridTime.kMax.value))
    for gen in range(4):
        for r in range(60):
            db.write_batch([(key_for(r), ht(1000 * (gen + 1)),
                             Value(primitive=f"g{gen}").encode())])
        db.flush()
    in_fids = [fm.file_id for fm in db.versions.live_files()]
    assert all(cache.contains((ns, fid)) for fid in in_fids)
    db.compact_all()
    # obsolete-file deletion dropped every input slab
    for fid in in_fids:
        assert not cache.contains((ns, fid))
    live_id = db.versions.live_files()[0].file_id
    assert cache.contains((ns, live_id))
    db.close()
    # DB close frees the whole namespace's residency
    assert not cache.contains((ns, live_id))
    assert cache.snapshot()["used_bytes"] == 0


# ---------------------------------------------------------------------------
# device-fault fallback: coherent cache, zero leaked pins


@pytest.mark.parametrize("site", ["dispatch", "result"])
def test_fault_fallback_cache_coherent_zero_pins(tmp_path, site):
    """A chained job under an injected persistent device fault completes
    natively (byte-identical), drops any partially installed output
    entries, keeps the INPUT slabs resident, and leaks zero pins."""
    rng = np.random.default_rng(25)
    runs = [_mk_run(rng, 600, 400) for _ in range(2)]
    readers = _write_runs(str(tmp_path), runs)
    cache = DeviceSlabCache(device=_device())
    for fid, r in zip((0, 1), readers):
        cache.stage(fid, r.read_all())

    device_faults.arm("runtime", site=site, count=100)  # persistent
    try:
        res = _run_chain_job(readers, str(tmp_path / "out"), cache, [0, 1])
    finally:
        device_faults.disarm_all()
    assert res.outputs, "fallback produced no outputs"
    # native fallback wrote the files; no output entry may be resident
    # (the device attempt's partials were deleted + dropped)
    for fid, _p, _props in res.outputs:
        assert not cache.contains(fid), \
            "cache entry survived for a deleted partial output"
    assert cache.pinned_count() == 0, "leaked pins after fault fallback"
    assert cache.contains(0) and cache.contains(1), \
        "input slabs were dropped by the fallback"
    # byte-identity with the pure-native job
    os.makedirs(str(tmp_path / "ref"))
    ids = iter(range(700, 800))
    ref = compaction_mod.run_compaction_job(
        readers, str(tmp_path / "ref"), lambda: next(ids), CUTOFF, True,
        device="native")
    assert _sst_bytes(res.outputs) == _sst_bytes(ref.outputs)
    for r in readers:
        r.close()


# ---------------------------------------------------------------------------
# scans over resident slabs


def test_scan_over_resident_slabs_matches_and_skips_decode(tmp_path):
    """A DB scan whose SSTs are cache-resident filters the resident
    matrix: results identical to the decode path, and only the blocks
    holding survivors are decoded (a narrow range touches ~1 block, not
    the whole file)."""
    from yugabyte_tpu.common.hybrid_time import HybridTime
    from yugabyte_tpu.docdb.value import Value
    from yugabyte_tpu.storage.db import DB, DBOptions
    from tests.test_storage import key_for, ht

    cache = DeviceSlabCache()
    opts = DBOptions(block_entries=64, auto_compact=False,
                     device_cache=cache,
                     retention_policy=lambda: HybridTime.kMax.value)
    db = DB(str(tmp_path / "db"), opts)
    n = 512
    for r in range(n):
        db.write_batch([(key_for(r), ht(1000 + r),
                         Value(primitive=r).encode())])
    db.flush()

    read_ht = HybridTime.kMax.value - 1
    full = list(db.scan_visible(read_ht))
    assert len(full) == n

    # narrow range over the resident file: only the survivor blocks
    # (block_entries=64 -> one or two of 8 blocks) decode
    blocks0 = _block_decode_counter().value()
    lo, hi = key_for(100), key_for(120)
    narrow = list(db.scan_visible(read_ht, lower_key=lo, upper_key=hi))
    decoded = _block_decode_counter().value() - blocks0
    assert [k for k, _v, _ht in narrow] == \
        sorted(k for k, _v, _ht in full if lo <= k < hi)
    assert 0 < decoded <= 2, \
        f"narrow resident scan decoded {decoded} blocks (expected <= 2)"

    # uncached reference: same results
    cache.drop_namespace(os.path.abspath(str(tmp_path / "db")))
    narrow2 = list(db.scan_visible(read_ht, lower_key=lo, upper_key=hi))
    assert narrow == narrow2
    db.close()


def test_flush_fed_chain_job_stays_resident_and_takes_the_pallas_merge(
        tmp_path, monkeypatch):
    """The chain every tablet of a write-heavy table runs: four runs
    through write_batch_columns + flush() on a fresh DB (each flush writes
    its slab through to the tserver's shared slab cache), then ONE
    compact_all(). Steered as the benchmark's rehearsal steers the CPU
    backend (a cold bucket goes to the device, the merge is the Pallas
    kernel): the job is a device decision and a Pallas merge, finds all
    four inputs resident, decodes no block, misses no slab, and its output
    is byte-identical to the native job over the same flushed files."""
    from yugabyte_tpu.storage import DB, DBOptions, bucket_health
    from yugabyte_tpu.storage.compaction import run_compaction_job
    from yugabyte_tpu.storage.sst import BlockCache, data_file_name
    from yugabyte_tpu.utils.metrics import kernel_metrics, \
        pipeline_stage_totals

    monkeypatch.setenv("YBTPU_MERGE_IMPL", "pallas")
    monkeypatch.setattr(bucket_health, "_on_tpu", lambda: True)
    monkeypatch.setattr(flags, "get_flag",
                        lambda name, _real=flags.get_flag:
                        0.0 if name == "shadow_verify_sample"
                        else _real(name))
    bucket_health.health_board().reset()
    rng = np.random.default_rng(23)
    n, key_space = 1 << 11, 1 << 12
    batches = []
    for g in range(4):
        keys = [b"S" + b"user%08d" % k + b"\x00\x00!" + b"K\x00\x01"
                for k in rng.integers(0, key_space, size=n).tolist()]
        ht = ((np.uint64(1_000_000 * (g + 1))
               + rng.permutation(n).astype(np.uint64)) << np.uint64(12))
        values = [b"S" + bytes(rng.integers(97, 123, size=24,
                                            dtype=np.uint8)) + b"\x00\x00"
                  for _ in range(n)]
        batches.append((keys, ht, np.zeros(n, np.uint32), values))

    def write_runs(db):
        for g, (keys, ht, wid, values) in enumerate(batches):
            db.write_batch_columns(keys, ht, wid, values, op_id=(1, g + 1))
            db.flush()

    plain = DB(str(tmp_path / "plain"), DBOptions(auto_compact=False))
    write_runs(plain)
    readers = [SSTReader(fm.path) for fm in plain.versions.live_files()]
    ids = iter(range(1000, 2000))
    os.makedirs(tmp_path / "native_out")
    native = run_compaction_job(readers, str(tmp_path / "native_out"),
                                lambda: next(ids), CUTOFF, True,
                                device="native")
    for r in readers:
        r.close()
    plain.close()

    cache = DeviceSlabCache(_device())
    block_cache = BlockCache(64 << 20)
    counters = offload_policy._offload_counters()
    pallas = kernel_metrics().counter("kernel_pallas_merge_total", "")
    for chain in range(2):         # the second with everything compiled
        db = DB(str(tmp_path / f"chain{chain}"), DBOptions(
            offload_policy=bucket_health.health_board(), device=_device(),
            device_cache=cache, block_cache=block_cache,
            retention_policy=lambda: CUTOFF, auto_compact=False))
        write_runs(db)
        inputs = list(db.versions.live_files())
        assert len(inputs) == 4
        assert all(db._device_cache.contains(fm.file_id) for fm in inputs)
        before = (counters["device"].value(), counters["native"].value(),
                  pallas.value(), _block_decode_counter().value(),
                  cache.misses)
        stages = dict(pipeline_stage_totals())
        db.compact_all()
        assert db.background_error is None
        after = (counters["device"].value(), counters["native"].value(),
                 pallas.value(), _block_decode_counter().value(),
                 cache.misses)
        assert tuple(a - b for a, b in zip(after, before)) \
            == (1, 0, 1, 0, 0)
        moved = pipeline_stage_totals()
        for stage in ("raw_read", "raw_parse", "decode"):
            assert moved.get(stage, 0.0) == stages.get(stage, 0.0), stage
        outs = [fm.path for fm in db.versions.live_files()]
        assert len(outs) == len(native.outputs)
        for mine, (_fid, theirs, _props) in zip(outs, native.outputs):
            with open(data_file_name(mine), "rb") as a, \
                    open(data_file_name(theirs), "rb") as b:
                assert a.read() == b.read()
        db.close()
    bucket_health.health_board().reset()
