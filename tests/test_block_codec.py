"""Device SST block codec (ops/block_codec.py): differential byte-identity
vs the host codec (block_format.decode_block/encode_block via the native
shell), typed corruption handling, and device-fault containment.

The contract under test:
  - device decode of raw block bytes produces the EXACT StagedCols matrix
    stage_slab(read_all()) builds — bit for bit, including the column
    stats — across block sizes, key widths, TTL mixes, compression,
    empty/single-entry blocks and max-width keys;
  - a codec-driven compaction writes files byte-identical (data AND base)
    to the shell-driven device-native job;
  - corrupt blocks surface typed Status.Corruption before anything
    uploads — never wrong bytes;
  - device faults at the dispatch/result sites quarantine the shape
    bucket and complete byte-identically via the native merge with zero
    leaked pins and zero outstanding staging leases; a transient result
    fault retries once and stays on device.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_run_merge import _make_run  # noqa: E402

from yugabyte_tpu.ops import block_codec, device_faults  # noqa: E402
from yugabyte_tpu.ops.merge_gc import stage_slab  # noqa: E402
from yugabyte_tpu.ops.slabs import ValueArray, gather_metrics  # noqa: E402
from yugabyte_tpu.storage import block_format  # noqa: E402
from yugabyte_tpu.storage import compaction as compaction_mod  # noqa: E402
from yugabyte_tpu.storage import integrity  # noqa: E402,F401 (flag defs)
from yugabyte_tpu.storage import offload_policy  # noqa: E402
from yugabyte_tpu.storage.device_cache import (DeviceSlabCache,  # noqa: E402
                                               host_staging_pool)
from yugabyte_tpu.storage.sst import (Frontier, SSTReader,  # noqa: E402
                                      SSTWriter, _block_decode_counter)
from yugabyte_tpu.utils import flags  # noqa: E402
from yugabyte_tpu.utils.status import Code, StatusError  # noqa: E402

CUTOFF = (10_000_000 << 12)


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    monkeypatch.setenv("YBTPU_DEVICE_CODEC", "1")
    device_faults.disarm_all()
    offload_policy.bucket_quarantine().clear()
    yield
    device_faults.disarm_all()
    offload_policy.bucket_quarantine().clear()


def _device():
    import jax
    return jax.devices()[0]


def _mk_run(rng, n, key_space, value_bytes=16, ttl_frac=0.0, w=3):
    slab = _make_run(rng, n, key_space, ttl_frac=ttl_frac, w=w)
    data = rng.integers(0, 256, size=n * value_bytes, dtype=np.uint8)
    offs = np.arange(n + 1, dtype=np.int64) * value_bytes
    slab.values = ValueArray(data, offs)
    return slab


def _write_runs(workdir, runs, block_entries=None):
    readers = []
    for i, slab in enumerate(runs):
        p = os.path.join(workdir, f"in{i:03d}.sst")
        SSTWriter(p, block_entries=block_entries).write(slab, Frontier())
        readers.append(SSTReader(p))
    return readers


def _run_job(readers, out_dir, cache=None, input_ids=None, first_id=100,
             is_major=True, prestage=False, cancel_token=None):
    os.makedirs(out_dir, exist_ok=True)
    if cache is None:
        cache = DeviceSlabCache(device=_device())
    if input_ids is None:
        input_ids = list(range(len(readers)))
    if prestage:
        for fid, r in zip(input_ids, readers):
            cache.stage(fid, r.read_all())
    ids = iter(range(first_id, first_id + 500))
    return compaction_mod.run_compaction_job_device_native(
        readers, out_dir, lambda: next(ids), CUTOFF, is_major,
        device=_device(), device_cache=cache, input_ids=input_ids,
        cancel=cancel_token)


def _file_bytes(outputs):
    out = []
    for _fid, base_path, _props in outputs:
        with open(base_path + ".sblock.0", "rb") as f:
            data = f.read()
        with open(base_path, "rb") as f:
            base = f.read()
        out.append((data, base))
    return out


# ---------------------------------------------------------------- decode


@pytest.mark.parametrize("n,block_entries,ttl_frac,w", [
    (700, 128, 0.0, 3),       # multi-block
    (700, 4096, 0.3, 3),      # single block + TTL entries
    (1, 64, 0.0, 3),          # single-entry file
    (129, 1, 0.0, 3),         # one entry per block (restart-interval floor)
    (350, 100, 0.0, 7),       # wide keys
])
def test_decode_matches_host_staging(tmp_path, n, block_entries,
                                     ttl_frac, w):
    """Device decode of raw block bytes == stage_slab over the host
    decode path, bit for bit (cols, stats, shape bucket)."""
    rng = np.random.default_rng(31)
    slab = _mk_run(rng, n, max(2, n // 2), ttl_frac=ttl_frac, w=w)
    [r] = _write_runs(str(tmp_path), [slab], block_entries=block_entries)
    ref = stage_slab(r.read_all())
    blocks0 = _block_decode_counter().value()
    rfb = block_codec.parse_raw_file(r.read_raw(), r.block_handles)
    st = block_codec.decode_file_to_staged(rfb, _device())
    assert _block_decode_counter().value() == blocks0, \
        "device decode touched the host decode path"
    assert (st.n, st.n_pad, st.w) == (ref.n, ref.n_pad, ref.w)
    assert np.array_equal(np.asarray(st.cols_dev), np.asarray(ref.cols_dev))
    assert np.array_equal(st.col_const, ref.col_const)
    assert np.array_equal(st.col_first, ref.col_first)
    assert np.array_equal(st.sort_rows, ref.sort_rows)
    assert st.n_sort == ref.n_sort
    # zero-copy values match the decoded rows
    want = r.read_all()
    got = rfb.values
    assert len(got) == want.n
    assert all(got[i] == want.values[int(want.value_idx[i])]
               for i in range(want.n))
    r.close()


def test_decode_max_width_keys(tmp_path):
    """Keys that exactly fill the stride (no zero pad in the final
    word) decode identically."""
    rng = np.random.default_rng(32)
    slab = _mk_run(rng, 200, 80, w=3)
    slab.key_len[:] = 12            # every key exactly w*4 bytes
    slab.doc_key_len[:] = 12
    [r] = _write_runs(str(tmp_path), [slab], block_entries=64)
    ref = stage_slab(r.read_all())
    rfb = block_codec.parse_raw_file(r.read_raw(), r.block_handles)
    st = block_codec.decode_file_to_staged(rfb, _device())
    assert np.array_equal(np.asarray(st.cols_dev), np.asarray(ref.cols_dev))
    r.close()


def test_decode_compressed_blocks(tmp_path):
    """zlib-compressed blocks: host decompress (C speed) + device
    decode, still bit-identical."""
    rng = np.random.default_rng(33)
    slab = _mk_run(rng, 500, 200)
    old = flags.get_flag("sst_compression")
    flags.set_flag("sst_compression", "zlib")
    try:
        [r] = _write_runs(str(tmp_path), [slab], block_entries=128)
    finally:
        flags.set_flag("sst_compression", old)
    ref = stage_slab(r.read_all())
    rfb = block_codec.parse_raw_file(r.read_raw(), r.block_handles)
    st = block_codec.decode_file_to_staged(rfb, _device())
    assert np.array_equal(np.asarray(st.cols_dev), np.asarray(ref.cols_dev))
    r.close()


def test_decode_empty_file_unsupported(tmp_path):
    rfb = block_codec.RawFileBlocks(
        n=0, w=1, counts=np.zeros(0, dtype=np.int64),
        strides_w=np.zeros(0, dtype=np.int64), bodies=[],
        value_parts=[])
    with pytest.raises(block_codec.BlockCodecUnsupported):
        block_codec.decode_file_to_staged(rfb, _device())


def test_corrupt_crc_raises_typed_corruption(tmp_path):
    """A flipped byte in a block surfaces Status.Corruption from the raw
    parse — BEFORE anything uploads; never wrong bytes."""
    rng = np.random.default_rng(34)
    slab = _mk_run(rng, 300, 120)
    [r] = _write_runs(str(tmp_path), [slab], block_entries=64)
    with open(r.data_path, "rb") as f:
        raw = bytearray(f.read())
    off, size, _cnt = r.block_handles[1]
    raw[off + block_format.HEADER_BYTES + 5] ^= 0x40   # body byte flip
    with pytest.raises(StatusError) as ei:
        block_codec.parse_raw_file(bytes(raw), r.block_handles)
    assert ei.value.status.code == Code.CORRUPTION
    # magic corruption too
    raw2 = bytearray(raw)
    raw2[off + block_format.HEADER_BYTES + 5] ^= 0x40  # restore body
    raw2[off] ^= 0xFF                                  # break the magic
    with pytest.raises(StatusError) as ei2:
        block_codec.parse_raw_file(bytes(raw2), r.block_handles)
    assert ei2.value.status.code == Code.CORRUPTION
    r.close()


@pytest.mark.requires_native("compaction_engine")
def test_corrupt_input_fails_job_without_fallback(tmp_path):
    """Corruption is NOT a device fault: the codec job surfaces it typed
    instead of silently completing via the native merge."""
    rng = np.random.default_rng(35)
    runs = [_mk_run(rng, 300, 150) for _ in range(2)]
    readers = _write_runs(str(tmp_path), runs, block_entries=64)
    with open(readers[0].data_path, "r+b") as f:
        off, size, _ = readers[0].block_handles[0]
        f.seek(off + block_format.HEADER_BYTES + 3)
        b = f.read(1)
        f.seek(off + block_format.HEADER_BYTES + 3)
        f.write(bytes([b[0] ^ 0x10]))
    with pytest.raises(StatusError) as ei:
        _run_job(readers, str(tmp_path / "out"))
    assert ei.value.status.code == Code.CORRUPTION
    for r in readers:
        r.close()


# ---------------------------------------------------------------- encode


@pytest.mark.requires_native("compaction_engine")
@pytest.mark.parametrize("compress", [False, True])
def test_codec_job_byte_identical_to_shell(tmp_path, compress):
    """The codec-driven compaction == the shell-driven device-native job
    over the same inputs: data files AND base files (incl. the learned
    index and bloom/index blocks), across a multi-file split."""
    rng = np.random.default_rng(36)
    runs = [_mk_run(rng, 900, 3000, ttl_frac=0.2) for _ in range(3)]
    old_comp = flags.get_flag("sst_compression")
    old_split = flags.get_flag("compaction_max_output_entries_per_sst")
    old_shadow = flags.get_flag("shadow_verify_sample")
    flags.set_flag("sst_compression", "zlib" if compress else "none")
    flags.set_flag("compaction_max_output_entries_per_sst", 700)
    flags.set_flag("shadow_verify_sample", 0.0)
    try:
        readers = _write_runs(str(tmp_path), runs)
        res = _run_job(readers, str(tmp_path / "codec"), is_major=False)
        os.environ["YBTPU_DEVICE_CODEC"] = "0"
        ref = _run_job(readers, str(tmp_path / "shell"), is_major=False,
                       prestage=True)
    finally:
        os.environ["YBTPU_DEVICE_CODEC"] = "1"
        flags.set_flag("sst_compression", old_comp)
        flags.set_flag("compaction_max_output_entries_per_sst", old_split)
        flags.set_flag("shadow_verify_sample", old_shadow)
    assert len(res.outputs) >= 2, "expected a multi-file split"
    assert res.rows_out == ref.rows_out
    assert res.rows_in == ref.rows_in
    assert res.tombstones_written == ref.tombstones_written
    assert _file_bytes(res.outputs) == _file_bytes(ref.outputs)
    for r in readers:
        r.close()


@pytest.mark.requires_native("compaction_engine")
def test_codec_counters_and_flat_host_decode(tmp_path):
    """A codec job moves ONLY the device codec counters: host block
    decode and shell ingest stay flat; device decode/encode counters
    increment; a shell job increments the encode fallback counter."""
    rng = np.random.default_rng(37)
    runs = [_mk_run(rng, 400, 200) for _ in range(2)]
    readers = _write_runs(str(tmp_path), runs, block_entries=100)
    old_shadow = flags.get_flag("shadow_verify_sample")
    old_digest = flags.get_flag("resident_digest_sample")
    flags.set_flag("shadow_verify_sample", 0.0)
    flags.set_flag("resident_digest_sample", 0.0)
    cm = block_codec.codec_metrics()
    try:
        b0 = _block_decode_counter().value()
        i0 = compaction_mod._ingest_decode_counter().value()
        d0 = cm["decode_blocks"].value()
        e0 = cm["encode_blocks"].value()
        f0 = cm["encode_fallbacks"].value()
        _run_job(readers, str(tmp_path / "codec"))
        assert _block_decode_counter().value() == b0
        assert compaction_mod._ingest_decode_counter().value() == i0
        assert cm["decode_blocks"].value() == d0 + 8  # 2 files x 4 blocks
        assert cm["encode_blocks"].value() > e0
        assert cm["encode_fallbacks"].value() == f0
        os.environ["YBTPU_DEVICE_CODEC"] = "0"
        _run_job(readers, str(tmp_path / "shell"), prestage=True,
                 first_id=700)
        assert cm["encode_fallbacks"].value() == f0 + 1
    finally:
        os.environ["YBTPU_DEVICE_CODEC"] = "1"
        flags.set_flag("shadow_verify_sample", old_shadow)
        flags.set_flag("resident_digest_sample", old_digest)
    for r in readers:
        r.close()


# ------------------------------------------------- device-fault containment


@pytest.mark.requires_native("compaction_engine")
@pytest.mark.parametrize("site", ["dispatch", "result"])
def test_persistent_fault_falls_back_byte_identical(tmp_path, site):
    """A persistent device fault in the codec path quarantines the shape
    bucket, completes via the native merge byte-identically, does not
    re-fault the next job (pre-dispatch native routing), and leaks zero
    pins and zero staging leases."""
    rng = np.random.default_rng(38)
    runs = [_mk_run(rng, 500, 250) for _ in range(2)]
    readers = _write_runs(str(tmp_path), runs)
    cache = DeviceSlabCache(device=_device())
    fb0 = compaction_mod._storage_fallback_counter().value()

    device_faults.arm("runtime", site=site, count=100)  # persistent
    try:
        res = _run_job(readers, str(tmp_path / "out"), cache=cache)
    finally:
        device_faults.disarm_all()
    assert res.outputs, "fallback produced no outputs"
    assert compaction_mod._storage_fallback_counter().value() == fb0 + 1
    assert cache.pinned_count() == 0, "leaked pins after fault fallback"
    assert host_staging_pool().outstanding() == 0
    for fid, _p, _props in res.outputs:
        assert not cache.contains(fid), \
            "cache entry survived for a deleted partial output"
    # quarantined: the NEXT job routes native pre-dispatch, no re-fault
    assert offload_policy.bucket_quarantine().snapshot()
    device_faults.arm("runtime", site=site, count=100)
    try:
        res2 = _run_job(readers, str(tmp_path / "out2"), cache=cache,
                        first_id=300)
    finally:
        device_faults.disarm_all()
    assert compaction_mod._storage_fallback_counter().value() == fb0 + 1, \
        "quarantined bucket re-entered the device path"
    # byte-identity with the pure-native job (data files: the native
    # reference carries no learned index, so base files legitimately
    # differ by the advisory model)
    os.makedirs(str(tmp_path / "ref"))
    ids = iter(range(500, 600))
    ref = compaction_mod.run_compaction_job(
        readers, str(tmp_path / "ref"), lambda: next(ids), CUTOFF, True,
        device="native")
    assert [d for d, _b in _file_bytes(res.outputs)] == \
        [d for d, _b in _file_bytes(ref.outputs)]
    assert [d for d, _b in _file_bytes(res2.outputs)] == \
        [d for d, _b in _file_bytes(ref.outputs)]
    for r in readers:
        r.close()


@pytest.mark.requires_native("compaction_engine")
def test_transient_decode_fault_retries_and_stays_on_device(tmp_path):
    """count=1 result fault fires at the decode download: the codec
    retries the launch once and the job completes WITHOUT the native
    fallback."""
    rng = np.random.default_rng(39)
    runs = [_mk_run(rng, 400, 200) for _ in range(2)]
    readers = _write_runs(str(tmp_path), runs)
    from yugabyte_tpu.ops.run_merge import _chunk_retry_counter
    r0 = _chunk_retry_counter().value()
    fb0 = compaction_mod._storage_fallback_counter().value()
    device_faults.arm("runtime", site="result", count=1)
    res = _run_job(readers, str(tmp_path / "out"))
    assert device_faults.armed_count() == 0, "fault must have fired"
    assert _chunk_retry_counter().value() == r0 + 1
    assert compaction_mod._storage_fallback_counter().value() == fb0, \
        "retry succeeded: no native fallback"
    assert not offload_policy.bucket_quarantine().snapshot()
    assert res.outputs
    for r in readers:
        r.close()


@pytest.mark.requires_native("compaction_engine")
def test_cancel_mid_codec_stage_c_sweeps_partials(tmp_path, monkeypatch):
    """Cancellation between codec span writes sweeps the already-written
    files and leaks nothing."""
    from yugabyte_tpu.utils.cancellation import (CancellationToken,
                                                 OperationCancelled)
    rng = np.random.default_rng(40)
    runs = [_mk_run(rng, 900, 4000) for _ in range(2)]
    readers = _write_runs(str(tmp_path), runs)
    old = flags.get_flag("compaction_max_output_entries_per_sst")
    flags.set_flag("compaction_max_output_entries_per_sst", 500)
    token = CancellationToken("test-job")
    orig = compaction_mod._DeviceCodecWriter._write_span

    def tripping(self, surv, mk, start, end, more_coming):
        orig(self, surv, mk, start, end, more_coming)
        token.cancel("mid-job shutdown")

    monkeypatch.setattr(compaction_mod._DeviceCodecWriter, "_write_span",
                        tripping)
    out_dir = str(tmp_path / "out")
    try:
        with pytest.raises(OperationCancelled):
            _run_job(readers, out_dir, cancel_token=token)
    finally:
        flags.set_flag("compaction_max_output_entries_per_sst", old)
    leftovers = os.listdir(out_dir) if os.path.isdir(out_dir) else []
    assert not leftovers, f"partial outputs leaked: {leftovers}"
    assert host_staging_pool().outstanding() == 0
    for r in readers:
        r.close()


@pytest.mark.requires_native("compaction_engine")
def test_codec_job_native_gather_matches_numpy_fallback(tmp_path,
                                                        monkeypatch):
    """The same job through _device_codec_body with the survivors' values
    copied by the native library and by the numpy fallback writes
    byte-identical files; the native run counts exactly its survivors."""
    rng = np.random.default_rng(38)
    runs = []
    for _ in range(3):
        slab = _make_run(rng, 900, 3000, ttl_frac=0.2)
        lens = rng.choice([1, 9, 41, 44, 47], size=slab.n).astype(np.int64)
        offs = np.zeros(slab.n + 1, dtype=np.int64)
        np.cumsum(lens, out=offs[1:])
        slab.values = ValueArray(
            rng.integers(0, 256, size=int(offs[-1]), dtype=np.uint8), offs)
        runs.append(slab)
    old_split = flags.get_flag("compaction_max_output_entries_per_sst")
    old_shadow = flags.get_flag("shadow_verify_sample")
    flags.set_flag("compaction_max_output_entries_per_sst", 700)
    flags.set_flag("shadow_verify_sample", 0.0)
    gm = gather_metrics()
    cm = block_codec.codec_metrics()
    try:
        readers = _write_runs(str(tmp_path), runs)
        n0, f0 = gm["native_rows"].value(), gm["fallback_rows"].value()
        e0 = cm["encode_fallbacks"].value()
        res = _run_job(readers, str(tmp_path / "native"), is_major=False)
        assert gm["native_rows"].value() - n0 == res.rows_out
        assert gm["fallback_rows"].value() == f0
        n1 = gm["native_rows"].value()
        monkeypatch.setattr(ValueArray, "gather", ValueArray._gather_numpy)
        ref = _run_job(readers, str(tmp_path / "numpy"), is_major=False)
        assert gm["native_rows"].value() == n1
        assert cm["encode_fallbacks"].value() == e0   # both took the codec
    finally:
        flags.set_flag("compaction_max_output_entries_per_sst", old_split)
        flags.set_flag("shadow_verify_sample", old_shadow)
    assert len(res.outputs) >= 2, "expected a multi-file split"
    assert res.tombstones_written == ref.tombstones_written > 0
    assert _file_bytes(res.outputs) == _file_bytes(ref.outputs)
    for r in readers:
        r.close()
