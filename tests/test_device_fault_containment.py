"""Device-offload failure containment (PR: robustness).

A fault in the stage-B kernel path of the compaction pipeline — XLA
compile error at dispatch, RESOURCE_EXHAUSTED/HBM OOM, or an async
runtime fault surfacing at decision download — must never corrupt the
writer or fail the job:

  - a transient fault gets ONE per-chunk retry and the job completes on
    the device path;
  - a persistent fault falls back mid-job to the native merge with
    output BYTE-IDENTICAL to a pure-native run, and the failing shape
    bucket is quarantined native-only (with timed decay);
  - cancellation (DB shutdown / tablet FAILED) aborts the in-flight
    pipeline at a stage boundary, deletes partial outputs and releases
    every HostStagingPool lease.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_run_merge import _make_run  # noqa: E402

from yugabyte_tpu.ops import device_faults, run_merge  # noqa: E402
from yugabyte_tpu.ops.slabs import ValueArray  # noqa: E402
from yugabyte_tpu.storage import compaction as compaction_mod  # noqa: E402
from yugabyte_tpu.storage import offload_policy  # noqa: E402
from yugabyte_tpu.storage.device_cache import (DeviceSlabCache,  # noqa: E402
                                               host_staging_pool)
from yugabyte_tpu.storage.sst import Frontier, SSTReader, SSTWriter  # noqa: E402
from yugabyte_tpu.utils import flags  # noqa: E402
from yugabyte_tpu.utils.cancellation import (CancellationToken,  # noqa: E402
                                             OperationCancelled)

CUTOFF = (10_000_000 << 12)

pytestmark = pytest.mark.requires_native("compaction_engine")


@pytest.fixture(autouse=True)
def _clean_fault_state():
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


def _sst_bytes(outputs):
    out = []
    for _fid, base_path, _props in outputs:
        with open(base_path + ".sblock.0", "rb") as f:
            out.append(f.read())
    return out


def _run_device_native(readers, out_dir, first_id=100, cancel=None):
    os.makedirs(out_dir, exist_ok=True)
    cache = DeviceSlabCache(device=_device())
    ids = list(range(len(readers)))
    for fid, r in zip(ids, readers):
        cache.stage(fid, r.read_all())
    gen = iter(range(first_id, first_id + 500))
    return compaction_mod.run_compaction_job_device_native(
        readers, out_dir, lambda: next(gen), CUTOFF, True,
        device=_device(), device_cache=cache, input_ids=ids,
        cancel=cancel)


def _native_reference(readers, out_dir, first_id=100):
    os.makedirs(out_dir, exist_ok=True)
    gen = iter(range(first_id, first_id + 500))
    return compaction_mod.run_compaction_job(
        readers, out_dir, lambda: next(gen), CUTOFF, True,
        device="native")


@pytest.mark.parametrize("kind,site", [
    ("compile", "dispatch"),
    ("oom", "result"),
    ("runtime", "result"),
])
def test_persistent_device_fault_falls_back_byte_identical(
        tmp_path, kind, site):
    """A fault that survives the retry completes the job via the native
    merge — SSTs byte-identical to a pure-native run — and quarantines
    the shape bucket."""
    rng = np.random.default_rng(7)
    runs = [_mk_run(rng, 1200, 5000) for _ in range(4)]
    readers = _write_runs(str(tmp_path), runs)
    try:
        res_native = _native_reference(readers, str(tmp_path / "native"))
        fallbacks0 = compaction_mod._storage_fallback_counter().value()
        device_faults.arm(kind, site=site, count=100)  # persistent
        res_dev = _run_device_native(readers, str(tmp_path / "dev"))
        device_faults.disarm_all()
        assert res_dev.rows_out == res_native.rows_out
        assert _sst_bytes(res_dev.outputs) == _sst_bytes(res_native.outputs)
        assert compaction_mod._storage_fallback_counter().value() \
            == fallbacks0 + 1
        # the failing shape bucket is parked native-only...
        qkey = offload_policy.bucket_key(
            run_merge.packed_run_ns([r.props.n_entries for r in readers]))
        snap = offload_policy.bucket_quarantine().snapshot()
        assert [e for e in snap if tuple(e["bucket"]) == qkey], snap
        # ...so the NEXT job routes native pre-dispatch (still armed
        # faults would otherwise fire — they don't, proving no kernel
        # launch happened)
        device_faults.arm(kind, site=site, count=100)
        res_q = _run_device_native(readers, str(tmp_path / "dev2"),
                                   first_id=300)
        assert _sst_bytes(res_q.outputs) == _sst_bytes(res_native.outputs)
        assert compaction_mod._storage_fallback_counter().value() \
            == fallbacks0 + 1, "quarantined job must not re-fault"
    finally:
        for r in readers:
            r.close()


def test_transient_fault_retries_once_and_stays_on_device(
        tmp_path, monkeypatch):
    """count=1 fault at decision download: the per-chunk retry re-carves
    + re-dispatches and the job completes WITHOUT the native fallback."""
    monkeypatch.setenv("YBTPU_MERGE_CHUNK_ROWS", "2048")  # force chunking
    rng = np.random.default_rng(11)
    runs = [_mk_run(rng, 1500, 6000) for _ in range(4)]
    readers = _write_runs(str(tmp_path), runs)
    try:
        res_native = _native_reference(readers, str(tmp_path / "native"))
        from yugabyte_tpu.utils.metrics import kernel_metrics
        retries = kernel_metrics().counter(
            "kernel_chunk_retry_total",
            "per-chunk kernel retries after a device fault")
        r0 = retries.value()
        fallbacks0 = compaction_mod._storage_fallback_counter().value()
        device_faults.arm("runtime", site="result", count=1)
        res_dev = _run_device_native(readers, str(tmp_path / "dev"))
        assert device_faults.armed_count() == 0, "fault must have fired"
        assert retries.value() == r0 + 1
        assert compaction_mod._storage_fallback_counter().value() \
            == fallbacks0, "retry succeeded: no native fallback"
        assert _sst_bytes(res_dev.outputs) == _sst_bytes(res_native.outputs)
        assert not offload_policy.bucket_quarantine().snapshot()
    finally:
        for r in readers:
            r.close()


def test_cancellation_aborts_pipeline_cleanly(tmp_path):
    """A cancelled job raises OperationCancelled, leaves NO partial
    output files and NO outstanding staging leases."""
    rng = np.random.default_rng(3)
    runs = [_mk_run(rng, 1200, 5000) for _ in range(4)]
    readers = _write_runs(str(tmp_path), runs)
    out_dir = str(tmp_path / "out")
    token = CancellationToken("test-job")
    token.cancel("test shutdown")
    try:
        with pytest.raises(OperationCancelled):
            _run_device_native(readers, out_dir, cancel=token)
        produced = [f for f in os.listdir(out_dir)] \
            if os.path.isdir(out_dir) else []
        assert not produced, f"partial outputs leaked: {produced}"
        assert host_staging_pool().outstanding() == 0
    finally:
        for r in readers:
            r.close()


def test_cancellation_mid_stage_c(tmp_path, monkeypatch):
    """Cancel DURING stage C (between chunk feeds): the already-written
    span files are swept by the attempt's unwind."""
    # trips the token from inside the SHELL's streaming writer — pin the
    # device codec off (its own mid-stage-C sweep is covered by
    # tests/test_block_codec.py)
    monkeypatch.setenv("YBTPU_DEVICE_CODEC", "0")
    monkeypatch.setenv("YBTPU_MERGE_CHUNK_ROWS", "2048")
    old = flags.get_flag("compaction_max_output_entries_per_sst")
    flags.set_flag("compaction_max_output_entries_per_sst", 800)
    rng = np.random.default_rng(5)
    runs = [_mk_run(rng, 1500, 6000) for _ in range(4)]
    readers = _write_runs(str(tmp_path), runs)
    out_dir = str(tmp_path / "out")
    token = CancellationToken("test-job")

    # trip the token from inside the pipeline: the first span write
    # cancels, so the NEXT boundary check aborts mid-job
    orig_write = compaction_mod._StreamingNativeWriter._write_span

    def tripping_write(self, start, end, more_coming):
        orig_write(self, start, end, more_coming)
        token.cancel("mid-job failure")

    monkeypatch.setattr(compaction_mod._StreamingNativeWriter,
                        "_write_span", tripping_write)
    try:
        with pytest.raises(OperationCancelled):
            _run_device_native(readers, out_dir, cancel=token)
        leftovers = [f for f in os.listdir(out_dir)] \
            if os.path.isdir(out_dir) else []
        assert not leftovers, f"partial outputs leaked: {leftovers}"
        assert host_staging_pool().outstanding() == 0
    finally:
        flags.set_flag("compaction_max_output_entries_per_sst", old)
        for r in readers:
            r.close()


def test_quarantine_timed_decay():
    q = offload_policy.BucketQuarantine()
    q.quarantine((4, 2048), reason="test", ttl_s=0.05)
    assert q.is_quarantined((4, 2048))
    assert not q.is_quarantined((8, 2048))
    import time
    time.sleep(0.08)
    assert not q.is_quarantined((4, 2048)), "window must decay"
    assert q.snapshot() == []


def test_db_close_cancels_inflight_token(tmp_path):
    """DB.close trips the cancellation seam; retry_background_work after
    a tablet-level cancel re-arms it."""
    from yugabyte_tpu.storage.db import DB, DBOptions
    db = DB(str(tmp_path / "db"), DBOptions(auto_compact=False))
    assert not db._cancel.cancelled
    db.cancel_background_work("tablet failed")
    assert db._cancel.cancelled
    assert db.retry_background_work()
    assert not db._cancel.cancelled, "recovery must re-arm the token"
    db.close()
    assert db._cancel.cancelled
