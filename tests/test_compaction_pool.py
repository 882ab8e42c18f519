"""Mesh-sharded compaction pool: multi-tablet differential suite.

N tablets compacted concurrently through the pool must be byte-identical
to sequential single-device runs; the scheduler must stay fair under a
saturating tablet; cancellation mid-job sweeps partial outputs with zero
leaked pins; a device fault in one wave quarantines the bucket and
completes every co-scheduled job natively instead of aborting them.
"""

import glob
import os
import threading
import time

import numpy as np
import pytest
import jax

from yugabyte_tpu.integration.synth import (attach_values, split_runs,
                                            synth_ycsb_runs)
from yugabyte_tpu.ops import device_faults
from yugabyte_tpu.ops.merge_gc import GCParams
from yugabyte_tpu.parallel.mesh import make_mesh
from yugabyte_tpu.storage import offload_policy
from yugabyte_tpu.storage.compaction import run_compaction_job
from yugabyte_tpu.storage.device_cache import (DeviceSlabCache,
                                               NamespacedSlabCache)
from yugabyte_tpu.storage.sst import (Frontier, SSTReader, SSTWriter,
                                      data_file_name)
from yugabyte_tpu.tserver.compaction_pool import CompactionPool, PoolRequest
from yugabyte_tpu.utils import flags
from yugabyte_tpu.utils.cancellation import (CancellationToken,
                                             OperationCancelled)

CUTOFF = 10_000_000 << 12


@pytest.fixture
def pool():
    p = CompactionPool(make_mesh(8))
    yield p
    p.shutdown()
    device_faults.disarm_all()
    offload_policy.bucket_quarantine().clear()


def _write_tablet_inputs(tmp_path, tag, n=12000, k=4, seed=0):
    slab, offsets = synth_ycsb_runs(n, k, n // 2, seed=seed)
    attach_values(slab, 16)
    runs = split_runs(slab, offsets)
    d = tmp_path / tag
    d.mkdir()
    paths = []
    for i, sub in enumerate(runs):
        p = str(d / f"{i:06d}.sst")
        SSTWriter(p).write(sub, Frontier())
        paths.append(p)
    return paths


def _out_bytes(result):
    blobs = []
    for _fid, p, _props in result.outputs:
        with open(p, "rb") as f:
            blobs.append(f.read())
        with open(data_file_name(p), "rb") as f:
            blobs.append(f.read())
    return blobs


def _merge_jobs(n_jobs, n=16000, seed0=0):
    jobs = []
    for j in range(n_jobs):
        slab, offsets = synth_ycsb_runs(n, 4, n // 2, seed=seed0 + j)
        jobs.append(split_runs(slab, offsets))
    return jobs


def test_pool_differential_byte_identical(tmp_path, pool):
    """Concurrent pooled compactions == sequential single-device runs,
    byte for byte, with zero leaked pins and outputs resident-installed
    into each tablet's shard partition."""
    shared = DeviceSlabCache(jax.devices()[0], capacity_bytes=1 << 30)
    tablets = {f"t{t}": _write_tablet_inputs(tmp_path, f"in{t}", seed=t)
               for t in range(4)}
    handles = {}
    caches = {}
    for tid, paths in tablets.items():
        readers = [SSTReader(p) for p in paths]
        cache = pool.partition_for(shared, f"db-{tid}", tid)
        for fid, r in enumerate(readers):
            cache.stage(fid, r.read_all())
        caches[tid] = cache
        outd = tmp_path / f"pool_out_{tid}"
        outd.mkdir()
        ids = iter(range(100, 10_000))
        handles[tid] = (pool.submit(tid, PoolRequest(
            inputs=readers, out_dir=str(outd),
            new_file_id=lambda it=ids: next(it),
            history_cutoff_ht=CUTOFF, is_major=True,
            input_ids=list(range(len(readers))),
            device_cache=cache)), readers)
    results = {}
    for tid, (h, readers) in handles.items():
        results[tid] = h.result(timeout=300)
        for r in readers:
            r.close()
    assert shared.pinned_count() == 0, "leaked pins after pooled jobs"
    snap = pool.snapshot()
    assert snap["waves"] >= 1
    assert snap["wave_jobs"] >= 4
    # outputs installed into the per-shard partitions (resident chain
    # survives sharding) — at least the single-file outputs
    cache_snap = shared.snapshot()
    assert "shards" in cache_snap and cache_snap["entries"] > 0
    for tid, paths in tablets.items():
        readers = [SSTReader(p) for p in paths]
        outd = tmp_path / f"seq_out_{tid}"
        outd.mkdir()
        ids = iter(range(100, 10_000))
        res = run_compaction_job(readers, str(outd),
                                 lambda it=ids: next(it), CUTOFF, True,
                                 device=jax.devices()[0])
        for r in readers:
            r.close()
        assert res.rows_out == results[tid].rows_out, tid
        assert _out_bytes(res) == _out_bytes(results[tid]), \
            f"{tid}: pooled outputs differ from the sequential run"


def test_pool_fairness_under_saturation(pool):
    """A tablet saturating the queue must not starve a light tablet: the
    light tablet's jobs complete long before the heavy backlog drains."""
    heavy_jobs = _merge_jobs(24, n=8000)
    light_jobs = _merge_jobs(2, n=8000, seed0=100)
    heavy = [pool.submit("heavy", PoolRequest(
        inputs=[], out_dir="", new_file_id=None,
        history_cutoff_ht=CUTOFF, is_major=True, slabs=runs))
        for runs in heavy_jobs]
    light = [pool.submit("light", PoolRequest(
        inputs=[], out_dir="", new_file_id=None,
        history_cutoff_ht=CUTOFF, is_major=True, slabs=runs))
        for runs in light_jobs]
    for h in light:
        h.result(timeout=300)
    for h in heavy:
        h.result(timeout=600)
    light_last = max(h.finished_at for h in light)
    after_light = sum(1 for h in heavy if h.finished_at > light_last)
    # without fairness the light tablet (submitted last) would wait for
    # the entire heavy backlog; with deficit scheduling a healthy slice
    # of the heavy queue must still be pending when light completes
    assert after_light >= 8, after_light


def test_pool_merge_decisions_match_single_device(pool):
    """Merge-only pool jobs return the exact decisions of a sequential
    single-device launch over the same runs."""
    from yugabyte_tpu.ops import run_merge
    jobs = _merge_jobs(6, n=10000)
    handles = [pool.submit(f"t{i}", PoolRequest(
        inputs=[], out_dir="", new_file_id=None,
        history_cutoff_ht=CUTOFF, is_major=True, slabs=runs))
        for i, runs in enumerate(jobs)]
    for h, runs in zip(handles, jobs):
        surv, mk_surv = h.result(timeout=300)
        perm, keep, mk = run_merge.merge_and_gc_runs(
            runs, GCParams(CUTOFF, True))
        assert np.array_equal(surv, perm[keep])
        assert np.array_equal(mk_surv, mk[keep])


def test_pool_cancellation_sweeps_partial_outputs(tmp_path, pool):
    """Cancel mid-job: partial outputs are swept, the handle raises
    OperationCancelled, no pins leak, co-scheduled jobs are unaffected."""
    paths = _write_tablet_inputs(tmp_path, "in_cancel", n=50000, seed=7)
    other_paths = _write_tablet_inputs(tmp_path, "in_other", n=12000,
                                       seed=8)
    readers = [SSTReader(p) for p in paths]
    other_readers = [SSTReader(p) for p in other_paths]
    outd = tmp_path / "out_cancel"
    outd.mkdir()
    outd2 = tmp_path / "out_other"
    outd2.mkdir()
    old_rows = flags.get_flag("compaction_max_output_entries_per_sst")
    old_rate = flags.get_flag("compaction_rate_bytes_per_sec")
    flags.set_flag("compaction_max_output_entries_per_sst", 4000)
    # pace file writes so the watcher below reliably lands its cancel
    # between two output spans
    flags.set_flag("compaction_rate_bytes_per_sec", 200_000)
    token = CancellationToken("test job")
    try:
        ids = iter(range(100, 10_000))
        h = pool.submit("victim", PoolRequest(
            inputs=readers, out_dir=str(outd),
            new_file_id=lambda: next(ids),
            history_cutoff_ht=CUTOFF, is_major=True), cancel=token)
        ids2 = iter(range(100, 10_000))
        h2 = pool.submit("bystander", PoolRequest(
            inputs=other_readers, out_dir=str(outd2),
            new_file_id=lambda: next(ids2),
            history_cutoff_ht=CUTOFF, is_major=True))

        def _watch():
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if glob.glob(str(outd / "*.sst")):
                    token.cancel("test cancel mid-write")
                    return
                time.sleep(0.001)
            token.cancel("test cancel (no file seen)")

        t = threading.Thread(target=_watch, daemon=True)
        t.start()
        with pytest.raises(OperationCancelled):
            h.result(timeout=300)
        t.join(timeout=60)
        res2 = h2.result(timeout=300)   # bystander completes normally
        assert res2.rows_out > 0
    finally:
        flags.set_flag("compaction_max_output_entries_per_sst", old_rows)
        flags.set_flag("compaction_rate_bytes_per_sec", old_rate)
        for r in readers + other_readers:
            r.close()
    # the unwind swept every partial output (base + data files)
    assert glob.glob(str(outd / "*.sst*")) == []
    assert pool.snapshot()["cancelled"] >= 1


def test_pool_wave_fault_quarantines_without_collateral(tmp_path, pool):
    """A device fault during a pooled wave quarantines the shape bucket
    and completes EVERY wave job natively, byte-identically — one bad
    shard never aborts co-scheduled tablets' jobs."""
    offload_policy.bucket_quarantine().clear()
    tablets = {f"f{t}": _write_tablet_inputs(tmp_path, f"inf{t}", seed=20 + t)
               for t in range(2)}
    device_faults.arm("runtime", site="dispatch", count=1)
    handles = {}
    try:
        for tid, paths in tablets.items():
            readers = [SSTReader(p) for p in paths]
            outd = tmp_path / f"pool_out_{tid}"
            outd.mkdir()
            ids = iter(range(100, 10_000))
            handles[tid] = (pool.submit(tid, PoolRequest(
                inputs=readers, out_dir=str(outd),
                new_file_id=lambda it=ids: next(it),
                history_cutoff_ht=CUTOFF, is_major=True)), readers)
        results = {}
        for tid, (h, readers) in handles.items():
            results[tid] = h.result(timeout=300)   # NOT aborted
            for r in readers:
                r.close()
    finally:
        device_faults.disarm_all()
    snap = pool.snapshot()
    assert snap["wave_faults"] >= 1
    assert snap["native_completions"] >= 2
    assert offload_policy.bucket_quarantine().snapshot(), \
        "wave fault must quarantine the shape bucket"
    offload_policy.bucket_quarantine().clear()
    # byte-identical to the sequential native path over the same inputs
    for tid, paths in tablets.items():
        readers = [SSTReader(p) for p in paths]
        outd = tmp_path / f"seq_out_{tid}"
        outd.mkdir()
        ids = iter(range(100, 10_000))
        res = run_compaction_job(readers, str(outd),
                                 lambda it=ids: next(it), CUTOFF, True,
                                 device="native")
        for r in readers:
            r.close()
        assert _out_bytes(res) == _out_bytes(results[tid]), tid


def test_pool_bucket_demotion_routes_native(pool):
    """RESYSTANCE-style measured routing through the health board: once
    the measured device rate of a bucket falls under its native rate,
    later jobs of that bucket run natively (and the snapshot says so)."""
    from yugabyte_tpu.storage.bucket_health import health_board
    board = health_board()
    board.reset()
    jobs = _merge_jobs(2, n=8000)
    h = pool.submit("warm", PoolRequest(
        inputs=[], out_dir="", new_file_id=None,
        history_cutoff_ht=CUTOFF, is_major=True, slabs=jobs[0]))
    h.result(timeout=300)
    snap_keys = [tuple(rec["bucket"])
                 for rec in board.snapshot()["keys"]
                 if rec["family"] == "run_merge_fused"
                 and rec["device_obs"] > 0]
    assert snap_keys, "wave must record a device rate on the board"
    bucket = snap_keys[0]
    # force the demotion crossover with board observations: native
    # measured far faster, then enough slow device results to clear the
    # warmup guard (one cold-compile sample must not demote alone)
    board.record_native("run_merge_fused", bucket, 10**9, 1.0)
    for _ in range(int(flags.get_flag("bucket_health_warmup_obs"))):
        board.record_device("run_merge_fused", bucket, 1, 1.0)
    assert board.state("run_merge_fused", bucket) == "degraded"
    before = pool.snapshot()["native_completions"]
    h2 = pool.submit("warm", PoolRequest(
        inputs=[], out_dir="", new_file_id=None,
        history_cutoff_ht=CUTOFF, is_major=True, slabs=jobs[1]))
    surv, mk_surv = h2.result(timeout=300)
    assert pool.snapshot()["native_completions"] == before + 1
    assert pool.snapshot()["bucket_rates"][
        f"k{bucket[0]}_m{bucket[1]}"]["demoted"]
    # native completion computes identical decisions
    from yugabyte_tpu.ops import run_merge
    perm, keep, mk = run_merge.merge_and_gc_runs(
        jobs[1], GCParams(CUTOFF, True))
    assert np.array_equal(surv, perm[keep])
    assert np.array_equal(mk_surv, mk[keep])
    board.reset()
