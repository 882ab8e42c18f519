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


# ---------------------------------------------------------------------------
# A job's own host stages run on the thread that submitted it; the
# scheduler thread keeps what takes the whole mesh.

def _tablet_request(pool, tmp_path, shared, tid, seed, n=12000, **kw):
    """One tablet's inputs written, staged into its cache partition (so
    the job pins them) and wrapped as a PoolRequest."""
    readers = [SSTReader(p) for p in
               _write_tablet_inputs(tmp_path, f"in-{tid}", n=n, seed=seed)]
    cache = None
    if shared is not None:
        cache = pool.partition_for(shared, f"db-{tid}", tid)
        for fid, r in enumerate(readers):
            cache.stage(fid, r.read_all())
    outd = tmp_path / f"out-{tid}"
    outd.mkdir()
    ids = iter(range(100, 10_000))
    return PoolRequest(
        inputs=readers, out_dir=str(outd), new_file_id=lambda: next(ids),
        history_cutoff_ht=CUTOFF, is_major=True,
        input_ids=list(range(len(readers))) if cache is not None else None,
        device_cache=cache, **kw), readers


def _on_own_threads(pool, requests):
    """Each tablet's job submitted and waited for on a thread of its own,
    as the server's compaction threads do. Returns per tablet
    (thread ident, result or exception)."""
    out = {}

    def body(tid, req):
        try:
            res = pool.submit(tid, req).result(timeout=300)
        except BaseException as e:  # noqa: BLE001 — handed to the test
            res = e
        out[tid] = (threading.get_ident(), res)

    threads = [threading.Thread(target=body, args=(tid, req), daemon=True)
               for tid, req in requests.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
        assert not t.is_alive()
    return out


def _record_threads(monkeypatch, owner, name, seen, key=lambda *a: None):
    real = getattr(owner, name)

    def wrapper(*a, **kw):
        seen.append((name, key(*a), threading.get_ident()))
        return real(*a, **kw)

    monkeypatch.setattr(owner, name, wrapper)


def _sequential_bytes(tmp_path, tid, readers):
    outd = tmp_path / f"seq-{tid}"
    outd.mkdir()
    ids = iter(range(100, 10_000))
    return _out_bytes(run_compaction_job(
        readers, str(outd), lambda: next(ids), CUTOFF, True,
        device=jax.devices()[0]))


def test_owners_stage_and_finish_their_own_jobs(tmp_path, pool,
                                                monkeypatch):
    """(a) Eight tablets' jobs from eight threads: `_stage_job` and
    `_finish_wave_job` run on the submitting threads, never on the
    scheduler's, and the outputs are the sequential jobs' bytes."""
    seen = []
    job_tid = lambda self, job, *a: job.tablet_id          # noqa: E731
    _record_threads(monkeypatch, CompactionPool, "_stage_job", seen, job_tid)
    _record_threads(monkeypatch, CompactionPool, "_finish_wave_job", seen,
                    job_tid)
    shared = DeviceSlabCache(jax.devices()[0], capacity_bytes=1 << 30)
    made = {f"t{t}": _tablet_request(pool, tmp_path, shared, f"t{t}", 40 + t)
            for t in range(8)}
    snap0 = pool.snapshot()
    out = _on_own_threads(pool, {tid: req for tid, (req, _r) in made.items()})
    snap = pool.snapshot()
    for stage in ("_stage_job", "_finish_wave_job"):
        ran = {tid: ident for name, tid, ident in seen if name == stage}
        assert ran == {tid: ident for tid, (ident, _res) in out.items()}
        assert pool._thread.ident not in ran.values()
    delta = {k: snap[k] - snap0[k]
             for k in ("wave_jobs", "owner_staged", "owner_finished")}
    assert delta == {"wave_jobs": 8, "owner_staged": 8, "owner_finished": 8}
    assert shared.pinned_count() == 0 and snap["tablets"] == {}
    for tid, (_req, readers) in made.items():
        assert _out_bytes(out[tid][1]) == \
            _sequential_bytes(tmp_path, tid, readers), tid
        for r in readers:
            r.close()


def test_a_staging_failure_fails_that_job_alone(tmp_path, pool, monkeypatch):
    """(b) One owner's staging raises: its handle carries the error, its
    wave-mates ride the wave and finish, nothing stays queued or pinned."""
    real = CompactionPool._stage_job

    def stage(self, job):
        if job.tablet_id == "bad":
            real(self, job)                 # pins taken, then the failure
            raise OSError("injected staging failure")
        real(self, job)

    monkeypatch.setattr(CompactionPool, "_stage_job", stage)
    shared = DeviceSlabCache(jax.devices()[0], capacity_bytes=1 << 30)
    made = {tid: _tablet_request(pool, tmp_path, shared, tid, 50 + i)
            for i, tid in enumerate(("good0", "bad", "good1"))}
    out = _on_own_threads(pool, {tid: req for tid, (req, _r) in made.items()})
    assert isinstance(out["bad"][1], OSError)
    assert glob.glob(str(tmp_path / "out-bad" / "*")) == []
    for tid in ("good0", "good1"):
        assert _out_bytes(out[tid][1]) == \
            _sequential_bytes(tmp_path, tid, made[tid][1]), tid
    snap = pool.snapshot()
    assert snap["queue_depth"] == 0 and snap["tablets"] == {}
    assert shared.pinned_count() == 0
    for _req, readers in made.values():
        for r in readers:
            r.close()


def test_cancel_between_staging_and_wave_leaves_nothing(tmp_path, pool,
                                                        monkeypatch):
    """(c) A job cancelled after its owner staged it and before its wave:
    OperationCancelled, no pin, no output; its wave-mate finishes."""
    gate = threading.Event()
    real = CompactionPool._await_staging

    def held(self, job):
        assert gate.wait(120)
        return real(self, job)

    monkeypatch.setattr(CompactionPool, "_await_staging", held)
    shared = DeviceSlabCache(jax.devices()[0], capacity_bytes=1 << 30)
    token = CancellationToken("victim")
    victim, v_readers = _tablet_request(pool, tmp_path, shared, "victim", 60)
    mate, m_readers = _tablet_request(pool, tmp_path, shared, "mate", 61)
    try:
        h = pool.submit("victim", victim, cancel=token)   # staged on return
        h2 = pool.submit("mate", mate)
        assert shared.pinned_count() > 0
        before = pool.snapshot()["cancelled"]
        token.cancel("between staging and wave")
    finally:
        gate.set()
    with pytest.raises(OperationCancelled):
        h.result(timeout=300)
    assert _out_bytes(h2.result(timeout=300)) == \
        _sequential_bytes(tmp_path, "mate", m_readers)
    assert glob.glob(str(tmp_path / "out-victim" / "*")) == []
    assert shared.pinned_count() == 0
    snap = pool.snapshot()
    assert snap["cancelled"] == before + 1 and snap["tablets"] == {}
    for r in v_readers + m_readers:
        r.close()


def test_a_job_nobody_waits_for_is_finished_by_the_scheduler(
        tmp_path, pool, monkeypatch):
    """(d) Callers that poll `done` and never wait in result(): the
    scheduler finishes their jobs, and the two counters' difference says
    how many."""
    seen = []
    _record_threads(monkeypatch, CompactionPool, "_finish_wave_job", seen)
    made = {f"p{t}": _tablet_request(pool, tmp_path, None, f"p{t}", 70 + t)
            for t in range(3)}
    snap0 = pool.snapshot()
    handles = {tid: pool.submit(tid, req) for tid, (req, _r) in made.items()}
    deadline = time.monotonic() + 300
    while not all(h.done for h in handles.values()):
        assert time.monotonic() < deadline
        time.sleep(0.005)
    snap = pool.snapshot()
    assert [ident for _n, _k, ident in seen] == [pool._thread.ident] * 3
    assert snap["wave_jobs"] - snap0["wave_jobs"] == 3
    assert snap["owner_staged"] - snap0["owner_staged"] == 3
    assert snap["owner_finished"] == snap0["owner_finished"]
    for tid, (_req, readers) in made.items():
        assert _out_bytes(handles[tid].result(timeout=1)) == \
            _sequential_bytes(tmp_path, tid, readers), tid
        for r in readers:
            r.close()


def test_only_the_scheduler_launches_multi_device_programs(
        tmp_path, monkeypatch):
    """(e) One thread owns the mesh: the wave dispatch and the mesh job's
    step and span gathers all come from the scheduler thread, while the
    owners' span gathers (one device each) come from their own."""
    from yugabyte_tpu.parallel import dist_compact
    from yugabyte_tpu.storage import bucket_health
    monkeypatch.setattr(bucket_health, "_on_tpu", lambda: True)
    bucket_health.health_board().reset()
    seen = []
    mesh_wide = ((dist_compact, "pooled_merge_gc"),
                 (dist_compact, "distributed_compact_with_outputs"),
                 (dist_compact.DistOutputs, "gather_span"),
                 (CompactionPool, "_run_exclusive"))
    for i, (owner, name) in enumerate(
            mesh_wide + ((dist_compact.PoolWaveHandle, "gather_span"),)):
        _record_threads(monkeypatch, owner, name, seen, lambda *a, _i=i: _i)
    flags.set_flag("distributed_compaction_min_rows", 4 * 2048)
    pool = CompactionPool(make_mesh(4))
    shared = DeviceSlabCache(jax.devices()[0], capacity_bytes=1 << 30)
    try:
        made = {f"w{t}": _tablet_request(pool, tmp_path, shared, f"w{t}",
                                         80 + t, n=4000, est_rows=4000)
                for t in range(4)}
        made["big"] = _tablet_request(pool, tmp_path, shared, "big", 90,
                                      n=4 * 2048, est_rows=4 * 2048)
        out = _on_own_threads(pool,
                              {tid: req for tid, (req, _r) in made.items()})
        sched = pool._thread.ident
    finally:
        pool.shutdown()
        flags.reset_flag("distributed_compaction_min_rows")
        bucket_health.health_board().reset()
    for tid, (_ident, res) in out.items():
        assert not isinstance(res, BaseException), (tid, res)
    by = {}
    for _name, i, ident in seen:
        by.setdefault(i, set()).add(ident)
    for i, (_owner, name) in enumerate(mesh_wide):
        assert by.get(i) == {sched}, (name, by.get(i), sched)
    owners = {ident for tid, (ident, _res) in out.items() if tid != "big"}
    assert by[len(mesh_wide)] and by[len(mesh_wide)] <= owners
    for _req, readers in made.values():
        for r in readers:
            r.close()


def test_shutdown_resolves_every_staged_and_queued_job(tmp_path,
                                                       monkeypatch):
    """(f) shutdown() with jobs staged and queued, one picked and waited
    for by the scheduler, one still staging: every handle resolves, no
    pin is left, nothing stays in the pool."""
    picked = threading.Event()
    release = threading.Event()
    real_await = CompactionPool._await_staging
    real_stage = CompactionPool._stage_job

    def held_await(self, job):
        picked.set()
        assert release.wait(120)
        return real_await(self, job)

    def slow_stage(self, job):
        real_stage(self, job)
        if job.tablet_id == "slow":
            assert release.wait(120)

    monkeypatch.setattr(CompactionPool, "_await_staging", held_await)
    monkeypatch.setattr(CompactionPool, "_stage_job", slow_stage)
    pool = CompactionPool(make_mesh(4))
    shared = DeviceSlabCache(jax.devices()[0], capacity_bytes=1 << 30)
    made = {tid: _tablet_request(pool, tmp_path, shared, tid, 95 + i)
            for i, tid in enumerate(("first", "queued0", "queued1", "slow"))}
    handles = {}
    try:
        handles["first"] = pool.submit("first", made["first"][0])
        assert picked.wait(60)          # the scheduler holds `first`
        for tid in ("queued0", "queued1"):
            handles[tid] = pool.submit(tid, made[tid][0])
        slow = threading.Thread(
            target=lambda: handles.__setitem__(
                "slow", pool.submit("slow", made["slow"][0])), daemon=True)
        slow.start()
        deadline = time.monotonic() + 60
        while pool.snapshot()["queue_depth"] < 3:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        stopper = threading.Thread(target=pool.shutdown, daemon=True)
        stopper.start()
        for tid in ("queued0", "queued1"):
            with pytest.raises(OperationCancelled):
                handles[tid].result(timeout=60)
    finally:
        release.set()
    slow.join(timeout=60)
    stopper.join(timeout=60)
    assert not slow.is_alive() and not stopper.is_alive()
    with pytest.raises(OperationCancelled):
        handles["slow"].result(timeout=60)
    # the round in flight when shutdown came runs to its end
    assert handles["first"].result(timeout=300).rows_out > 0
    with pytest.raises(OperationCancelled):
        pool.submit("late", made["first"][0]).result(timeout=1)
    assert shared.pinned_count() == 0
    snap = pool.snapshot()
    assert snap["queue_depth"] == 0 and snap["tablets"] == {}
    for _req, readers in made.values():
        for r in readers:
            r.close()


def test_one_staging_at_a_time_and_picked_jobs_first(monkeypatch):
    """Stagings do not run side by side (Python under the interpreter
    lock: they would only slow each other), and the turn goes to a job
    the scheduler has picked before one that is still queued."""
    from yugabyte_tpu.tserver.compaction_pool import PoolJobHandle, _Job
    pool = CompactionPool(make_mesh(4))
    inside, order = [], []
    most = [0]
    hold = threading.Event()

    def turn(job, wait_for=None):
        with pool._staging_turn(job):
            inside.append(job)
            most[0] = max(most[0], len(inside))
            order.append(job.tablet_id)
            if wait_for is not None:
                assert wait_for.wait(60)
            time.sleep(0.01)
            inside.remove(job)

    try:
        jobs = {tid: _Job(tid, None, PoolJobHandle(tid, CancellationToken()))
                for tid in ("holder", "queued0", "queued1", "picked")}
        threads = [threading.Thread(target=turn, args=(jobs["holder"], hold),
                                    daemon=True)]
        threads[0].start()
        deadline = time.monotonic() + 60
        while not inside:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        for tid in ("queued0", "queued1", "picked"):    # `picked` asks last
            threads.append(threading.Thread(target=turn, args=(jobs[tid],),
                                            daemon=True))
            threads[-1].start()
            while jobs[tid] not in pool._stage_waiters:
                assert time.monotonic() < deadline
                time.sleep(0.001)
        with pool._cond:            # what `_take_round` does to a pick
            pool._running["picked"] = [jobs["picked"]]
        hold.set()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        hold.set()
        with pool._cond:
            pool._running.clear()
        pool.shutdown()
    assert most[0] == 1
    assert order[:2] == ["holder", "picked"] and len(order) == 4
