"""Mesh-sharded compaction pool: many tablets share one device mesh.

ROADMAP item 3's throughput service (what LUDA did for GPU compaction
offload): the headline is AGGREGATE multi-job rows/s across concurrent
tablets, not single-job latency. Queued jobs from concurrent tablets are
packed into shape-bucketed batch slots — one tablet job per mesh device,
ONE shard_map dispatch per wave (parallel/dist_compact.pooled_merge_gc,
the mesh-level extension of ops/run_merge.pack_runs_greedy's slot
packing) — while a job at or above `distributed_compaction_min_rows`
takes the whole mesh exclusively through the key-range-sharded
dist-native path.

Scheduling is RESYSTANCE-style measured, fair and contained:

  - measured per-bucket rates: every wave updates an EWMA device rows/s
    per shape bucket, every native completion the native twin; a bucket
    whose device rate falls below its native rate is DEMOTED (jobs run
    natively) until the measurements say otherwise — routing by
    observation, not calibration faith;
  - fairness: tablets are served in deficit order (least rows served
    first), and wave slots fill round-robin across tablet queue heads —
    a tablet saturating the queue cannot starve the others;
  - cancellation: every job carries a CancellationToken checked at each
    stage boundary; a cancelled job's partial outputs are swept and its
    input pins released, co-scheduled jobs unaffected;
  - fault containment: a device fault in a wave quarantines that shape
    bucket (storage/offload_policy.BucketQuarantine — same vocabulary as
    the single-device containment) and completes every affected job
    NATIVELY, byte-identically; a host-side failure in one job's write
    stage fails only that job's handle.

Per-slot merge products stay device-resident: each job's output spans
gather on ITS slot's device and install into the tablet's cache
partition (storage/device_cache.ShardPartition), so the resident
L0->L1->L2 chain survives sharding.

Threads. A job's own host stages run on the thread that submitted it:
`submit()` queues the job and then stages it (`_stage_job`: the job's own
files, its own slot; one staging at a time, `_staging_turn`), and the
thread waiting in `PoolJobHandle.result()` finishes it
(`_finish_wave_job`: its own slot of the wave handle, its own outputs)
once the wave's decisions are on the host, beside the other owners. A
job nobody waits for is finished by the scheduler. The pool's one
scheduler thread keeps the queue, the round, the wave dispatch and the
mesh-sized job.

ONE THREAD OWNS THE MESH: only the scheduler thread launches a program
that spans more than one device (`pooled_merge_gc`, the mesh job's step
and its `_dist_gather_span`). Each device runs what it is handed in
order, so two threads that enqueue multi-device programs in different
orders on different devices can leave every device waiting for a peer
that is busy with the other program: a deadlock. Owner threads launch
single-device programs only (`PoolWaveHandle.gather_span` on the slot's
device, `stage_runs_from_staged` on the partition's device), which wait
for no peer.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from yugabyte_tpu.utils import flags
from yugabyte_tpu.utils.cancellation import (CancellationToken,
                                             OperationCancelled)
from yugabyte_tpu.utils.metrics import pipeline_span, pool_span
from yugabyte_tpu.utils.trace import TRACE

# Longest the scheduler holds a round back while fewer wave jobs than
# slots are queued. Compaction threads handed work at the same instant
# arrive within a few milliseconds of each other; a wave job takes some
# hundreds.
_WAVE_LINGER_S = 0.020

# How often the scheduler, waiting for a picked job's owner to end its
# staging, looks at the job's cancel token.
_STAGING_POLL_S = 0.050


@dataclass
class PoolRequest:
    """One tablet compaction job as the pool schedules it."""
    inputs: List                      # SSTReaders, newest-first pick order
    out_dir: str
    new_file_id: object               # callable -> next file id
    history_cutoff_ht: int
    is_major: bool
    retain_deletes: bool = False
    block_entries: Optional[int] = None
    input_ids: Optional[List[int]] = None
    device_cache: object = None       # NamespacedSlabCache / ShardPartition
    est_rows: int = 0
    # merge-only jobs (decisions service, no SST I/O): the bench's
    # device-stage rung and the unit tests use this form
    slabs: Optional[List] = None


class PoolJobHandle:
    """Caller's side of a submitted job: wait for the result, or cancel.
    The thread that waits in `result()` is the job's owner: the pool hands
    it the job's finishing work (`_offer`) and it runs that work there."""

    def __init__(self, tablet_id: str, cancel: CancellationToken):
        self.tablet_id = tablet_id
        self.cancel_token = cancel
        self._cv = threading.Condition()
        self._done = threading.Event()
        self._waiters = 0                 # guarded-by: _cv
        self._owner_work = None           # guarded-by: _cv
        self._result = None
        self._exc: Optional[BaseException] = None
        self.submitted_at = time.monotonic()
        self.finished_at: Optional[float] = None

    def cancel(self, reason: str = "cancelled") -> None:
        self.cancel_token.cancel(reason)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None):
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            self._waiters += 1
            try:
                while self._owner_work is None and not self._done.is_set():
                    left = None if deadline is None \
                        else deadline - time.monotonic()
                    if left is not None and left <= 0:
                        raise TimeoutError("pool job still running")
                    self._cv.wait(left)
                work, self._owner_work = self._owner_work, None
            finally:
                self._waiters -= 1
        if work is not None:
            work()      # resolves this handle, whatever becomes of the job
        if self._exc is not None:
            raise self._exc
        return self._result

    def _offer(self, work) -> bool:
        """Hand the job's finishing work to a thread waiting in
        `result()`. False when none waits: the caller runs it."""
        with self._cv:
            if not self._waiters or self._done.is_set():
                return False
            self._owner_work = work
            self._cv.notify_all()
            return True

    def _resolve(self, result=None, exc: Optional[BaseException] = None
                 ) -> None:
        with self._cv:
            self._result = result
            self._exc = exc
            self.finished_at = time.monotonic()
            self._done.set()
            self._cv.notify_all()


@dataclass(eq=False)
class _Job:
    tablet_id: str
    request: PoolRequest
    handle: PoolJobHandle
    # set during wave staging, by the submitting thread alone until it
    # sets `staged_mark`
    filtered_inputs: List = field(default_factory=list)
    slabs: List = field(default_factory=list)
    staged: object = None
    dropped_rows: int = 0
    pins: List[int] = field(default_factory=list)
    # staging has ended, however it ended / the handle's outcome is
    # decided. A job that will not run loses its pins to whichever side
    # finds the other's mark set, under the pool's lock
    staged_mark: bool = False         # guarded-by: CompactionPool._lock
    finished: bool = False            # guarded-by: CompactionPool._lock
    # its finishing work went to the thread waiting in result()
    with_owner: bool = False          # scheduler thread only


def _bucket_name(bucket: Tuple[int, int]) -> str:
    return f"k{bucket[0]}_m{bucket[1]}"


class CompactionPool:
    """One per tablet server (next to the thread pool it rides behind):
    the scheduler that turns a device mesh into a multi-tablet compaction
    throughput service."""

    def __init__(self, mesh, device=None, name: str = "compaction-pool"):
        from yugabyte_tpu.utils import lock_rank
        from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
        self.mesh = mesh
        self.n_slots = int(mesh.devices.size)
        self.device = (device if device is not None
                       else list(mesh.devices.flat)[0])
        self._lock = lock_rank.tracked(threading.Lock(),
                                       "compaction_pool.lock")
        self._cond = threading.Condition(self._lock)
        self._queues: Dict[str, deque] = {}       # guarded-by: _lock
        self._credits: Dict[str, float] = {}      # rows served; _lock
        self._running: Dict[str, List[_Job]] = {}  # guarded-by: _lock
        self._shutdown = False                    # guarded-by: _lock
        self._staging = False                     # guarded-by: _lock
        self._stage_waiters: List[_Job] = []      # guarded-by: _lock
        self._last_fill = 0.0                     # guarded-by: _lock
        e = ROOT_REGISTRY.entity("server", "compaction_pool")
        self._c_jobs = e.counter(
            "compaction_pool_jobs_total", "jobs submitted to the pool")
        self._c_waves = e.counter(
            "compaction_pool_waves_total",
            "pooled wave dispatches (one shard_map launch each)")
        self._c_wave_jobs = e.counter(
            "compaction_pool_wave_jobs_total",
            "jobs whose device stage rode a pooled wave slot")
        self._c_owner_staged = e.counter(
            "compaction_pool_owner_staged_total",
            "wave jobs staged on the thread that submitted them")
        self._c_owner_finished = e.counter(
            "compaction_pool_owner_finished_total",
            "wave jobs finished on the thread waiting for their result "
            "(compaction_pool_wave_jobs_total less this: finished by the "
            "scheduler, nobody waiting)")
        self._c_native = e.counter(
            "compaction_pool_native_completions_total",
            "pool jobs completed on the native path (bucket demoted, "
            "quarantined, or wave fault containment)")
        self._c_faults = e.counter(
            "compaction_pool_wave_faults_total",
            "wave dispatches that hit a device fault (bucket "
            "quarantined; jobs completed natively)")
        self._c_cancelled = e.counter(
            "compaction_pool_cancelled_total",
            "pool jobs cancelled before or during execution")
        self._g_queue = e.gauge(
            "compaction_pool_queue_depth", "jobs queued across tablets")
        self._g_running = e.gauge(
            "compaction_pool_running_count", "jobs currently executing")
        self._g_fill = e.gauge(
            "compaction_pool_slot_occupancy_ratio",
            "filled slots / mesh slots of the most recent wave")
        self._h_wall = e.histogram(
            "compaction_pool_job_wall_ms",
            "submit-to-done wall time per pool job")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=name)
        self._thread.start()

    # ------------------------------------------------------------- client API
    def partition_for(self, shared_cache, namespace: str, tablet_id: str):
        """The tablet's sticky cache partition: home shard =
        hash(tablet_id) mod mesh size, staged onto that shard's device."""
        from yugabyte_tpu.storage.device_cache import ShardPartition
        shard = hash(tablet_id) % self.n_slots
        return ShardPartition(shared_cache, namespace, shard,
                              list(self.mesh.devices.flat)[shard])

    def submit(self, tablet_id: str, request: PoolRequest,
               cancel: Optional[CancellationToken] = None) -> PoolJobHandle:
        token = cancel or CancellationToken(f"pool job {tablet_id}")
        handle = PoolJobHandle(tablet_id, token)
        job = _Job(tablet_id, request, handle)
        with self._cond:
            if self._shutdown:
                handle._resolve(exc=OperationCancelled(
                    "compaction pool shut down"))
                return handle
            q = self._queues.setdefault(tablet_id, deque())
            if tablet_id not in self._credits:
                # newcomers start at the current minimum so they are
                # served promptly without eternal priority
                self._credits[tablet_id] = min(self._credits.values(),
                                               default=0.0)
            q.append(job)
            self._c_jobs.increment()
            self._g_queue.set(self._queue_depth_unlocked())
            self._cond.notify_all()
        if not self._is_mesh_job(job):
            self._stage_on_this_thread(job)
        return handle

    def _stage_on_this_thread(self, job: _Job) -> None:
        """The submitter's half of a wave job: stage it while it waits in
        the queue. Nothing to merge, an expired-input result, a
        cancellation and a failure resolve this job alone."""
        try:
            with self._staging_turn(job):
                job.handle.cancel_token.check()
                with pool_span("stage"):
                    self._stage_job(job)
            if job.staged is not None:
                self._c_owner_staged.increment()
        except BaseException as e:  # yblint: contained(per-job: the job's handle carries it and result() raises it)  # noqa: BLE001
            self._finish(job, exc=e)
        with self._cond:
            job.staged_mark = True
            wanted = not job.finished
            if not wanted:
                q = self._queues.get(job.tablet_id)
                if q and job in q:
                    q.remove(job)
                    self._g_queue.set(self._queue_depth_unlocked())
            self._cond.notify_all()
        if not wanted:
            self._unpin(job)

    @contextlib.contextmanager
    def _staging_turn(self, job: _Job):
        """One staging at a time, a job the scheduler has picked and
        waits for before one still queued. Staging is Python block by
        block under the interpreter lock: side by side, eight stagings
        took ten times as long each, and longer together than one after
        another (PERF.md, PR 30). What they run beside is the
        scheduler's dispatches and the other owners' finishes, which wait
        on the device, on files and in the native library."""
        with pipeline_span("pool_wait"), self._cond:
            self._stage_waiters.append(job)
            while self._staging or (
                    not self._picked_unlocked(job)
                    and any(self._picked_unlocked(j)
                            for j in self._stage_waiters)):
                self._cond.wait()
            self._stage_waiters.remove(job)
            self._staging = True
        try:
            yield
        finally:
            with self._cond:
                self._staging = False
                self._cond.notify_all()

    def _picked_unlocked(self, job: _Job) -> bool:
        return job in self._running.get(job.tablet_id, ())

    def submit_compaction(self, tablet_id: str, *, inputs, out_dir,
                          new_file_id, history_cutoff_ht, is_major,
                          retain_deletes: bool = False,
                          block_entries: Optional[int] = None,
                          input_ids: Optional[List[int]] = None,
                          device_cache=None, est_rows: int = 0,
                          cancel: Optional[CancellationToken] = None
                          ) -> PoolJobHandle:
        """Keyword-argument convenience front for storage/db.py (which
        must not import this module's dataclasses — the pool object is
        dependency-injected through TabletOptions)."""
        return self.submit(tablet_id, PoolRequest(
            inputs=list(inputs), out_dir=out_dir, new_file_id=new_file_id,
            history_cutoff_ht=history_cutoff_ht, is_major=is_major,
            retain_deletes=retain_deletes, block_entries=block_entries,
            input_ids=list(input_ids) if input_ids is not None else None,
            device_cache=device_cache, est_rows=est_rows), cancel=cancel)

    def cancel_tablet(self, tablet_id: str,
                      reason: str = "tablet cancelled") -> int:
        """Cancel every queued and running job of one tablet: each aborts
        at its next stage boundary, on whichever thread runs it. Returns
        how many jobs were signalled."""
        with self._cond:
            jobs = list(self._queues.get(tablet_id, ())) \
                + list(self._running.get(tablet_id, ()))
        for job in jobs:
            job.handle.cancel(reason)
        return len(jobs)

    def shutdown(self) -> None:
        with self._cond:
            self._shutdown = True
            queued = [j for q in self._queues.values() for j in q]
            for q in self._queues.values():
                q.clear()
            self._g_queue.set(0)
            self._cond.notify_all()
        for job in queued:
            self._abandon(job, OperationCancelled(
                "compaction pool shut down"))
        deadline = time.monotonic() + 10
        self._thread.join(timeout=10)
        # jobs their owners are still finishing
        with self._cond:
            self._cond.wait_for(
                lambda: not any(self._running.values()),
                timeout=max(0.0, deadline - time.monotonic()))

    def snapshot(self) -> dict:
        """The /compactionz "pool" block: queue depth, per-tablet
        queued/running, packed-slot occupancy and the health board's
        measured per-bucket rates the scheduler routes by."""
        from yugabyte_tpu.storage.bucket_health import health_board
        rates = {}
        for rec in health_board().snapshot()["keys"]:
            if rec["family"] != "run_merge_fused":
                continue
            rates[_bucket_name(tuple(rec["bucket"]))] = {
                "device_rows_per_sec": rec["device_rows_per_sec"],
                "native_rows_per_sec": rec["native_rows_per_sec"],
                "state": rec["state"],
                "demoted": rec["state"] in ("degraded", "quarantined"),
            }
        with self._lock:
            tablets = {}
            for tid, q in self._queues.items():
                r = len(self._running.get(tid, ()))
                if q or r:
                    tablets[tid] = {"queued": len(q), "running": r}
            for tid, r in self._running.items():
                if r and tid not in tablets:
                    tablets[tid] = {"queued": 0, "running": len(r)}
            return {
                "mesh_slots": self.n_slots,
                "queue_depth": self._queue_depth_unlocked(),
                "tablets": tablets,
                "slot_occupancy_ratio": round(self._last_fill, 3),
                "bucket_rates": rates,
                "waves": self._c_waves.value(),
                "wave_jobs": self._c_wave_jobs.value(),
                "owner_staged": self._c_owner_staged.value(),
                "owner_finished": self._c_owner_finished.value(),
                "native_completions": self._c_native.value(),
                "wave_faults": self._c_faults.value(),
                "cancelled": self._c_cancelled.value(),
            }

    # ------------------------------------------------------------- scheduling
    def _queue_depth_unlocked(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def _running_count_unlocked(self) -> int:
        return sum(len(r) for r in self._running.values())

    def _is_mesh_job(self, job: _Job) -> bool:
        """A job at or above `distributed_compaction_min_rows` takes the
        whole mesh (`_run_exclusive`) and no wave slot."""
        return (self.n_slots > 1 and job.request.slabs is None
                and job.request.est_rows >= flags.get_flag(
                    "distributed_compaction_min_rows"))

    def _wave_jobs_queued_unlocked(self) -> int:
        return sum(1 for q in self._queues.values() for job in q
                   if not self._is_mesh_job(job))

    def _linger_for_full_wave_unlocked(self) -> None:
        """Hold the round back while fewer wave jobs than slots are
        queued: for `_WAVE_LINGER_S` (a round taken at the first arrival
        would dispatch a one-job wave), and for as long as jobs of
        earlier waves are still with their owners, whose threads bring
        the next jobs when they are done; each one that retires starts
        the linger anew. The scheduler used to finish those jobs itself
        before it came here, so this never starts a round later than
        that did."""
        deadline = time.monotonic() + _WAVE_LINGER_S
        while not self._shutdown \
                and self._wave_jobs_queued_unlocked() < self.n_slots:
            with_owners = self._running_count_unlocked()
            left = deadline - time.monotonic()
            if left <= 0 and not with_owners:
                return
            self._cond.wait(timeout=left if left > 0 else _WAVE_LINGER_S)
            if self._running_count_unlocked() < with_owners:
                deadline = time.monotonic() + _WAVE_LINGER_S

    def _take_round(self) -> List[_Job]:
        """Pop queue heads in deficit-fair order: tablets sorted by rows
        served ascending, then round-robin across their queues until
        n_slots wave jobs are picked or the queues drain. A mesh-sized
        head rides the round without taking a slot (it runs exclusively
        after the waves), so it never leaves a wave short."""
        with self._cond:
            while not self._shutdown \
                    and self._queue_depth_unlocked() == 0:
                self._cond.wait(timeout=0.5)
            self._linger_for_full_wave_unlocked()
            if self._shutdown:
                return []
            order = sorted(
                (tid for tid, q in self._queues.items() if q),
                key=lambda tid: self._credits.get(tid, 0.0))
            picked: List[_Job] = []
            slots = 0
            while slots < self.n_slots:
                progressed = False
                for tid in order:
                    q = self._queues.get(tid)
                    if q and slots < self.n_slots:
                        picked.append(q.popleft())
                        slots += not self._is_mesh_job(picked[-1])
                        progressed = True
                if not progressed:
                    break
            for job in picked:
                self._running.setdefault(job.tablet_id, []).append(job)
            self._g_queue.set(self._queue_depth_unlocked())
            self._g_running.set(self._running_count_unlocked())
            return picked

    def _loop(self) -> None:
        while True:
            jobs = self._take_round()
            if not jobs:
                with self._lock:
                    if self._shutdown:
                        return
                continue
            try:
                self._run_round(jobs)
            except Exception as e:  # noqa: BLE001 — scheduler must survive
                TRACE("compaction pool: round failed: %s", e)
                for job in jobs:
                    if not job.with_owner:
                        self._abandon(job, e)
            finally:
                # a job with its owner leaves `_running` when the owner's
                # finish has returned
                for job in jobs:
                    if not job.with_owner:
                        self._retire(job)

    # -------------------------------------------------------------- execution
    def _finish(self, job: _Job, result=None,
                exc: Optional[BaseException] = None) -> None:
        """Resolve the job's handle, once: the first caller's outcome
        stands (the scheduler abandoning a cancelled job can meet its
        owner's staging failure)."""
        rows = 0
        if result is not None:
            rows = getattr(result, "rows_in", 0) or \
                (sum(s.n for s in job.slabs) if job.slabs else 0)
        with self._lock:
            if job.finished:
                return
            job.finished = True
            self._credits[job.tablet_id] = \
                self._credits.get(job.tablet_id, 0.0) + float(rows or 1)
        if isinstance(exc, OperationCancelled):
            self._c_cancelled.increment()
        self._h_wall.increment(
            (time.monotonic() - job.handle.submitted_at) * 1e3)
        job.handle._resolve(result=result, exc=exc)

    def _abandon(self, job: _Job, exc: BaseException) -> None:
        """Resolve a job that will not run. Staged, its pins go here;
        still staging, its owner releases them when it finds the job
        finished."""
        self._finish(job, exc=exc)
        with self._lock:
            staged = job.staged_mark
        if staged:
            self._unpin(job)

    def _retire(self, job: _Job) -> None:
        """The job has left the pool: out of `_running`."""
        with self._cond:
            running = self._running.get(job.tablet_id, ())
            if job in running:
                running.remove(job)
                self._g_running.set(self._running_count_unlocked())
                self._cond.notify_all()

    def _await_staging(self, job: _Job) -> bool:
        """Wait for a picked job's owner to end its staging. False when
        the job is resolved already: by its staging (nothing to merge, a
        failure) or by its cancel token, which ends the wait early."""
        token = job.handle.cancel_token
        with self._cond:
            while not job.staged_mark and not token.cancelled:
                self._cond.wait(_STAGING_POLL_S)
        if token.cancelled:
            try:
                token.check()
            except OperationCancelled as e:
                self._abandon(job, e)
        return not job.handle.done

    def _run_round(self, jobs: List[_Job]) -> None:
        from yugabyte_tpu.ops.merge_gc import GCParams
        from yugabyte_tpu.storage import compaction as compaction_mod

        # every wave job was staged by its submitter (filter, read, pack
        # / cache restage, pin): wait for those this round picked
        staged_jobs: List[_Job] = []
        big_jobs: List[_Job] = []
        for job in jobs:
            if self._is_mesh_job(job):
                big_jobs.append(job)
                continue
            with pool_span("sched_wait"):
                wanted = self._await_staging(job)
            if wanted:
                staged_jobs.append(job)

        # shape-bucketed wave groups: (k_pad, m, w, is_major,
        # retain_deletes) — each group is one shard_map dispatch
        groups: Dict[tuple, List[_Job]] = {}
        for job in staged_jobs:
            st = job.staged
            key = (st.k_pad, st.m, st.w, job.request.is_major,
                   job.request.retain_deletes)
            groups.setdefault(key, []).append(job)
        from yugabyte_tpu.storage.bucket_health import health_board
        board = health_board()
        for key, group in groups.items():
            bucket = key[:3]
            if not board.allow_device("run_merge_fused",
                                      (bucket[0], bucket[1])):
                # the health board parked the bucket (measured demotion,
                # open fault-quarantine window, or sticky mismatch): run
                # these natively until a probe / the decay re-opens it
                for job in group:
                    with pool_span("native"):
                        self._complete_natively(job, record_rate=True)
                continue
            self._run_wave(bucket, key[3], key[4], group)

        # whole-mesh jobs run after the waves (exclusive use of the mesh)
        for job in big_jobs:
            with pool_span("exclusive"):
                self._run_exclusive(job)

    def _stage_job(self, job: _Job) -> None:
        """Filter + read + pack one job's device-stage input. Resident
        hit: every input present in the job's cache partition restages
        ON DEVICE (ops/run_merge.stage_runs_from_staged — zero upload);
        miss: host pack (parallel/dist_compact.stage_pool_slot)."""
        from yugabyte_tpu.parallel.dist_compact import (pool_slot_bucket,
                                                        stage_pool_slot)
        from yugabyte_tpu.storage.compaction import filter_expired_inputs
        req = job.request
        if req.slabs is not None:
            # merge-only job (decisions service): slabs arrive pre-read
            job.filtered_inputs = []
            job.slabs = [s for s in req.slabs if s.n]
            if not job.slabs:
                job.staged = None
                self._finish(job, result=None)
                return
            b = pool_slot_bucket(job.slabs)
            job.staged = stage_pool_slot(job.slabs, *b)
            return
        inputs, dropped = filter_expired_inputs(
            req.inputs, req.history_cutoff_ht, req.is_major,
            req.retain_deletes)
        job.dropped_rows = sum(r.props.n_entries for r in dropped)
        inputs = [r for r in inputs if r.props.n_entries]
        job.filtered_inputs = inputs
        if not inputs:
            from yugabyte_tpu.storage.compaction import CompactionResult
            job.staged = None
            self._finish(job, result=CompactionResult(
                [], job.dropped_rows, 0))
            return
        cache = req.device_cache
        ids = req.input_ids
        if cache is not None and ids is not None:
            # keep the id pairing aligned with the FILTERED list
            id_of = {id(r): fid for r, fid in zip(req.inputs, ids)}
            ids = [id_of[id(r)] for r in inputs]
            for fid in ids:
                if cache.pin(fid):
                    job.pins.append(fid)
        job.slabs = [r.read_all() for r in inputs]
        job.slabs = [s for s in job.slabs if s.n]
        resident = (cache is not None and ids is not None
                    and all(cache.contains(fid) for fid in ids))
        if resident:
            from yugabyte_tpu.ops.run_merge import stage_runs_from_staged
            staged_list = [cache.get(fid) for fid in ids]
            if all(st is not None for st in staged_list):
                job.staged = stage_runs_from_staged(staged_list)
                return
        b = pool_slot_bucket(job.slabs)
        job.staged = stage_pool_slot(job.slabs, *b)

    def _unpin(self, job: _Job) -> None:
        with self._lock:    # once: an abandoned job's two sides can meet
            pins, job.pins = job.pins, []
        cache = job.request.device_cache
        if cache is not None:
            for fid in pins:
                cache.unpin(fid)

    def _run_wave(self, bucket: Tuple[int, int, int], is_major: bool,
                  retain_deletes: bool, group: List[_Job]) -> None:
        from yugabyte_tpu.ops import device_faults
        from yugabyte_tpu.ops.merge_gc import GCParams
        from yugabyte_tpu.parallel.dist_compact import pooled_merge_gc
        from yugabyte_tpu.storage.bucket_health import health_board
        board = health_board()

        # waves are mesh-slot sized; a larger group runs in several
        waves = [group[i:i + self.n_slots]
                 for i in range(0, len(group), self.n_slots)]
        for wave in waves:
            with self._lock:
                self._last_fill = len(wave) / self.n_slots
            self._g_fill.set(len(wave) / self.n_slots)
            t0 = time.monotonic()
            try:
                # the one dispatch and the wait for its decisions
                with pool_span("wave"):
                    handle = pooled_merge_gc(
                        self.mesh,
                        [(job.staged,
                          GCParams(job.request.history_cutoff_ht, is_major,
                                   retain_deletes))
                         for job in wave])
            except Exception as e:  # noqa: BLE001 — wave fault containment
                if not device_faults.is_device_fault(e):
                    for job in wave:
                        self._unpin(job)
                        self._finish(job, exc=e)
                    continue
                # one shard's fault quarantines the BUCKET and completes
                # every wave job natively — co-scheduled tablets' jobs
                # finish byte-identically instead of aborting
                self._c_faults.increment()
                board.record_fault(
                    "run_merge_fused", (bucket[0], bucket[1]),
                    reason=f"pool wave fault: {type(e).__name__}: {e}")
                TRACE("compaction pool: wave device fault (%r) — bucket "
                      "k_pad=%d m=%d quarantined; completing %d job(s) "
                      "natively", e, bucket[0], bucket[1], len(wave))
                for job in wave:
                    with pool_span("native"):
                        self._complete_natively(job, record_rate=False)
                continue
            self._c_waves.increment()
            wall = max(time.monotonic() - t0, 1e-9)
            rows = sum(job.staged.n for job in wave)
            board.record_device("run_merge_fused",
                                (bucket[0], bucket[1]), rows, wall)
            for slot, job in enumerate(wave):
                self._c_wave_jobs.increment()
                # a merge-only job's finish is its decisions themselves
                job.with_owner = job.request.slabs is None \
                    and job.handle._offer(functools.partial(
                        self._finish_slot, job, handle, slot))
                if not job.with_owner:
                    self._finish_slot(job, handle, slot)

    def _finish_slot(self, job: _Job, handle, slot: int) -> None:
        """One wave job from its slot's decisions to a resolved handle,
        on its owner's thread or, nobody waiting, on the scheduler's."""
        by_owner = threading.current_thread() is not self._thread
        result = exc = None
        try:
            with pool_span("finish"):
                result = self._finish_wave_job(job, handle, slot)
        except BaseException as e:  # noqa: BLE001 — per-job containment
            exc = e
        self._unpin(job)
        if by_owner:
            self._c_owner_finished.increment()
        self._finish(job, result=result, exc=exc)
        if by_owner:
            self._retire(job)

    def _finish_wave_job(self, job: _Job, handle, slot: int):
        """Stage C of one wave job: write outputs from the slot's
        decisions through the sequential writer rules (byte-identical),
        installing survivor spans from the slot's device into the
        tablet's cache partition as each SST hits disk. Returns the
        job's result."""
        from yugabyte_tpu.storage.compaction import (
            CompactionResult, run_compaction_job_with_decisions)
        job.handle.cancel_token.check()
        perm, keep, mk = handle.decisions[slot]
        surv = perm[keep]
        mk_surv = mk[keep]
        req = job.request
        if req.slabs is not None:
            # merge-only job: the decisions ARE the result
            return surv, mk_surv
        rows_in = sum(s.n for s in job.slabs) + job.dropped_rows
        on_span = None
        cache = req.device_cache
        if cache is not None:
            in_levels = [cache.level_of(fid)
                         for fid in (req.input_ids or [])
                         if fid is not None]
            out_level = 1 + max([lv for lv in in_levels
                                 if lv is not None], default=0)
            installed: List[int] = []

            def on_span(fid, base_path, start, end,
                        _lvl=out_level, _installed=installed):
                from yugabyte_tpu.storage import integrity
                st = handle.gather_span(slot, start, end)
                target = getattr(cache, "device", None)
                if target is not None and target != "native":
                    import jax as _jax
                    # commit the span to the partition's device so later
                    # merges never mix committed devices
                    st.cols_dev = _jax.device_put(st.cols_dev, target)
                if integrity.maybe_verify_resident_entry(st, base_path):
                    cache.put(fid, st, level=_lvl)
                    _installed.append(fid)
        return run_compaction_job_with_decisions(
            job.filtered_inputs, job.slabs, req.out_dir, req.new_file_id,
            req.history_cutoff_ht, req.is_major, req.retain_deletes,
            req.block_entries, surv, mk_surv, rows_in,
            frontier_inputs=req.inputs, cancel=job.handle.cancel_token,
            on_span=on_span)

    def _complete_natively(self, job: _Job, record_rate: bool) -> None:
        """Byte-identical native completion of one pool job (demoted
        bucket or wave-fault containment)."""
        from yugabyte_tpu.storage import compaction as compaction_mod
        try:
            job.handle.cancel_token.check()
            req = job.request
            t0 = time.monotonic()
            if req.slabs is not None:
                # merge-only job: the CPU baseline computes the identical
                # decisions (differential-tested against the kernel)
                from yugabyte_tpu.ops.slabs import concat_slabs
                from yugabyte_tpu.storage.cpu_baseline import (
                    compact_cpu_baseline)
                live = [s for s in job.slabs if s.n]
                merged = concat_slabs(live)
                offsets = np.concatenate(
                    ([0], np.cumsum([s.n for s in live]))).tolist()
                perm, keep, mk = compact_cpu_baseline(
                    merged, offsets, req.history_cutoff_ht, req.is_major,
                    req.retain_deletes)
                result = (perm[keep], mk[keep])
                rows = merged.n
            else:
                result = compaction_mod.run_compaction_job(
                    req.inputs, req.out_dir, req.new_file_id,
                    req.history_cutoff_ht, req.is_major,
                    req.retain_deletes, device="native",
                    block_entries=req.block_entries,
                    cancel=job.handle.cancel_token, _no_combined=True)
                rows = result.rows_in
            self._c_native.increment()
            if record_rate and job.staged is not None:
                from yugabyte_tpu.storage.bucket_health import health_board
                health_board().record_native(
                    "run_merge_fused", (job.staged.k_pad, job.staged.m),
                    rows, max(time.monotonic() - t0, 1e-9))
            self._finish(job, result=result)
        except BaseException as e:  # noqa: BLE001 — per-job containment
            self._finish(job, exc=e)
        finally:
            self._unpin(job)

    def _run_exclusive(self, job: _Job) -> None:
        """A mesh-sized job: the whole mesh, key-range-sharded
        (storage/compaction.run_compaction_job routes it through the
        dist-native path)."""
        from yugabyte_tpu.storage import compaction as compaction_mod
        req = job.request
        try:
            job.handle.cancel_token.check()
            result = compaction_mod.run_compaction_job(
                req.inputs, req.out_dir, req.new_file_id,
                req.history_cutoff_ht, req.is_major, req.retain_deletes,
                device=self.device, block_entries=req.block_entries,
                device_cache=req.device_cache, input_ids=req.input_ids,
                mesh=self.mesh, cancel=job.handle.cancel_token)
            self._finish(job, result=result)
        except BaseException as e:  # noqa: BLE001 — per-job containment
            self._finish(job, exc=e)
