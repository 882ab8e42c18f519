"""One tablet server's tablets compacting through its mesh pool, on the
normal path: `ServerExecutionContext` on default flags over a four-device
virtual mesh, 24 tablets' regular DBs opened with the server's own options,
every `DB.compact_all()` run on the server's `PriorityThreadPool`, so that
`DB._dispatch_compaction -> pool.submit_compaction -> pool_wait` is what is
tested (the deployment `kv64-pool-v5e4` of the benchmark, at toy sizes).

Three rounds of 8 wave jobs + 1 mesh-sized job, as the cell's traffic has
them (round r takes tablets 9r .. 9r+8 mod 24, so tablets 0-2 are made anew
in the third round). Only `distributed_compaction_min_rows` is lowered, so
that a 2^13-row job is mesh-sized; the board is told it is on a TPU, as in
tests/test_chip_smoke.py, because a COLD bucket routes native elsewhere.
"""

import os
import subprocess
import sys
import threading
import time

import jax
import pytest

from benchmarks import datagen, reference
from benchmarks.drivers.compaction import _decode_outputs
from benchmarks.drivers.pool import _Template
from benchmarks.run import Context
from yugabyte_tpu.common.hybrid_time import HybridTime
from yugabyte_tpu.parallel.mesh import make_mesh
from yugabyte_tpu.storage import DB, DBOptions, SSTReader, bucket_health
from yugabyte_tpu.storage.sst import BlockCache
from yugabyte_tpu.tserver.compaction_pool import CompactionPool
from yugabyte_tpu.tserver.server_context import ServerExecutionContext
from yugabyte_tpu.utils import flags
from yugabyte_tpu.utils.metrics import kernel_metrics, pipeline_stage_totals

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARES = {"row_tombstone": 0.05, "int64_column": 0.25, "string_column": 0.70}
CUTOFF = HybridTime.from_micros(10_000_000_000).value
N_TABLETS, ROUNDS = 24, 3
ROUND = ["mesh"] + ["wave"] * 8
ROWS_PER_RUN = {"wave": 512, "mesh": 2048}
# self times of the pool's one scheduler thread; `device` and `merge_stage`
# open only under its `pool_exclusive` here (the mesh job's step)
SCHEDULER_STAGES = ("pool_sched_wait", "pool_wave", "pool_exclusive",
                    "pool_native", "device", "merge_stage")

pytestmark = pytest.mark.requires_native("compaction_engine")


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("pool-deployment"))
    mp = pytest.MonkeyPatch()
    mp.setattr(bucket_health, "_on_tpu", lambda: True)
    bucket_health.health_board().reset()
    flags.set_flag("distributed_compaction_min_rows", 4 * ROWS_PER_RUN["mesh"])
    exclusive = []
    real_exclusive = CompactionPool._run_exclusive
    mp.setattr(CompactionPool, "_run_exclusive",
               lambda self, job: (exclusive.append(job.tablet_id),
                                  real_exclusive(self, job))[1])
    gen = datagen.Kv64Runs(27, SHARES)
    ctx = Context({}, {}, {}, 27, None, root, [])   # the driver's own set-up
    templates = {name: _Template(ctx, name, gen,
                                 {"l0_runs": 4, "rows_per_run": rows}, 0.5,
                                 CUTOFF)
                 for name, rows in ROWS_PER_RUN.items()}
    devs = jax.devices()
    server = ServerExecutionContext(device=devs[0], mesh=make_mesh(4))
    pool = server.compaction_pool
    opts = server.tablet_options()
    opts.auto_compact = False
    dist_steps = kernel_metrics().counter(
        "kernel_dist_compact_dispatch_total", "")
    snap0, stages0, dist0 = pool.snapshot(), pipeline_stage_totals(), \
        dist_steps.value()
    jobs = []
    t0 = time.monotonic()
    try:
        for r in range(ROUNDS):
            tasks = []
            for j, name in enumerate(ROUND):
                tablet = (r * len(ROUND) + j) % N_TABLETS
                db_dir = os.path.join(root, "tablets", f"tablet-{tablet:02d}",
                                      "regular")
                templates[name].db.checkpoint(db_dir)
                db = DB(db_dir, opts.regular_db_options(lambda: CUTOFF))
                tasks.append({"db": db, "dir": db_dir, "template": name,
                              "done": threading.Event(), "error": None})

            def body(task):
                try:
                    task["db"].compact_all()
                except BaseException as e:  # noqa: BLE001 — asserted below
                    task["error"] = e
                finally:
                    task["done"].set()

            for task in tasks:      # all nine at the same instant
                server.pool.submit(lambda task=task: body(task))
            for task in tasks:
                assert task["done"].wait(300)
                db = task["db"]
                assert task["error"] is None and db.background_error is None
                names = [os.path.basename(fm.path)
                         for fm in db.versions.live_files()]
                db.close()
                done = task["dir"] + f"-done-{r}"
                os.rename(task["dir"], done)
                jobs.append({"template": task["template"], "outputs": [
                    (None, os.path.join(done, n), None) for n in names]})
        wall_ms = (time.monotonic() - t0) * 1e3
        snap, stages = pool.snapshot(), pipeline_stage_totals()
        yield {"jobs": jobs, "templates": templates, "server": server,
               "exclusive": exclusive, "wall_ms": wall_ms,
               "dist_steps": dist_steps.value() - dist0,
               "pool": {k: snap[k] - snap0[k] for k in (
                   "waves", "wave_jobs", "owner_staged", "owner_finished",
                   "native_completions", "wave_faults")},
               "stage_ms": {s: stages[s] - stages0[s] for s in stages}}
    finally:
        server.shutdown()
        for t in templates.values():
            t.db.close()
        flags.reset_flag("distributed_compaction_min_rows")
        mp.undo()
        bucket_health.health_board().reset()


def test_every_output_is_the_native_jobs_bytes(deployment):
    assert len(deployment["jobs"]) == ROUNDS * len(ROUND)
    for job in deployment["jobs"]:
        assert deployment["templates"][job["template"]].same_as_native(
            job["outputs"])


@pytest.mark.parametrize("template", ["wave", "mesh"])
def test_every_output_is_the_references_rows(deployment, template):
    expect = deployment["templates"][template].expect
    mine = [j for j in deployment["jobs"] if j["template"] == template]
    assert mine
    for job in mine:
        assert reference.count_row_mismatches(
            expect, _decode_outputs(job["outputs"])) == 0


def test_waves_are_full_on_default_flags(deployment):
    """The regression test of the forced change: two compaction threads and
    a scheduler that dispatches the first arrival alone never fill a wave."""
    assert flags.get_flag("tserver_compaction_pool_size") == 2
    pool = deployment["pool"]
    assert pool["native_completions"] == 0 and pool["wave_faults"] == 0
    assert pool["wave_jobs"] == ROUNDS * ROUND.count("wave")
    assert pool["wave_jobs"] == pool["waves"] * 4       # fill 1.0


def test_the_mesh_sized_job_takes_the_whole_mesh(deployment):
    assert len(deployment["exclusive"]) == ROUNDS * ROUND.count("mesh")
    assert deployment["dist_steps"] == len(deployment["exclusive"])
    assert all("tablet-" in tid for tid in deployment["exclusive"])


def test_pool_stage_counters_move_and_fit_the_pool_threads_wall(deployment):
    ms = deployment["stage_ms"]
    for stage in ("pool_stage", "pool_finish", "pool_wave", "pool_exclusive",
                  "pool_sched_wait", "pool_wait"):
        assert ms[stage] > 0, stage
    assert ms["pool_native"] == 0
    # self times on one thread, the scheduler's: they cannot add up to
    # more than its wall. `pool_stage` and `pool_finish` run on the
    # submitters' threads, several at once, and are in no such sum.
    assert sum(ms[s] for s in SCHEDULER_STAGES) <= deployment["wall_ms"]
    # every wave job was staged and finished by the thread that sat in
    # `DB._dispatch_compaction` for it
    pool = deployment["pool"]
    assert pool["owner_staged"] == pool["owner_finished"] \
        == pool["wave_jobs"] == ROUNDS * ROUND.count("wave")


def test_the_servers_threads_follow_the_mesh_and_only_the_mesh(deployment):
    assert len(deployment["server"].pool._threads) == 8
    plain = ServerExecutionContext(device=jax.devices()[0])
    try:
        assert plain.compaction_pool is None and plain.mesh is None
        assert len(plain.pool._threads) == flags.get_flag(
            "tserver_compaction_pool_size")
    finally:
        plain.shutdown()


def test_a_short_wave_is_held_back_briefly_and_a_full_one_not_at_all(
        monkeypatch):
    """The scheduler waits for a full wave, and not for long."""
    from yugabyte_tpu.tserver import compaction_pool
    monkeypatch.setattr(compaction_pool, "_WAVE_LINGER_S", 0.3)
    pool = CompactionPool(make_mesh(4))
    try:
        for queued, low, high in ((0, 0.3, 2.0), (4, 0.0, 0.2)):
            monkeypatch.setattr(pool, "_wave_jobs_queued_unlocked",
                                lambda queued=queued: queued)
            with pool._cond:
                t0 = time.monotonic()
                pool._linger_for_full_wave_unlocked()
                assert low <= time.monotonic() - t0 < high
    finally:
        pool.shutdown()


def test_a_short_wave_waits_for_jobs_still_with_their_owners(monkeypatch):
    """An owner that is finishing a job frees a thread that may bring the
    next one: while fewer wave jobs than slots are queued the round is
    held until the owners are done (as it was when the scheduler finished
    their jobs itself), and each one that retires starts the linger anew."""
    from yugabyte_tpu.tserver import compaction_pool
    from yugabyte_tpu.utils.cancellation import CancellationToken
    monkeypatch.setattr(compaction_pool, "_WAVE_LINGER_S", 0.1)
    pool = CompactionPool(make_mesh(4))
    job = compaction_pool._Job("t", None, compaction_pool.PoolJobHandle(
        "t", CancellationToken()))
    try:
        monkeypatch.setattr(pool, "_wave_jobs_queued_unlocked", lambda: 3)
        with pool._cond:
            pool._running["t"] = [job]
        threading.Timer(0.5, pool._retire, args=(job,)).start()
        with pool._cond:
            t0 = time.monotonic()
            pool._linger_for_full_wave_unlocked()
            assert 0.5 + 0.1 <= time.monotonic() - t0 < 3.0
    finally:
        pool.shutdown()


def test_a_closed_readers_blocks_leave_the_block_cache(tmp_path):
    """The cache is keyed by path: a tablet directory made anew must not be
    served the blocks of the file that had the path before."""
    gen = datagen.Kv64Runs(3, SHARES)
    cache = BlockCache(64 << 20)
    seen = []
    for rows in (256, 1024):
        db = DB(str(tmp_path / "regular"), DBOptions(auto_compact=False,
                                                     block_cache=cache))
        run = gen.run(rows, rows, 1_000_000)
        db.ingest_packed(run["keys_blob"], run["key_offs"], run["ht"],
                         run["wid"], run["vals_blob"], run["val_offs"],
                         op_id=(1, 1))
        path = db.versions.live_files()[0].path
        reader = SSTReader(path, cache)
        seen.append(reader.read_all().n)
        assert cache.used > 0
        reader.close()
        db.close()
        assert cache.used == 0 and not cache._map
        os.rename(str(tmp_path / "regular"), str(tmp_path / f"old-{rows}"))
    assert seen == [256, 1024]


@pytest.mark.parametrize("control,correct", [(None, True),
                                             ("history_cutoff_zero", False)])
def test_the_pool_cell_rehearses(control, correct):
    """`run.py --rehearse` takes `jax.devices()[:4]` and the server builds
    its mesh over every device JAX shows: a process of its own, with four
    CPU devices made before JAX starts."""
    import json
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cmd = [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
           "--workload", "pool.kv64-v5e4", "--seed", str(2**31 + 29),
           "--seconds", "1", "--rehearse"]
    if control:
        cmd += ["--control", control]
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600, cwd=REPO)
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode == (0 if correct else 1)
    assert line["correct"] is correct and line["rehearsal"]
    assert line["failed"] == 0 and line["attempted"] >= 18
    assert line["device"]["count"] == 4 and not line["metrics"]
    if correct:
        assert all(c["value"] == 0 for c in line["compared"].values())
    else:
        assert line["compared"]["jobs_differing_from_native"]["value"] \
            == line["attempted"]
        assert line["compared"]["native_rows_differing_from_reference"][
            "value"] == 0
