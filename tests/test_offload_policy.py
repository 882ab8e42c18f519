"""Offload routing (VERDICT r3 #2): production compactions route device
vs native from LIVE bucket-health measurement, never into a known
pessimization — the policy seam is the BucketHealthBoard
(storage/bucket_health.py), which replaced the static calibration file
in PR 16. These tests cover the policy-site plumbing: the use_device()
gate the compaction job calls, the forced-mode flags, the shared
(k_pad, m) bucket vocabulary, server-context ownership, and the
quarantine registry's restore path."""

import pytest

from yugabyte_tpu.storage import offload_policy
from yugabyte_tpu.storage.bucket_health import BucketHealthBoard, health_board
from yugabyte_tpu.utils import flags

FAM = "run_merge_fused"


@pytest.fixture(autouse=True)
def _clean_board():
    health_board().reset()
    yield
    health_board().reset()


def _warm(board, bucket, device_rate, native_rate):
    board.record_native(FAM, bucket, int(native_rate), 1.0)
    for _ in range(int(flags.get_flag("bucket_health_warmup_obs"))):
        board.record_device(FAM, bucket, int(device_rate), 1.0)
    return board


def test_unobserved_is_native():
    """VERDICT r4 #4 carried forward: without measured proof the device
    never wins a policy decision — a COLD bucket routes native (and its
    compile cost is the prewarm op's to pay, not a live job's)."""
    board = BucketHealthBoard()
    assert not board.use_device(FAM, (4, 2048), est_rows=100_000)
    assert not board.use_device(FAM, (64, 1 << 20), est_rows=10 << 20,
                                cached=True)


def test_measured_pessimization_stays_native():
    # r3's measured reality: device e2e 0.088x native
    board = _warm(BucketHealthBoard(), (64, 1 << 22),
                  device_rate=128_000, native_rate=1_450_000)
    assert board.state(FAM, (64, 1 << 22)) == "degraded"
    # deterministic: demotion stamps the probe clock, so no probe slot
    # opens within the default interval
    assert not board.use_device(FAM, (64, 1 << 22), cached=True)


def test_measured_win_offloads():
    board = _warm(BucketHealthBoard(), (64, 1 << 22),
                  device_rate=5_000_000, native_rate=1_450_000)
    assert board.use_device(FAM, (64, 1 << 22), cached=True)
    # per-bucket rule: a small bucket measured slow stays native while
    # the large winning bucket offloads
    _warm(board, (4, 1 << 14), device_rate=100_000, native_rate=1_000_000)
    assert not board.use_device(FAM, (4, 1 << 14), cached=True)
    assert board.use_device(FAM, (64, 1 << 22), cached=True)


def test_mode_flags_force():
    board = _warm(BucketHealthBoard(), (4, 2048),
                  device_rate=1, native_rate=10)  # measured: device loses
    flags.set_flag("device_offload_mode", "device")
    try:
        assert board.use_device(FAM, (4, 2048))
        assert board.use_device(FAM, (8, 4096))  # even COLD buckets
    finally:
        flags.set_flag("device_offload_mode", "auto")
    flags.set_flag("device_offload_mode", "native")
    try:
        healthy = _warm(BucketHealthBoard(), (4, 2048),
                        device_rate=10, native_rate=1)
        assert not healthy.use_device(FAM, (4, 2048))
    finally:
        flags.set_flag("device_offload_mode", "auto")


def test_bucket_key_vocabulary():
    """The (k_pad, m) vocabulary every dispatch site and the kernel
    manifest agree on: run-major padded layout, power-of-two k."""
    from yugabyte_tpu.ops.run_merge import run_bucket
    assert offload_policy.bucket_key([]) == (0, 0)
    assert offload_policy.bucket_key([100]) == (1, run_bucket(100))
    assert offload_policy.bucket_key([100, 0, 200]) \
        == (2, run_bucket(200))
    assert offload_policy.bucket_key([10, 10, 10, 10, 10])[0] == 8
    assert offload_policy.point_read_bucket_key(4096) == (1, 4096)


def test_compaction_job_cold_routes_native(tmp_path, monkeypatch):
    """run_compaction_job on a COLD (never-measured) bucket must not
    touch the device kernel at all."""
    import jax

    from yugabyte_tpu.integration.synth import (attach_values, split_runs,
                                                synth_ycsb_runs)
    from yugabyte_tpu.ops import run_merge
    from yugabyte_tpu.storage.compaction import run_compaction_job
    from yugabyte_tpu.storage.sst import Frontier, SSTReader, SSTWriter

    n = 4096
    slab, offsets = synth_ycsb_runs(n, 4, n // 2, seed=3)
    attach_values(slab, 16)
    paths = []
    runs = split_runs(slab, offsets)
    for i, sub in enumerate(runs):
        p = str(tmp_path / f"{i:06d}.sst")
        SSTWriter(p).write(sub, Frontier())
        paths.append(p)

    def boom(*a, **k):
        raise AssertionError("device kernel invoked on a COLD bucket")
    monkeypatch.setattr(run_merge, "merge_and_gc_runs", boom)
    monkeypatch.setattr(run_merge, "launch_merge_gc", boom)

    board = health_board()
    readers = [SSTReader(p) for p in paths]
    ids = iter(range(1, 100))
    out = tmp_path / "out"
    out.mkdir()
    res = run_compaction_job(readers, str(out), lambda: next(ids),
                             (10_000_000 << 12), True,
                             device=jax.devices()[0],
                             offload_policy=board)
    for r in readers:
        r.close()
    assert res.rows_out > 0
    # the native completion fed the board's LIVE native EWMA, and the
    # bucket is now a prewarm candidate
    qkey = offload_policy.bucket_key(
        run_merge.packed_run_ns([r.n for r in runs]))
    snap = {(k["family"], tuple(k["bucket"])): k
            for k in board.snapshot()["keys"]}
    assert snap[(FAM, qkey)]["native_obs"] >= 1
    assert (FAM, qkey) in board.prewarm_priorities()


def test_compaction_job_measured_demotion_routes_native(
        tmp_path, monkeypatch):
    """A bucket the board measured as a pessimization routes native
    pre-dispatch — no kernel launch, no staging."""
    import jax

    from yugabyte_tpu.integration.synth import (attach_values, split_runs,
                                                synth_ycsb_runs)
    from yugabyte_tpu.ops import run_merge
    from yugabyte_tpu.storage.compaction import run_compaction_job
    from yugabyte_tpu.storage.sst import Frontier, SSTReader, SSTWriter

    n = 4096
    slab, offsets = synth_ycsb_runs(n, 4, n // 2, seed=5)
    attach_values(slab, 16)
    paths = []
    runs = split_runs(slab, offsets)
    for i, sub in enumerate(runs):
        p = str(tmp_path / f"{i:06d}.sst")
        SSTWriter(p).write(sub, Frontier())
        paths.append(p)
    qkey = offload_policy.bucket_key(
        run_merge.packed_run_ns([r.n for r in runs]))
    board = health_board()
    _warm(board, qkey, device_rate=1_000, native_rate=1_000_000)
    assert board.state(FAM, qkey) == "degraded"

    def boom(*a, **k):
        raise AssertionError("device kernel invoked on a DEGRADED bucket")
    monkeypatch.setattr(run_merge, "merge_and_gc_runs", boom)
    monkeypatch.setattr(run_merge, "launch_merge_gc", boom)

    readers = [SSTReader(p) for p in paths]
    ids = iter(range(1, 100))
    out = tmp_path / "out"
    out.mkdir()
    res = run_compaction_job(readers, str(out), lambda: next(ids),
                             (10_000_000 << 12), True,
                             device=jax.devices()[0],
                             offload_policy=board)
    for r in readers:
        r.close()
    assert res.rows_out > 0


def test_server_context_owns_board():
    import jax

    from yugabyte_tpu.tserver.server_context import ServerExecutionContext
    ctx = ServerExecutionContext(device=jax.devices()[0])
    try:
        assert ctx.health_board is health_board()
        opts = ctx.tablet_options()
        assert opts.offload_policy is health_board()
    finally:
        ctx.shutdown()


def test_quarantine_registry_is_the_boards():
    """bucket_quarantine() and the board share ONE memory of poisoned
    buckets — a legacy quarantine shows up as board state and decays
    into PROBATION through the board's machinery."""
    q = offload_policy.bucket_quarantine()
    assert q is health_board().quarantine_registry()
    q.quarantine((4, 2048), reason="legacy fault", ttl_s=60.0)
    assert not health_board().allow_device(FAM, (4, 2048))
    assert health_board().state(FAM, (4, 2048)) == "quarantined"
    # snapshot carries the registry entry for /compactionz and /healthz
    snap = health_board().snapshot()
    assert [e for e in snap["quarantine"]
            if tuple(e["bucket"]) == (4, 2048)]


def test_quarantine_restore_reopens_window_without_counter():
    import time

    q = offload_policy.BucketQuarantine()
    added0 = offload_policy._quarantine_counter("added").value()
    q.restore((4, 2048), reason="restored", faults=3, remaining_s=60.0)
    assert q.is_quarantined((4, 2048))
    assert offload_policy._quarantine_counter("added").value() == added0, \
        "a restart is not a new fault: restore must not bump the counter"
    snap = q.snapshot()
    assert snap[0]["faults"] == 3 and snap[0]["reason"] == "restored"
    # a zero-remaining restore decays on first check
    q2 = offload_policy.BucketQuarantine()
    q2.restore((8, 2048), reason="stale", faults=1, remaining_s=0.0)
    time.sleep(0.01)
    assert not q2.is_quarantined((8, 2048))


def test_declared_surface_covers_dispatch_vocabulary():
    """Every family the dispatch sites route through must speak the
    manifest's (k_pad, m) vocabulary (the board keys records by it)."""
    surface = offload_policy.declared_surface_keys()
    if not surface:
        pytest.skip("no committed kernel manifest")
    counts = offload_policy.declared_surface_counts()
    assert counts, "manifest declares families"
    # sanity: manifest keys are (k_pad, m) int pairs
    assert all(len(k) == 2 and all(isinstance(x, int) for x in k)
               for k in surface)
