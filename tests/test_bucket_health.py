"""Bucket-health board (PR: robustness): the live device-vs-native
routing authority that replaced the frozen calibration file.

One health record per (kernel family, shape bucket) runs the state
machine COLD -> WARMING -> HEALTHY <-> DEGRADED -> QUARANTINED ->
PROBATION -> HEALTHY, fed by measured rows/s EWMAs, fault events and
sticky shadow mismatches. These tests drive the machine directly on
private board instances (injectable clock for the probe timing), stress
the quarantine registry's timed-decay under churn, round-trip the
persisted board, and — the nemesis proof — throttle ONE shape bucket's
device dispatch with the 'slow' fault kind and watch the full
self-healing cycle: demote, complete natively byte-identical, re-promote
via a winning probe.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_device_fault_containment import (_mk_run, _native_reference,  # noqa: E402
                                           _run_device_native, _sst_bytes,
                                           _write_runs)

from yugabyte_tpu.ops import device_faults, run_merge  # noqa: E402
from yugabyte_tpu.storage import offload_policy  # noqa: E402
from yugabyte_tpu.storage.bucket_health import (BucketHealthBoard,  # noqa: E402
                                                health_board)
from yugabyte_tpu.storage.device_cache import host_staging_pool  # noqa: E402
from yugabyte_tpu.utils import flags  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_state():
    device_faults.disarm_all()
    health_board().reset()
    yield
    device_faults.disarm_all()
    health_board().reset()


def _warm(board, fam, b, device_rate=1000.0, native_rate=100.0):
    """Walk a key to a settled post-warmup state: HEALTHY when the
    device rate wins, DEGRADED when native does."""
    board.record_native(fam, b, int(native_rate), 1.0)
    for _ in range(int(flags.get_flag("bucket_health_warmup_obs"))):
        board.record_device(fam, b, int(device_rate), 1.0)
    return board


# -- state machine -----------------------------------------------------


def test_cold_routes_native_then_first_result_warms():
    board = BucketHealthBoard()
    fam, b = "run_merge_fused", (4, 2048)
    assert board.state(fam, b) == "cold"
    # policy gate: COLD routes native (compile cost not amortized)...
    assert not board.use_device(fam, b, est_rows=1000)
    # ...but the containment gate passes — the dispatch IS the warmup
    assert board.allow_device(fam, b)
    board.record_device(fam, b, 1000, 1.0)
    assert board.state(fam, b) == "warming"
    assert board.use_device(fam, b, est_rows=1000)


def test_warmup_guard_blocks_single_sample_demotion():
    board = BucketHealthBoard()
    fam, b = "run_merge_fused", (8, 2048)
    warmup = int(flags.get_flag("bucket_health_warmup_obs"))
    board.record_native(fam, b, 10**6, 1.0)
    for i in range(warmup - 1):
        board.record_device(fam, b, 100, 1.0)  # measured 10,000x slower
        assert board.state(fam, b) == "warming", \
            f"obs {i + 1} < warmup must not demote (cold-compile sample)"
    board.record_device(fam, b, 100, 1.0)
    assert board.state(fam, b) == "degraded"
    assert not board.use_device(fam, b, est_rows=1000)
    assert board.snapshot()["counters"]["demotions"] == 1


def test_healthy_demotes_when_native_ewma_overtakes():
    board = _warm(BucketHealthBoard(), "scan_agg", (1, 4096))
    assert board.state("scan_agg", (1, 4096)) == "healthy"
    assert board.use_device("scan_agg", (1, 4096))
    # the native path speeds up (host upgrade, lighter load): the next
    # native completions overtake the device EWMA and demote the bucket
    for _ in range(3):
        board.record_native("scan_agg", (1, 4096), 10**7, 1.0)
    assert board.state("scan_agg", (1, 4096)) == "degraded"
    snap = board.snapshot()
    assert snap["counters"]["demotions"] == 1
    assert any(t["to"] == "degraded" for t in snap["transitions"])


def test_per_key_isolation():
    board = _warm(BucketHealthBoard(), "run_merge_fused", (4, 2048),
                  device_rate=10.0, native_rate=10**6)  # degraded
    _warm(board, "run_merge_fused", (8, 2048))          # healthy
    assert board.state("run_merge_fused", (4, 2048)) == "degraded"
    assert board.state("run_merge_fused", (8, 2048)) == "healthy"
    assert board.use_device("run_merge_fused", (8, 2048))
    assert not board.use_device("run_merge_fused", (4, 2048))
    # same bucket under another family is its own record
    assert board.state("block_decode", (4, 2048)) == "cold"


# -- probe gate --------------------------------------------------------


def test_probe_gate_single_flight_backoff_and_native_gap():
    tnow = [1000.0]
    board = BucketHealthBoard(clock=lambda: tnow[0])
    fam, b = "scan_filtered", (1, 4096)
    _warm(board, fam, b, device_rate=100.0, native_rate=10**9)
    assert board.state(fam, b) == "degraded"
    interval = float(flags.get_flag("bucket_health_probe_interval_s"))

    # demotion stamps last_probe_t: the first probe waits a full interval
    assert not board.allow_device(fam, b)
    tnow[0] += interval + 1
    assert board.allow_device(fam, b), "probe slot must open"
    # single flight: a concurrent thread is refused while it's pending...
    got = []
    t = threading.Thread(target=lambda: got.append(board.allow_device(fam, b)))
    t.start()
    t.join()
    assert got == [False]
    # ...but the claiming thread (the probing job re-checks) passes
    assert board.allow_device(fam, b)

    # the probe LOSES: backoff doubles and a native gap is forced
    board.record_device(fam, b, 100, 1.0)
    assert not board.allow_device(fam, b), "native gap after a lost probe"
    tnow[0] += interval + 1
    assert not board.allow_device(fam, b), "backoff x2 not yet elapsed"
    tnow[0] += interval + 1
    assert board.allow_device(fam, b), "second probe after 2x interval"
    board.record_device(fam, b, 100, 1.0)  # loses again -> backoff x4

    # the probe WINS: backoff resets and the bucket is promoted
    tnow[0] += 4 * interval + 1
    assert not board.allow_device(fam, b)  # the forced native gap
    assert board.allow_device(fam, b)
    board.record_device(fam, b, 10**12, 0.001)
    assert board.state(fam, b) == "healthy"
    snap = board.snapshot()["counters"]
    assert snap["probes"] == 3
    assert snap["probe_failures"] == 2
    assert snap["promotions"] == 1


def test_probe_timeout_releases_wedged_slot():
    tnow = [1000.0]
    board = BucketHealthBoard(clock=lambda: tnow[0])
    fam, b = "point_read_locate", (1, 2048)
    _warm(board, fam, b, device_rate=100.0, native_rate=10**9)
    interval = float(flags.get_flag("bucket_health_probe_interval_s"))
    tnow[0] += interval + 1
    assert board.allow_device(fam, b)  # probe claimed, then the job dies
    from yugabyte_tpu.storage import bucket_health as bh
    tnow[0] += bh._PROBE_TIMEOUT_S + 1
    got = []
    t = threading.Thread(target=lambda: got.append(board.allow_device(fam, b)))
    t.start()
    t.join()
    assert got == [True], "a silently-dead probe must not wedge the bucket"


# -- fault / quarantine / mismatch ------------------------------------


def test_fault_quarantine_decays_to_probation_then_healthy():
    board = BucketHealthBoard()
    fam, b = "point_read_locate", (1, 2048)
    board.record_device(fam, b, 1000, 1.0)  # warming
    board.record_fault(fam, b, "RESOURCE_EXHAUSTED: hbm oom", ttl_s=0.05)
    assert board.state(fam, b) == "quarantined"
    assert not board.allow_device(fam, b)
    time.sleep(0.08)
    assert board.allow_device(fam, b), "decayed window re-proves on device"
    assert board.state(fam, b) == "probation"
    for _ in range(int(flags.get_flag("bucket_health_probation_obs"))):
        board.record_device(fam, b, 1000, 1.0)
    assert board.state(fam, b) == "healthy"
    snap = board.snapshot()["counters"]
    assert snap["quarantines"] == 1
    assert snap["promotions"] == 1


def test_fault_during_probation_requarantines():
    board = BucketHealthBoard()
    fam, b = "block_encode", (1, 4096)
    board.record_fault(fam, b, "boom", ttl_s=0.05)
    time.sleep(0.08)
    assert board.allow_device(fam, b)
    assert board.state(fam, b) == "probation"
    board.record_fault(fam, b, "boom again", ttl_s=60.0)
    assert board.state(fam, b) == "quarantined"
    assert not board.allow_device(fam, b)
    snap = board.snapshot()
    assert snap["counters"]["quarantines"] == 2
    assert snap["keys"][0]["faults"] == 2


def test_mismatch_sticky_until_operator_clear():
    old = flags.get_flag("device_fault_quarantine_s")
    flags.set_flag("device_fault_quarantine_s", 0.05)
    board = BucketHealthBoard()
    fam, b = "block_decode", (1, 4096)
    try:
        board.record_mismatch(fam, b, "digest mismatch vs native oracle")
        assert board.state(fam, b) == "quarantined"
        assert not board.allow_device(fam, b)
        time.sleep(0.08)  # the TIMED window decays...
        assert not board.allow_device(fam, b), \
            "sticky mismatch must outlive the timed quarantine window"
        assert board.state(fam, b) == "quarantined"
        assert board.clear_mismatch() == 1
        assert board.state(fam, b) == "probation"
        assert board.allow_device(fam, b)
        for _ in range(int(flags.get_flag("bucket_health_probation_obs"))):
            board.record_device(fam, b, 1000, 1.0)
        assert board.state(fam, b) == "healthy"
        assert board.snapshot()["counters"]["mismatch"] == 1
    finally:
        flags.set_flag("device_fault_quarantine_s", old)


def test_quarantine_rearm_survives_decay_churn():
    """PR 16 timed-decay race regression: is_quarantined used to read
    the clock OUTSIDE the registry lock, letting a decay check race a
    concurrent re-arm. Under heavy churn of expiring windows, a freshly
    re-armed LONG window must never be reported open-for-device."""
    q = offload_policy.BucketQuarantine()
    b = (4, 2048)
    stop = threading.Event()

    def churn():
        while not stop.is_set():
            q.open_window(b)

    threads = [threading.Thread(target=churn) for _ in range(4)]
    for t in threads:
        t.start()
    lost_at = None
    try:
        for i in range(200):
            q.quarantine(b, "short", ttl_s=0.0003)
            time.sleep(0.0006)  # decays under churn
            q.quarantine(b, "long", ttl_s=60.0)
            if not q.is_quarantined(b):
                lost_at = i
                break
            q.clear()
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert lost_at is None, \
        f"round {lost_at}: churn deleted a freshly re-armed window"


def test_legacy_quarantine_clear_resets_whole_board():
    """Every legacy fixture isolates itself with
    bucket_quarantine().clear() — that must wipe the WHOLE board, not
    leave buckets demoted from the previous test."""
    board = health_board()
    _warm(board, "run_merge_fused", (4, 2048),
          device_rate=10.0, native_rate=10**6)
    board.record_fault("scan_agg", (1, 4096), "boom", ttl_s=60.0)
    assert board.state("run_merge_fused", (4, 2048)) == "degraded"
    offload_policy.bucket_quarantine().clear()
    assert board.state("run_merge_fused", (4, 2048)) == "cold"
    assert board.state("scan_agg", (1, 4096)) == "cold"
    snap = board.snapshot()
    assert snap["keys"] == [] and snap["quarantine"] == []
    assert all(v == 0 for v in snap["counters"].values())


# -- persistence -------------------------------------------------------


def test_persistence_round_trip(tmp_path):
    path = str(tmp_path / "bucket_health.json")
    b1 = BucketHealthBoard()
    # a HEALTHY key with live rates
    _warm(b1, "run_merge_fused", (4, 2048))
    # a QUARANTINED key mid-window
    b1.record_fault("scan_filtered", (1, 4096), "hbm oom", ttl_s=60.0)
    # a sticky-mismatch key
    b1.record_mismatch("block_decode", (1, 8192), "digest mismatch")
    # a COLD key that only saw traffic
    assert not b1.use_device("dist_compact", (4, 1 << 20), est_rows=10)
    b1.save(path)

    b2 = BucketHealthBoard()
    assert b2.load(path) == 4
    # quarantine resumes its remaining decay window
    assert b2.state("scan_filtered", (1, 4096)) == "quarantined"
    assert not b2.allow_device("scan_filtered", (1, 4096))
    # sticky mismatch stays sticky (no timed decay)
    assert b2.state("block_decode", (1, 8192)) == "quarantined"
    assert not b2.allow_device("block_decode", (1, 8192))
    snap = {(k["family"], tuple(k["bucket"])): k
            for k in b2.snapshot()["keys"]}
    assert "mismatch" in snap[("block_decode", (1, 8192))]
    # the healthy key restarts WARMING with rates CLEARED — a restarted
    # process re-measures instead of routing on last run's numbers
    assert b2.state("run_merge_fused", (4, 2048)) == "warming"
    rec = snap[("run_merge_fused", (4, 2048))]
    assert rec["device_obs"] == 0 and rec["device_rows_per_sec"] == 0.0
    assert rec["native_obs"] == 0 and rec["native_rows_per_sec"] == 0.0
    # COLD stays COLD; fault/traffic tallies survive
    assert b2.state("dist_compact", (4, 1 << 20)) == "cold"
    assert snap[("dist_compact", (4, 1 << 20))]["traffic"] == 1
    assert snap[("scan_filtered", (1, 4096))]["faults"] == 1


def test_load_missing_or_corrupt_is_cold_start(tmp_path):
    board = BucketHealthBoard()
    assert board.load(str(tmp_path / "nope.json")) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert board.load(str(bad)) == 0
    assert board.snapshot()["keys"] == []


# -- prewarm feed ------------------------------------------------------


def test_prewarm_priorities_traffic_order_and_prewarmed_transition():
    board = BucketHealthBoard()
    for _ in range(3):
        board.use_device("run_merge_fused", (8, 2048))
    board.use_device("run_merge_fused", (4, 2048))
    for _ in range(2):
        board.use_device("scan_filtered", (1, 4096))
    pri = board.prewarm_priorities()
    assert pri[0] == ("run_merge_fused", (8, 2048))
    assert pri[1] == ("scan_filtered", (1, 4096))
    # the prewarm op pays the compile: COLD -> WARMING, off the list,
    # and the policy gate stops forcing native
    board.record_prewarmed("run_merge_fused", (8, 2048))
    assert board.state("run_merge_fused", (8, 2048)) == "warming"
    assert ("run_merge_fused", (8, 2048)) not in board.prewarm_priorities()
    assert board.use_device("run_merge_fused", (8, 2048))


# -- the 'slow' nemesis kind ------------------------------------------


def test_slow_kind_bucket_pinning():
    device_faults.arm("slow", "dispatch", count=1, delay_s=0.05,
                      bucket=(4, 2048))
    # bucket-less call sites skip pinned entries
    t0 = time.monotonic()
    device_faults.maybe_fault("dispatch")
    assert time.monotonic() - t0 < 0.04
    assert device_faults.armed_count() == 1
    # wrong bucket: skipped
    device_faults.maybe_fault("dispatch", bucket=(8, 2048))
    assert device_faults.armed_count() == 1
    # match: sleeps without raising, consumed
    t0 = time.monotonic()
    device_faults.maybe_fault("dispatch", bucket=(4, 2048))
    assert time.monotonic() - t0 >= 0.045
    assert device_faults.armed_count() == 0
    # an unpinned slow entry fires anywhere
    device_faults.arm("slow", "dispatch", count=1, delay_s=0.05)
    t0 = time.monotonic()
    device_faults.maybe_fault("dispatch")
    assert time.monotonic() - t0 >= 0.045
    assert device_faults.armed_count() == 0


def test_slow_stacks_with_loud_fault():
    """A slow AND faulty device is expressible: the slow entry sleeps,
    then the loud entry raises on the SAME call; both are consumed."""
    device_faults.arm("slow", "dispatch", count=1, delay_s=0.05)
    device_faults.arm("runtime", "dispatch", count=1)
    t0 = time.monotonic()
    with pytest.raises(Exception):
        device_faults.maybe_fault("dispatch")
    assert time.monotonic() - t0 >= 0.045
    assert device_faults.armed_count() == 0


# -- the self-healing cycle, end to end -------------------------------


@pytest.mark.requires_native("compaction_engine")
def test_slow_bucket_demotes_completes_native_and_repromotes(tmp_path):
    """The nemesis proof: throttle ONE shape bucket's device dispatch
    (no exception — just latency), watch the board demote it on the
    measured rate crossover, verify the parked job completes natively
    BYTE-IDENTICAL without touching the device, then clear the
    slowness and watch a winning probe re-promote the bucket."""
    board = health_board()
    rng = np.random.default_rng(21)
    runs = [_mk_run(rng, 1200, 5000) for _ in range(4)]
    readers = _write_runs(str(tmp_path), runs)
    qkey = offload_policy.bucket_key(
        run_merge.packed_run_ns([r.props.n_entries for r in readers]))
    old_interval = flags.get_flag("bucket_health_probe_interval_s")
    try:
        res_native = _native_reference(readers, str(tmp_path / "native"))
        # seed an astronomically fast native EWMA so the throttled
        # device path deterministically loses the rate race
        board.record_native("run_merge_fused", qkey, 10**9, 1.0)
        device_faults.arm("slow", "dispatch", count=1000, delay_s=0.05,
                          bucket=qkey)
        warmup = int(flags.get_flag("bucket_health_warmup_obs"))
        for i in range(warmup):
            res = _run_device_native(readers, str(tmp_path / f"slow{i}"),
                                     first_id=1000 * (i + 1))
            assert _sst_bytes(res.outputs) == _sst_bytes(res_native.outputs)
        assert board.state("run_merge_fused", qkey) == "degraded"
        assert device_faults.armed_count() < 1000, \
            "the pinned slow nemesis must actually have fired"

        # DEGRADED parks the next job at the containment gate: native
        # completion, byte-identical, and the still-armed slow entries
        # never fire — proof no device dispatch happened
        armed_before = device_faults.armed_count()
        res_parked = _run_device_native(readers, str(tmp_path / "parked"),
                                        first_id=7000)
        assert _sst_bytes(res_parked.outputs) == _sst_bytes(res_native.outputs)
        assert device_faults.armed_count() == armed_before, \
            "a parked job must not dispatch the device"
        assert host_staging_pool().outstanding() == 0

        # the device recovers: drag the seeded native EWMA back below
        # the measured device rate, then let a probe run and win
        device_faults.disarm_all()
        for _ in range(80):
            board.record_native("run_merge_fused", qkey, 1, 100.0)
        flags.set_flag("bucket_health_probe_interval_s", 0.0)
        res_probe = _run_device_native(readers, str(tmp_path / "probe"),
                                       first_id=9000)
        assert _sst_bytes(res_probe.outputs) == _sst_bytes(res_native.outputs)
        assert board.state("run_merge_fused", qkey) == "healthy", \
            "the winning probe must re-promote the bucket"
        tally = board.snapshot()["counters"]
        assert tally["demotions"] >= 1
        assert tally["probes"] >= 1
        assert tally["promotions"] >= 1
        assert host_staging_pool().outstanding() == 0
    finally:
        device_faults.disarm_all()
        flags.set_flag("bucket_health_probe_interval_s", old_interval)
        for r in readers:
            r.close()
