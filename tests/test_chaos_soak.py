"""Crash-recovery chaos soak (PR: robustness) — the distributed twin of
PR 1's disk-fault harness.

A write workload runs against an RF3 MiniCluster while the nemesis
drives five consecutive fault cycles:

  1. tserver crash-stop mid-load + restart (WAL replay / catch-up),
  2. raft leader partition (a new leader must emerge in the connected
     majority; the stale leader rejoins on heal),
  3. injected ENOSPC on SST writes + device faults in the stage-B
     kernel path while compactions run under device_offload_mode=device
     (background-error containment + mid-job native fallback +
     shape-bucket quarantine underneath),
  4. at-rest corruption nemesis: bit-flips in a follower's written SST
     bytes, detected by one scrub cycle -> replica FAILED (corrupt) ->
     master rebuilds it in place from a healthy peer,
  5. slow-bucket nemesis: the 'slow' fault kind throttles the device
     dispatch path (latency only, no exception) with measured routing
     live — the bucket-health board must demote the slowed merge
     buckets, park their jobs on the native path, and re-promote them
     via a winning sampled probe once the slowness clears.

Invariants asserted after the cycles heal:
  - every ACKNOWLEDGED write is readable with its last-acked value,
  - raft terms never regress across any cycle,
  - all tablets converge RUNNING with ready leaders,
  - zero UNDETECTED mismatches: cross-replica digests agree on every
    tablet after the corruption cycle heals,
  - the host staging pool has zero leaked leases.

Slow-marked (tier-2): run with
  pytest tests/test_chaos_soak.py -m slow
YBTPU_SOAK_SECONDS scales the per-cycle hold (default ~3s).
"""

import os
import threading
import time

import pytest

from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema
from yugabyte_tpu.docdb.doc_key import DocKey
from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
from yugabyte_tpu.integration.chaos import NemesisController
from yugabyte_tpu.integration.mini_cluster import (MiniCluster,
                                                   MiniClusterOptions)
from yugabyte_tpu.ops import device_faults
from yugabyte_tpu.storage import offload_policy
from yugabyte_tpu.storage.bucket_health import health_board
from yugabyte_tpu.storage.device_cache import host_staging_pool
from yugabyte_tpu.utils import env as env_mod
from yugabyte_tpu.utils import flags

SCHEMA = Schema(
    columns=[ColumnSchema("k", DataType.STRING),
             ColumnSchema("v", DataType.STRING)],
    num_hash_key_columns=1)


def dk(k: str) -> DocKey:
    return DocKey(hash_components=(k,))


class _Workload:
    """Sequential acked-write tracker: only writes the cluster ACKED are
    recorded, so the post-heal verification is exactly the durability
    contract (an unacked write may or may not survive)."""

    def __init__(self, client, table):
        self.client = client
        self.table = table
        self.acked = {}          # key -> last acked value (writer-only)
        self.attempts = 0
        self.errors = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="chaos-soak-writer")

    def _run(self):
        i = 0
        while not self._stop.is_set():
            key, val = f"k{i % 500:04d}", f"v{i}"
            self.attempts += 1
            try:
                self.client.write(self.table, [QLWriteOp(
                    WriteOpKind.INSERT, dk(key), {"v": val})])
                self.acked[key] = val
            except Exception:
                # fault window: not acked, not recorded — the client's
                # replica walk + backoff already retried under the hood
                self.errors += 1
                time.sleep(0.05)
            i += 1

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=15)
        return dict(self.acked)


@pytest.mark.slow
@pytest.mark.requires_native("compaction_engine")
def test_chaos_soak_three_nemesis_cycles(tmp_path):
    hold = float(os.environ.get("YBTPU_SOAK_SECONDS", 3))
    old_flags = {f: flags.get_flag(f) for f in
                 ("replication_factor", "memstore_size_bytes",
                  "device_offload_mode", "bucket_health_probe_interval_s")}
    flags.set_flag("replication_factor", 3)
    flags.set_flag("memstore_size_bytes", 16384)  # force flush/compaction
    flags.set_flag("device_offload_mode", "device")  # kernel path live
    fi_env = env_mod.FaultInjectionEnv()
    env_mod.set_env(fi_env)
    device_faults.disarm_all()
    offload_policy.bucket_quarantine().clear()

    cluster = MiniCluster(MiniClusterOptions(
        num_tservers=3, fs_root=str(tmp_path / "cluster"))).start()
    nem = NemesisController(cluster, seed=7)
    workload = None
    try:
        client = cluster.new_client()
        client.create_namespace("db")
        table = client.create_table("db", "soak", SCHEMA, num_tablets=2)
        cluster.wait_all_replicas_running(table.table_id)
        tablet_id = client.meta_cache.tablets(table.table_id)[0].tablet_id

        workload = _Workload(cluster.new_client(), table).start()
        time.sleep(hold)  # baseline load before the first fault

        # ---- cycle 1: tserver crash-stop + restart ------------------
        terms = nem.capture_terms()
        nem.kill_tserver(1)
        time.sleep(hold)
        nem.restart_tserver(1)
        nem.wait_all_healthy(table.table_id, timeout_s=90)
        after = nem.capture_terms()
        nem.check_terms_monotonic(terms, after)

        # ---- cycle 2: raft leader partition -------------------------
        terms = after
        old_leader = nem.partition_leader(tablet_id)
        new_leader = cluster.wait_for_tablet_leader(
            tablet_id, timeout_s=45, exclude={old_leader})
        assert new_leader != old_leader
        time.sleep(hold)
        nem.heal()
        nem.wait_all_healthy(table.table_id, timeout_s=90)
        after = nem.capture_terms()
        nem.check_terms_monotonic(terms, after)

        # ---- cycle 3: ENOSPC + device faults during compaction ------
        terms = after
        fi_env.set_fault("enospc", path_filter=".sst", count=2)
        device_faults.arm("runtime", site="result", count=2)
        device_faults.arm("compile", site="dispatch", count=1)
        time.sleep(hold * 2)  # flushes + compactions under fault
        fi_env.clear_faults()
        device_faults.disarm_all()
        nem.wait_all_healthy(table.table_id, timeout_s=120)
        nem.check_terms_monotonic(terms, nem.capture_terms())

        # ---- cycle 4: at-rest corruption nemesis --------------------
        # bit-flip a FOLLOWER replica's written SST bytes, then force a
        # scrub cycle: detection must fail the replica (sticky corrupt)
        # and the master must rebuild it from a healthy peer.
        terms = nem.capture_terms()
        follower_ts = follower_peer = None
        for ts in cluster.tservers:
            peer = ts.tablet_manager.get_tablet(tablet_id)
            if not peer.raft.is_leader():
                follower_ts, follower_peer = ts, peer
                break
        assert follower_ts is not None
        follower_peer.tablet.flush()   # ensure at-rest bytes exist
        import glob as _glob
        data_files = sorted(_glob.glob(os.path.join(
            follower_peer.tablet.regular_db.db_dir, "*.sblock.0")))
        assert data_files, "follower flush produced no SST to corrupt"
        for path in reversed(data_files):  # newest first: a concurrent
            try:                           # compaction may eat the old
                fi_env.corrupt_range(path, length=64, nbits=3)
                break
            except OSError:
                continue
        old_scrub = flags.get_flag("scrub_interval_s")
        flags.set_flag("scrub_interval_s", 0.01)
        try:
            time.sleep(0.02)
            deadline = time.monotonic() + 30
            while follower_peer.state != "FAILED" \
                    and time.monotonic() < deadline:
                follower_ts.scrub_op.perform()
                time.sleep(0.1)
        finally:
            flags.set_flag("scrub_interval_s", old_scrub)
        assert follower_peer.state == "FAILED" \
            and follower_peer.failed_corrupt, \
            "scrub cycle must detect the corrupted SST"
        # master rebuild loop: the replica comes back RUNNING on a NEW
        # peer object with the corruption gone
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            try:
                p = follower_ts.tablet_manager.get_tablet(tablet_id)
                if p is not follower_peer and p.state == "RUNNING":
                    break
            except Exception:
                pass  # mid-rebuild
            time.sleep(0.2)
        nem.wait_all_healthy(table.table_id, timeout_s=120)
        nem.check_terms_monotonic(terms, nem.capture_terms())

        # ---- cycle 5: slow-bucket nemesis ---------------------------
        # Flip to MEASURED routing (the forced-device mode above was
        # cycle 3's kernel-path coverage) and throttle the device
        # dispatch with latency only: the health board must demote the
        # slowed merge buckets on the rate crossover, complete their
        # parked jobs natively (observable: record_native fires on the
        # degraded keys), then re-promote via a winning probe once the
        # slowness clears. Byte correctness of the parked completions
        # rides the verification below — acked reads plus the
        # cross-replica digest agreement cover every SST written here.
        board = health_board()
        flags.set_flag("device_offload_mode", "auto")
        # cycles 1-4 may have parked merge buckets behind a 300s fault
        # quarantine — that memory is THEIR proof, not this cycle's
        # subject: wipe the board so measured routing restarts live
        offload_policy.bucket_quarantine().clear()

        def _merge_keys(snap):
            return [k for k in snap["keys"]
                    if k["family"] == "run_merge_fused"]

        def _degraded(snap):
            return [k for k in snap["keys"]
                    if k["family"] == "run_merge_fused"
                    and k["state"] == "degraded"]

        deadline = time.monotonic() + 90
        while not _merge_keys(board.snapshot()) \
                and time.monotonic() < deadline:
            time.sleep(0.2)
        snap = board.snapshot()
        assert _merge_keys(snap), \
            "soak produced no merge-bucket traffic to throttle"
        # Seed each observed bucket barely-HEALTHY: native EWMA at its
        # live value (or a high floor), device just above it. The next
        # throttled completion folds ~0.7x into the device EWMA and
        # crosses below native — so demotion fires on a REAL measured
        # device completion, not on synthetic numbers.
        warm = int(flags.get_flag("bucket_health_warmup_obs"))
        for k in _merge_keys(snap):
            b = tuple(k["bucket"])
            rate = float(k["native_rows_per_sec"])
            if rate <= 0:
                board.record_native("run_merge_fused", b, 10**6, 1.0)
                rate = 1e6
            for _ in range(warm):
                board.record_device("run_merge_fused", b,
                                    int(rate * 1.05) + 1, 1.0)
        snap = board.snapshot()
        base = {tuple(k["bucket"]): k["native_obs"]
                for k in _merge_keys(snap)}
        demo0 = snap["counters"]["demotions"]
        promo0 = snap["counters"]["promotions"]
        device_faults.arm("slow", "dispatch", count=10**6, delay_s=0.05)
        deadline = time.monotonic() + 120
        while board.snapshot()["counters"]["demotions"] == demo0 \
                and time.monotonic() < deadline:
            time.sleep(0.2)
        snap = board.snapshot()
        assert snap["counters"]["demotions"] > demo0, \
            "slow nemesis did not demote any merge bucket"
        assert _degraded(snap)
        # parked jobs complete NATIVELY and the board measures them:
        # native_obs on a degraded bucket growing past its seed proves
        # a real native completion (no faults armed, no other recorder)
        deadline = time.monotonic() + 120
        parked = False
        while not parked and time.monotonic() < deadline:
            snap = board.snapshot()
            parked = any(k["native_obs"] > base[tuple(k["bucket"])]
                         for k in _degraded(snap)
                         if tuple(k["bucket"]) in base)
            if not parked:
                time.sleep(0.2)
        assert parked, \
            "no parked native completion observed on a degraded bucket"

        # the device recovers: clear the slowness, drag the seeded
        # native EWMAs back down, and let a sampled probe win (the
        # promotion event only fires from a REAL job's device result)
        device_faults.disarm_all()
        flags.set_flag("bucket_health_probe_interval_s", 0.0)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            snap = board.snapshot()
            if snap["counters"]["promotions"] > promo0:
                break
            for k in _degraded(snap):
                board.record_native("run_merge_fused", tuple(k["bucket"]),
                                    1, 1000.0)
            time.sleep(0.05)
        snap = board.snapshot()
        assert snap["counters"]["promotions"] > promo0, \
            "cleared bucket did not re-promote via a winning probe: " \
            f"counters={snap['counters']} states={snap['states']} " \
            f"merge_keys={_merge_keys(snap)!r}"
        nem.wait_all_healthy(table.table_id, timeout_s=90)

        # ---- verification -------------------------------------------
        acked = workload.stop()
        workload = None
        assert len(acked) >= 10, \
            f"soak produced too few acked writes: {len(acked)}"
        missing = []
        for key, want in sorted(acked.items()):
            row = client.read_row(table, dk(key))
            got = None if row is None else \
                row.columns[SCHEMA.column_id("v")]
            # the writer may have acked a NEWER value for this key after
            # the snapshot, but never an older one — compare sequence no.
            if got is None or int(got[1:]) < int(want[1:]):
                missing.append((key, want, got))
        assert not missing, \
            f"acknowledged writes lost after heal: {missing[:10]}"
        # zero UNDETECTED mismatches: after the corruption cycle healed,
        # every tablet's replicas agree digest-for-digest at one pinned
        # read time (divergence the loop failed to repair would show
        # here)
        from yugabyte_tpu.utils.status import StatusError
        for tid in client.meta_cache.tablets(table.table_id):
            read_ht = None
            for ts in cluster.tservers:  # pin one read time (leader-only)
                try:
                    read_ht = client._messenger.call(
                        ts.address, "tserver", "scan",
                        tablet_id=tid.tablet_id, limit=1)["read_ht"]
                    break
                except StatusError:
                    continue
            assert read_ht is not None, f"no leader for {tid.tablet_id}"
            sums = set()
            for ts in cluster.tservers:
                sums.add(client._messenger.call(
                    ts.address, "tserver", "checksum_tablet",
                    timeout_s=60.0, tablet_id=tid.tablet_id,
                    read_ht=read_ht)["checksum"])
            assert len(sums) == 1, \
                f"undetected replica divergence on {tid.tablet_id}: {sums}"
        assert host_staging_pool().outstanding() == 0, \
            "staging-pool leases leaked during the chaos run"
    finally:
        if workload is not None:
            workload.stop()
        nem.close()
        cluster.shutdown()
        env_mod.set_env(env_mod.Env())
        device_faults.disarm_all()
        offload_policy.bucket_quarantine().clear()
        for f, v in old_flags.items():
            flags.set_flag(f, v)
