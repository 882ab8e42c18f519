"""End-to-end data integrity (PR: robustness): shadow-verified device
kernels + the scrub-and-repair loop.

Covers the full loop at every layer:

  - at-rest corruption injection (``FaultInjectionEnv.corrupt_range`` /
    ``corrupt_file_range``) and the ``verify_sst`` deep check behind
    ``sst_dump --verify`` / ``ldb verify``;
  - read-path containment: a corrupt block routes to the background-
    error slot (sticky Corruption, in-place retry refused) and surfaces
    RETRYABLY to the client, never as a raw Corruption;
  - ``DB.scrub`` quarantining corrupt SSTs (``*.corrupt``) + the
    ``ScrubTabletsOp`` interval scheduling;
  - online shadow verification: an injected bit flip in a device-
    produced survivor chunk is caught BEFORE install, the job completes
    natively byte-identical and the shape bucket is quarantined — and
    without shadow verification the same flip lands silently (the
    surface the feature closes);
  - the cluster loop: corrupt-at-rest SST detected within one scrub
    cycle -> tablet FAILED (heartbeat-reported) -> master rebuilds the
    replica in place from a healthy peer with zero acked-write loss;
    leader-driven digest divergence detection likewise ends in a
    rebuild.
"""

import glob
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_device_fault_containment import (  # noqa: E402
    CUTOFF, _mk_run, _native_reference, _run_device_native, _sst_bytes,
    _write_runs)

from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime  # noqa: E402
from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema  # noqa: E402
from yugabyte_tpu.docdb.doc_key import DocKey, SubDocKey  # noqa: E402
from yugabyte_tpu.docdb.value import Value  # noqa: E402
from yugabyte_tpu.ops import device_faults, run_merge  # noqa: E402
from yugabyte_tpu.storage import compaction as compaction_mod  # noqa: E402
from yugabyte_tpu.storage import integrity, offload_policy  # noqa: E402
from yugabyte_tpu.storage.db import DB, DBOptions  # noqa: E402
from yugabyte_tpu.tserver.maintenance_manager import (  # noqa: E402
    MaintenanceOpStats, ScrubTabletsOp)
from yugabyte_tpu.utils import env as env_mod  # noqa: E402
from yugabyte_tpu.utils import flags  # noqa: E402
from yugabyte_tpu.utils.env import corrupt_file_range  # noqa: E402
from yugabyte_tpu.utils.status import Code, StatusError  # noqa: E402

pytestmark = pytest.mark.requires_native("compaction_engine")


@pytest.fixture(autouse=True)
def _clean_state():
    device_faults.disarm_all()
    offload_policy.bucket_quarantine().clear()
    yield
    device_faults.disarm_all()
    offload_policy.bucket_quarantine().clear()


@pytest.fixture()
def shadow_all():
    """Verify EVERY device job (tests must not depend on sampling luck)."""
    old = flags.get_flag("shadow_verify_sample")
    flags.set_flag("shadow_verify_sample", 1.0)
    yield
    flags.set_flag("shadow_verify_sample", old)


def wait_for(pred, timeout=60.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"timeout: {msg}"
        time.sleep(0.05)


def _key(i):
    return SubDocKey(DocKey(range_components=(f"r{i:04d}",)),
                     (("col", 0),)).encode(include_ht=False)


def _items(lo, hi):
    return [(_key(i), DocHybridTime(HybridTime((i + 1) << 12), 0),
             Value(primitive=f"v{i}").encode()) for i in range(lo, hi)]


def _fill_db(tmp_path, n=80):
    db = DB(str(tmp_path / "db"), DBOptions(auto_compact=False))
    db.write_batch(_items(0, n))
    db.flush()
    return db


def _data_files(db_dir):
    return sorted(glob.glob(os.path.join(db_dir, "*.sblock.0")))


# ------------------------------------------------------ at-rest corruption
class TestCorruptRange:
    def test_flips_exactly_requested_bits(self, tmp_path):
        p = str(tmp_path / "f")
        payload = bytes(range(256)) * 4
        with open(p, "wb") as f:
            f.write(payload)
        flipped = corrupt_file_range(p, offset=100, length=64, nbits=3)
        assert len(flipped) == 3
        with open(p, "rb") as f:
            got = f.read()
        assert got != payload
        diff = [i for i in range(len(payload)) if got[i] != payload[i]]
        assert diff == flipped
        for i in diff:
            assert 100 <= i < 164
            # exactly one bit differs per corrupted byte
            assert bin(got[i] ^ payload[i]).count("1") == 1

    def test_env_wrapper_counts(self, tmp_path):
        fi = env_mod.FaultInjectionEnv()
        p = str(tmp_path / "f")
        fi.write_file(p, b"x" * 100)
        fi.corrupt_range(p)
        assert fi.corruptions_injected == 1

    def test_empty_file_rejected(self, tmp_path):
        p = str(tmp_path / "f")
        open(p, "wb").close()
        with pytest.raises(ValueError):
            corrupt_file_range(p)


# ----------------------------------------------------------- verify_sst
class TestVerifySST:
    def test_clean_sst_verifies(self, tmp_path):
        db = _fill_db(tmp_path)
        try:
            base = next(iter(db._readers.values())).base_path
            rep = integrity.verify_sst(base)
            assert rep.ok, rep.errors
            assert rep.n_blocks >= 1
            assert rep.n_entries == 80
            assert rep.bytes_verified > 0
        finally:
            db.close()

    def test_data_block_bitflip_detected(self, tmp_path):
        db = _fill_db(tmp_path)
        try:
            base = next(iter(db._readers.values())).base_path
            corrupt_file_range(_data_files(db.db_dir)[0], length=16,
                               nbits=2)
            rep = integrity.verify_sst(base)
            assert not rep.ok
            assert any("block" in e for e in rep.errors), rep.errors
        finally:
            db.close()

    def test_base_file_bitflip_detected(self, tmp_path):
        db = _fill_db(tmp_path)
        try:
            base = next(iter(db._readers.values())).base_path
            # hit the index/bloom/props region (front of the base file)
            corrupt_file_range(base, offset=4, length=8, nbits=1)
            rep = integrity.verify_sst(base)
            assert not rep.ok
            assert any("base" in e for e in rep.errors), rep.errors
        finally:
            db.close()

    def test_sst_dump_verify_exit_codes(self, tmp_path, capsys):
        from yugabyte_tpu.tools import sst_dump
        db = _fill_db(tmp_path)
        try:
            base = next(iter(db._readers.values())).base_path
            assert sst_dump.main([base, "--verify"]) == 0
            corrupt_file_range(_data_files(db.db_dir)[0], nbits=1)
            assert sst_dump.main([base, "--verify"]) == 1
            out = capsys.readouterr().out
            assert "CORRUPT" in out
        finally:
            db.close()

    def test_ldb_verify_exit_codes(self, tmp_path, capsys):
        from yugabyte_tpu.tools import ldb
        db = _fill_db(tmp_path)
        db_dir = db.db_dir
        try:
            assert ldb.main(["verify", "--db", db_dir]) == 0
            corrupt_file_range(_data_files(db_dir)[0], nbits=1)
            assert ldb.main(["verify", "--db", db_dir]) == 1
            assert "CORRUPT" in capsys.readouterr().out
        finally:
            db.close()


# ------------------------------------------------- read-path containment
class TestReadPathContainment:
    def test_get_routes_corruption_retryably(self, tmp_path):
        old = flags.get_flag("read_native")
        flags.set_flag("read_native", False)  # exercise the Python path
        db = _fill_db(tmp_path)
        try:
            corrupt_file_range(_data_files(db.db_dir)[0], length=32,
                               nbits=2)
            with pytest.raises(StatusError) as ei:
                db.get(_key(10))
            # retryable to the client (walks replicas), NOT a raw
            # Corruption exception
            assert ei.value.status.code == Code.SERVICE_UNAVAILABLE
            assert db.background_error is not None
            assert db.background_error.code == Code.CORRUPTION
            # sticky: in-place retry cannot restore lost bytes
            assert db.retry_background_work() is False
            assert db.background_error is not None
        finally:
            db.close()
            flags.set_flag("read_native", old)


# ---------------------------------------------------------------- scrub
class TestDBScrub:
    def test_clean_scrub_reports_totals(self, tmp_path):
        db = _fill_db(tmp_path)
        try:
            rep = db.scrub()
            assert rep["files"] == 1 and not rep["corrupt"]
            assert rep["entries"] == 80 and rep["bytes"] > 0
            assert db.background_error is None
        finally:
            db.close()

    def test_scrub_detects_quarantines_and_parks_sticky(self, tmp_path):
        db = _fill_db(tmp_path)
        try:
            base = next(iter(db._readers.values())).base_path
            data = _data_files(db.db_dir)[0]
            corrupt_file_range(data, length=16, nbits=2)
            rep = db.scrub()
            assert rep["corrupt"] and rep["corrupt"][0]["path"] == base
            # quarantined: both halves renamed *.corrupt
            assert os.path.exists(base + ".corrupt")
            assert os.path.exists(data + ".corrupt")
            assert not os.path.exists(base) and not os.path.exists(data)
            assert any(q["path"] == base
                       for q in integrity.quarantined_files())
            # parked with the STICKY corruption error
            assert db.background_error.code == Code.CORRUPTION
            assert db.retry_background_work() is False
        finally:
            db.close()

    def test_scrub_throttles_through_limiter(self, tmp_path):
        from yugabyte_tpu.utils.rate_limiter import RateLimiter
        db = _fill_db(tmp_path)
        try:
            limiter = RateLimiter(1 << 30)
            db.scrub(limiter=limiter)
            assert limiter.total_through > 0
        finally:
            db.close()


class _StubTablet:
    def __init__(self):
        self.scrubbed = 0

    def scrub(self, limiter=None, cancel=None):
        self.scrubbed += 1
        return {"files": 1, "blocks": 2, "entries": 10, "bytes": 100,
                "corrupt": []}


class _StubRaft:
    def is_leader(self):
        return False


class _StubPeer:
    def __init__(self, tid):
        self.tablet_id = tid
        self.state = "RUNNING"
        self.tablet = _StubTablet()
        self.raft = _StubRaft()
        self.scrub_state = {}


class TestScrubOp:
    def test_interval_gating_and_rotation(self):
        old = flags.get_flag("scrub_interval_s")
        flags.set_flag("scrub_interval_s", 0.05)
        try:
            peers = [_StubPeer("t1"), _StubPeer("t2")]
            op = ScrubTabletsOp(peers_fn=lambda: peers)
            stats = MaintenanceOpStats()
            op.update_stats(stats)
            assert not stats.runnable, "nothing due right after start"
            time.sleep(0.08)
            op.update_stats(stats)
            assert stats.runnable
            op.perform()
            op.perform()
            assert peers[0].tablet.scrubbed == 1
            assert peers[1].tablet.scrubbed == 1
            assert peers[0].scrub_state["files"] == 1
            assert peers[0].scrub_state["last_scrub_ts"] > 0
            op.update_stats(stats)
            assert not stats.runnable, "both tablets freshly scrubbed"
            # FAILED tablets are skipped
            time.sleep(0.08)
            peers[0].state = peers[1].state = "FAILED"
            op.update_stats(stats)
            assert not stats.runnable
            # flag 0 disables outright
            peers[0].state = "RUNNING"
            flags.set_flag("scrub_interval_s", 0.0)
            op.update_stats(stats)
            assert not stats.runnable
        finally:
            flags.set_flag("scrub_interval_s", old)


# ------------------------------------------------ shadow verification
class TestShadowVerify:
    def test_bitflip_caught_pre_install_and_native_completion(
            self, tmp_path, shadow_all):
        """Acceptance: an injected bit flip in a device-produced survivor
        chunk is detected by shadow verification before SST install, the
        job completes natively byte-identical, and the bucket is
        quarantined."""
        rng = np.random.default_rng(21)
        runs = [_mk_run(rng, 1200, 5000) for _ in range(4)]
        readers = _write_runs(str(tmp_path), runs)
        try:
            res_native = _native_reference(readers, str(tmp_path / "nat"))
            mm0 = integrity.shadow_mismatch_counter().value()
            fb0 = compaction_mod._storage_fallback_counter().value()
            device_faults.arm("bitflip", site="survivor", count=1)
            res_dev = _run_device_native(readers, str(tmp_path / "dev"))
            assert device_faults.armed_count() == 0, \
                "the bit flip must have fired"
            assert integrity.shadow_mismatch_counter().value() == mm0 + 1
            assert compaction_mod._storage_fallback_counter().value() \
                == fb0 + 1
            # byte-identical native completion
            assert res_dev.rows_out == res_native.rows_out
            assert _sst_bytes(res_dev.outputs) \
                == _sst_bytes(res_native.outputs)
            # the shape bucket is quarantined
            qkey = offload_policy.bucket_key(run_merge.packed_run_ns(
                [r.props.n_entries for r in readers]))
            snap = offload_policy.bucket_quarantine().snapshot()
            assert [e for e in snap if tuple(e["bucket"]) == qkey], snap
        finally:
            for r in readers:
                r.close()

    def test_clean_job_verifies_byte_identical(self, tmp_path,
                                               shadow_all):
        rng = np.random.default_rng(23)
        runs = [_mk_run(rng, 1000, 4000) for _ in range(4)]
        readers = _write_runs(str(tmp_path), runs)
        try:
            res_native = _native_reference(readers, str(tmp_path / "nat"))
            jobs0 = integrity.integrity_metrics().counter(
                "shadow_verify_jobs_total", "").value()
            mm0 = integrity.shadow_mismatch_counter().value()
            res_dev = _run_device_native(readers, str(tmp_path / "dev"))
            assert _sst_bytes(res_dev.outputs) \
                == _sst_bytes(res_native.outputs)
            assert integrity.integrity_metrics().counter(
                "shadow_verify_jobs_total", "").value() == jobs0 + 1
            assert integrity.shadow_mismatch_counter().value() == mm0
            assert not offload_policy.bucket_quarantine().snapshot()
        finally:
            for r in readers:
                r.close()

    def test_unverified_bitflip_lands_silently(self, tmp_path):
        """The surface shadow verification closes: with sampling off, the
        same injected flip produces a DIFFERENT (silently corrupt) SST
        and no alarm fires."""
        old = flags.get_flag("shadow_verify_sample")
        flags.set_flag("shadow_verify_sample", 0.0)
        rng = np.random.default_rng(29)
        runs = [_mk_run(rng, 1200, 5000) for _ in range(4)]
        readers = _write_runs(str(tmp_path), runs)
        try:
            res_native = _native_reference(readers, str(tmp_path / "nat"))
            mm0 = integrity.shadow_mismatch_counter().value()
            fb0 = compaction_mod._storage_fallback_counter().value()
            device_faults.arm("bitflip", site="survivor", count=1)
            res_dev = _run_device_native(readers, str(tmp_path / "dev"))
            assert device_faults.armed_count() == 0
            assert _sst_bytes(res_dev.outputs) \
                != _sst_bytes(res_native.outputs), \
                "flip should corrupt the output when unverified"
            assert integrity.shadow_mismatch_counter().value() == mm0
            assert compaction_mod._storage_fallback_counter().value() \
                == fb0
        finally:
            flags.set_flag("shadow_verify_sample", old)
            for r in readers:
                r.close()


# ------------------------------------------------------ the cluster loop
SCHEMA = Schema(
    columns=[ColumnSchema("k", DataType.STRING),
             ColumnSchema("v", DataType.STRING)],
    num_hash_key_columns=1)


def dk(k: str) -> DocKey:
    return DocKey(hash_components=(k,))


@pytest.fixture
def cluster(tmp_path):
    from yugabyte_tpu.integration.mini_cluster import (MiniCluster,
                                                       MiniClusterOptions)
    flags.set_flag("replication_factor", 3)
    flags.set_flag("load_balancer_dead_grace_ms", 400)
    c = MiniCluster(MiniClusterOptions(
        num_masters=1, num_tservers=3,
        fs_root=str(tmp_path / "cluster"))).start()
    yield c
    flags.reset_flag("load_balancer_dead_grace_ms")
    c.shutdown()


def _tablet_peers(cluster, tablet_id):
    """(leader_ts, leader_peer, follower_ts, follower_peer)."""
    leader = follower = None
    for ts in cluster.tservers:
        peer = ts.tablet_manager.get_tablet(tablet_id)
        if peer.raft.is_leader():
            leader = (ts, peer)
        elif follower is None:
            follower = (ts, peer)
    assert leader and follower
    return (*leader, *follower)


def _checksums(cluster, client, tablet_id):
    read_ht = None
    for ts in cluster.tservers:   # pin one read time at the leader
        try:
            read_ht = client._messenger.call(
                ts.address, "tserver", "scan", tablet_id=tablet_id,
                limit=1)["read_ht"]
            break
        except StatusError:
            continue
    assert read_ht is not None, "no leader answered the read-time pin"
    sums = []
    for ts in cluster.tservers:
        resp = client._messenger.call(
            ts.address, "tserver", "checksum_tablet", timeout_s=30.0,
            tablet_id=tablet_id, read_ht=read_ht)
        sums.append(resp["checksum"])
    return sums


class TestClusterScrubRepairLoop:
    def test_corrupt_sst_detected_failed_and_rebuilt(self, cluster):
        """The acceptance loop: at-rest corruption on a follower is
        detected within one scrub cycle, the tablet goes FAILED
        (heartbeat-reported, corrupt), and the master rebuilds the
        replica in place from a healthy peer with zero acked-write
        loss."""
        client = cluster.new_client()
        client.create_namespace("db")
        from yugabyte_tpu.docdb.doc_operations import (QLWriteOp,
                                                       WriteOpKind)
        table = client.create_table("db", "t", SCHEMA, num_tablets=1)
        cluster.wait_all_replicas_running(table.table_id)
        cluster.wait_for_table_leaders("db", "t")
        acked = {}
        for i in range(120):
            client.write(table, [QLWriteOp(WriteOpKind.INSERT,
                                           dk(f"k{i:04d}"),
                                           {"v": f"v{i}"})])
            acked[f"k{i:04d}"] = f"v{i}"
        tablet_id = client.meta_cache.tablets(table.table_id)[0].tablet_id
        _lts, _lp, fts, fpeer = _tablet_peers(cluster, tablet_id)
        fpeer.tablet.flush()
        data_files = _data_files(fpeer.tablet.regular_db.db_dir)
        assert data_files, "follower flush produced no SST"
        corrupt_file_range(data_files[0], length=64, nbits=3)

        # one scrub cycle detects it
        old_interval = flags.get_flag("scrub_interval_s")
        flags.set_flag("scrub_interval_s", 0.01)
        try:
            time.sleep(0.02)
            for _ in range(4):   # rotate through hosted tablets
                fts.scrub_op.perform()
                if fpeer.state == "FAILED":
                    break
        finally:
            flags.set_flag("scrub_interval_s", old_interval)
        assert fpeer.state == "FAILED" and fpeer.failed_corrupt
        assert fpeer.tablet.regular_db.background_error.code \
            == Code.CORRUPTION
        # in-place retry refuses (sticky)
        assert not fts.tablet_manager.recover_failed_tablet(tablet_id)

        # heartbeat-reported -> master rebuilds the replica IN PLACE
        def rebuilt():
            try:
                p = fts.tablet_manager.get_tablet(tablet_id)
            except StatusError:
                return False  # mid-rebuild: torn down, not yet reopened
            return p is not fpeer and p.state == "RUNNING"
        wait_for(rebuilt, timeout=90,
                 msg="master rebuilds the corrupt replica")
        cluster.wait_all_replicas_running(table.table_id)

        # zero acked-write loss + replicas converge byte-for-byte
        for k, want in sorted(acked.items())[::10]:
            row = client.read_row(table, dk(k))
            assert row is not None
            assert row.columns[SCHEMA.column_id("v")] == want
        wait_for(lambda: len(set(_checksums(cluster, client,
                                            tablet_id))) == 1,
                 timeout=60, msg="replica digests converge after rebuild")
        # ysck-visible state: the rebuilt replica reports clean
        st = client._messenger.call(
            fts.address, "tserver", "scrub_status", tablet_id=tablet_id)
        assert st["state"] == "RUNNING" and not st["failed_corrupt"]

    def test_digest_divergence_fails_follower_for_rebuild(self, cluster):
        """Cross-replica digest exchange: a follower whose resolved rows
        diverge from the leader's is failed (corrupt) after the strike
        threshold and rebuilt from the leader."""
        client = cluster.new_client()
        client.create_namespace("db")
        from yugabyte_tpu.docdb.doc_key import split_key_and_ht
        from yugabyte_tpu.docdb.doc_operations import (QLWriteOp,
                                                       WriteOpKind)
        table = client.create_table("db", "d", SCHEMA, num_tablets=1)
        cluster.wait_all_replicas_running(table.table_id)
        cluster.wait_for_table_leaders("db", "d")
        for i in range(40):
            client.write(table, [QLWriteOp(WriteOpKind.INSERT,
                                           dk(f"k{i:04d}"),
                                           {"v": f"v{i}"})])
        tablet_id = client.meta_cache.tablets(table.table_id)[0].tablet_id
        lts, lpeer, _fts, fpeer = _tablet_peers(cluster, tablet_id)

        # no divergence: digest exchange is quiet
        assert lts._scrub_digest_check(lpeer) == 0

        # diverge the follower: rewrite an existing row's newest version
        # at a later hybrid time DIRECTLY into its DB (bypassing raft)
        ikey, value = next(fpeer.tablet.regular_db.iter_from(b""))
        prefix, dht = split_key_and_ht(ikey)
        newer = DocHybridTime(HybridTime(dht.ht.value + (1000 << 12)), 0)
        fpeer.tablet.regular_db.write_batch([(prefix, newer, value)])

        mm0 = integrity.replica_mismatch_counter().value()
        assert lts._scrub_digest_check(lpeer) >= 1   # strike 1
        assert fpeer.state == "RUNNING", "one strike must not fail it"
        assert lts._scrub_digest_check(lpeer) >= 1   # strike 2 -> FAILED
        assert integrity.replica_mismatch_counter().value() >= mm0 + 2
        wait_for(lambda: fpeer.state == "FAILED", timeout=10,
                 msg="diverged follower failed after strike threshold")
        assert fpeer.failed_corrupt

        # the master rebuilds it from the leader; digests converge
        def rebuilt():
            try:
                p = _fts.tablet_manager.get_tablet(tablet_id)
            except StatusError:
                return False
            return p is not fpeer and p.state == "RUNNING"
        wait_for(rebuilt, timeout=90, msg="diverged replica rebuilt")
        cluster.wait_all_replicas_running(table.table_id)
        wait_for(lambda: lts._scrub_digest_check(
            lts.tablet_manager.get_tablet(tablet_id)) == 0,
            timeout=60, msg="digests agree after rebuild")
