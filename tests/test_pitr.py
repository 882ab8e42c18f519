"""PITR: snapshot schedules + restore to a point in time.

The schedule substrate (catalog run_snapshot_schedules: due snapshots
taken, expired ones pruned — ref master_snapshot_coordinator.cc) and the
restore rule: the EARLIEST snapshot taken at-or-after the target time is
read AT that time — the MVCC history inside the snapshot files
reconstructs the exact state, including rows deleted after the target.
"""

import time

import pytest

from yugabyte_tpu.client.session import YBSession
from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema
from yugabyte_tpu.docdb.doc_key import DocKey
from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
from yugabyte_tpu.integration.mini_cluster import (MiniCluster,
                                                   MiniClusterOptions)
from yugabyte_tpu.tools.yb_admin import AdminClient
from yugabyte_tpu.utils import flags
from yugabyte_tpu.utils.status import StatusError

SCHEMA = Schema(
    columns=[ColumnSchema("k", DataType.STRING),
             ColumnSchema("v", DataType.STRING)],
    num_hash_key_columns=1)


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    old_rf = flags.get_flag("replication_factor")
    flags.set_flag("replication_factor", 1)
    c = MiniCluster(MiniClusterOptions(
        num_masters=1, num_tservers=1,
        fs_root=str(tmp_path_factory.mktemp("pitr")))).start()
    # every test below creates its tables in "db": the namespace belongs
    # to the fixture so each test also passes when run alone
    c.new_client().create_namespace("db")
    yield c
    c.shutdown()
    flags.set_flag("replication_factor", old_rf)


def dk(k):
    return DocKey(hash_components=(k,))


def _write(client, table, rows):
    s = YBSession(client)
    for k, v in rows:
        if v is None:
            s.apply(table, QLWriteOp(WriteOpKind.DELETE_ROW, dk(k), {}))
        else:
            s.apply(table, QLWriteOp(WriteOpKind.INSERT, dk(k), {"v": v}))
    s.flush()


def test_restore_to_time(cluster):
    client = cluster.new_client()
    table = client.create_table("db", "events", SCHEMA, num_tablets=2)
    cluster.wait_all_replicas_running(table.table_id)
    admin = AdminClient([cluster.master_addrs()[0]])

    _write(client, table, [("a", "v1"), ("b", "v1"), ("doomed", "v1")])
    time.sleep(0.02)
    t1 = int(time.time() * 1e6)          # the restore target
    time.sleep(0.02)
    # post-t1 mutations that the restore must NOT see
    _write(client, table, [("a", "v2"), ("doomed", None), ("new", "v2")])
    admin.create_snapshot("db", "events")   # snapshot AFTER t1: covers it

    admin.restore_to_time("db", "events", t1, "events_at_t1")
    restored = client.open_table("db", "events_at_t1")

    def val(t, k):
        row = client.read_row(t, dk(k))
        if row is None:
            return None
        return list(row.columns.values())[0] if row.columns else None

    assert val(restored, "a") == "v1"        # pre-overwrite value
    assert val(restored, "b") == "v1"
    assert val(restored, "doomed") == "v1"   # deletion undone
    assert val(restored, "new") is None      # post-t1 insert absent
    # live table unchanged
    assert val(table, "a") == "v2"
    assert val(table, "doomed") is None


def test_restore_requires_covering_snapshot(cluster):
    client = cluster.new_client()
    table = client.create_table("db", "nocover", SCHEMA, num_tablets=1)
    cluster.wait_all_replicas_running(table.table_id)
    admin = AdminClient([cluster.master_addrs()[0]])
    _write(client, table, [("x", "v1")])
    admin.create_snapshot("db", "nocover")
    future = int(time.time() * 1e6) + 60_000_000
    with pytest.raises(StatusError):
        admin.restore_to_time("db", "nocover", future, "nope")


def test_snapshot_schedule_takes_and_prunes(cluster):
    client = cluster.new_client()
    table = client.create_table("db", "sched", SCHEMA, num_tablets=1)
    cluster.wait_all_replicas_running(table.table_id)
    _write(client, table, [("s", "v")])
    master = cluster.leader_master()
    cat = master.catalog
    # long interval: exactly ONE snapshot is due (taken by our explicit
    # call OR by the master bg loop, whichever runs first — interval 0
    # would race the bg loop into extra snapshots)
    sched = cat.create_snapshot_schedule("db", "sched",
                                         interval_s=3600, retention_s=3600)
    try:
        cat.run_snapshot_schedules()
        deadline = time.time() + 10
        snaps = []
        while time.time() < deadline:
            snaps = [s for s in cat.list_snapshots()
                     if s.get("schedule_id") == sched["schedule_id"]]
            if snaps:
                break
            time.sleep(0.1)
        assert len(snaps) == 1
        assert snaps[0]["snapshot_micros"] > 0
        # shrink retention to zero: next tick prunes it
        sched2 = dict(sched, retention_s=0.0,
                      last_snapshot_unix=time.time() + 3600)
        with cat._lock:
            cat.sys.upsert("snapshot_schedule", sched["schedule_id"], sched2)
        time.sleep(0.01)
        cat.run_snapshot_schedules()
        snaps = [s for s in cat.list_snapshots()
                 if s.get("schedule_id") == sched["schedule_id"]]
        assert snaps == []
    finally:
        cat.delete_snapshot_schedule(sched["schedule_id"])


def test_schedule_survives_in_sys_catalog(cluster):
    master = cluster.leader_master()
    cat = master.catalog
    sched = cat.create_snapshot_schedule("db", "events", 300, 86400)
    try:
        listed = cat.list_snapshot_schedules()
        assert any(s["schedule_id"] == sched["schedule_id"] for s in listed)
    finally:
        cat.delete_snapshot_schedule(sched["schedule_id"])
    assert all(s["schedule_id"] != sched["schedule_id"]
               for s in cat.list_snapshot_schedules())


def test_schedule_retention_reaches_tablets(cluster):
    """PITR history protection: a schedule whose interval exceeds the
    history retention flag must hold tablet history cutoffs back, or
    compaction collapses the MVCC versions a restore needs (ADVICE r3;
    ref tablet_retention_policy.cc AllowedHistoryCutoff)."""
    client = cluster.new_client()
    table = client.create_table("db", "held", SCHEMA, num_tablets=1)
    cluster.wait_all_replicas_running(table.table_id)
    master = cluster.leader_master()
    cat = master.catalog
    sched = cat.create_snapshot_schedule("db", "held",
                                         interval_s=7200, retention_s=86400)
    covered = set(cat.get_table("db", "held")["tablet_ids"])
    try:
        deadline = time.time() + 10
        held = False
        while time.time() < deadline and not held:
            for ts in cluster.tservers:
                for peer in ts.tablet_manager.peers():
                    t = peer.tablet
                    if (t is not None and peer.tablet_id in covered
                            and t.retention_policy.override_s >= 7200):
                        held = True
            time.sleep(0.1)
        assert held, "retention override never reached the tablet"
        # the held-back cutoff is at least interval_s deep
        cutoff = t.retention_policy.history_cutoff()
        now_us = int(time.time() * 1e6)
        assert cutoff <= (now_us - 7200 * 1_000_000 + 2_000_000) << 12
    finally:
        cat.delete_snapshot_schedule(sched["schedule_id"])
    # deleting the schedule must RELEASE the deep retention (review r4):
    # the next heartbeat's complete map resets uncovered tablets to zero
    deadline = time.time() + 10
    released = False
    while time.time() < deadline and not released:
        released = all(
            peer.tablet.retention_policy.override_s == 0.0
            for ts in cluster.tservers
            for peer in ts.tablet_manager.peers()
            if peer.tablet is not None and peer.tablet_id in covered)
        time.sleep(0.1)
    assert released, "retention override not cleared after schedule delete"


def test_restore_below_history_floor_rejected(cluster):
    """A restore target older than the snapshot's guaranteed MVCC history
    floor must fail loudly instead of returning silently-wrong data."""
    client = cluster.new_client()
    try:
        client.create_namespace("db")
    except StatusError:
        pass  # created by an earlier test in the module-scoped cluster
    table = client.create_table("db", "floorcheck", SCHEMA, num_tablets=1)
    cluster.wait_all_replicas_running(table.table_id)
    master = cluster.leader_master()
    cat = master.catalog
    snap = cat.create_table_snapshot("db", "floorcheck")
    assert "history_floor_micros" in snap
    too_old = snap["history_floor_micros"] - 10_000_000
    with pytest.raises(StatusError) as ei:
        cat.pick_restore_snapshot("db", "floorcheck", too_old)
    assert "history floor" in str(ei.value)
