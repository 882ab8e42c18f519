"""Crash-fault tests: real processes, kill -9, torn WAL tails, crash
points (round-2 Missing #7 / Weak #7; ref src/yb/integration-tests/
external_mini_cluster.h, rocksdb/db/fault_injection_test.cc,
cluster_verifier.h).

These spawn real master/tserver subprocesses (integration/
external_mini_cluster.py) — the only way a test can kill -9 a server.
"""

import os
import time

import pytest

from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema
from yugabyte_tpu.docdb.doc_key import DocKey
from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
from yugabyte_tpu.docdb.value import Value
from yugabyte_tpu.integration.external_mini_cluster import (
    ExternalMiniCluster, _free_port, _Node)
from yugabyte_tpu.utils.status import StatusError


def _schema():
    return Schema([ColumnSchema("k", DataType.STRING),
                   ColumnSchema("v", DataType.INT64)],
                  num_hash_key_columns=1, num_range_key_columns=0)


def _op(k, v):
    return QLWriteOp(WriteOpKind.INSERT, DocKey(hash_components=(k,)),
                     {"v": v})


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = ExternalMiniCluster(
        str(tmp_path_factory.mktemp("extcluster")), num_tservers=3,
        rf=3).start()
    yield c
    c.shutdown()


def test_node_that_dies_before_ready_says_why(tmp_path):
    """A failed start carries the end of the child's stderr (it used to
    go to DEVNULL: "master m0 failed to start: ''")."""
    node = _Node("tserver", "ets9", str(tmp_path / "ts9"), _free_port(),
                 "127.0.0.1:1", 3)
    with pytest.raises(RuntimeError) as e:
        node.start(extra_flags={"no_such_flag_anywhere": 1})
    assert "tserver ets9 failed to start: ''" in str(e.value)
    assert "no_such_flag_anywhere" in str(e.value)
    assert "no_such_flag_anywhere" in open(node.stderr_path).read()
    assert not node.alive()


def _wait_writes_ok(client, table, deadline_s=60.0):
    deadline = time.monotonic() + deadline_s
    while True:
        try:
            client.write(table, [_op("warmup", 0)])
            return
        except StatusError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.3)


def test_leader_kill9_mid_load_loses_no_acked_write(cluster):
    """The headline crash test: kill -9 a tserver while a client hammers
    writes; every ACKED write must survive, and all replicas must
    converge to identical checksums."""
    cluster.wait_tservers_alive(3)
    client = cluster.new_client()
    client.create_namespace("crashns")
    table = client.create_table("crashns", "t", _schema(), num_tablets=2)
    _wait_writes_ok(client, table)
    acked = {}
    victim = 0
    killed = False
    for i in range(300):
        k = f"row{i:04d}"
        try:
            client.write(table, [_op(k, i)])
            acked[k] = i
        except StatusError:
            pass  # unacked: free to be lost or applied
        if i == 120:
            cluster.tservers[victim].kill9()   # mid-load, no warning
            killed = True
    assert killed and len(acked) > 250
    # cluster must still serve (RF=3 survives one loss)
    for k, v in list(acked.items())[:20]:
        row = client.read_row(table, DocKey(hash_components=(k,)))
        assert row is not None
    # restart the victim on its old data dir; it must catch up
    cluster.tservers[victim].start()
    _wait_writes_ok(client, table)
    # every acked write present at a consistent snapshot
    seen = {}
    for row in client.scan(table):
        d = row.to_dict(table.schema)
        if d["k"] in acked:
            seen[d["k"]] = d["v"]
    missing = {k for k in acked if k not in seen}
    assert not missing, f"lost {len(missing)} acked writes: {sorted(missing)[:5]}"
    # replicas byte-converge (incl. the restarted one)
    cluster.verify_replica_checksums(client, table)
    client.close()


def test_crash_point_mid_flush_recovers(cluster):
    """kill -9 exactly between SST write and manifest install
    (db.flush:before_manifest): the orphan SST must be ignored and every
    row recovered from the WAL."""
    cluster.wait_tservers_alive(3)
    client = cluster.new_client()
    client.create_namespace("flushns")
    table = client.create_table("flushns", "tf", _schema(), num_tablets=1)
    _wait_writes_ok(client, table)
    for i in range(40):
        client.write(table, [_op(f"pre{i:03d}", i)])
    # re-arm ts1 to die mid-flush, then force the flush path by restarting
    # it with the crash point armed (bootstrap replays then flushes on
    # write volume; drive writes until it dies)
    victim = 1
    # a tiny memstore makes the flush (and its crash point) fire quickly
    cluster.restart_tserver(victim,
                            crash_point="db.flush:before_manifest",
                            extra_flags={"memstore_size_bytes": 4096})
    deadline = time.monotonic() + 90
    i = 0
    while cluster.tservers[victim].alive():
        try:
            client.write(table, [_op(f"fl{i:05d}", i)])
            i += 1
        except StatusError:
            pass  # the victim may lead this tablet and die mid-write
        if time.monotonic() > deadline:
            pytest.fail("flush crash point did not fire in time")
    # normal restart: recovery must see every row despite the torn flush
    cluster.tservers[victim].start()
    _wait_writes_ok(client, table)
    for k, v in [("pre000", 0), (f"fl{i-1:05d}", i - 1)]:
        row = client.read_row(table, DocKey(hash_components=(k,)))
        assert row is not None, k
    cluster.verify_replica_checksums(client, table)
    client.close()


def test_torn_wal_tail_replay(cluster, tmp_path):
    """Truncate the WAL mid-record on a killed node; restart must stop at
    the torn record and rejoin, re-fetching the tail from the leader."""
    cluster.wait_tservers_alive(3)
    client = cluster.new_client()
    client.create_namespace("tornns")
    table = client.create_table("tornns", "tt", _schema(), num_tablets=1)
    _wait_writes_ok(client, table)
    for i in range(60):
        client.write(table, [_op(f"w{i:03d}", i)])
    victim = 2
    cluster.tservers[victim].kill9()
    # tear the last WAL segment of every tablet dir on the victim
    root = cluster.tservers[victim].fs_root
    torn = 0
    for dirpath, _dirs, files in os.walk(root):
        wals = sorted(f for f in files if f.startswith("wal-"))
        if wals and dirpath.endswith("wal"):
            p = os.path.join(dirpath, wals[-1])
            size = os.path.getsize(p)
            if size > 7:
                with open(p, "r+b") as f:
                    f.truncate(size - 7)  # mid-record
                torn += 1
    assert torn > 0, "no WAL segment found to tear"
    cluster.tservers[victim].start()
    _wait_writes_ok(client, table)
    # all rows still readable; replicas reconverge (the torn replica
    # re-replicates its missing tail from the leader)
    for i in range(0, 60, 7):
        row = client.read_row(table,
                              DocKey(hash_components=(f"w{i:03d}",)))
        assert row is not None
    cluster.verify_replica_checksums(client, table)
    client.close()


def test_master_kill9_and_restart(cluster):
    """The control plane dies and returns: data plane writes keep working
    (leaders keep leases without the master), and DDL works again after
    the master restarts on its sys catalog."""
    cluster.wait_tservers_alive(3)
    client = cluster.new_client()
    client.create_namespace("mns")
    table = client.create_table("mns", "tm", _schema(), num_tablets=1)
    _wait_writes_ok(client, table)
    cluster.master.kill9()
    # data path unaffected by a dead master (locations already cached)
    for i in range(10):
        client.write(table, [_op(f"m{i}", i)])
    cluster.master.start()
    client2 = cluster.new_client()
    deadline = time.monotonic() + 60
    while True:
        try:
            client2.create_namespace("mns2")
            break
        except StatusError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.5)
    t2 = client2.open_table("mns", "tm")
    row = client2.read_row(t2, DocKey(hash_components=("m3",)))
    assert row is not None and row.to_dict(t2.schema)["v"] == 3
    client.close()
    client2.close()
