"""MiniCluster: real Master + TabletServer objects in one process.

Capability parity with the reference test harness (ref:
integration-tests/mini_cluster.h:101-120 — in-process multi-node cluster on
loopback RPC with ephemeral ports; MiniMaster / MiniTabletServer
tserver/mini_tablet_server.h). This is the primary multi-node test vehicle:
everything uses real sockets, real WALs, real Raft — only the process
boundary is collapsed.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from yugabyte_tpu.client.client import YBClient
from yugabyte_tpu.master.master import Master, MasterOptions
from yugabyte_tpu.tserver.tablet_server import (
    TabletServer, TabletServerOptions)
from yugabyte_tpu.utils.status import Status, StatusError


@dataclass
class MiniClusterOptions:
    num_masters: int = 1
    num_tservers: int = 3
    fs_root: str = "/tmp/ybtpu-minicluster"
    tablet_options_factory: Optional[Callable] = None


class MiniCluster:
    def __init__(self, opts: MiniClusterOptions):
        self.opts = opts
        self.masters: List[Master] = []
        self.tservers: List[TabletServer] = []
        self._clients: List[YBClient] = []

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "MiniCluster":
        master_ids = [f"m{i}" for i in range(self.opts.num_masters)]
        for mid in master_ids:
            self.masters.append(Master(MasterOptions(
                master_id=mid,
                fs_root=os.path.join(self.opts.fs_root, mid),
                master_ids=master_ids)))
        addr_map = {m.master_id: m.address for m in self.masters}
        for m in self.masters:
            m.set_master_addrs(addr_map)
            m.start()
        deadline = time.monotonic() + 30
        while not any(m.catalog.is_leader() for m in self.masters):
            if time.monotonic() > deadline:
                raise StatusError(Status.TimedOut("no master leader"))
            time.sleep(0.01)
        for i in range(self.opts.num_tservers):
            self.add_tablet_server()
        self._wait_tservers_registered()
        return self

    def _wait_tservers_registered(self, timeout_s: float = 30.0) -> None:
        """Block until the master leader lists every tserver live: a
        tserver's start-up heartbeat is one best-effort RPC, and on a
        loaded machine the first DDL otherwise races it ("need 3 live
        tservers for RF=3, have 0"). Paced by the synchronous re-beat
        itself, not by a sleep."""
        deadline = time.monotonic() + timeout_s
        want = {ts.server_id for ts in self.tservers}
        while True:
            live = {d.server_id for d in self.leader_master()
                    .catalog.ts_manager.live_descriptors()}
            missing = want - live
            if not missing:
                return
            if time.monotonic() > deadline:
                raise StatusError(Status.TimedOut(
                    f"tservers never registered: {sorted(missing)}"))
            for ts in self.tservers:
                if ts.server_id in missing:
                    ts.heartbeater.heartbeat_now()

    def add_tablet_server(self) -> TabletServer:
        sid = f"ts{len(self.tservers)}"
        ts = TabletServer(TabletServerOptions(
            server_id=sid,
            fs_root=os.path.join(self.opts.fs_root, sid),
            master_addrs=self.master_addrs(),
            tablet_options_factory=self.opts.tablet_options_factory))
        ts.start()
        self.tservers.append(ts)
        return ts

    def restart_tablet_server(self, index: int) -> TabletServer:
        """Stop and recreate a tserver over the same data dirs (crash
        recovery path: WAL replay + catalog re-registration)."""
        old = self.tservers[index]
        sid, fs_root = old.server_id, old.opts.fs_root
        old.shutdown()
        ts = TabletServer(TabletServerOptions(
            server_id=sid, fs_root=fs_root,
            master_addrs=self.master_addrs(),
            tablet_options_factory=self.opts.tablet_options_factory))
        ts.start()
        self.tservers[index] = ts
        return ts

    def master_addrs(self) -> List[str]:
        return [m.address for m in self.masters]

    def leader_master(self) -> Master:
        for m in self.masters:
            if m.catalog.is_leader():
                return m
        raise StatusError(Status.NotFound("no master leader"))

    def new_client(self) -> YBClient:
        client = YBClient(self.master_addrs())
        self._clients.append(client)
        return client

    # -------------------------------------------------------------- helpers
    def wait_all_replicas_running(self, table_id: str,
                                  timeout_s: float = 30.0) -> None:
        """Block until every tablet of the table has all replicas created
        and a ready leader (the reference's WaitForTabletsRunning)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                locs = self.leader_master().catalog.get_table_locations(
                    table_id)
            except StatusError:
                time.sleep(0.05)
                continue
            hosted = {}
            for ts in self.tservers:
                for tid in ts.tablet_manager.tablet_ids():
                    hosted.setdefault(tid, set()).add(ts.server_id)
            ok = True
            for loc in locs:
                have = hosted.get(loc["tablet_id"], set())
                if not set(s["server_id"] for s in loc["replicas"]) <= have:
                    ok = False
                    break
                if loc["leader"] is None:
                    ok = False
                    break
            if ok:
                return
            time.sleep(0.05)
        raise StatusError(Status.TimedOut(
            f"replicas of {table_id} not all running"))

    def wait_for_table_leaders(self, namespace: str, name: str,
                               timeout_s: float = 30.0) -> List[str]:
        """Deadline-poll until EVERY tablet of `namespace.name` has a
        READY leader; returns the tablet ids.

        The table-level form of wait_for_tablet_leader — the deflake
        primitive for tests that CREATE TABLE (possibly via a query
        layer) and immediately write: on a loaded single-core runner a
        fresh tablet's first election can outlast the client retry
        budget, so the write races the election (the known tier-1
        leadership-timing flake)."""
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                cat = self.leader_master().catalog
                table = cat.get_table(namespace, name)
                tablet_ids = list(table["tablet_ids"])
                break
            except (StatusError, StopIteration):
                if time.monotonic() > deadline:
                    raise StatusError(Status.TimedOut(
                        f"table {namespace}.{name} not in catalog within "
                        f"{timeout_s}s"))
                time.sleep(0.02)
        for tid in tablet_ids:
            self.wait_for_tablet_leader(
                tid, timeout_s=max(0.1, deadline - time.monotonic()))
        return tablet_ids

    def wait_for_tablet_leader(self, tablet_id: str,
                               timeout_s: float = 30.0,
                               exclude: Optional[set] = None) -> str:
        """Deadline-poll the live tservers' raft state until one reports
        READY leadership for `tablet_id`; returns its server_id.

        This is the deflake primitive for leader-failover tests: on a
        loaded single-core CI machine an election can outlast the
        client's retry budget, so a test that kills a leader and
        immediately writes races the election (the known tier-1 flake).
        Polling actual leader state — instead of a fixed sleep or retry
        exhaustion — makes the wait exactly as long as the election."""
        exclude = exclude or set()
        deadline = time.monotonic() + timeout_s
        while True:
            for ts in self.tservers:
                if ts.server_id in exclude:
                    continue
                try:
                    if tablet_id not in ts.tablet_manager.tablet_ids():
                        continue
                    peer = ts.tablet_manager.get_tablet(tablet_id)
                    if peer.raft.is_leader() and peer.raft.leader_ready():
                        return ts.server_id
                except Exception:
                    continue  # server mid-shutdown/bootstrap: keep polling
            if time.monotonic() > deadline:
                raise StatusError(Status.TimedOut(
                    f"no ready leader for tablet {tablet_id} within "
                    f"{timeout_s}s"))
            time.sleep(0.02)

    def shutdown(self) -> None:
        for c in self._clients:
            c.close()
        for ts in self.tservers:
            ts.shutdown()
        for m in self.masters:
            m.shutdown()
