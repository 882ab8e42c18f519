"""ExternalMiniCluster: real master/tserver PROCESSES for crash testing.

Capability parity with the reference's harness (ref:
src/yb/integration-tests/external_mini_cluster.h — spawns real
yb-master/yb-tserver binaries, kills them with SIGKILL, restarts them on
the same data dirs; cluster_verifier.h — cross-replica checksum
verification). The in-process MiniCluster cannot test crashes — a Python
thread cannot be kill -9'd; these nodes can.

Crash points inside a node are armed via env (utils/sync_point.py):
    cluster.restart_tserver(0, crash_point="db.flush:before_manifest")
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

from yugabyte_tpu.client.client import YBClient


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class _Node:
    def __init__(self, role: str, server_id: str, fs_root: str, port: int,
                 master_addrs: str, rf: int):
        self.role = role
        self.server_id = server_id
        self.fs_root = fs_root
        self.port = port
        self.master_addrs = master_addrs
        self.rf = rf
        self.proc: Optional[subprocess.Popen] = None

    @property
    def address(self) -> str:
        return f"127.0.0.1:{self.port}"

    @property
    def stderr_path(self) -> str:
        """Everything the node's processes wrote to stderr, restarts
        appended."""
        return os.path.join(self.fs_root, f"{self.server_id}.stderr")

    def start(self, crash_point: Optional[str] = None,
              wait_ready: bool = True,
              extra_flags: Optional[Dict[str, object]] = None) -> None:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("YBTPU_CRASH_POINT", None)
        cmd = [sys.executable, "-m",
               "yugabyte_tpu.integration.node_runner", self.role,
               "--fs-root", self.fs_root, "--port", str(self.port),
               "--server-id", self.server_id, "--rf", str(self.rf)]
        if crash_point:
            # armed post-startup so bootstrap-time hits don't kill the
            # node before READY
            cmd += ["--crash-point", crash_point]
        for k, v in (extra_flags or {}).items():
            cmd += ["--flag", f"{k}={v}"]
        if self.master_addrs:
            cmd += ["--master-addrs", self.master_addrs]
        os.makedirs(self.fs_root, exist_ok=True)
        with open(self.stderr_path, "ab") as err:  # the child keeps its copy
            self.proc = subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=err, env=env, text=True)
        if wait_ready:
            line = self.proc.stdout.readline()
            if not line.startswith("READY"):
                try:  # it is dying: let it finish saying why
                    self.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    self.kill9()
                with open(self.stderr_path, errors="replace") as f:
                    said = "".join(f.readlines()[-20:])
                raise RuntimeError(
                    f"{self.role} {self.server_id} failed to start: "
                    f"{line!r}; its stderr ends:\n{said}")

    def kill9(self) -> None:
        """SIGKILL — no shutdown hooks, no flushes (the crash under test)."""
        if self.proc is not None:
            try:
                self.proc.kill()
            except ProcessLookupError:
                pass
            self.proc.wait()
            self.proc = None

    def wait_exit(self, timeout_s: float = 30.0) -> int:
        assert self.proc is not None
        rc = self.proc.wait(timeout=timeout_s)
        self.proc = None
        return rc

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


class ExternalMiniCluster:
    def __init__(self, fs_root: str, num_tservers: int = 3, rf: int = 3,
                 default_flags: Optional[Dict[str, object]] = None):
        """default_flags: flag overrides applied to EVERY node at start
        and restart (e.g. relaxed raft election timing for a soak on an
        oversubscribed CI core)."""
        self.fs_root = fs_root
        self.rf = rf
        self.default_flags = dict(default_flags or {})
        os.makedirs(fs_root, exist_ok=True)
        mport = _free_port()
        self.master = _Node("master", "m0",
                            os.path.join(fs_root, "master"), mport, "", rf)
        self.tservers: List[_Node] = [
            _Node("tserver", f"ets{i}", os.path.join(fs_root, f"ts{i}"),
                  _free_port(), f"127.0.0.1:{mport}", rf)
            for i in range(num_tservers)]

    def start(self) -> "ExternalMiniCluster":
        self.master.start(extra_flags=self.default_flags or None)
        for ts in self.tservers:
            ts.start(extra_flags=self.default_flags or None)
        return self

    def new_client(self) -> YBClient:
        return YBClient([self.master.address])

    def wait_tservers_alive(self, n: int, timeout_s: float = 60.0) -> None:
        """Block until the master reports >= n live tservers (fresh starts
        and post-kill restarts race heartbeat registration)."""
        client = self.new_client()
        deadline = time.monotonic() + timeout_s
        try:
            while True:
                try:
                    live = [t for t in client.list_tservers()
                            if t.get("alive")]
                    if len(live) >= n:
                        return
                except Exception:  # noqa: BLE001 — master still starting
                    pass
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"{n} live tservers not reached in {timeout_s}s")
                time.sleep(0.3)
        finally:
            client.close()

    def wait_table_leaders(self, client: YBClient, table_id: str,
                           timeout_s: float = 60.0) -> None:
        """Deadline-poll the master's location map until EVERY tablet of
        the table reports a leader (the external-cluster twin of
        MiniCluster.wait_for_table_leaders — the deflake primitive for
        create-then-write: a fresh tablet's first election can outlast a
        writer's retry budget)."""
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                locs = client._master_call("get_table_locations",
                                           table_id=table_id)
                if locs and all(loc.get("leader") for loc in locs):
                    return
            except Exception:  # noqa: BLE001 — tablets still registering
                pass
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"leaders of table {table_id} not elected in "
                    f"{timeout_s}s")
            time.sleep(0.3)

    def restart_tserver(self, i: int, crash_point: Optional[str] = None,
                        extra_flags: Optional[Dict[str, object]] = None
                        ) -> None:
        self.tservers[i].kill9()
        merged = dict(self.default_flags)
        merged.update(extra_flags or {})
        self.tservers[i].start(crash_point=crash_point,
                               extra_flags=merged or None)

    def shutdown(self) -> None:
        for ts in self.tservers:
            ts.kill9()
        self.master.kill9()

    # ------------------------------------------------------------ verifier
    def verify_replica_checksums(self, client: YBClient, table,
                                 timeout_s: float = 60.0) -> Dict[str, int]:
        """Every replica of every tablet must hold an identical committed
        state at one read time (ref cluster_verifier.h). Returns
        tablet_id -> checksum."""
        locs = client._master_call("get_table_locations",
                                   table_id=table.table_id)
        out: Dict[str, int] = {}
        deadline = time.monotonic() + timeout_s

        def _until(fn):
            while True:
                try:
                    return fn()
                except Exception:  # noqa: BLE001 — converging/failing over
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.3)

        # one read time per tablet: pinned by a leader scan (tried through
        # the replicas — whichever currently leads answers)
        for loc in locs:
            tablet_id = loc["tablet_id"]
            addrs = [rep["addr"] for rep in loc["replicas"]
                     if rep["addr"] is not None]

            def _pin_read_ht():
                last = None
                for addr in addrs:
                    try:
                        return client._messenger.call(
                            addr, "tserver", "scan", tablet_id=tablet_id,
                            limit=1)["read_ht"]
                    except Exception as e:  # noqa: BLE001 — not the leader
                        last = e
                raise last  # type: ignore[misc]

            read_ht = _until(_pin_read_ht)
            sums = {}
            for rep in loc["replicas"]:
                addr = rep["addr"]
                if addr is None:
                    continue
                resp = _until(lambda a=addr: client._messenger.call(
                    a, "tserver", "checksum_tablet", timeout_s=30.0,
                    tablet_id=tablet_id, read_ht=read_ht))
                sums[rep["server_id"]] = resp["checksum"]
            assert len(set(sums.values())) == 1, (
                f"replica divergence on {tablet_id}: {sums}")
            out[tablet_id] = next(iter(sums.values()))
        return out
