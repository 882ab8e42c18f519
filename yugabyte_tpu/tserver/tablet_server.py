"""TabletServer: process object tying messenger, tablet manager, heartbeater.

Capability parity with the reference bringup (ref: src/yb/tserver/
tablet_server.h:71, tablet_server_main.cc:310 — Messenger + RpcServer start,
TSTabletManager::Init reopening local tablets, Heartbeater::Start). One
TabletServer per process in production; MiniCluster runs several in-process
on loopback ports (ref integration-tests/mini_cluster.h).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from yugabyte_tpu.common.hybrid_time import HybridClock
from yugabyte_tpu.rpc.consensus_service import RpcTransport
from yugabyte_tpu.rpc.messenger import Messenger
from yugabyte_tpu.tablet.tablet import TabletOptions
from yugabyte_tpu.tserver.heartbeater import Heartbeater
from yugabyte_tpu.utils.status import StatusError
from yugabyte_tpu.tserver.tablet_service import TabletServiceImpl
from yugabyte_tpu.tserver.ts_tablet_manager import TSTabletManager
from yugabyte_tpu.utils.metrics import MetricRegistry

TABLET_SERVICE = "tserver"


@dataclass
class TabletServerOptions:
    server_id: str
    fs_root: str
    master_addrs: List[str] = field(default_factory=list)
    bind_host: str = "127.0.0.1"
    port: int = 0
    tablet_options_factory: Optional[Callable[[], TabletOptions]] = None
    webserver_port: Optional[int] = 0  # None disables; 0 = ephemeral


class TabletServer:
    def __init__(self, opts: TabletServerOptions):
        self.opts = opts
        self.server_id = opts.server_id
        os.makedirs(opts.fs_root, exist_ok=True)
        self.clock = HybridClock()
        self.metrics = MetricRegistry()
        self.messenger = Messenger(f"ts-{opts.server_id}",
                                   bind_host=opts.bind_host, port=opts.port,
                                   metrics=self.metrics)
        # server_id -> host:port map for consensus peer resolution; seeded
        # with ourselves, refreshed by every heartbeat response.
        self._addr_map: Dict[str, str] = {opts.server_id: self.address}
        self._addr_lock = threading.Lock()
        self.transport = RpcTransport(self.messenger, self._resolve_peer)
        # The server-wide execution context is the DEFAULT tablet-options
        # source: every hosted tablet shares one compaction pool, device
        # handle, HBM slab cache and block cache (ref: db_impl.cc:201-440
        # shared PriorityThreadPool; a custom factory overrides for tests).
        self.exec_context = None
        tablet_options_factory = opts.tablet_options_factory
        if tablet_options_factory is None:
            from yugabyte_tpu.tserver.server_context import (
                ServerExecutionContext)
            self.exec_context = ServerExecutionContext(metrics=self.metrics)
            tablet_options_factory = self.exec_context.tablet_options
        self.tablet_manager = TSTabletManager(
            opts.server_id, opts.fs_root, self.transport, clock=self.clock,
            tablet_options_factory=tablet_options_factory,
            metrics=self.metrics, messenger=self.messenger)
        from yugabyte_tpu.tserver.transaction_coordinator import (
            TransactionCoordinator)
        self.coordinator = TransactionCoordinator(
            leader_resolver=self.lookup_tablet_leader,
            messenger=self.messenger)
        self.tablet_manager.status_resolver = self.resolve_txn_status
        self.service = TabletServiceImpl(self.tablet_manager,
                                         addr_updater=self.update_addr_map,
                                         coordinator=self.coordinator,
                                         client_provider=self.local_client,
                                         overload_provider=lambda:
                                         self.overloadz())
        self.messenger.register_service(TABLET_SERVICE, self.service)
        self.heartbeater = Heartbeater(
            self.messenger, opts.master_addrs, opts.server_id, self.address,
            report_provider=self.tablet_manager.generate_report,
            on_response=self._handle_heartbeat_response)
        # Server-wide memory arbitration: global memstore limit + cache GC
        # under one tracker tree (ref: tserver/tablet_memory_manager.h:39).
        from yugabyte_tpu.tserver.tablet_memory_manager import (
            TabletMemoryManager)
        from yugabyte_tpu.utils.mem_tracker import root_tracker
        self.memory_manager = TabletMemoryManager(
            peers_fn=self._tablet_peers,
            block_cache=(self.exec_context.block_cache
                         if self.exec_context is not None else None),
            metric_entity=self.metrics.entity("server", "memory"),
            server_id=opts.server_id)
        # Scored background-op scheduling: flush/log-GC/compact ranked by
        # (ram anchored, log bytes retained, perf debt) — the automatic
        # WAL-GC trigger (ref tablet/maintenance_manager.cc FindBestOp).
        from yugabyte_tpu.tserver.maintenance_manager import (
            MaintenanceManager)
        self.maintenance_manager = MaintenanceManager(
            peers_fn=self._tablet_peers,
            metric_entity=self.metrics.entity("server", "maintenance"),
            # full recovery path: in-place background-error retry first,
            # then re-bootstrap (sealed WAL) via the tablet manager
            recover_fn=lambda peer: self.tablet_manager
            .recover_failed_tablet(peer.tablet_id))
        if self.exec_context is not None:
            # one-shot startup compile of the common compaction-kernel
            # shape buckets (flag-gated; no-op for device="native")
            prewarm = self.exec_context.prewarm_op()
            if prewarm is not None:
                self.maintenance_manager.register_op(prewarm)
        # at-rest integrity scrubber (interval-gated; leader tablets also
        # run the cross-replica digest exchange after a clean local scrub)
        from yugabyte_tpu.tserver.maintenance_manager import ScrubTabletsOp
        self._digest_strikes: Dict = {}  # (tablet, server) -> consecutive
        #                                  mismatches; _addr_lock guards
        self.scrub_op = ScrubTabletsOp(
            peers_fn=self._tablet_peers,
            digest_check=self._scrub_digest_check)
        self.maintenance_manager.register_op(self.scrub_op)
        self.webserver = None
        if opts.webserver_port is not None:
            from yugabyte_tpu.server.webserver import Webserver
            self.webserver = Webserver(self.metrics, opts.bind_host,
                                       opts.webserver_port)
            self.webserver.register_json("/status", self._status_page)
            self.webserver.register_json(
                "/tablets", self.tablet_manager.generate_report)
            self.webserver.register_json(
                "/memz", lambda: root_tracker().tree_json())
            # observability endpoints (ref /rpcz rpc/rpcz_store.cc,
            # /tracez + /threadz from util/debug-util.cc). /tracez groups
            # spans by trace_id so multi-hop requests read as one tree.
            from yugabyte_tpu.utils import trace as trace_mod
            self.webserver.register_json("/rpcz", self.messenger.rpcz)
            self.webserver.register_json("/tracez", trace_mod.tracez_page)
            self.webserver.register_json("/threadz", trace_mod.threadz)
            # /compactionz: per-DB flush/compaction stats incl. running
            # write amplification (the GetProperty("rocksdb.stats")
            # analogue, ref rocksdb/db/internal_stats.cc)
            self.webserver.register_json("/compactionz", self.compactionz)
            # /integrityz: shadow-verification + scrub + quarantine state
            # (the data-integrity loop's single pane of glass)
            self.webserver.register_json("/integrityz", self.integrityz)
            # /servez: the batched serve path — group-commit write
            # batching, client-batch coalescing and follower-read
            # vouch accounting (ROADMAP item 1)
            self.webserver.register_json("/servez", self.servez)
            # /healthz: the bucket-health board — per-(kernel family,
            # bucket) state, measured rates, probe history and the
            # transition log (storage/bucket_health.py)
            self.webserver.register_json("/healthz", self.healthz)
            # /timeseriesz: the telemetry timebase — per-metric ring-
            # buffer history with rates + sparklines, self-scraped by
            # the in-process sampler (utils/timeseries.py)
            self.webserver.register_json("/timeseriesz", self.timeseriesz)

    def _tablet_peers(self):
        return self.tablet_manager.peers()

    def healthz(self) -> dict:
        """Liveness (`status: ok`, what probes key on) plus the
        bucket-health board's single pane of glass: per-key state +
        rates + probe history, the state histogram, open quarantine
        windows and the recent transition log; and every native library
        this process could not get, with the reason (its callers run
        their Python paths)."""
        from yugabyte_tpu.storage.bucket_health import health_board
        from yugabyte_tpu.utils import native_build
        return {"status": "ok", "server_id": self.server_id,
                "bucket_health": health_board().snapshot(),
                "native_unavailable": native_build.unavailable()}

    def timeseriesz(self) -> dict:
        """The in-process time-series store: per-metric raw window,
        rate-over-window and sparkline downsample, plus the store's
        meta block (memory bound, sampler overhead, drop counts)."""
        from yugabyte_tpu.utils.timeseries import timeseries_store
        page = timeseries_store().page()
        page["server_id"] = self.server_id
        return page

    def _health_board_path(self) -> str:
        from yugabyte_tpu.utils import flags as _flags
        return _flags.get_flag("bucket_health_path") or os.path.join(
            self.opts.fs_root, "bucket_health.json")

    def compactionz(self) -> dict:
        """Flush/compaction stats per hosted tablet DB + server totals."""
        tablets = []
        totals = {"flush_bytes_written": 0, "compaction_bytes_read": 0,
                  "compaction_bytes_written": 0, "versions_gcd": 0,
                  "tombstones_written": 0}
        for peer in self.tablet_manager.peers():
            tablet = getattr(peer, "tablet", None)
            if tablet is None:
                continue
            entry = {"tablet_id": peer.tablet_id}
            for part in ("regular", "intents"):
                db = getattr(tablet, f"{part}_db", None)
                if db is None:
                    continue
                stats = db.compaction_stats.to_dict()
                entry[part] = stats
                for k in totals:
                    totals[k] += stats.get(k, 0)
            tablets.append(entry)
        ingested = totals["flush_bytes_written"]
        totals["write_amplification"] = round(
            (ingested + totals["compaction_bytes_written"]) / ingested,
            3) if ingested else 0.0
        # where offloaded-compaction wall time went (host decode/pack vs
        # device compute+transfer vs native output I/O) plus the shape-
        # bucket executable reuse — the pipeline-stall view of the page
        from yugabyte_tpu.utils.metrics import (kernel_metrics,
                                                pipeline_stage_totals)
        ke = kernel_metrics()
        pipeline = {f"stage_{k}_ms": round(v, 1)
                    for k, v in pipeline_stage_totals().items()}
        pipeline["compile_bucket_hits"] = ke.counter(
            "kernel_compile_bucket_hits_total",
            "kernel launches that reused an already-compiled shape "
            "bucket").value()
        pipeline["compile_bucket_misses"] = ke.counter(
            "kernel_compile_bucket_misses_total",
            "first launches of a shape bucket (compile or persistent-"
            "cache load)").value()
        # whose columns a flush's device slab came from: the native
        # encoder's, or pack_kvs entry by entry (0 beside a compiler)
        from yugabyte_tpu.storage.db import flush_slab_metrics
        for source, counter in flush_slab_metrics().items():
            pipeline[f"flush_slab_{source}_total"] = counter.value()
        # device block codec (ops/block_codec.py): blocks decoded/encoded
        # on device vs jobs that wrote through the native shell encode
        from yugabyte_tpu.ops.block_codec import codec_metrics
        cm = codec_metrics()
        pipeline["compaction_block_decode_device_total"] = \
            cm["decode_blocks"].value()
        pipeline["compaction_block_encode_device_total"] = \
            cm["encode_blocks"].value()
        pipeline["compaction_block_encode_fallback_total"] = \
            cm["encode_fallbacks"].value()
        # device-fault containment: shape buckets parked native-only
        # after a kernel-path fault (timed decay), plus how often the
        # mid-job native fallback and the per-chunk retry actually fired
        from yugabyte_tpu.storage.compaction import (
            _storage_fallback_counter)
        from yugabyte_tpu.storage.offload_policy import bucket_quarantine
        device_faults = {
            "quarantined_buckets": bucket_quarantine().snapshot(),
            "native_fallbacks": _storage_fallback_counter().value(),
            "chunk_retries": ke.counter(
                "kernel_chunk_retry_total",
                "per-chunk kernel retries after a device fault").value(),
        }
        # batched point reads: batch/bloom-skip/learned-index/fallback
        # counters for the device serve path (ops/point_read.py)
        from yugabyte_tpu.ops.point_read import point_read_snapshot
        # query pushdown: fused filtered/aggregating scan counters —
        # hits and per-reason fallbacks, per-bucket dispatches, and the
        # blocks-decoded-per-scan histogram (ops/scan.pushdown_snapshot)
        from yugabyte_tpu.ops.scan import pushdown_snapshot
        out = {"server_id": self.server_id, "totals": totals,
               "pipeline": pipeline, "device_faults": device_faults,
               "point_reads": point_read_snapshot(),
               "scans": pushdown_snapshot(),
               "tablets": tablets}
        # HBM residency: the multi-level resident set behind the chained
        # L0->L1->L2 compaction path — per-level entries/bytes, pins and
        # eviction pressure (storage/device_cache.py snapshot)
        ctx = self.exec_context
        if ctx is not None:
            # what the kernels run on: a run asserts platform == "tpu"
            out["device"] = ctx.device_info()
        if ctx is not None and ctx.device_cache is not None:
            out["device_cache"] = ctx.device_cache.snapshot()
        # mesh-sharded compaction pool: queue depth, per-tablet
        # queued/running, packed-slot occupancy and the measured
        # per-bucket aggregate rates the scheduler routes by
        if ctx is not None and getattr(ctx, "compaction_pool", None) \
                is not None:
            out["pool"] = ctx.compaction_pool.snapshot()
        return out

    def servez(self) -> dict:
        """Serve-path state: group-commit write batching (one raft
        replicate / WAL fsync per batch), batched point-read counters,
        per-replica follower-read vouch status, and the overload block
        (bounded RPC queue + per-tablet write-pressure state)."""
        from yugabyte_tpu.ops.point_read import point_read_snapshot
        from yugabyte_tpu.utils.latency import serve_path_attribution_page
        from yugabyte_tpu.utils.metrics import serve_path_snapshot
        tablets = []
        for peer in self.tablet_manager.peers():
            tablets.append({
                "tablet_id": peer.tablet_id,
                "role": peer.raft.observed_state()[0].value,
                "vouched": peer.is_vouched(),
                "vouch_read_ht": peer._vouch_read_ht,
            })
        return {"server_id": self.server_id,
                "serve_path": serve_path_snapshot(),
                # per-stage latency attribution: where a batched write /
                # multi_read spends its end-to-end wall, as percentages
                # of the e2e histogram (utils/latency.py)
                "attribution": serve_path_attribution_page(),
                "point_reads": point_read_snapshot(),
                "overload": self.overloadz(),
                "tablets": tablets}

    def overloadz(self) -> dict:
        """The overload block: every shedding layer's live state — the
        messenger's bounded service queue (depth, overflow/expired
        counters, measured retry_after hint), the server-wide memstore
        tracker, and each hosted tablet's write-pressure state machine
        (tablet/admission.py). Served inside /servez and over the
        `overload_status` RPC (bench scraping on external clusters)."""
        from yugabyte_tpu.utils import flags as _flags
        from yugabyte_tpu.utils.metrics import serve_path_metrics
        mm = self.memory_manager
        tracker = mm.memstore_tracker
        m = serve_path_metrics()
        pressure = []
        for peer in self.tablet_manager.peers():
            admission = getattr(getattr(peer, "tablet", None),
                                "admission", None)
            if admission is not None:
                pressure.append(admission.snapshot())
        return {
            "rpc": self.messenger.overload_snapshot(),
            "memstore": {
                "consumption_bytes": tracker.consumption(),
                "limit_bytes": tracker.limit,
                "reject_fraction": _flags.get_flag(
                    "memstore_reject_fraction"),
            },
            "write_throttle_rejections_total": m.counter(
                "write_throttle_rejections_total",
                "writes rejected retryably by the write-pressure "
                "state machine").value(),
            "write_pressure": pressure,
        }

    def integrityz(self) -> dict:
        """Data-integrity state: shadow-verify sampling + mismatch
        counters, scrubber totals, quarantined files, and per-tablet
        scrub timestamps / corruption flags."""
        from yugabyte_tpu.storage import integrity
        tablets = []
        for peer in self.tablet_manager.peers():
            tablets.append({
                "tablet_id": peer.tablet_id,
                "state": peer.state,
                "failed_corrupt": bool(getattr(peer, "failed_corrupt",
                                               False)),
                "scrub": dict(getattr(peer, "scrub_state", None) or {}),
            })
        return {"server_id": self.server_id,
                "shadow_verify": integrity.shadow_snapshot(),
                "resident_digest": integrity.resident_digest_snapshot(),
                "scrub": integrity.scrub_snapshot(),
                "quarantined_files": integrity.quarantined_files(),
                "tablets": tablets}

    def _scrub_digest_check(self, peer) -> int:
        """Leader-driven cross-replica digest exchange for one tablet
        (reuses the checksum_tablet RPC): every follower's visibility-
        resolved digest at one pinned read time must match the leader's.
        A follower that mismatches ``--scrub_replica_fail_after``
        CONSECUTIVE rounds is marked FAILED+corrupt through
        mark_tablet_failed, and the master rebuilds it from a healthy
        peer — the repair arm for divergence that byte-level CRCs cannot
        see. Returns the mismatches seen this round."""
        from yugabyte_tpu.storage.integrity import (
            replica_mismatch_counter)
        from yugabyte_tpu.utils import flags as _flags
        from yugabyte_tpu.utils.trace import TRACE
        tablet_id = peer.tablet_id
        if not peer.raft.is_leader():
            return 0
        read_ht = peer.tablet.read_time(None).value
        try:
            local = self.service.checksum_tablet(tablet_id, read_ht)
        except StatusError as e:
            TRACE("scrub digest: local checksum of %s failed: %s",
                  tablet_id, e)
            return 0
        mismatches = 0
        fail_after = int(_flags.get_flag("scrub_replica_fail_after"))
        for pid in peer.raft.config.peer_ids:
            sid = pid.split("/", 1)[0]
            if sid == self.server_id:
                continue
            addr = self._resolve_peer(pid)
            if addr is None:
                continue
            key = (tablet_id, sid)
            try:
                remote = self.messenger.call(
                    addr, "tserver", "checksum_tablet", timeout_s=30.0,
                    tablet_id=tablet_id, read_ht=read_ht)
            except StatusError as e:
                # unreachable / mid-repair follower: not divergence
                # evidence — reset its strike count and move on
                TRACE("scrub digest: checksum of %s on %s failed: %s",
                      tablet_id, sid, e)
                with self._addr_lock:
                    self._digest_strikes.pop(key, None)
                continue
            if remote["checksum"] == local["checksum"]:
                with self._addr_lock:
                    self._digest_strikes.pop(key, None)
                # matching digest = follower-read license: the replica's
                # resolved rows provably agree with the leader's at
                # read_ht, so bounded-staleness reads may land there
                # until the vouch TTL lapses (ROADMAP item 1 safety rail)
                try:
                    self.messenger.call(
                        addr, "tserver", "vouch_tablet", timeout_s=10.0,
                        tablet_id=tablet_id, read_ht=read_ht)
                except StatusError as e:
                    # vouch is an optimization, never correctness: an
                    # unreachable follower just stays unvouched and keeps
                    # refusing follower reads until the next clean round
                    TRACE("scrub digest: vouch of %s on %s failed: %s",
                          tablet_id, sid, e)
                continue
            mismatches += 1
            replica_mismatch_counter().increment()
            with self._addr_lock:
                strikes = self._digest_strikes.get(key, 0) + 1
                self._digest_strikes[key] = strikes
            TRACE("scrub digest: %s on %s diverges from leader "
                  "(%#x != %#x; strike %d/%d)", tablet_id, sid,
                  remote["checksum"], local["checksum"], strikes,
                  fail_after)
            if strikes >= fail_after:
                with self._addr_lock:
                    self._digest_strikes.pop(key, None)
                try:
                    self.messenger.call(
                        addr, "tserver", "mark_tablet_failed",
                        timeout_s=10.0, tablet_id=tablet_id,
                        reason=(f"scrub digest divergence from leader "
                                f"{self.server_id} at read_ht={read_ht}"),
                        corrupt=True)
                except StatusError as e:
                    TRACE("scrub digest: failing %s on %s failed "
                          "(retried next scrub round): %s", tablet_id,
                          sid, e)
        return mismatches

    def _status_page(self) -> dict:
        if self.exec_context is not None:
            self.exec_context.refresh_metrics()
        return {"server_id": self.server_id, "rpc_address": self.address,
                "num_tablets": len(self.tablet_manager.tablet_ids())}

    @property
    def address(self) -> str:
        return self.messenger.address

    def _resolve_peer(self, peer_id: str) -> Optional[str]:
        server_id = peer_id.split("/", 1)[0]
        with self._addr_lock:
            return self._addr_map.get(server_id)

    def _handle_heartbeat_response(self, resp: dict) -> None:
        with self._addr_lock:
            self._addr_map.update(resp.get("addr_map") or {})
        for tablet_id in resp.get("tablets_to_delete") or []:
            self.tablet_manager.delete_tablet(tablet_id)
        self._reconcile_pollers(resp.get("replication") or [])
        self.tablet_manager.apply_history_retention(
            resp.get("history_retention"))
        for upd in resp.get("schema_updates") or []:
            try:
                self.tablet_manager.alter_tablet_schema(
                    upd["tablet_id"], upd["schema"], upd["version"])
            except StatusError:
                pass  # tablet moved/deleted since the report
        keys = resp.get("universe_keys")
        if keys:
            self._apply_universe_keys(keys)

    def _apply_universe_keys(self, keys) -> None:
        """Encryption at rest: the master ships the key registry via
        heartbeats; once keys exist, every NEW storage file this process
        writes is encrypted (old plaintext files stay readable)."""
        from yugabyte_tpu.utils import env as env_mod
        known = getattr(self, "_universe_key_ids", set())
        ids = {m["key_id"] for m in keys}
        if ids == known:
            return
        reg = env_mod.UniverseKeys()
        for m in keys:
            reg.add(m["key_id"], bytes.fromhex(m["key"]),
                    make_latest=bool(m.get("latest")))
        env_mod.enable_encryption(reg)
        self._universe_key_ids = ids

    # ------------------------------------------------------------- xCluster
    def _reconcile_pollers(self, specs) -> None:
        """Start/stop xCluster pollers per the master's heartbeat piggyback
        (ref: cdc_consumer.cc reconciling pollers from the consumer
        registry)."""
        from yugabyte_tpu.cdc.poller import XClusterPoller
        if not hasattr(self, "_pollers"):
            self._pollers = {}
        want = {(s["replication_id"], s["tablet_id"]): s for s in specs}
        with self._addr_lock:
            if getattr(self, "_shutting_down", False):
                return  # a late heartbeat must not resurrect pollers
            for key in list(self._pollers):
                if key not in want:
                    self._pollers.pop(key).stop()
            for key, s in want.items():
                if key not in self._pollers:
                    self._pollers[key] = XClusterPoller(
                        self, s["replication_id"], s["tablet_id"],
                        s["source_master_addrs"], s["src_table"],
                        s["src_namespace"], s["checkpoint"]).start()

    def report_replication_checkpoint(self, replication_id: str,
                                      tablet_id: str, index: int) -> None:
        client = self.local_client()
        if client is not None:
            try:
                client._master_call("update_replication_checkpoint",
                                    replication_id=replication_id,
                                    tablet_id=tablet_id, index=index)
            except StatusError:
                pass  # retried on the next progress report

    def update_addr_map(self, addr_map: Dict[str, str]) -> None:
        with self._addr_lock:
            self._addr_map.update(addr_map)

    def local_client(self):
        """Lazily built YBClient for tserver-initiated cluster ops (index
        backfill writes; the reference's tservers likewise embed a client,
        ref tserver/tablet_server.cc client_future). Shares this server's
        messenger."""
        with self._addr_lock:
            client = getattr(self, "_local_client", None)
            if client is None and self.opts.master_addrs:
                from yugabyte_tpu.client.client import YBClient
                client = YBClient(self.opts.master_addrs,
                                  messenger=self.messenger)
                self._local_client = client
            return client

    # ------------------------------------------------ transaction plumbing
    def lookup_tablet_leader(self, tablet_id: str) -> Optional[str]:
        """Best-effort leader address for any tablet in the cluster: local
        raft state first, then the master's leader map."""
        from yugabyte_tpu.utils.status import StatusError
        try:
            peer = self.tablet_manager.get_tablet(tablet_id)
            if peer.raft.is_leader():
                return self.address
            hint = peer.raft.leader_hint()
            if hint:
                addr = self._resolve_peer(hint)
                if addr:
                    return addr
        except StatusError:
            pass
        for maddr in self.opts.master_addrs:
            try:
                return self.messenger.call(maddr, "master",
                                           "get_tablet_leader",
                                           timeout_s=3.0,
                                           tablet_id=tablet_id)
            except StatusError:
                continue
        return None

    def resolve_txn_status(self, status_tablet: str, txn_id: bytes,
                           read_ht: Optional[int] = None) -> dict:
        """Status resolver wired into every hosted data tablet (ref
        TransactionStatusResolver). Conservative on any failure: a pending
        answer never exposes uncommitted data. read_ht (the reader's
        snapshot) floors any later commit above it via the coordinator's
        clock."""
        from yugabyte_tpu.utils.status import StatusError
        try:
            peer = self.tablet_manager.get_tablet(status_tablet)
            if peer.raft.is_leader():
                return self.coordinator.status(peer, txn_id, read_ht)
        except StatusError:
            pass
        addr = self.lookup_tablet_leader(status_tablet)
        if addr is None:
            return {"status": "pending", "commit_ht": None}
        try:
            return self.messenger.call(addr, "tserver", "txn_status",
                                       timeout_s=5.0,
                                       tablet_id=status_tablet,
                                       txn_id=txn_id,
                                       observing_read_ht=read_ht)
        except StatusError:
            return {"status": "pending", "commit_ht": None}

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "TabletServer":
        # Encryption-at-rest keys must be available BEFORE bootstrap reads
        # any (possibly encrypted) WAL/SST: fetch the registry from a
        # master first (unavailable masters: proceed; heartbeats retrofit
        # the keys, and encrypted tablets simply cannot serve until then).
        self._fetch_universe_keys()
        # restore the bucket-health board before any tablet opens: open
        # quarantine windows and sticky mismatch marks must gate the very
        # first post-restart compaction (rates re-learn from scratch)
        from yugabyte_tpu.storage.bucket_health import health_board
        health_board().load(self._health_board_path())
        self.tablet_manager.open_existing()
        self.memory_manager.init()
        self.maintenance_manager.init()
        # telemetry timebase: register this server's scrape sources on
        # the process store and ref-count the sampler thread up. The
        # sources take their own snapshots — the serve path never sees
        # the store's lock.
        from yugabyte_tpu.utils.timeseries import timeseries_store
        ts = timeseries_store()
        ts.register_registry(f"server.{self.server_id}", self.metrics)
        ts.register_source(f"overload.{self.server_id}",
                           self._overload_series)
        ts.register_source(f"context.{self.server_id}",
                           self._context_series)
        ts.start()
        self._timeseries_started = True
        if self.opts.master_addrs:
            # Register before serving so the master knows our address by the
            # time it places tablets here.
            self.heartbeater.heartbeat_now()
            self.heartbeater.start()
        return self

    def _fetch_universe_keys(self, deadline_s: float = 10.0) -> None:
        import time as _time
        if not self.opts.master_addrs:
            return
        # only insist on keys when local files actually need them
        need = self._has_encrypted_files()
        deadline = _time.monotonic() + deadline_s
        while _time.monotonic() < deadline:
            for addr in self.opts.master_addrs:
                try:
                    keys = self.messenger.call(addr, "master",
                                               "get_universe_keys",
                                               timeout_s=3.0)
                except Exception:  # noqa: BLE001 — master still starting
                    continue
                if keys:
                    self._apply_universe_keys(keys)
                    return
                if not need:
                    # a keyless universe answered: nothing to wait for
                    return
                # an empty reply in an encrypted universe (e.g. a master
                # without the sidecar): keep asking — bootstrap without
                # keys cannot read the local data
            _time.sleep(0.3)
        if need:
            from yugabyte_tpu.utils.trace import TRACE
            TRACE("ts %s: encrypted files present but no universe keys "
                  "obtained; encrypted tablets will fail closed",
                  self.server_id)

    def _has_encrypted_files(self) -> bool:
        from yugabyte_tpu.utils.env import looks_encrypted
        for dirpath, _dirs, files in os.walk(self.opts.fs_root):
            for f in files:
                if f.startswith("wal-") or ".sst" in f:
                    if looks_encrypted(os.path.join(dirpath, f)):
                        return True
        return False

    def _overload_series(self) -> dict:
        """Flat numeric series of the overload block (queue depth,
        shed counters, memstore consumption) for the time-series
        sampler."""
        snap = self.overloadz()
        out = {}
        for k, v in (snap.get("rpc") or {}).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"rpc.{k}"] = float(v)
        mem = snap.get("memstore") or {}
        out["memstore.consumption_bytes"] = float(
            mem.get("consumption_bytes") or 0)
        out["memstore.limit_bytes"] = float(mem.get("limit_bytes") or 0)
        out["write_throttle_rejections.total"] = float(
            snap.get("write_throttle_rejections_total") or 0)
        return out

    def _context_series(self) -> dict:
        """Flat numeric series of the shared execution context: HBM
        device-cache residency and compaction-pool queue state."""
        ctx = self.exec_context
        out = {}
        if ctx is None:
            return out
        if ctx.device_cache is not None:
            for k, v in ctx.device_cache.snapshot().items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    out[f"device_cache.{k}"] = float(v)
        pool = getattr(ctx, "compaction_pool", None)
        if pool is not None:
            for k, v in pool.snapshot().items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    out[f"pool.{k}"] = float(v)
        return out

    def shutdown(self) -> None:
        if getattr(self, "_timeseries_started", False):
            self._timeseries_started = False
            from yugabyte_tpu.utils.timeseries import timeseries_store
            timeseries_store().stop()
        with self._addr_lock:
            self._shutting_down = True
            pollers = list(getattr(self, "_pollers", {}).values())
        for p in pollers:
            p.stop()
        self.heartbeater.stop()
        self.transport.batcher.stop()
        self.memory_manager.shutdown()
        self.maintenance_manager.shutdown()
        if self.webserver is not None:
            self.webserver.shutdown()
        self.tablet_manager.shutdown()
        # persist the bucket-health board after the last compaction has
        # drained (durable facts only: states, faults, quarantine
        # windows, mismatch reasons — rates restart as WARMING)
        from yugabyte_tpu.storage.bucket_health import health_board
        health_board().save(self._health_board_path())
        if self.exec_context is not None:
            self.exec_context.shutdown()
        self.messenger.shutdown()
