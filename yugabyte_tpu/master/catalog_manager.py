"""CatalogManager + TSManager: DDL, tablet placement, tserver liveness.

Capability parity with the reference (ref: src/yb/master/catalog_manager.h:141
— namespace/table/tablet lifecycle; ts_manager.h — TSDescriptor registry from
heartbeats; catalog_loaders.cc — in-memory state rebuilt from the sys catalog
on master failover; catalog_manager_bg_tasks.cc — background reconciliation
re-sending unacknowledged tablet-creation work).

All durable state lives in the SysCatalog; everything here is a cache keyed
off it, rebuilt by `ensure_loaded()` whenever this master (re)gains
sys-catalog leadership.
"""

from __future__ import annotations

import threading
import time
import uuid
from typing import Dict, List, Optional, Sequence, Set, Tuple

from yugabyte_tpu.common.partition import PartitionSchema
from yugabyte_tpu.common.wire import (
    partition_from_wire, partition_schema_from_wire, partition_to_wire,
    schema_from_wire, schema_to_wire)
from yugabyte_tpu.master.sys_catalog import SysCatalog
from yugabyte_tpu.utils import flags
from yugabyte_tpu.utils.status import Status, StatusError
from yugabyte_tpu.utils.trace import TRACE
from yugabyte_tpu.utils import lock_rank

flags.define_flag("tserver_unresponsive_timeout_ms", 3000,
                  "a tserver missing heartbeats this long is treated as dead "
                  "(ref tserver_unresponsive_timeout_ms)")
flags.define_flag("replication_factor", 3,
                  "default table replication factor (ref replication_factor)")
# extra MVCC history beyond a PITR schedule's interval, covering snapshot
# timing jitter + heartbeat propagation of the retention override
_SCHEDULE_RETENTION_SLACK_S = 60.0

# Same definition as tablet.py (define_flag is idempotent for identical
# defaults and raises loudly on drift): a master-only process needs the
# value for snapshot history floors without importing the tablet stack.
flags.define_flag(
    "timestamp_history_retention_interval_sec", 900,
    "how far back in time reads are repeatable; compaction keeps overwritten "
    "values younger than this (ref tablet_retention_policy.h:29)")


def _base_history_retention_s() -> float:
    return float(flags.get_flag("timestamp_history_retention_interval_sec"))


flags.define_flag("index_backfill_grace_ms", 500,
                  "wait between index creation and the backfill snapshot so "
                  "every writer observes the index in write mode first (the "
                  "reference waits for schema-version acks from all "
                  "tservers, ref backfill_index.cc WaitForSchemaVersion)")


class TSDescriptor:
    def __init__(self, server_id: str, addr: str):
        self.server_id = server_id
        self.addr = addr
        self.last_heartbeat = time.monotonic()
        self.num_tablets = 0
        self.reported_tablets: Set[str] = set()
        # replicas this server reports in FAILED state (background storage
        # error): the load balancer re-replicates them without waiting for
        # the whole server to go silent
        self.failed_tablets: Set[str] = set()
        # the corruption subset of failed_tablets (scrub / read-path CRC /
        # digest divergence): rebuilt IN PLACE from a healthy peer — the
        # server is fine, the replica's data is not
        self.corrupt_tablets: Set[str] = set()

    def alive(self) -> bool:
        timeout = flags.get_flag("tserver_unresponsive_timeout_ms") / 1000.0
        return time.monotonic() - self.last_heartbeat < timeout


class TSManager:
    """ref src/yb/master/ts_manager.h"""

    def __init__(self):
        self._descs: Dict[str, TSDescriptor] = {}
        self._lock = threading.Lock()

    def heartbeat(self, server_id: str, addr: str,
                  report: List[dict]) -> TSDescriptor:  # yblint: wire-pair(tablet_report, reads)
        with self._lock:
            desc = self._descs.get(server_id)
            if desc is None or desc.addr != addr:
                desc = TSDescriptor(server_id, addr)
                self._descs[server_id] = desc
            desc.last_heartbeat = time.monotonic()
            desc.num_tablets = len(report)
            desc.reported_tablets = {t["tablet_id"] for t in report}
            desc.failed_tablets = {t["tablet_id"] for t in report
                                   if t.get("state") == "FAILED"}
            desc.corrupt_tablets = {t["tablet_id"] for t in report
                                    if t.get("state") == "FAILED"
                                    and t.get("failed_corrupt")}
            return desc

    def live_descriptors(self) -> List[TSDescriptor]:
        with self._lock:
            return [d for d in self._descs.values() if d.alive()]

    def all_descriptors(self) -> List[TSDescriptor]:
        with self._lock:
            return list(self._descs.values())

    def addr_map(self) -> Dict[str, str]:
        with self._lock:
            return {sid: d.addr for sid, d in self._descs.items()}

    def get(self, server_id: str) -> Optional[TSDescriptor]:
        with self._lock:
            return self._descs.get(server_id)


class CatalogManager:
    def __init__(self, sys_catalog: SysCatalog, messenger):
        self.sys = sys_catalog
        self.messenger = messenger
        self.ts_manager = TSManager()
        self._lock = lock_rank.tracked(threading.RLock(),
                                       "catalog._lock")
        # one snapshot-schedule tick at a time: the master's bg loop and an
        # explicit run_snapshot_schedules() that both read a schedule as
        # due would each take its snapshot (taken BEFORE catalog._lock,
        # never under it)
        self._schedule_tick_lock = threading.Lock()
        self._loaded_term = -1  # guarded-by: _lock
        self.namespaces: Dict[str, dict] = {}  # guarded-by: _lock
        self.tables: Dict[str, dict] = {}  # guarded-by: _lock
        self.tablets: Dict[str, dict] = {}  # guarded-by: _lock
        self.sequences: Dict[str, dict] = {}  # "ns.name" -> {next, ...}
        self.views: Dict[str, dict] = {}      # "ns.name" -> {sql, ...}
        # volatile: tablet_id -> (leader server_id, term); replica acks
        self.tablet_leaders: Dict[str, Tuple[str, int]] = {}  # guarded-by: _lock
        self._confirmed: Set[Tuple[str, str]] = set()  # (tablet_id, server)
        # volatile: authoritative Raft config index per tablet (from leader
        # reports); used to recognize evicted stale replicas.
        self._config_indexes: Dict[str, int] = {}
        # memoized table_id -> required history retention (PITR schedules);
        # None = rebuild on next heartbeat (see _history_retention_for)
        self._retention_by_table: Optional[Dict[str, float]] = None

    # ------------------------------------------------------------ leadership
    def is_leader(self) -> bool:
        return (self.sys.peer.raft.is_leader()
                and self.sys.peer.raft.leader_ready())

    def ensure_loaded(self) -> None:
        """Rebuild caches from the sys catalog after (re)gaining leadership
        (ref catalog_loaders.cc)."""
        term = self.sys.peer.raft.current_term
        with self._lock:
            if self._loaded_term == term:
                return
            namespaces: Dict[str, dict] = {}
            tables: Dict[str, dict] = {}
            tablets: Dict[str, dict] = {}
            sequences: Dict[str, dict] = {}
            views: Dict[str, dict] = {}
            for etype, eid, meta in self.sys.scan_all():
                if etype == "namespace":
                    namespaces[eid] = meta
                elif etype == "table":
                    tables[eid] = meta
                elif etype == "tablet":
                    tablets[eid] = meta
                elif etype == "sequence":
                    sequences[eid] = meta
                elif etype == "view":
                    views[eid] = meta
            self.namespaces = namespaces
            self.tables = tables
            self.tablets = tablets
            self.sequences = sequences
            self.views = views
            self._confirmed.clear()
            self._replication_cache = None
            self._loaded_term = term
            TRACE("catalog loaded at term %d: %d namespaces, %d tables, "
                  "%d tablets", term, len(namespaces), len(tables),
                  len(tablets))

    # ------------------------------------------------------------------- DDL
    def create_namespace(self, name: str) -> None:
        with self._lock:
            if name in self.namespaces:
                raise StatusError(Status.AlreadyPresent(
                    f"namespace {name!r} exists"))
            meta = {"name": name}
            self.sys.upsert("namespace", name, meta)
            self.namespaces[name] = meta

    def list_namespaces(self) -> List[str]:
        with self._lock:
            return sorted(self.namespaces)

    # ------------------------------------------------------------ sequences
    # PG sequences (ref: src/postgres/src/backend/commands/sequence.c;
    # YSQL routes them through the master-side sequences table,
    # src/yb/yql/pggate pg_sequence_cache). Allocation persists through
    # the sys catalog BEFORE returning, so a master restart never hands
    # out a duplicate block.
    def create_sequence(self, namespace: str, name: str, start: int = 1,
                        if_not_exists: bool = False) -> None:
        key = f"{namespace}.{name}"
        with self._lock:
            if key in self.sequences:
                if if_not_exists:
                    return
                raise StatusError(Status.AlreadyPresent(
                    f"sequence {name!r} exists"))
            meta = {"namespace": namespace, "name": name,
                    "next": int(start)}
            self.sys.upsert("sequence", key, meta)
            self.sequences[key] = meta

    def drop_sequence(self, namespace: str, name: str,
                      if_exists: bool = False) -> None:
        key = f"{namespace}.{name}"
        with self._lock:
            if key not in self.sequences:
                if if_exists:
                    return
                raise StatusError(Status.NotFound(
                    f"sequence {name!r} does not exist"))
            self.sys.delete("sequence", key)
            del self.sequences[key]

    def sequence_next(self, namespace: str, name: str,
                      cache: int = 1) -> int:
        """Allocate [returned, returned+cache) and persist the advance."""
        key = f"{namespace}.{name}"
        cache = max(1, int(cache))
        with self._lock:
            meta = self.sequences.get(key)
            if meta is None:
                raise StatusError(Status.NotFound(
                    f"sequence {name!r} does not exist"))
            val = int(meta["next"])
            meta = dict(meta, next=val + cache)
            self.sys.upsert("sequence", key, meta)
            self.sequences[key] = meta
            return val

    # --------------------------------------------------------------- views
    # PG views stored as the defining SELECT text in the sys catalog
    # (ref: PG pg_rewrite / DefineView; YSQL keeps view defs in the
    # postgres catalog replicated through the master's sys catalog).
    def create_view(self, namespace: str, name: str, sql: str,
                    or_replace: bool = False) -> None:
        key = f"{namespace}.{name}"
        with self._lock:
            if key in self.views and not or_replace:
                raise StatusError(Status.AlreadyPresent(
                    f"view {name!r} exists"))
            if self._find_table(namespace, name) is not None:
                raise StatusError(Status.AlreadyPresent(
                    f"{name!r} is a table"))
            meta = {"namespace": namespace, "name": name, "sql": sql}
            self.sys.upsert("view", key, meta)
            self.views[key] = meta

    def drop_view(self, namespace: str, name: str,
                  if_exists: bool = False) -> None:
        key = f"{namespace}.{name}"
        with self._lock:
            if key not in self.views:
                if if_exists:
                    return
                raise StatusError(Status.NotFound(
                    f"view {name!r} does not exist"))
            self.sys.delete("view", key)
            del self.views[key]

    def get_view(self, namespace: str, name: str) -> Optional[str]:
        with self._lock:
            meta = self.views.get(f"{namespace}.{name}")
            return None if meta is None else meta["sql"]

    def list_views(self, namespace: str) -> List[dict]:
        """[{name, sql}] in name order — one call serves catalog queries
        (pg_views) without per-view lookups."""
        with self._lock:
            return sorted(({"name": m["name"], "sql": m["sql"]}
                           for m in self.views.values()
                           if m["namespace"] == namespace),
                          key=lambda m: m["name"])

    def _find_table(self, namespace: str, name: str) -> Optional[str]:
        with self._lock:
            for tid, t in self.tables.items():
                if t["namespace"] == namespace and t["name"] == name:
                    return tid
        return None

    def create_table(self, namespace: str, name: str, schema_wire: dict,
                     partition_schema_wire: dict, num_tablets: int,
                     replication_factor: Optional[int] = None) -> dict:
        rf = replication_factor or flags.get_flag("replication_factor")
        with self._lock:
            if namespace not in self.namespaces:
                raise StatusError(Status.NotFound(
                    f"namespace {namespace!r} not found"))
            if f"{namespace}.{name}" in self.views:
                raise StatusError(Status.AlreadyPresent(
                    f"{name!r} is a view"))
            if self._find_table(namespace, name) is not None:
                raise StatusError(Status.AlreadyPresent(
                    f"table {namespace}.{name} exists"))
            live = self.ts_manager.live_descriptors()
            if len(live) < rf:
                raise StatusError(Status.ServiceUnavailable(
                    f"need {rf} live tservers for RF={rf}, have {len(live)}"))
            table_id = uuid.uuid4().hex[:16]
            ps = partition_schema_from_wire(partition_schema_wire)
            partitions = ps.create_partitions(num_tablets)
            tablet_metas: List[dict] = []
            for i, part in enumerate(partitions):
                tablet_id = f"{table_id}.t{i:04d}"
                # Reuse the snapshot validated above — re-listing here could
                # see fewer than rf live tservers (TOCTOU).
                replicas = self._pick_replicas(live, rf, seed_index=i)
                tablet_metas.append({
                    "tablet_id": tablet_id, "table_id": table_id,
                    "partition": partition_to_wire(part),
                    "hash_partitioning": ps.hash_partitioning,
                    "replicas": replicas})
            table_meta = {
                "table_id": table_id, "name": name, "namespace": namespace,
                "schema": schema_wire,
                "partition_schema": partition_schema_wire,
                "tablet_ids": [t["tablet_id"] for t in tablet_metas]}
            # Persist FIRST so a crash never leaves orphan replicas the
            # heartbeat cleanup would misread as live state (see
            # tablets_to_delete below); replica creation is re-driven by the
            # reconciler until every ack lands.
            self.sys.upsert("table", table_id, table_meta)
            for tm in tablet_metas:
                self.sys.upsert("tablet", tm["tablet_id"], tm)
            self.tables[table_id] = table_meta
            for tm in tablet_metas:
                self.tablets[tm["tablet_id"]] = tm
        self.reconcile_tablets()
        return table_meta

    def _pick_replicas(self, live: List[TSDescriptor], rf: int,
                       seed_index: int) -> List[str]:
        """Least-loaded placement over live tservers (ref
        CatalogManager::SelectReplicasForTablet round-robin by load)."""
        live = sorted(live, key=lambda d: (d.num_tablets, d.server_id))
        picked = [live[(seed_index + j) % len(live)] for j in range(rf)]
        # rotation can alias on small clusters; dedup preserving order
        seen, out = set(), []
        for d in picked:
            if d.server_id not in seen:
                seen.add(d.server_id)
                out.append(d)
        for d in live:
            if len(out) >= rf:
                break
            if d.server_id not in seen:
                seen.add(d.server_id)
                out.append(d)
        for d in out:
            d.num_tablets += 1  # keeps subsequent picks spreading
        return [d.server_id for d in out]

    # ---------------------------------------------------------------- alter
    def alter_table(self, namespace: str, name: str,
                    add_columns: Sequence[Tuple[str, str]] = (),
                    drop_columns: Sequence[str] = ()) -> dict:
        """Online ALTER TABLE ADD/DROP COLUMN (ref CatalogManager::
        AlterTable + async AlterTable tasks, catalog_manager.cc): the new
        schema persists with a bumped version, then propagates to every
        hosted replica — directly here for latency, and via heartbeat
        reconciliation for replicas that miss the push (see
        process_heartbeat schema piggyback). ADD appends a slot (ids
        stable, no data rewrite); DROP tombstones the slot in place."""
        from yugabyte_tpu.common.schema import DataType
        with self._lock:
            # read-modify-write under the catalog lock: concurrent ALTERs
            # must serialize or one silently loses its column AND collides
            # on schema_version (tservers already at the winning version
            # would never be repaired by heartbeat reconciliation)
            table = next((t for t in self.tables.values()
                          if t["namespace"] == namespace
                          and t["name"] == name), None)
            if table is None:
                raise StatusError(Status.NotFound(
                    f"table {namespace}.{name}"))
            schema = schema_from_wire(table["schema"])
            try:
                for col, type_name in add_columns:
                    schema = schema.with_added_column(col,
                                                      DataType(type_name))
                for col in drop_columns:
                    schema = schema.with_dropped_column(col)
            except (ValueError, KeyError) as e:
                raise StatusError(Status.InvalidArgument(str(e))) from e
            version = table.get("schema_version", 0) + 1
            table = dict(table, schema=schema_to_wire(schema),
                         schema_version=version)
            self.sys.upsert("table", table["table_id"], table)
            self.tables[table["table_id"]] = table
            tablet_ids = [t for t in table["tablet_ids"]
                          if t in self.tablets]
            targets = [(t, s) for t in tablet_ids
                       for s in self.tablets[t]["replicas"]]
        addr_map = self.ts_manager.addr_map()

        def push():
            # fire-and-forget latency optimization (the reference's async
            # AlterTable tasks); heartbeat reconciliation is the guarantee
            for tablet_id, server_id in targets:
                addr = addr_map.get(server_id)
                if addr is None:
                    continue
                try:
                    self.messenger.call(addr, "tserver",
                                        "alter_tablet_schema",
                                        timeout_s=2.0, tablet_id=tablet_id,
                                        schema=table["schema"],
                                        version=version)
                except StatusError:
                    pass
        threading.Thread(target=push, daemon=True,
                         name="alter-push").start()
        return table

    def _schema_updates_for(self, report: List[dict]) -> List[dict]:  # yblint: wire-pair(tablet_report, reads)
        """Heartbeat piggyback: alter orders for reported tablets whose
        schema version lags the catalog's (the reconciliation half of
        alter_table — a replica that missed the direct push, or was
        bootstrapped from an old snapshot, converges here)."""
        out = []
        with self._lock:
            for t in report:
                tm = self.tablets.get(t.get("tablet_id"))
                if tm is None:
                    continue
                table = self.tables.get(tm["table_id"])
                if table is None:
                    continue
                want = table.get("schema_version", 0)
                if t.get("schema_version", 0) < want:
                    out.append({"tablet_id": t["tablet_id"],
                                "schema": table["schema"],
                                "version": want})
        return out

    # --------------------------------------------------------------- indexes
    def create_index(self, namespace: str, table_name: str, index_name: str,
                     column, num_tablets: int = 2) -> dict:
        """CREATE INDEX: create the index table, attach IndexInfo to the
        indexed table (write-and-delete mode), wait out the schema
        propagation grace, run the tablet-side backfill, then flip the
        index readable (ref: src/yb/master/backfill_index.cc
        MultiStageAlterTable + BackfillTable state machine, compressed to
        WRITE_AND_DELETE -> backfill -> READABLE)."""
        from yugabyte_tpu.common.index import (
            STATE_BACKFILLING, STATE_READABLE, IndexInfo,
            index_table_schema)
        from yugabyte_tpu.common.schema import Schema
        from yugabyte_tpu.common.wire import schema_from_wire, schema_to_wire

        with self._lock:
            table_id = self._find_table(namespace, table_name)
            if table_id is None:
                raise StatusError(Status.NotFound(
                    f"table {namespace}.{table_name} not found"))
            table_meta = self.tables[table_id]
            for w in table_meta.get("indexes", []):
                if w["index_name"] == index_name:
                    raise StatusError(Status.AlreadyPresent(
                        f"index {index_name!r} exists"))
            main_schema = schema_from_wire(table_meta["schema"])
        columns = [column] if isinstance(column, str) else list(column)
        try:
            idx_schema = index_table_schema(main_schema, columns)
        except (ValueError, KeyError) as e:
            raise StatusError(Status.InvalidArgument(str(e)))
        idx_meta = self.create_table(
            namespace, index_name, schema_to_wire(idx_schema),
            {"hash_partitioning": True}, num_tablets)
        info = IndexInfo(index_name, idx_meta["table_id"],
                         tuple(columns), STATE_BACKFILLING)
        self._set_index_state(table_id, info)
        # Schema propagation grace: every writer must observe the index in
        # write mode before the backfill snapshot is taken, or a write
        # racing the backfill scan would leave the index missing its entry
        # (the reference waits for all tservers to ack the schema version;
        # our clients refresh table metadata on a TTL instead). The grace
        # must comfortably exceed that TTL — a handle cached just before
        # the index persisted stays stale for a full TTL.
        grace_ms = max(flags.get_flag("index_backfill_grace_ms"),
                       3 * flags.get_flag("table_cache_ttl_ms"))
        time.sleep(grace_ms / 1000.0)
        try:
            self._backfill_index(namespace, table_id, info)
        except BaseException:
            # failure-atomic DDL: detach the half-built index and drop its
            # table so CREATE INDEX can be retried (a permanently
            # 'backfilling' index would tax every DML and serve no reads)
            with self._lock:
                table = dict(self.tables[table_id])
                table["indexes"] = [w for w in table.get("indexes", [])
                                    if w["index_name"] != index_name]
                self.sys.upsert("table", table_id, table)
                self.tables[table_id] = table
            try:
                self.delete_table(namespace, index_name)
            except StatusError:
                pass
            raise
        info.state = STATE_READABLE
        self._set_index_state(table_id, info)
        return info.to_wire()

    def _set_index_state(self, table_id: str, info) -> None:
        with self._lock:
            table = dict(self.tables[table_id])
            idxs = [w for w in table.get("indexes", [])
                    if w["index_name"] != info.index_name]
            idxs.append(info.to_wire())
            table["indexes"] = idxs
            self.sys.upsert("table", table_id, table)
            self.tables[table_id] = table

    def _backfill_index(self, namespace: str, table_id: str, info) -> None:
        """Drive one backfill_index_tablet RPC per main-table tablet (ref
        backfill_index.cc BackfillChunk; the tserver scans its local tablet
        at a snapshot and writes index entries at that read time)."""
        with self._lock:
            tablet_ids = [t for t in self.tables[table_id]["tablet_ids"]
                          if t in self.tablets
                          and len(self._split_children_in_catalog(t)) != 2]
        deadline = time.monotonic() + 60.0
        for tablet_id in tablet_ids:
            while True:
                # leaders arrive via heartbeats; a freshly created table's
                # tablets may still be electing — wait, don't abort
                addr_map = self.ts_manager.addr_map()
                with self._lock:
                    leader = self.tablet_leaders.get(tablet_id)
                addr = addr_map.get(leader[0]) if leader else None
                if addr is not None:
                    try:
                        self.messenger.call(
                            addr, "tserver", "backfill_index_tablet",
                            timeout_s=300.0, tablet_id=tablet_id,
                            namespace=namespace,
                            index_table=info.index_name,
                            column=list(info.columns))
                        break
                    except StatusError as e:
                        if time.monotonic() > deadline:
                            raise
                        TRACE("index backfill of %s retrying: %s",
                              tablet_id, e)
                elif time.monotonic() > deadline:
                    raise StatusError(Status.ServiceUnavailable(
                        f"no leader for {tablet_id}; index backfill "
                        f"aborted"))
                time.sleep(0.1)

    def delete_table(self, namespace: str, name: str) -> None:
        with self._lock:
            table_id = self._find_table(namespace, name)
            if table_id is None:
                raise StatusError(Status.NotFound(
                    f"table {namespace}.{name} not found"))
            meta = self.tables[table_id]
            for tablet_id in meta["tablet_ids"]:
                self.sys.delete("tablet", tablet_id)
                self.tablets.pop(tablet_id, None)
                self.tablet_leaders.pop(tablet_id, None)
            self.sys.delete("table", table_id)
            self.tables.pop(table_id, None)
        # Actual replica teardown rides the next heartbeat response
        # (tablets_to_delete), mirroring the reference's deferred deletes.

    # --------------------------------------------------------------- lookups
    def get_table(self, namespace: str, name: str) -> dict:
        with self._lock:
            table_id = self._find_table(namespace, name)
            if table_id is None:
                raise StatusError(Status.NotFound(
                    f"table {namespace}.{name} not found"))
            return dict(self.tables[table_id])

    def list_tables(self, namespace: Optional[str] = None) -> List[dict]:
        with self._lock:
            return [dict(t) for t in self.tables.values()
                    if namespace is None or t["namespace"] == namespace]

    def balancer_snapshot(self) -> Tuple[Dict[str, dict],
                                         Dict[str, tuple]]:
        """Locked (tablets, tablet_leaders) shallow snapshot for the load
        balancer's read-only scan — it runs off the heartbeat threads and
        must not iterate the live guarded dicts bare."""
        with self._lock:
            return ({tid: dict(tm) for tid, tm in self.tablets.items()},
                    dict(self.tablet_leaders))

    def tablet_replicas(self, tablet_id: str) -> List[str]:
        with self._lock:
            return list(self.tablets[tablet_id]["replicas"])

    def has_tablet(self, tablet_id: str) -> bool:
        with self._lock:
            return tablet_id in self.tablets

    def get_table_locations(self, table_id: str) -> List[dict]:
        addr_map = self.ts_manager.addr_map()
        with self._lock:
            table = self.tables.get(table_id)
            if table is None:
                raise StatusError(Status.NotFound(f"table {table_id}"))
            out = []
            for tablet_id in table["tablet_ids"]:
                if len(self._split_children_in_catalog(tablet_id)) == 2:
                    continue  # split parent: clients route to the children
                tm = self.tablets[tablet_id]
                leader = self.tablet_leaders.get(tablet_id)
                out.append({
                    "tablet_id": tablet_id,
                    "partition": tm["partition"],
                    "replicas": [{"server_id": s,
                                  "addr": addr_map.get(s)}
                                 for s in tm["replicas"]],
                    "leader": leader[0] if leader else None})
            out.sort(key=lambda t: t["partition"]["start"])
            return out

    # ------------------------------------------------------------ heartbeats
    def process_heartbeat(self, server_id: str, addr: str,
                          report: List[dict]) -> dict:  # yblint: wire-pair(tablet_report, reads)
        desc = self.ts_manager.heartbeat(server_id, addr, report)
        to_delete = []
        reported_ids = {t["tablet_id"] for t in report}
        with self._lock:
            # Confirmation tracks what the tserver REPORTS, not what was
            # ever acked: a wiped/re-provisioned tserver stops reporting a
            # tablet and the reconciler must re-drive its creation.
            self._confirmed = {(tid, sid) for (tid, sid) in self._confirmed
                               if sid != server_id or tid in reported_ids}
            for t in report:
                tablet_id = t["tablet_id"]
                if tablet_id not in self.tablets:
                    if t.get("split_parent") in self.tablets:
                        # ADOPT a freshly split child the tservers created
                        # (ref CatalogManager::RegisterNewTabletForSplit).
                        self._adopt_split_child_locked(t)
                    else:
                        # Not in the catalog => table dropped (or orphan of
                        # a failed create persisted-first): tear it down.
                        to_delete.append(tablet_id)
                        continue
                # Evicted stale replica (ref master-driven tombstoning of
                # not-in-config replicas): this server is not in the
                # tablet's replica set AND its config predates the
                # authoritative one — its data was moved elsewhere.
                auth_index = self._config_indexes.get(tablet_id)
                if (server_id not in self.tablets[tablet_id]["replicas"]
                        and auth_index is not None
                        and t.get("config_index", 0) < auth_index):
                    to_delete.append(tablet_id)
                    continue
                self._confirmed.add((tablet_id, server_id))
                if t["role"] == "leader" and t.get("leader_ready"):
                    cur = self.tablet_leaders.get(tablet_id)
                    if cur is None or t["term"] >= cur[1]:
                        self.tablet_leaders[tablet_id] = (server_id,
                                                          t["term"])
                        self._config_indexes[tablet_id] = max(
                            self._config_indexes.get(tablet_id, 0),
                            t.get("config_index", 0))
                        # The leader's ACTIVE consensus config is the truth
                        # for replica membership; the catalog follows it
                        # (a crash between ChangeConfig and catalog persist
                        # heals here).
                        reported = t.get("replica_servers")
                        if (reported and sorted(reported)
                                != sorted(self.tablets[tablet_id]
                                          ["replicas"])):
                            self._persist_tablet_replicas_locked(
                                tablet_id, list(reported))
        resp = {
            "addr_map": self.ts_manager.addr_map(),
            "tablets_to_delete": to_delete,
        }
        try:
            with self._lock:
                repl = self._replication_work_for(reported_ids)
            if repl:
                resp["replication"] = repl
        except Exception:  # noqa: BLE001 — must never fail heartbeats
            pass
        try:
            keys = self.universe_keys_provider()
            if keys:
                resp["universe_keys"] = keys
        except Exception:  # noqa: BLE001 — must never fail heartbeats
            pass
        try:
            # always present (possibly {}): the tserver resets tablets NOT
            # in the map to zero, so deleting a schedule releases the deep
            # retention instead of pinning it until restart
            resp["history_retention"] = self._history_retention_for(
                reported_ids)
        except Exception:  # noqa: BLE001 — must never fail heartbeats
            pass
        try:
            updates = self._schema_updates_for(report)
            if updates:
                resp["schema_updates"] = updates
        except Exception:  # noqa: BLE001 — must never fail heartbeats
            pass
        return resp

    def _history_retention_for(self, tablet_ids) -> dict:
        """Per-tablet minimum MVCC history retention implied by active PITR
        snapshot schedules: a restore target can be up to interval_s older
        than its covering snapshot, so tablets under a schedule must retain
        at least interval_s (+slack) of history or compaction collapses the
        versions the restore needs (ref tablet_retention_policy.cc
        AllowedHistoryCutoff fed by the snapshot coordinator).

        The per-table map is cached — heartbeats arrive ~1/s per tserver
        and must not pay a full sys-catalog scan each; schedule create/
        delete invalidates."""
        per_table = self._retention_by_table
        if per_table is None:
            per_table = {}
            for sched in self.list_snapshot_schedules():
                try:
                    table = self.get_table(sched["namespace"],
                                           sched["table"])
                except StatusError:
                    continue
                need = sched["interval_s"] + _SCHEDULE_RETENTION_SLACK_S
                tid = table["table_id"]
                per_table[tid] = max(per_table.get(tid, 0.0), need)
            self._retention_by_table = per_table
        if not per_table:
            return {}
        out = {}
        with self._lock:
            for tablet_id in tablet_ids:
                tm = self.tablets.get(tablet_id)
                if tm and tm["table_id"] in per_table:
                    out[tablet_id] = per_table[tm["table_id"]]
        return out

    def _adopt_split_child_locked(self, t: dict) -> None:  # yblint: wire-pair(tablet_report, reads)
        parent_id = t["split_parent"]
        parent_tm = self.tablets[parent_id]
        child_id = t["tablet_id"]
        tm = {"tablet_id": child_id, "table_id": t["table_id"],
              "partition": t["partition"],
              "hash_partitioning": parent_tm.get("hash_partitioning", True),
              "replicas": list(parent_tm["replicas"]),
              "split_parent": parent_id}
        self.sys.upsert("tablet", child_id, tm)
        self.tablets[child_id] = tm
        table = self.tables.get(t["table_id"])
        if table is not None and child_id not in table["tablet_ids"]:
            table = dict(table)
            table["tablet_ids"] = table["tablet_ids"] + [child_id]
            self.sys.upsert("table", table["table_id"], table)
            self.tables[table["table_id"]] = table
        TRACE("catalog: adopted split child %s of %s", child_id, parent_id)

    def _split_children_in_catalog(self, tablet_id: str) -> List[str]:
        with self._lock:
            return [c for c in (f"{tablet_id}.s0", f"{tablet_id}.s1")
                    if c in self.tablets]

    def retire_split_parents(self) -> int:
        """Drop split parents whose children are adopted and fully
        replicated; their hosts then tear the parent replicas down via the
        heartbeat to_delete path (ref deferred parent deletion in
        tablet_split_manager.cc)."""
        retired = 0
        with self._lock:
            for tablet_id, tm in list(self.tablets.items()):
                children = self._split_children_in_catalog(tablet_id)
                if len(children) != 2:
                    continue
                if not all((c, s) in self._confirmed
                           for c in children
                           for s in self.tablets[c]["replicas"]):
                    continue
                if not all(c in self.tablet_leaders for c in children):
                    continue
                table = self.tables.get(tm["table_id"])
                self.sys.delete("tablet", tablet_id)
                self.tablets.pop(tablet_id, None)
                self.tablet_leaders.pop(tablet_id, None)
                if table is not None and tablet_id in table["tablet_ids"]:
                    table = dict(table)
                    table["tablet_ids"] = [
                        x for x in table["tablet_ids"] if x != tablet_id]
                    self.sys.upsert("table", table["table_id"], table)
                    self.tables[table["table_id"]] = table
                retired += 1
                TRACE("catalog: retired split parent %s", tablet_id)
        return retired

    # ------------------------------------------------- encryption at rest
    # The key material itself lives OUTSIDE the data it encrypts (a
    # plaintext sidecar on the master, the stand-in for an external KMS —
    # ref ent/src/yb/master/universe_key_registry_service.cc sourcing keys
    # out-of-band): storing keys in the sys catalog would be circular on
    # restart. The Master owns the registry; this provider hook feeds the
    # heartbeat responses.
    universe_keys_provider = staticmethod(lambda: [])

    # ---------------------------------------------------- xCluster streams
    def setup_universe_replication(self, replication_id: str,
                                   source_master_addrs: List[str],
                                   tables: List[List[str]]) -> dict:
        """Register async replication from a source universe (ref:
        ent/src/yb/master/catalog_manager_ent.cc SetupUniverseReplication).
        tables: [src_namespace, src_table, dst_namespace, dst_table] rows;
        each target table's tablet leaders then run CDC pollers delivered
        via heartbeats. Partition counts must match — the pollers map
        source tablets by partition start."""
        entries = []
        for src_ns, src_table, dst_ns, dst_table in tables:
            with self._lock:
                dst_id = self._find_table(dst_ns, dst_table)
                if dst_id is None:
                    raise StatusError(Status.NotFound(
                        f"target table {dst_ns}.{dst_table} not found"))
                n_dst = len(self.tables[dst_id]["tablet_ids"])
            # validate against the SOURCE universe now: a tablet-count
            # mismatch would otherwise "succeed" and replicate nothing
            # (pollers match exact partition ranges)
            src_meta = None
            for addr in source_master_addrs:
                try:
                    src_meta = self.messenger.call(
                        addr, "master", "get_table", timeout_s=10.0,
                        namespace=src_ns, name=src_table)
                    break
                except StatusError as e:
                    if getattr(e, "extra", {}).get("not_leader"):
                        continue
                    raise StatusError(Status.InvalidArgument(
                        f"source table {src_ns}.{src_table}: "
                        f"{e.status.message}"))
            if src_meta is None:
                raise StatusError(Status.ServiceUnavailable(
                    "no reachable source master"))
            n_src = len(src_meta["tablet_ids"])
            if n_src != n_dst:
                raise StatusError(Status.InvalidArgument(
                    f"tablet count mismatch for {src_ns}.{src_table}: "
                    f"source {n_src} vs target {n_dst}"))
            entries.append({"src_namespace": src_ns,
                            "src_table": src_table,
                            "dst_table_id": dst_id,
                            "n_tablets": n_dst})
        meta = {"replication_id": replication_id,
                "source_master_addrs": list(source_master_addrs),
                "tables": entries, "checkpoints": {}}
        with self._lock:
            if self.sys.get("replication", replication_id) is not None:
                raise StatusError(Status.AlreadyPresent(
                    f"replication {replication_id!r} exists"))
            self.sys.upsert("replication", replication_id, meta)
            self._replication_cache = None
        return meta

    def delete_universe_replication(self, replication_id: str) -> None:
        with self._lock:
            self.sys.delete("replication", replication_id)
            self._replication_cache = None

    def _replications(self) -> List[dict]:
        """In-memory cache, invalidated by setup/delete/checkpoint writes
        — heartbeats (the hottest master path) must not scan the whole
        sys catalog when no replication is configured."""
        cache = getattr(self, "_replication_cache", None)
        if cache is None:
            cache = [m for t, _i, m in self.sys.scan_all()
                     if t == "replication"]
            self._replication_cache = cache
        return cache

    def update_replication_checkpoint(self, replication_id: str,
                                      tablet_id: str, index: int) -> None:
        with self._lock:
            meta = self.sys.get("replication", replication_id)
            if meta is None:
                return
            cp = meta.get("checkpoints", {})
            if cp.get(tablet_id, -1) >= index:
                return
            cp[tablet_id] = index
            meta["checkpoints"] = cp
            self.sys.upsert("replication", replication_id, meta)
            # update the heartbeat cache IN PLACE: invalidating here would
            # force a full sys-catalog rescan per checkpoint report
            cache = getattr(self, "_replication_cache", None)
            if cache is not None:
                for i, m in enumerate(cache):
                    if m.get("replication_id") == replication_id:
                        cache[i] = meta
                        break

    def _replication_work_for(self, reported_ids) -> List[dict]:
        """Heartbeat piggyback: poller specs for replicated target tablets
        this tserver reports (its leadership is checked tserver-side)."""
        out = []
        for meta in self._replications():
            for t in meta["tables"]:
                with self._lock:
                    table = self.tables.get(t["dst_table_id"])
                if table is None:
                    continue
                for tablet_id in table["tablet_ids"]:
                    if tablet_id not in reported_ids:
                        continue
                    out.append({
                        "replication_id": meta["replication_id"],
                        "tablet_id": tablet_id,
                        "source_master_addrs": meta["source_master_addrs"],
                        "src_namespace": t["src_namespace"],
                        "src_table": t["src_table"],
                        "checkpoint": meta.get("checkpoints", {}).get(
                            tablet_id, 0)})
        return out

    # ------------------------------------------------------------ snapshots
    def create_table_snapshot(self, namespace: str, name: str,
                              schedule_id: Optional[str] = None) -> dict:
        """Coordinate a consistent table snapshot: a raft-replicated
        snapshot barrier on every tablet (ref master SnapshotCoordinator,
        ent/src/yb/master/async_snapshot_tasks.cc); metadata persists in
        the sys catalog so restores survive master failover.

        snapshot_ht (master clock AFTER every barrier replicated) bounds
        the snapshot's coverage: all writes with HT <= any T <=
        snapshot_ht are contained — per tablet, a write with a smaller HT
        precedes the barrier in raft order — which is what PITR's
        restore-to-time selection relies on."""
        import time as _time
        table = self.get_table(namespace, name)
        snapshot_id = uuid.uuid4().hex[:16]
        # coverage bound sampled BEFORE the first barrier: a write with
        # HT <= this time precedes every barrier in per-tablet order, so
        # the snapshot provably contains all state up to snapshot_micros.
        # (Stamping after the barriers would claim coverage for writes
        # that landed between a tablet's barrier and the stamp — a PITR
        # restore would silently miss them.)
        snapshot_micros = int(_time.time() * 1e6)
        addr_map = self.ts_manager.addr_map()
        with self._lock:
            tablet_ids = [t for t in table["tablet_ids"]
                          if t in self.tablets]
            leaders = {t: self.tablet_leaders.get(t) for t in tablet_ids}
        for tablet_id in tablet_ids:
            leader = leaders.get(tablet_id)
            if leader is None or addr_map.get(leader[0]) is None:
                raise StatusError(Status.ServiceUnavailable(
                    f"no leader for {tablet_id}; snapshot aborted"))
            self.messenger.call(addr_map[leader[0]], "tserver",
                                "snapshot_tablet", timeout_s=60.0,
                                tablet_id=tablet_id,
                                snapshot_id=snapshot_id)
        # Guaranteed MVCC history floor inside this snapshot's files: the
        # base retention flag always applies; a schedule's deeper override
        # only counts for as long as the schedule has existed (the override
        # rides heartbeats, so versions older than the schedule may already
        # be compacted away).  Restores below the floor are rejected rather
        # than silently returning post-compaction state.
        effective_s = _base_history_retention_s()
        if schedule_id is not None:
            sched = self.sys.get("snapshot_schedule", schedule_id)
            if sched is not None:
                need = sched["interval_s"] + _SCHEDULE_RETENTION_SLACK_S
                age = max(0.0, _time.time()
                          - sched.get("created_unix", _time.time()))
                effective_s = max(effective_s,
                                  min(need, effective_s + age))
        meta = {"snapshot_id": snapshot_id, "namespace": namespace,
                "table": name, "table_id": table["table_id"],
                "schema": table["schema"],
                "partition_schema": table["partition_schema"],
                "tablet_ids": tablet_ids,
                "snapshot_micros": snapshot_micros,
                "history_floor_micros": int(snapshot_micros
                                            - effective_s * 1e6),
                "schedule_id": schedule_id}
        with self._lock:
            self.sys.upsert("snapshot", snapshot_id, meta)
        return meta

    # ----------------------------------------------- PITR snapshot schedules
    def create_snapshot_schedule(self, namespace: str, name: str,
                                 interval_s: float,
                                 retention_s: float) -> dict:
        """Periodic snapshots with retention — the PITR substrate (ref
        ent master SnapshotCoordinator schedules,
        master_snapshot_coordinator.cc). The master bg loop takes a
        snapshot every interval and prunes ones past retention; any time
        within retention is restorable (restore reads the earliest
        snapshot taken at-or-after the target time AT that time — MVCC
        history inside the snapshot files carries the exact state)."""
        self.get_table(namespace, name)   # validates existence
        sched = {"schedule_id": uuid.uuid4().hex[:16],
                 "namespace": namespace, "table": name,
                 "interval_s": float(interval_s),
                 "retention_s": float(retention_s),
                 "created_unix": time.time(),
                 "last_snapshot_unix": 0.0}
        with self._lock:
            self.sys.upsert("snapshot_schedule", sched["schedule_id"], sched)
        self._retention_by_table = None
        return sched

    def list_snapshot_schedules(self) -> List[dict]:
        return [m for t, _id, m in self.sys.scan_all()
                if t == "snapshot_schedule"]

    def delete_snapshot_schedule(self, schedule_id: str) -> None:
        # the schedule's snapshots go with it — with no schedule there is
        # no retention horizon left to ever prune them
        for snap in self.list_snapshots():
            if snap.get("schedule_id") == schedule_id:
                try:
                    self.delete_snapshot(snap["snapshot_id"])
                except StatusError:
                    pass
        with self._lock:
            self.sys.delete("snapshot_schedule", schedule_id)
        self._retention_by_table = None

    def run_snapshot_schedules(self) -> int:
        """One bg-loop tick: take due snapshots, prune expired ones.
        Returns snapshots taken."""
        with self._schedule_tick_lock:
            return self._run_snapshot_schedules_tick()

    def _run_snapshot_schedules_tick(self) -> int:
        import time as _time
        now = _time.time()
        taken = 0
        snapshots = self.list_snapshots()   # one catalog scan per tick
        for sched in self.list_snapshot_schedules():
            if now - sched["last_snapshot_unix"] >= sched["interval_s"]:
                try:
                    snapshots.append(self.create_table_snapshot(
                        sched["namespace"], sched["table"],
                        schedule_id=sched["schedule_id"]))
                    taken += 1
                    sched = dict(sched, last_snapshot_unix=now)
                    with self._lock:
                        # re-check under the lock: a concurrent
                        # delete_snapshot_schedule must not be undone by
                        # upserting our stale copy back
                        if self.sys.get("snapshot_schedule",
                                        sched["schedule_id"]) is not None:
                            self.sys.upsert("snapshot_schedule",
                                            sched["schedule_id"], sched)
                except StatusError:
                    pass  # table gone / no leader: retried next tick;
                    # retention pruning below must still run (a dropped
                    # table's expired snapshots would otherwise leak
                    # forever)
            horizon = (now - sched["retention_s"]) * 1e6
            for snap in snapshots:
                if snap.get("schedule_id") == sched["schedule_id"] and \
                        snap.get("snapshot_micros", 0) < horizon:
                    try:
                        self.delete_snapshot(snap["snapshot_id"])
                    except StatusError:
                        pass
        return taken

    def pick_restore_snapshot(self, namespace: str, name: str,
                              restore_micros: int) -> dict:
        """The PITR selection rule: the EARLIEST snapshot whose
        snapshot_micros >= the restore time contains the target state in
        its MVCC history (a snapshot taken before the target time lacks
        the writes between its barrier and the target)."""
        cands = [s for s in self.list_snapshots()
                 if s["namespace"] == namespace and s["table"] == name
                 and s.get("snapshot_micros", 0) >= restore_micros]
        if not cands:
            raise StatusError(Status.NotFound(
                f"no snapshot of {namespace}.{name} covers time "
                f"{restore_micros} — outside the retention window?"))
        best = min(cands, key=lambda s: s["snapshot_micros"])
        floor = best.get("history_floor_micros")
        if floor is not None and restore_micros < floor:
            raise StatusError(Status.InvalidArgument(
                f"restore time {restore_micros} predates snapshot "
                f"{best['snapshot_id']}'s guaranteed MVCC history floor "
                f"{floor}: compaction may have collapsed the needed "
                f"versions (raise timestamp_history_retention_interval_sec "
                f"or shorten the schedule interval)"))
        return best

    def list_snapshots(self) -> List[dict]:
        return [m for _t, _id, m in self.sys.scan_all()
                if _t == "snapshot"]

    def get_snapshot(self, snapshot_id: str) -> dict:
        meta = self.sys.get("snapshot", snapshot_id)
        if meta is None:
            raise StatusError(Status.NotFound(f"snapshot {snapshot_id}"))
        return meta

    def delete_snapshot(self, snapshot_id: str) -> None:
        meta = self.get_snapshot(snapshot_id)
        addr_map = self.ts_manager.addr_map()
        for tablet_id in meta["tablet_ids"]:
            for desc in self.ts_manager.all_descriptors():
                addr = addr_map.get(desc.server_id)
                if addr is None:
                    continue
                try:
                    self.messenger.call(addr, "tserver",
                                        "delete_tablet_snapshot",
                                        timeout_s=10.0,
                                        tablet_id=tablet_id,
                                        snapshot_id=snapshot_id)
                except StatusError:
                    pass  # replica gone / not hosting: fine
        with self._lock:
            self.sys.delete("snapshot", snapshot_id)

    def split_tablet(self, tablet_id: str) -> List[str]:
        """Drive a split through the tablet's leader (ref master
        TabletSplitManager)."""
        addr_map = self.ts_manager.addr_map()
        with self._lock:
            if tablet_id not in self.tablets:
                raise StatusError(Status.NotFound(f"tablet {tablet_id}"))
            leader = self.tablet_leaders.get(tablet_id)
        if leader is None or addr_map.get(leader[0]) is None:
            raise StatusError(Status.ServiceUnavailable(
                f"no known leader for {tablet_id}"))
        return self.messenger.call(addr_map[leader[0]], "tserver",
                                   "split_tablet", tablet_id=tablet_id)

    def _persist_tablet_replicas_locked(self, tablet_id: str,
                                        replicas: List[str]) -> None:
        tm = dict(self.tablets[tablet_id])
        tm["replicas"] = replicas
        self.sys.upsert("tablet", tablet_id, tm)
        self.tablets[tablet_id] = tm

    def update_tablet_replicas(self, tablet_id: str,
                               replicas: List[str]) -> None:
        with self._lock:
            if tablet_id in self.tablets:
                self._persist_tablet_replicas_locked(tablet_id, replicas)

    # -------------------------------------------------------- reconciliation
    def reconcile_tablets(self) -> int:
        """Issue (idempotent) create_tablet RPCs for replicas that have not
        yet reported the tablet (ref catalog_manager_bg_tasks.cc resending
        unacked CreateTablet work). Returns RPCs issued."""
        addr_map = self.ts_manager.addr_map()
        with self._lock:
            work = []
            for tablet_id, tm in self.tablets.items():
                table = self.tables.get(tm["table_id"])
                if table is None:
                    continue
                if tm.get("split_parent") in self.tablets:
                    # Split still propagating: every replica creates this
                    # child from its own parent snapshot when the SPLIT op
                    # applies — creating it empty here would diverge it.
                    continue
                # If live replicas already hold data, a missing one must be
                # REBUILT from them (remote bootstrap), not created empty —
                # an empty voter would need the whole log, which may be GC'd.
                leader = self.tablet_leaders.get(tablet_id)
                confirmed_any = any((tablet_id, s) in self._confirmed
                                    for s in tm["replicas"])
                source_addr = (addr_map.get(leader[0])
                               if confirmed_any and leader else None)
                for server_id in tm["replicas"]:
                    if (tablet_id, server_id) in self._confirmed:
                        continue
                    work.append((tablet_id, tm, table, server_id,
                                 source_addr))
        issued = [0]
        lock = threading.Lock()

        def send(tablet_id, tm, table, server_id, addr, source_addr):
            try:
                if source_addr is not None and source_addr != addr:
                    self.messenger.call(
                        addr, "tserver", "start_remote_bootstrap",
                        timeout_s=60.0, tablet_id=tablet_id,
                        source_addr=source_addr)
                else:
                    self.messenger.call(
                        addr, "tserver", "create_tablet", timeout_s=5.0,
                        tablet_id=tablet_id, table_id=tm["table_id"],
                        schema=table["schema"],
                        peer_server_ids=tm["replicas"],
                        partition=tm["partition"],
                        hash_partitioning=tm.get("hash_partitioning", True),
                        addr_map=addr_map)
                with lock:
                    issued[0] += 1
            except StatusError as e:
                TRACE("reconcile: create %s on %s failed: %s",
                      tablet_id, server_id, e)

        # Parallel fan-out: one blackholed tserver must not head-of-line
        # block creation on healthy ones (acks arrive via heartbeats, so a
        # straggler thread finishing late is harmless and idempotent).
        threads = []
        for tablet_id, tm, table, server_id, source_addr in work:
            addr = addr_map.get(server_id)
            if addr is None:
                continue
            t = threading.Thread(target=send, daemon=True,
                                 args=(tablet_id, tm, table, server_id,
                                       addr, source_addr))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=6.0)
        return issued[0]
