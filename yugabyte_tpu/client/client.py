"""YBClient: DDL + routed data ops with leader-aware retries.

Capability parity with the reference (ref: src/yb/client/client.h:264 —
table/namespace admin via master leader with follower redirect
(client_master_rpc.cc), data ops routed by MetaCache with NOT_THE_LEADER
retry + location refresh, ref batcher.cc error handling).
"""

from __future__ import annotations

import struct
import time
from typing import Dict, List, Optional, Sequence, Tuple

from yugabyte_tpu.common.hybrid_time import HybridTime
from yugabyte_tpu.common.partition import PartitionSchema
from yugabyte_tpu.common.schema import Schema
from yugabyte_tpu.common.wire import (
    doc_key_to_wire, partition_schema_from_wire, partition_schema_to_wire,
    row_from_wire, schema_from_wire, schema_to_wire, write_op_to_wire)
from yugabyte_tpu.client.meta_cache import MetaCache, RemoteTablet
from yugabyte_tpu.docdb.doc_key import DocKey
from yugabyte_tpu.docdb.doc_operations import QLWriteOp
from yugabyte_tpu.rpc.messenger import (
    Messenger, RemoteError, RpcTimeout, ServiceUnavailable)
from yugabyte_tpu.utils import flags
from yugabyte_tpu.utils import latency
from yugabyte_tpu.utils.backoff import Backoff, RetryBudget
from yugabyte_tpu.utils.status import Code, Status, StatusError
from yugabyte_tpu.utils.trace import TRACE, Trace, span

flags.define_flag("client_rpc_retries", 12,
                  "per-operation retry budget (leader changes, restarts)")
flags.define_flag("client_op_timeout_s", 60.0,
                  "overall per-operation deadline across ALL retries "
                  "(ref client.h default_admin_operation_timeout): the "
                  "retry walk clamps its backoff sleeps and per-attempt "
                  "RPC timeouts to the remaining budget and surfaces "
                  "DeadlineExceeded instead of retrying past it; "
                  "<= 0 disables the bound")
flags.define_flag("follower_read_staleness_ms", 500.0,
                  "bounded-staleness follower reads resolve at "
                  "now - this (ref yb_follower_read_staleness_ms): far "
                  "enough behind that a healthy follower's propagated "
                  "safe time already covers the read point, so the read "
                  "never blocks on the leader")


def follower_read_ht() -> HybridTime:
    """The bounded-staleness read point for follower reads."""
    stale_us = int(flags.get_flag("follower_read_staleness_ms") * 1000)
    return HybridTime.from_micros(
        max(0, int(time.time() * 1e6) - stale_us))


def _op_deadline_s() -> Optional[float]:
    t = flags.get_flag("client_op_timeout_s")
    return t if t and t > 0 else None


def _deadline_exceeded(what: str, backoff: Backoff,
                       last_err) -> StatusError:
    return StatusError(Status.TimedOut(
        f"{what}: per-op deadline "
        f"({flags.get_flag('client_op_timeout_s')}s) exceeded after "
        f"{backoff.attempts} retry rounds (last: {last_err})"))

MASTER_SERVICE = "master"
TABLET_SERVICE = "tserver"


class YBTable:
    """Table handle: schema + partitioning + key encoding helpers
    (ref client.h YBTable)."""

    def __init__(self, meta: dict):
        self.table_id = meta["table_id"]
        self.name = meta["name"]
        self.namespace = meta["namespace"]
        # bumped by ALTER TABLE; writes/reads carry it so a tserver whose
        # tablet still runs the older schema rejects retryably instead of
        # misencoding the new columns (ref tablet schema version checks)
        self.schema_version = meta.get("schema_version", 0)
        self.schema: Schema = schema_from_wire(meta["schema"])
        self.partition_schema: PartitionSchema = partition_schema_from_wire(
            meta["partition_schema"])
        # secondary indexes attached to this table (common/index.IndexInfo
        # wire dicts); maintained by the query layers on DML
        self.indexes: List[dict] = list(meta.get("indexes", []))

    def partition_key_for(self, doc_key: DocKey) -> bytes:
        return self.partition_schema.partition_key(
            doc_key.hash_code, doc_key.encode())


class YBClient:
    def __init__(self, master_addrs: Sequence[str],
                 messenger: Optional[Messenger] = None):
        import threading
        import uuid
        self._messenger = messenger or Messenger("client")
        self._owns_messenger = messenger is None
        self._master_addrs = list(master_addrs)
        self._master_leader: Optional[str] = None
        self.meta_cache = MetaCache(
            lambda table_id: self._master_call("get_table_locations",
                                               table_id=table_id))
        # exactly-once identity: (client_id, per-write request id) rides
        # every write RPC; retries REUSE the id so the server dedups them
        # (ref consensus/retryable_requests.cc)
        self.client_id = uuid.uuid4().bytes
        self._request_counter = 0
        self._request_lock = threading.Lock()
        # One token-bucket retry budget shared by EVERY retry loop of
        # this client (master hunts, replica walks, scans, sessions):
        # retries beyond the budget surface a typed RetryBudgetExhausted
        # instead of multiplying offered load against an already
        # saturated cluster (ref rpc retrier budgets; first attempts are
        # never charged).
        self.retry_budget = RetryBudget()

    def _next_request_id(self) -> int:
        with self._request_lock:
            self._request_counter += 1
            return self._request_counter

    # ----------------------------------------------------------- master RPCs
    def _master_call(self, mth: str, _retry_ctx: Optional[dict] = None,
                     _timeout_s: Optional[float] = None, **args):
        """Find and call the master leader, following not-leader hints
        (ref client_master_rpc.cc). `_retry_ctx`, when given, records
        whether a send may have reached the master before failing — callers
        of non-idempotent DDL use it to disambiguate an AlreadyPresent
        caused by their own timed-out first attempt."""
        addrs = ([self._master_leader] if self._master_leader else []) + [
            a for a in self._master_addrs if a != self._master_leader]
        last_err: Optional[Exception] = None
        backoff = Backoff(base_s=0.1, cap_s=1.0,
                          deadline_s=_op_deadline_s())
        with Trace(f"client.master.{mth}"):
            return self._master_call_traced(mth, _retry_ctx, _timeout_s,
                                            addrs, last_err, backoff, args)

    def _master_call_traced(self, mth, _retry_ctx, _timeout_s, addrs,
                            last_err, backoff, args):
        for _ in range(flags.get_flag("client_rpc_retries")):
            for addr in list(addrs):
                try:
                    TRACE("client: master %s at %s", mth, addr)
                    rem = backoff.remaining_s()
                    att_timeout = _timeout_s
                    if rem is not None:
                        # one slow attempt must not blow the whole op
                        # budget: clamp this attempt to what is left
                        att_timeout = min(att_timeout, rem) \
                            if att_timeout is not None else rem
                    ret = self._messenger.call(addr, MASTER_SERVICE, mth,
                                               timeout_s=att_timeout,
                                               **args)
                    self._master_leader = addr
                    return ret
                except RemoteError as e:
                    if e.extra.get("not_leader"):
                        hint = e.extra.get("leader_hint")
                        if hint and hint not in addrs:
                            addrs.append(hint)
                        last_err = e
                        self.retry_budget.spend_or_raise(
                            f"master.{mth}", last_err=e)
                        continue
                    if e.extra.get("overloaded"):
                        # typed shedding rejection (bounded RPC queue /
                        # write admission): retry, honoring the server's
                        # measured retry_after hint at the round sleep
                        backoff.note_server_hint(
                            e.extra.get("retry_after_ms"))
                        last_err = e
                        self.retry_budget.spend_or_raise(
                            f"master.{mth}", last_err=e)
                        continue
                    raise
                except RpcTimeout as e:  # yblint: contained(retry walk: last_err re-raised on deadline/retry exhaustion below)
                    # The request may have been executing when we gave up.
                    if _retry_ctx is not None:
                        _retry_ctx["maybe_applied"] = True
                    last_err = e
                    self.retry_budget.spend_or_raise(
                        f"master.{mth}", last_err=e)
                    continue
                except ServiceUnavailable as e:  # yblint: contained(retry walk: last_err re-raised on deadline/retry exhaustion below)
                    last_err = e
                    self.retry_budget.spend_or_raise(
                        f"master.{mth}", last_err=e)
                    continue
            self._master_leader = None
            if not backoff.sleep():  # jittered, not lockstep
                # overall per-op deadline spent: surface instead of
                # burning the remaining retry rounds against a wall
                raise _deadline_exceeded(f"master.{mth}", backoff,
                                         last_err)
        raise StatusError(Status.ServiceUnavailable(
            f"no reachable master leader for {mth} (last: {last_err})"))

    # ------------------------------------------------------------------- DDL
    def create_namespace(self, name: str) -> None:
        ctx: Dict[str, bool] = {}
        try:
            self._master_call("create_namespace", _retry_ctx=ctx, name=name)
        except RemoteError as e:
            # AlreadyPresent after our own timed-out attempt means the
            # first send landed: the create succeeded.
            if not (e.status.code == Code.ALREADY_PRESENT
                    and ctx.get("maybe_applied")):
                raise

    # ------------------------------------------------------------ sequences
    # ref: src/postgres sequence.c via the master-backed counter
    def create_sequence(self, namespace: str, name: str, start: int = 1,
                        if_not_exists: bool = False) -> None:
        ctx: Dict[str, bool] = {}
        try:
            self._master_call("create_sequence", _retry_ctx=ctx,
                              namespace=namespace, name=name, start=start,
                              if_not_exists=if_not_exists)
        except RemoteError as e:
            if not (e.status.code == Code.ALREADY_PRESENT
                    and ctx.get("maybe_applied")):
                raise

    def drop_sequence(self, namespace: str, name: str,
                      if_exists: bool = False) -> None:
        self._master_call("drop_sequence", namespace=namespace, name=name,
                          if_exists=if_exists)

    def create_view(self, namespace: str, name: str, sql: str,
                    or_replace: bool = False) -> None:
        ctx: Dict[str, bool] = {}
        try:
            self._master_call("create_view", _retry_ctx=ctx,
                              namespace=namespace, name=name, sql=sql,
                              or_replace=or_replace)
        except RemoteError as e:
            # our own timed-out first attempt may have applied
            if not (e.status.code == Code.ALREADY_PRESENT
                    and ctx.get("maybe_applied")):
                raise

    def drop_view(self, namespace: str, name: str,
                  if_exists: bool = False) -> None:
        ctx: Dict[str, bool] = {}
        try:
            self._master_call("drop_view", _retry_ctx=ctx,
                              namespace=namespace, name=name,
                              if_exists=if_exists)
        except RemoteError as e:
            if not (e.status.code == Code.NOT_FOUND
                    and ctx.get("maybe_applied")):
                raise

    def get_view(self, namespace: str, name: str):
        return self._master_call("get_view", namespace=namespace,
                                 name=name)

    def list_views(self, namespace: str):
        return self._master_call("list_views", namespace=namespace)

    def sequence_next(self, namespace: str, name: str,
                      cache: int = 1) -> int:
        # NOT idempotent-retried through _retry_ctx: a duplicate allocate
        # only skips values, which PG sequences explicitly permit
        return int(self._master_call("sequence_next", namespace=namespace,
                                     name=name, cache=cache))

    def create_table(self, namespace: str, name: str, schema: Schema,
                     num_tablets: int = 4,
                     partition_schema: Optional[PartitionSchema] = None,
                     replication_factor: Optional[int] = None) -> YBTable:
        ps = partition_schema or PartitionSchema(
            hash_partitioning=bool(schema.num_hash_key_columns))
        ctx: Dict[str, bool] = {}
        try:
            meta = self._master_call(
                "create_table", _retry_ctx=ctx, namespace=namespace,
                name=name, schema=schema_to_wire(schema),
                partition_schema=partition_schema_to_wire(ps),
                num_tablets=num_tablets,
                replication_factor=replication_factor)
        except RemoteError as e:
            if not (e.status.code == Code.ALREADY_PRESENT
                    and ctx.get("maybe_applied")):
                raise
            meta = self._master_call("get_table", namespace=namespace,
                                     name=name)
        return YBTable(meta)

    def delete_table(self, namespace: str, name: str) -> None:
        self._master_call("delete_table", namespace=namespace, name=name)

    def alter_table(self, namespace: str, name: str,
                    add_columns: Sequence[Tuple[str, str]] = (),
                    drop_columns: Sequence[str] = ()) -> YBTable:
        """Online ALTER TABLE ADD/DROP COLUMN (ref client.h AlterTable):
        returns the table handle at the NEW schema version."""
        meta = self._master_call(
            "alter_table", namespace=namespace, name=name,
            add_columns=[list(c) for c in add_columns],
            drop_columns=list(drop_columns))
        return YBTable(meta)

    def create_index(self, namespace: str, table: str, index_name: str,
                     column, num_tablets: int = 2,
                     timeout_s: float = 600.0) -> dict:
        """Create a secondary index and run its online backfill; returns
        the IndexInfo wire dict with state 'readable' on success.

        The RPC covers the whole grace + backfill, so it gets a long
        timeout; an AlreadyPresent after our own timed-out attempt means
        the first send is still building — poll the table meta for the
        index to turn readable instead of failing."""
        # normalize the public entry point once: downstream layers (master
        # catalog, tserver backfill) always see a list of column names
        column = [column] if isinstance(column, str) else list(column)
        from yugabyte_tpu.common.index import STATE_READABLE
        ctx: Dict[str, bool] = {}
        try:
            return self._master_call(
                "create_index", _retry_ctx=ctx, _timeout_s=timeout_s,
                namespace=namespace, table=table, index_name=index_name,
                column=column, num_tablets=num_tablets)
        except RemoteError as e:
            if not (e.status.code == Code.ALREADY_PRESENT
                    and ctx.get("maybe_applied")):
                raise
        backoff = Backoff(base_s=0.25, cap_s=2.0, deadline_s=timeout_s)
        while True:
            meta = self._master_call("get_table", namespace=namespace,
                                     name=table)
            for w in meta.get("indexes", []):
                if (w["index_name"] == index_name
                        and w.get("state") == STATE_READABLE):
                    return w
            if not backoff.sleep():
                break
        raise StatusError(Status.TimedOut(
            f"index {index_name} did not become readable"))

    def setup_universe_replication(self, replication_id: str,
                                   source_master_addrs: Sequence[str],
                                   tables: Sequence[Sequence[str]]) -> dict:
        """Async xCluster replication: tables is a list of
        [src_namespace, src_table, dst_namespace, dst_table]."""
        return self._master_call(
            "setup_universe_replication", replication_id=replication_id,
            source_master_addrs=list(source_master_addrs),
            tables=[list(t) for t in tables])

    def delete_universe_replication(self, replication_id: str) -> None:
        self._master_call("delete_universe_replication",
                          replication_id=replication_id)

    def open_table(self, namespace: str, name: str) -> YBTable:
        return YBTable(self._master_call("get_table", namespace=namespace,
                                         name=name))

    def list_tables(self, namespace: Optional[str] = None) -> List[dict]:
        return self._master_call("list_tables", namespace=namespace)

    def list_namespaces(self) -> List[str]:
        return self._master_call("list_namespaces")

    def list_tservers(self) -> List[dict]:
        return self._master_call("list_tservers")

    # ------------------------------------------------------- tablet-side ops
    def _tablet_call(self, table: YBTable, tablet: RemoteTablet, mth: str,
                     refresh_key: Optional[bytes] = None,
                     spread_replicas: bool = False, **args):
        """Call a tablet's leader, retrying through replicas and refreshing
        locations on failure (ref batcher.cc + meta_cache.cc retry logic).
        Split markers propagate up immediately — the caller must re-route
        by key (a split parent's replacement differs per key).

        spread_replicas: follower-read mode — start the replica walk at a
        random replica instead of leader-first so read load spreads
        across the raft group; an unvouched/lagging replica answers
        retryably and the walk moves on."""
        if refresh_key is None:
            refresh_key = tablet.partition.start
        last_err: Optional[Exception] = None
        backoff = Backoff(base_s=0.05, cap_s=1.0,
                          deadline_s=_op_deadline_s())
        # Root span of the distributed trace: the messenger stamps this
        # span's context on every attempt's wire header, so the tserver
        # handler (and the raft fan-out under it) stitches to one
        # trace_id. Nested calls (retries, split re-routes) inherit.
        with Trace(f"client.{mth}"):
            return self._tablet_call_traced(table, tablet, mth,
                                            refresh_key, last_err,
                                            backoff, args,
                                            spread_replicas)

    def _tablet_call_traced(self, table, tablet, mth, refresh_key,
                            last_err, backoff, args,
                            spread_replicas=False):
        import random as _random
        for attempt in range(flags.get_flag("client_rpc_retries")):
            addrs = tablet.candidate_addrs()
            if spread_replicas and len(addrs) > 1:
                # followers first in random order, leader last: load
                # spreads across vouched replicas, and the leader stays
                # in the walk as the deterministic fallback when every
                # follower refuses (unvouched / safe time behind)
                rest = addrs[1:]
                _random.shuffle(rest)
                addrs = rest + addrs[:1]
            for addr in addrs:
                try:
                    TRACE("client: %s tablet %s at %s (attempt %d)",
                          mth, tablet.tablet_id, addr, attempt)
                    rem = backoff.remaining_s()
                    att_timeout = None if rem is None else min(
                        rem, flags.get_flag("rpc_default_timeout_s"))
                    return self._messenger.call(
                        addr, TABLET_SERVICE, mth, timeout_s=att_timeout,
                        tablet_id=tablet.tablet_id, **args)
                except RemoteError as e:
                    if e.extra.get("tablet_split") or \
                            e.extra.get("wrong_tablet"):
                        raise
                    if e.extra.get("tablet_failed"):
                        # This replica parked itself after a background
                        # storage error: stop preferring it and walk the
                        # other replicas now; the master re-replicates /
                        # a new leader emerges while we retry.
                        tablet.mark_leader(None)
                        last_err = e
                        self.retry_budget.spend_or_raise(
                            f"{mth} tablet {tablet.tablet_id}",
                            last_err=e)
                        continue
                    if e.extra.get("not_leader"):
                        hint = e.extra.get("leader_hint")
                        if hint:
                            tablet.mark_leader(hint)
                        last_err = e
                        self.retry_budget.spend_or_raise(
                            f"{mth} tablet {tablet.tablet_id}",
                            last_err=e)
                        continue
                    if e.extra.get("overloaded"):
                        # typed shedding rejection (bounded RPC queue /
                        # write-pressure hard limit): retryable — the
                        # server's measured retry_after_ms floors the
                        # round's backoff sleep so this client cannot
                        # come back before the queue/flush drains
                        backoff.note_server_hint(
                            e.extra.get("retry_after_ms"))
                        last_err = e
                        self.retry_budget.spend_or_raise(
                            f"{mth} tablet {tablet.tablet_id}",
                            last_err=e)
                        continue
                    if (e.status.code in (Code.NOT_FOUND,
                                          Code.SERVICE_UNAVAILABLE,
                                          Code.TIMED_OUT)
                            or e.extra.get("replication_aborted")):
                        # TIMED_OUT is the server's OperationOutcomeUnknown:
                        # the entry may still commit. Retrying HERE — with
                        # the same request id — is what makes the
                        # retryable-request dedup close the double-apply
                        # hole (the op args carry client_id/request_id).
                        # replication_aborted tags a raft entry overwritten
                        # by a new leader: provably not committed, retry on
                        # the re-resolved leader. (Bare Code.ABORTED is NOT
                        # retried — it is also the terminal answer for an
                        # aborted TRANSACTION, which must surface.)
                        last_err = e
                        self.retry_budget.spend_or_raise(
                            f"{mth} tablet {tablet.tablet_id}",
                            last_err=e)
                        continue
                    raise
                except (RpcTimeout, ServiceUnavailable) as e:  # yblint: contained(replica walk: last_err re-raised on deadline/retry exhaustion below)
                    last_err = e
                    self.retry_budget.spend_or_raise(
                        f"{mth} tablet {tablet.tablet_id}", last_err=e)
                    continue
            # All replicas failed: refresh locations and back off
            # (decorrelated jitter — concurrent clients desynchronize).
            if not backoff.sleep():
                raise _deadline_exceeded(
                    f"{mth} on tablet {tablet.tablet_id}", backoff,
                    last_err)
            tablet = self.meta_cache.lookup_tablet(
                table.table_id, refresh_key, refresh=True)
        raise StatusError(Status.ServiceUnavailable(
            f"{mth} on tablet {tablet.tablet_id} exhausted retries "
            f"(last: {last_err})"))

    def write(self, table: YBTable, ops: Sequence[QLWriteOp],
              tablet: Optional[RemoteTablet] = None,
              _depth: int = 0) -> HybridTime:
        """Write a batch that must all land in ONE tablet (the session
        batcher groups ops per tablet before calling this). If the tablet
        split underneath us, re-group the ops by key over the fresh
        locations — the batch may now span both children.

        Every attempt of this logical write carries the same
        (client_id, request_id), so a retry after an unknown outcome
        (timeout mid-replication, leader change) cannot double-apply."""
        pk = table.partition_key_for(ops[0].doc_key)
        if tablet is None:
            tablet = self.meta_cache.lookup_tablet(table.table_id, pk)
        request_id = self._next_request_id()
        try:
            resp = self._tablet_call(
                table, tablet, "write", refresh_key=pk,
                ops=[write_op_to_wire(op) for op in ops],
                client_id=self.client_id, request_id=request_id,
                schema_version=table.schema_version)
            return HybridTime(resp["propagated_ht"])
        except RemoteError as e:
            if not (e.extra.get("tablet_split")
                    or e.extra.get("wrong_tablet")) or _depth >= 8:
                raise
        # Give the master a beat to adopt the children, then re-route.
        time.sleep(0.15 * (_depth + 1))
        self.meta_cache.invalidate(table.table_id)
        groups: Dict[str, Tuple[RemoteTablet, List[QLWriteOp]]] = {}
        for op in ops:
            opk = table.partition_key_for(op.doc_key)
            t = self.meta_cache.lookup_tablet(table.table_id, opk)
            groups.setdefault(t.tablet_id, (t, []))[1].append(op)
        ht = HybridTime(0)
        for t, group in groups.values():
            ht = max(ht, self.write(table, group, tablet=t,
                                    _depth=_depth + 1),
                     key=lambda h: h.value)
        return ht

    def read_row(self, table: YBTable, doc_key: DocKey,
                 read_ht: Optional[HybridTime] = None,
                 projection: Optional[Sequence[str]] = None,
                 follower_read: bool = False):
        """follower_read: bounded-staleness read (read point defaults to
        now - follower_read_staleness_ms) that any VOUCHED replica may
        serve — the replica walk starts at a random replica to spread
        load, and unvouched replicas refuse retryably so the walk falls
        through to the leader."""
        pk = table.partition_key_for(doc_key)
        tablet = self.meta_cache.lookup_tablet(table.table_id, pk)
        if follower_read and read_ht is None:
            read_ht = follower_read_ht()
        w = self._tablet_call(
            table, tablet, "read_row", refresh_key=pk,
            spread_replicas=follower_read,
            doc_key=doc_key_to_wire(doc_key),
            read_ht=read_ht.value if read_ht else None,
            projection=list(projection) if projection else None,
            allow_follower=follower_read,
            schema_version=table.schema_version)
        return row_from_wire(w)

    def multi_read(self, table: YBTable, doc_keys: Sequence[DocKey],
                   read_ht: Optional[HybridTime] = None,
                   projection: Optional[Sequence[str]] = None,
                   follower_read: bool = False):
        """Batched point-row reads: keys group per tablet and each group
        rides ONE multi_read RPC (one leader-lease check + read-point
        resolution server-side, and the batched device point-read path
        under it), instead of a read_row round trip per key. Returns
        rows aligned with doc_keys (None = absent).

        follower_read: see read_row — bounded-staleness batch served by
        any vouched replica, spreading read load across the raft group."""
        # the whole batch on the caller's thread; the per-tablet calls
        # (fan-out threads) are its children
        with span("client/multi_read_batch") as batch:
            groups: Dict[str, Tuple[RemoteTablet, bytes, List[int]]] = {}
            for i, dk in enumerate(doc_keys):
                pk = table.partition_key_for(dk)
                tablet = self.meta_cache.lookup_tablet(table.table_id, pk)
                groups.setdefault(tablet.tablet_id,
                                  (tablet, pk, []))[2].append(i)
            if follower_read and read_ht is None:
                read_ht = follower_read_ht()
            out: List = [None] * len(doc_keys)
            errors: List[Exception] = []

            def fetch(tablet, pk, idxs) -> None:
                try:
                    # serve-path attribution: one budget per tablet group —
                    # each group is one RPC, so the per-group e2e decomposes
                    # cleanly into its own server's stage map (a fan-out
                    # batch records one attribution sample per tablet)
                    with latency.budget_scope(latency.OP_MULTI_READ,
                                              parent=batch):
                        resp = self._tablet_call(
                            table, tablet, "multi_read", refresh_key=pk,
                            spread_replicas=follower_read,
                            doc_keys=[doc_key_to_wire(doc_keys[i])
                                      for i in idxs],
                            read_ht=read_ht.value if read_ht else None,
                            projection=(list(projection) if projection
                                        else None),
                            allow_follower=follower_read,
                            schema_version=table.schema_version)
                except Exception as e:  # noqa: BLE001 — re-raised below
                    errors.append(e)
                    return
                for i, w in zip(idxs, resp["rows"]):
                    out[i] = None if w is None else row_from_wire(w)

            grps = list(groups.values())
            if len(grps) == 1:
                fetch(*grps[0])
            else:
                # per-tablet fan-out: the batch's wall time is the slowest
                # tablet's RPC, not the sum (mirrors the session batcher)
                import threading as _threading
                threads = [_threading.Thread(target=fetch, args=g, daemon=True)
                           for g in grps]
                for t in threads:
                    t.start()
                with span("client/await_fanout"):
                    for t in threads:
                        t.join()
        if errors:
            raise errors[0]
        return out

    def scan(self, table: YBTable, read_ht: Optional[HybridTime] = None,
             projection: Optional[Sequence[str]] = None,
             page_size: int = 4096,
             filters: Optional[Sequence[Sequence]] = None,
             txn_id: Optional[bytes] = None,
             start_cursor: bytes = b"", start_lower: bytes = b"",
             scan_state: Optional[dict] = None):
        """Full-table scan in partition-key order, paging within each
        tablet (ref pg_doc_op.h:399 fan-out + paging). The read point the
        first page resolves is pinned for every later page and tablet, so
        the whole scan is one consistent snapshot. A partition-key cursor
        + a global doc-key lower bound make the scan robust to tablets
        splitting or moving mid-scan: doc keys order the same way as
        partition keys, so re-looking up the cursor can never re-yield or
        skip rows.

        start_cursor/start_lower resume a previous scan (a query layer's
        paging-state continuation); scan_state, when given, is updated
        with the pinned {'read_ht': ...} so the caller can embed it in a
        continuation token."""
        pinned = read_ht.value if read_ht else None
        cursor = start_cursor   # partition-key-space position
        lower = start_lower     # doc-key resume bound (global, monotonic)
        failures = 0
        backoff = Backoff(base_s=0.1, cap_s=1.0)
        while True:
            tablet = self.meta_cache.lookup_tablet(table.table_id, cursor)
            try:
                resp = self._tablet_call(
                    table, tablet, "scan", refresh_key=cursor,
                    lower_doc_key=lower, read_ht=pinned,
                    projection=list(projection) if projection else None,
                    limit=page_size,
                    filters=[list(f) for f in filters] if filters else None,
                    txn_id=txn_id)
            except RemoteError as e:
                # Only split/moved/not-found/overloaded are worth
                # re-routing; other errors are deterministic and must
                # surface immediately.
                retryable = (e.extra.get("tablet_split")
                             or e.extra.get("wrong_tablet")
                             or e.extra.get("overloaded")
                             or e.status.code == Code.NOT_FOUND)
                failures += 1
                if not retryable or failures > 8:
                    raise
                if e.extra.get("overloaded"):
                    backoff.note_server_hint(e.extra.get("retry_after_ms"))
                self.retry_budget.spend_or_raise(
                    f"scan {table.name}", last_err=e)
                time.sleep(backoff.next_delay())
                self.meta_cache.invalidate(table.table_id)
                continue
            failures = 0
            backoff = Backoff(base_s=0.1, cap_s=1.0)
            if pinned is None:
                pinned = resp.get("read_ht")
            if scan_state is not None:
                scan_state["read_ht"] = pinned
            for w in resp["rows"]:
                yield row_from_wire(w)
            if resp.get("resume_key"):
                lower = resp["resume_key"]
                continue
            if not tablet.partition.end:
                return
            cursor = tablet.partition.end

    def scan_aggregate(self, table: YBTable, aggregates: Sequence[Sequence],
                       filters: Optional[Sequence[Sequence]] = None,
                       read_ht: Optional[HybridTime] = None,
                       partition_key: Optional[bytes] = None,
                       lower_doc_key: bytes = b"",
                       upper_doc_key: Optional[bytes] = None,
                       row_cb=None, page_size: int = 4096,
                       group_by: Optional[Sequence[str]] = None,
                       walk_stats: Optional[dict] = None):
        """Aggregate pushdown walk (ROADMAP item 5): per tablet, ask the
        scan RPC to compute [[fn, col], ...] over the filtered row set in
        ONE fused device dispatch. Tablets that cannot push (intents,
        uncompilable spec, device fault/quarantine, no device) return
        ROWS instead; those stream to `row_cb` and the caller folds them
        into its own accumulator — per-tablet row sets are disjoint, so
        device partials and host partials combine exactly.

        partition_key pins the walk to one tablet (the partition-prefix
        scan shape); otherwise every tablet of the table is visited at
        one pinned snapshot. Returns (combined_partial_or_None, read_ht)
        — None when NO tablet answered with a device partial.

        group_by (value-column names), product terms (`[fn, [[kind,
        col], ...]]`) and DECIMAL / DATE / CHAR columns select the typed,
        grouped kernel: the combined partial is then {"groups": [{"key",
        "rows", "terms"}]}, merged group by group in exact integers.
        walk_stats, when given, receives {"tablets", "from_rows"}: the
        tablets visited and those that answered in rows."""
        from yugabyte_tpu.docdb.scan_spec import combine_agg_partials
        pinned = read_ht.value if read_ht else None
        cursor = partition_key if partition_key is not None else b""
        partials: List[dict] = []
        failures = 0
        backoff = Backoff(base_s=0.1, cap_s=1.0)
        aggs = [list(a) for a in aggregates]
        flts = [list(f) for f in filters] if filters else None
        lower = lower_doc_key
        ask_agg = True   # first page per tablet tries the fused path
        extra = {"group_by": list(group_by)} if group_by else {}
        visited = from_rows = 0
        while True:
            tablet = self.meta_cache.lookup_tablet(table.table_id, cursor)
            try:
                # serve-path attribution: one budget a tablet call, as
                # multi_read has one a tablet group
                with latency.budget_scope(latency.OP_SCAN):
                    resp = self._tablet_call(
                        table, tablet, "scan", refresh_key=cursor,
                        lower_doc_key=lower, upper_doc_key=upper_doc_key,
                        read_ht=pinned, limit=page_size, filters=flts,
                        aggregates=aggs if ask_agg else None, **extra)
            except RemoteError as e:
                retryable = (e.extra.get("tablet_split")
                             or e.extra.get("wrong_tablet")
                             or e.extra.get("overloaded")
                             or e.status.code == Code.NOT_FOUND)
                failures += 1
                if not retryable or failures > 8:
                    raise
                if e.extra.get("overloaded"):
                    backoff.note_server_hint(e.extra.get("retry_after_ms"))
                self.retry_budget.spend_or_raise(
                    f"scan_aggregate {table.name}", last_err=e)
                time.sleep(backoff.next_delay())
                self.meta_cache.invalidate(table.table_id)
                continue
            failures = 0
            backoff = Backoff(base_s=0.1, cap_s=1.0)
            if pinned is None:
                pinned = resp.get("read_ht")
            visited += ask_agg
            if "agg" in resp and resp["agg"] is not None:
                partials.append(resp["agg"])
            else:
                from_rows += ask_agg
                for w in resp["rows"]:
                    if row_cb is not None:
                        row_cb(row_from_wire(w))
                if resp.get("resume_key"):
                    # this tablet fell back to rows: page through it
                    # without re-attempting the fused path mid-tablet
                    lower = resp["resume_key"]
                    ask_agg = False
                    continue
            ask_agg = True
            lower = lower_doc_key
            if partition_key is not None or not tablet.partition.end:
                break
            cursor = tablet.partition.end
        if walk_stats is not None:
            walk_stats.update(tablets=visited, from_rows=from_rows)
        combined = combine_agg_partials(partials) if partials else None
        return combined, pinned

    def scan_key_range(self, table: YBTable, partition_key: bytes,
                       lower_doc_key: bytes,
                       upper_doc_key: Optional[bytes] = None,
                       read_ht: Optional[HybridTime] = None,
                       page_size: int = 4096,
                       filters: Optional[Sequence[Sequence]] = None,
                       scan_state: Optional[dict] = None):
        """Paged scan of one doc-key range within the tablet owning
        partition_key (prefix reads: all fields of one document family,
        e.g. a redis hash's subkeys).

        filters: pushed-down [[col, op, value], ...] conjunction — the
        tserver evaluates it (fused device kernel where compilable)
        before rows cross the wire. scan_state, when given, receives the
        pinned {'read_ht': ...} for query-layer paging-state
        continuation tokens."""
        pinned = read_ht.value if read_ht else None
        lower = lower_doc_key
        failures = 0
        backoff = Backoff(base_s=0.1, cap_s=1.0)
        while True:
            tablet = self.meta_cache.lookup_tablet(table.table_id,
                                                   partition_key)
            try:
                resp = self._tablet_call(
                    table, tablet, "scan", refresh_key=partition_key,
                    lower_doc_key=lower, upper_doc_key=upper_doc_key,
                    read_ht=pinned, limit=page_size,
                    filters=[list(f) for f in filters] if filters
                    else None)
            except RemoteError as e:
                # Same split/moved/overload re-route as scan(): resume
                # from the current doc-key bound after a refresh.
                retryable = (e.extra.get("tablet_split")
                             or e.extra.get("wrong_tablet")
                             or e.extra.get("overloaded")
                             or e.status.code == Code.NOT_FOUND)
                failures += 1
                if not retryable or failures > 8:
                    raise
                if e.extra.get("overloaded"):
                    backoff.note_server_hint(e.extra.get("retry_after_ms"))
                self.retry_budget.spend_or_raise(
                    f"scan_key_range {table.name}", last_err=e)
                time.sleep(backoff.next_delay())
                self.meta_cache.invalidate(table.table_id)
                continue
            failures = 0
            backoff = Backoff(base_s=0.1, cap_s=1.0)
            if pinned is None:
                pinned = resp.get("read_ht")
            if scan_state is not None:
                scan_state["read_ht"] = pinned
            for w in resp["rows"]:
                yield row_from_wire(w)
            if not resp.get("resume_key"):
                return
            lower = resp["resume_key"]

    def close(self) -> None:
        if self._owns_messenger:
            self._messenger.shutdown()
