"""YBSession + Batcher: buffered writes coalesced per tablet.

Capability parity with the reference (ref: src/yb/client/session.h:96 —
Apply buffers ops, Flush groups them per tablet and sends one WriteRpc per
tablet in parallel; batcher.h:148 Batcher states, batcher.cc error
collection). The session is a real batcher now:

- per-tablet coalescing: apply() resolves the destination tablet ONCE and
  buffers the op under it, so flush has its groups in hand;
- flush window + max batch: a tablet group reaching
  ``ybsession_max_batch_ops`` flushes itself in the background without
  waiting for the explicit flush() (AUTO_FLUSH_BACKGROUND, ref
  session.h FlushMode), and an optional time window
  (``flush_interval_s``) sweeps stragglers;
- parallel fan-out: per-tablet groups go out concurrently (one sender
  thread per group; a single group sends on the caller thread);
- per-op status demux: a failed group maps its error back onto each of
  its ops; flush() raises SessionFlushError carrying the per-op
  (table, op, error) list instead of first-error-wins (ref
  batcher.cc CollectedErrors);
- retry/dedup rides below: each per-tablet write RPC carries one
  (client_id, request_id) retryable-request id (client.write), so a
  retried batch can never double-apply.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

from yugabyte_tpu.client.client import YBClient, YBTable
from yugabyte_tpu.docdb.doc_operations import QLWriteOp
from yugabyte_tpu.utils import flags
from yugabyte_tpu.utils import latency
from yugabyte_tpu.utils.status import Code, Status, StatusError
from yugabyte_tpu.utils.trace import AMBIENT, span

flags.define_flag("ybsession_max_batch_ops", 512,
                  "a per-tablet group reaching this many buffered ops "
                  "flushes itself in the background (ref "
                  "YB_CLIENT_MAX_BATCH_SIZE / batcher max buffer)")
flags.define_flag("ybsession_max_buffered_bytes", 8 << 20,
                  "cap on buffered + in-flight op bytes per session (ref "
                  "YBSession::SetBufferBytesLimit); apply() blocks — or "
                  "raises SessionBufferFull with block=False — until "
                  "sends drain below it; 0 = unbounded")
flags.define_flag("ybsession_max_buffered_ops", 0,
                  "cap on buffered + in-flight op COUNT per session; "
                  "0 = unbounded (the byte cap is the primary bound)")


class SessionBufferFull(StatusError):
    """apply(block=False) found the session's buffered+in-flight cap
    reached: typed, retryable, same `overloaded` extra shape as server
    shedding so callers classify client- and server-side pushback
    identically."""

    def __init__(self, msg: str):
        super().__init__(Status(Code.BUSY, msg))
        self.extra = {"overloaded": True, "session_buffer_full": True}


def _op_bytes(op: QLWriteOp) -> int:
    """Cheap stable estimate of one op's buffered footprint: encoded doc
    key (memoized on the DocKey) + value payloads + fixed per-column
    overhead. Used for admission only — never for wire encoding."""
    n = 32 + len(op.doc_key.encode())
    for v in op.values.values():
        n += 24 + (len(v) if isinstance(v, (str, bytes)) else 8)
    n += 24 * (len(op.columns_to_delete) + len(op.collection_ops))
    return n


class SessionFlushError(StatusError):
    """One or more per-tablet groups failed. ``per_op`` lists every op
    that did NOT land as (table, op, error); ops absent from the list
    were acknowledged (per-op demux, ref batcher.cc CollectedErrors)."""

    def __init__(self, per_op: List[Tuple[YBTable, QLWriteOp, Exception]]):
        first = per_op[0][2]
        st = first.status if isinstance(first, StatusError) else \
            Status.IoError(str(first))
        super().__init__(st)
        self.per_op = per_op
        self.extra = getattr(first, "extra", {})

    def __str__(self) -> str:
        return (f"{len(self.per_op)} op(s) failed; first: "
                f"{self.per_op[0][2]}")


class _TabletGroup:
    __slots__ = ("table", "tablet", "ops", "bytes", "created")

    def __init__(self, table: YBTable, tablet):
        self.table = table
        self.tablet = tablet
        self.ops: List[QLWriteOp] = []
        self.bytes = 0
        # when the group's first op buffered — the send opens the op's
        # LatencyBudget at this instant, so the e2e decomposition
        # includes the batcher queue wait as the client_queue stage
        self.created = time.monotonic()


class YBSession:
    def __init__(self, client: YBClient,
                 flush_interval_s: Optional[float] = None,
                 max_batch_ops: Optional[int] = None):
        self._client = client
        self._groups: Dict[str, _TabletGroup] = {}
        self._n_pending = 0
        # buffered (grouped, unsent) + in-flight (sending) op bytes —
        # the session's memory-admission bound: apply() blocks until
        # sends drain under ybsession_max_buffered_bytes, so a client
        # outpacing the cluster backs up at ITS end instead of buffering
        # unboundedly (the client arm of overload protection)
        self._buffered_bytes = 0           # guarded-by: _lock
        self._inflight_bytes = 0           # guarded-by: _lock
        self.buffer_full_waits_total = 0   # guarded-by: _lock
        self._lock = threading.Lock()
        self._flush_interval_s = flush_interval_s
        self._max_batch_ops = max_batch_ops
        # errors from background (max-batch / timer) flushes surface at
        # the NEXT explicit flush() — an acked-looking apply must not
        # silently lose its batch (ref session.h deferred flush status)
        self._async_errors: List[Tuple[YBTable, QLWriteOp, Exception]] = []
        self._inflight = 0            # background flushes not yet settled
        self._inflight_ops = 0        # ops inside in-flight sends
        self._inflight_cv = threading.Condition(self._lock)
        self._closed = False
        self._timer: Optional[threading.Thread] = None
        if flush_interval_s:
            self._timer = threading.Thread(
                target=self._timer_loop, daemon=True,
                name="ybsession-flush-timer")
            self._timer.start()

    # ------------------------------------------------------------- buffering
    def apply(self, table: YBTable, op: QLWriteOp,
              block: bool = True) -> None:
        """Buffer one op under its destination tablet. A group hitting the
        max-batch size is handed to a background sender immediately —
        the caller keeps applying while the batch replicates.

        Admission cap (the client arm of overload protection): buffered
        + in-flight bytes are bounded by ``ybsession_max_buffered_bytes``
        (and optionally op count by ``ybsession_max_buffered_ops``).
        Over the cap, apply() BLOCKS until sends drain — self-flushing
        the buffer in the background if nothing is in flight, so the
        wait always makes progress — or, with ``block=False``, raises
        the typed retryable SessionBufferFull instead. Either way a
        client outpacing the cluster backs up at its own edge rather
        than buffering unboundedly."""
        pk = table.partition_key_for(op.doc_key)
        tablet = self._client.meta_cache.lookup_tablet(table.table_id, pk)
        limit = (self._max_batch_ops
                 if self._max_batch_ops is not None
                 else flags.get_flag("ybsession_max_batch_ops"))
        sz = _op_bytes(op)
        byte_cap = flags.get_flag("ybsession_max_buffered_bytes")
        op_cap = flags.get_flag("ybsession_max_buffered_ops")
        full: Optional[_TabletGroup] = None
        with self._inflight_cv:
            while True:
                out_bytes = self._buffered_bytes + self._inflight_bytes
                out_ops = self._n_pending + self._inflight_ops
                # an op larger than the whole cap still admits into an
                # EMPTY buffer — rejecting it forever would wedge
                over = ((byte_cap and out_bytes
                         and out_bytes + sz > byte_cap)
                        or (op_cap and out_ops
                            and out_ops + 1 > op_cap))
                if not over or self._closed:
                    break
                if not block:
                    raise SessionBufferFull(
                        f"session buffer full ({out_bytes} bytes / "
                        f"{out_ops} ops in flight; cap {byte_cap} bytes"
                        + (f" / {op_cap} ops" if op_cap else "") + ")")
                self.buffer_full_waits_total += 1
                if self._inflight == 0 and self._groups:
                    # nothing is draining: hand every buffered group to
                    # background senders NOW (AUTO_FLUSH_BACKGROUND on
                    # buffer-full, ref session.h) so this wait cannot
                    # deadlock on work only this thread could flush
                    for g in list(self._groups.values()):
                        self._note_group_inflight_locked(g)
                        self._spawn_send(g)
                    self._groups.clear()
                    self._n_pending = 0
                    continue
                self._inflight_cv.wait(timeout=2.0)
            key = f"{table.table_id}/{tablet.tablet_id}"
            group = self._groups.get(key)
            if group is None:
                group = self._groups[key] = _TabletGroup(table, tablet)
            group.ops.append(op)
            group.bytes += sz
            self._buffered_bytes += sz
            self._n_pending += 1
            if limit and len(group.ops) >= limit:
                del self._groups[key]
                self._n_pending -= len(group.ops)
                self._note_group_inflight_locked(group)
                full = group
        if full is not None:
            self._spawn_send(full)

    def _note_group_inflight_locked(self, group: _TabletGroup) -> None:
        """Move one group's admission accounting from buffered to
        in-flight (caller holds _lock and has removed/clears the group
        from _groups; _n_pending is the caller's responsibility)."""
        self._inflight += 1
        self._inflight_ops += len(group.ops)
        self._buffered_bytes -= group.bytes
        self._inflight_bytes += group.bytes

    def has_pending_operations(self) -> bool:
        with self._lock:
            return bool(self._n_pending or self._inflight)

    def outstanding_bytes(self) -> int:
        """Buffered + in-flight op bytes counted against the admission
        cap (observability + tests)."""
        with self._lock:
            return self._buffered_bytes + self._inflight_bytes

    # --------------------------------------------------------------- sending
    def _send_group(self, group: _TabletGroup,
                    errors: List[Tuple[YBTable, QLWriteOp, Exception]],
                    errors_lock: threading.Lock, parent=AMBIENT) -> None:
        """parent: the flush's span, when a fan-out thread sends."""
        try:
            # serve-path attribution: the budget's clock starts when the
            # group's first op buffered, so the time the batch waited in
            # the batcher is the client_queue stage; every later layer
            # (wire encode, service queue, raft, WAL, apply) records its
            # slice into the same ambient budget, and on success the
            # scope exit feeds the serve_path histograms
            with latency.budget_scope(latency.OP_WRITE, t0=group.created,
                                      parent=parent) as budget:
                budget.record(latency.STAGE_CLIENT_QUEUE,
                              (time.monotonic() - group.created) * 1e3)
                self._client.write(group.table, group.ops,
                                   tablet=group.tablet)
        except Exception as e:  # noqa: BLE001  # yblint: contained(demuxed onto every op of the group; flush re-raises them as SessionFlushError)
            with errors_lock:
                errors.extend((group.table, op, e) for op in group.ops)

    def _spawn_send(self, group: _TabletGroup) -> None:
        def run():
            try:
                self._send_group(group, self._async_errors, self._lock)
            finally:
                with self._inflight_cv:
                    self._inflight -= 1
                    self._inflight_ops -= len(group.ops)
                    self._inflight_bytes -= group.bytes
                    self._inflight_cv.notify_all()
        threading.Thread(target=run, daemon=True,
                         name="ybsession-bg-flush").start()

    def _timer_loop(self) -> None:
        period = self._flush_interval_s
        while True:
            time.sleep(period)
            with self._lock:
                if self._closed:
                    return
                groups = list(self._groups.values())
                self._groups.clear()
                self._n_pending = 0
                for g in groups:
                    self._note_group_inflight_locked(g)
            for g in groups:
                self._spawn_send(g)

    def flush(self) -> int:
        """Send all buffered ops, one write RPC per destination tablet,
        fanned out concurrently, then wait for any background flushes to
        settle. Returns the number of ops this call flushed; raises
        SessionFlushError listing every failed op (per-op demux) if any
        group — foreground or background — failed since the last
        flush."""
        with self._lock:
            groups = list(self._groups.values())
            self._groups.clear()
            self._n_pending = 0
            moved_bytes = sum(g.bytes for g in groups)
            moved_ops = sum(len(g.ops) for g in groups)
            # foreground sends still count toward the admission cap (a
            # concurrent apply() must see them as in-flight bytes)
            self._buffered_bytes -= moved_bytes
            self._inflight_bytes += moved_bytes
            self._inflight_ops += moved_ops
        n_ops = moved_ops
        errors: List[Tuple[YBTable, QLWriteOp, Exception]] = []
        errors_lock = threading.Lock()
        try:
            # the whole flush on the caller's thread; the per-tablet sends
            # (fan-out threads) are its children
            with span("client/flush") as flush:
                if len(groups) == 1:
                    # single-tablet batch (the overwhelmingly common case
                    # under key-grouped load): skip the thread spawn
                    self._send_group(groups[0], errors, errors_lock)
                elif groups:
                    threads = [threading.Thread(
                        target=self._send_group,
                        args=(g, errors, errors_lock, flush),
                        daemon=True) for g in groups]
                    for t in threads:
                        t.start()
                    with span("client/await_fanout"):
                        for t in threads:
                            t.join()
        finally:
            with self._inflight_cv:
                self._inflight_bytes -= moved_bytes
                self._inflight_ops -= moved_ops
                self._inflight_cv.notify_all()
        # settle background flushes so their errors surface HERE, not on
        # some later unrelated flush
        with self._inflight_cv:
            while self._inflight:
                self._inflight_cv.wait()
            if self._async_errors:
                errors.extend(self._async_errors)
                self._async_errors = []
        if errors:
            raise SessionFlushError(errors)
        return n_ops

    def close(self) -> None:
        """Flush remaining ops and stop the background timer."""
        with self._lock:
            self._closed = True
        self.flush()
