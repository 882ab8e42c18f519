"""Per-op serve-path latency attribution: the LatencyBudget.

A `LatencyBudget` rides one batched-write or multi_read op alongside the
existing trace context (utils/trace.py) and splits the op's measured
end-to-end wall time into named, disjoint stages:

  batched write : client_queue -> wire_encode -> [wire_transfer] ->
                  rpc_queue -> raft_replicate (-> wal_fsync -> apply)
                  -> server_other
  multi_read    : wire_encode -> [wire_transfer] -> rpc_queue ->
                  device_dispatch | host_fallback -> row_assembly ->
                  server_other

The carrier is a contextvar, exactly like the trace span stack, so the
client batcher, the RPC messenger, raft, the WAL appender and the
storage layer all record into the same object without any plumbing
through intermediate signatures. Two sites cross threads and carry the
budget explicitly instead: `Log.append_async` (the WAL appender thread
records the group-commit fsync slice) and raft's `_budget_by_index` map
(the commit worker records the apply slice), both mirroring how the
trace context already crosses the same boundaries.

Server-side stages cross the wire back to the client: the RPC response
carries a `lat` stage map (rpc/codec.py::LAT_HEADER_KEY) that
`Messenger.call` merges into the caller's budget, so the CLIENT-side
end-to-end histogram decomposes into SERVER-side stages. Two residual
stages telescope the decomposition closed: `server_other` (handler wall
minus the measured server stages) and `wire_transfer` (end-to-end minus
everything measured anywhere) — which is why the named stages sum to
the measured e2e by construction (>=90% asserted in
tests/test_telemetry.py; the clamp to >=0 under cross-thread clock
slack is the only way to lose mass).

Stage sites are spans of the one span rail (utils/trace.py::span), so
every stage also shows on the profiler's clock as "yb/serve/<stage>":
`stage_span(stage)` records the span's inclusive time under the stage —
what the open-coded clock pairs recorded before — and, for the two stages
that have sub-stages, its self time under `<stage>_other`. A SUB-STAGE
(`sub_span(name)`) is a named slice inside `device_dispatch` or
`server_other`; it records its self time in `budget.subs`, which rides
the wire beside the stage map but is NOT part of `measured_ms()`: the
telescoping above is over stages alone, and a sub-stage only says where
inside its stage the time went. /servez lists sub-stages under `stages`
with an `"of": <stage>` mark.

Lock-free by design (acceptance: ZERO new locks on the hot path): every
mutation is a single dict-item write under the GIL, and each stage has
exactly one writer thread. Aggregation into the `serve_path` histograms
(which carry trace-id exemplars for /servez -> /tracez click-through)
happens once per op at finalize time, off the per-stage hot path.
"""

from __future__ import annotations

import contextvars
import time
from typing import Dict, Optional

from yugabyte_tpu.utils import metrics as _metrics
from yugabyte_tpu.utils import ybsan
from yugabyte_tpu.utils.trace import AMBIENT, span

OP_WRITE = "write"
OP_MULTI_READ = "multi_read"
OP_SCAN = "scan"    # one tablet's call of an aggregate-pushdown walk

# Stage names (the vocabulary /servez and the README document).
STAGE_CLIENT_QUEUE = "client_queue"      # op waited in the session batcher
STAGE_WIRE_ENCODE = "wire_encode"        # request frame encode + socket send
STAGE_WIRE_TRANSFER = "wire_transfer"    # residual: link + response decode
STAGE_RPC_QUEUE = "rpc_queue"            # inbound service-pool queue wait
STAGE_RAFT_REPLICATE = "raft_replicate"  # replicate wall minus fsync/apply
STAGE_WAL_FSYNC = "wal_fsync"            # group-commit fsync slice
STAGE_APPLY = "apply"                    # committed-entry apply (row encode)
STAGE_SERVER_OTHER = "server_other"      # residual: handler wall minus above
STAGE_DEVICE_DISPATCH = "device_dispatch"  # fused point-read kernel path
STAGE_HOST_FALLBACK = "host_fallback"    # native per-key read path
STAGE_ROW_ASSEMBLY = "row_assembly"      # winner-row flat-row assembly

# Literal per-(op, stage) histogram names: kept literal (not composed)
# so the metric-names lint pass covers every family of the attribution
# namespace at its construction site.
_WRITE_STAGE_HISTOGRAMS = {
    STAGE_CLIENT_QUEUE: "serve_path_write_client_queue_ms",
    STAGE_WIRE_ENCODE: "serve_path_write_wire_encode_ms",
    STAGE_WIRE_TRANSFER: "serve_path_write_wire_transfer_ms",
    STAGE_RPC_QUEUE: "serve_path_write_rpc_queue_ms",
    STAGE_RAFT_REPLICATE: "serve_path_write_raft_replicate_ms",
    STAGE_WAL_FSYNC: "serve_path_write_wal_fsync_ms",
    STAGE_APPLY: "serve_path_write_apply_ms",
    STAGE_SERVER_OTHER: "serve_path_write_server_other_ms",
}
_READ_STAGE_HISTOGRAMS = {
    STAGE_WIRE_ENCODE: "serve_path_multi_read_wire_encode_ms",
    STAGE_WIRE_TRANSFER: "serve_path_multi_read_wire_transfer_ms",
    STAGE_RPC_QUEUE: "serve_path_multi_read_rpc_queue_ms",
    STAGE_DEVICE_DISPATCH: "serve_path_multi_read_device_dispatch_ms",
    STAGE_HOST_FALLBACK: "serve_path_multi_read_host_fallback_ms",
    STAGE_ROW_ASSEMBLY: "serve_path_multi_read_row_assembly_ms",
    STAGE_SERVER_OTHER: "serve_path_multi_read_server_other_ms",
}
_SCAN_STAGE_HISTOGRAMS = {
    STAGE_WIRE_ENCODE: "serve_path_scan_wire_encode_ms",
    STAGE_WIRE_TRANSFER: "serve_path_scan_wire_transfer_ms",
    STAGE_RPC_QUEUE: "serve_path_scan_rpc_queue_ms",
    STAGE_DEVICE_DISPATCH: "serve_path_scan_device_dispatch_ms",
    STAGE_HOST_FALLBACK: "serve_path_scan_host_fallback_ms",
    STAGE_SERVER_OTHER: "serve_path_scan_server_other_ms",
}
# Sub-stages: stage -> {sub-stage: per-op histogram names}. A sub-stage
# is a slice INSIDE its stage (outside measured_ms()); `<stage>_other` /
# `server_other_rest` is what the named slices leave.
SUB_DEVICE_WAIT = "device_wait"          # host blocked on a device result
SUB_DISPATCH_OTHER = "device_dispatch_other"
SUB_SERVER_REST = "server_other_rest"
_READ_SUB_HISTOGRAMS = {
    # of device_dispatch (storage/db.py, ops/point_read.py)
    "stage_lookup": "serve_path_multi_read_stage_lookup_ms",
    "stage_miss": "serve_path_multi_read_stage_miss_ms",
    "query_pack": "serve_path_multi_read_query_pack_ms",
    "device_enqueue": "serve_path_multi_read_device_enqueue_ms",
    SUB_DEVICE_WAIT: "serve_path_multi_read_device_wait_ms",
    "chunk_combine": "serve_path_multi_read_chunk_combine_ms",
    "value_fetch": "serve_path_multi_read_value_fetch_ms",
    SUB_DISPATCH_OTHER: "serve_path_multi_read_device_dispatch_other_ms",
    # of server_other (tserver/tablet_service.py, tablet/tablet.py)
    "request_decode": "serve_path_multi_read_request_decode_ms",
    "read_point": "serve_path_multi_read_read_point_ms",
    "key_build": "serve_path_multi_read_key_build_ms",
    "response_encode": "serve_path_multi_read_response_encode_ms",
    SUB_SERVER_REST: "serve_path_multi_read_server_other_rest_ms",
}
_WRITE_SUB_HISTOGRAMS = {
    # of server_other (tserver/tablet_service.py, tablet/, docdb/)
    "request_decode": "serve_path_write_request_decode_ms",
    "admission": "serve_path_write_admission_ms",
    "docop_encode": "serve_path_write_docop_encode_ms",
    "write_lock_wait": "serve_path_write_write_lock_wait_ms",
    "batch_encode": "serve_path_write_batch_encode_ms",
    SUB_SERVER_REST: "serve_path_write_server_other_rest_ms",
}
_SCAN_SUB_HISTOGRAMS = {
    # of device_dispatch (tablet/tablet.py, storage/db.py,
    # ops/scan_group.py): the aggregate pushdown's dispatch
    "stage_lookup": "serve_path_scan_stage_lookup_ms",
    "query_pack": "serve_path_scan_query_pack_ms",
    "device_enqueue": "serve_path_scan_device_enqueue_ms",
    SUB_DEVICE_WAIT: "serve_path_scan_device_wait_ms",
    "partial_build": "serve_path_scan_partial_build_ms",
    SUB_DISPATCH_OTHER: "serve_path_scan_device_dispatch_other_ms",
    SUB_SERVER_REST: "serve_path_scan_server_other_rest_ms",
}
_SUB_OF = {
    "partial_build": STAGE_DEVICE_DISPATCH,
    "stage_lookup": STAGE_DEVICE_DISPATCH,
    "stage_miss": STAGE_DEVICE_DISPATCH,
    "query_pack": STAGE_DEVICE_DISPATCH,
    "device_enqueue": STAGE_DEVICE_DISPATCH,
    SUB_DEVICE_WAIT: STAGE_DEVICE_DISPATCH,
    "chunk_combine": STAGE_DEVICE_DISPATCH,
    "value_fetch": STAGE_DEVICE_DISPATCH,
    SUB_DISPATCH_OTHER: STAGE_DEVICE_DISPATCH,
    "request_decode": STAGE_SERVER_OTHER,
    "read_point": STAGE_SERVER_OTHER,   # lease check + read-time wait
    "key_build": STAGE_SERVER_OTHER,
    "response_encode": STAGE_SERVER_OTHER,
    "admission": STAGE_SERVER_OTHER,
    "docop_encode": STAGE_SERVER_OTHER,
    "write_lock_wait": STAGE_SERVER_OTHER,
    "batch_encode": STAGE_SERVER_OTHER,
    SUB_SERVER_REST: STAGE_SERVER_OTHER,
}
_E2E_HISTOGRAMS = {
    OP_WRITE: "serve_path_write_e2e_ms",
    OP_MULTI_READ: "serve_path_multi_read_e2e_ms",
    OP_SCAN: "serve_path_scan_e2e_ms",
}
_STAGE_TABLES = {
    OP_WRITE: _WRITE_STAGE_HISTOGRAMS,
    OP_MULTI_READ: _READ_STAGE_HISTOGRAMS,
    OP_SCAN: _SCAN_STAGE_HISTOGRAMS,
}
_SUB_TABLES = {
    OP_WRITE: _WRITE_SUB_HISTOGRAMS,
    OP_MULTI_READ: _READ_SUB_HISTOGRAMS,
    OP_SCAN: _SCAN_SUB_HISTOGRAMS,
}
SUB_WIRE_KEY = "sub"   # the sub-stage map's key inside the wire stage map


@ybsan.shadow(stages=ybsan.SINGLE_WRITER_PER_KEY)
class LatencyBudget:
    """One op's wall clock, split into named disjoint stage slices.

    `stages` maps stage name -> accumulated milliseconds. Mutations are
    single dict-item writes (GIL-atomic) with one writer thread per
    stage — no lock, by acceptance-criteria design. `trace_id` is the
    op's root trace id, stamped where the wire encode happens (the
    trace context is live there) and attached as the e2e histogram
    exemplar at finalize.
    """

    __slots__ = ("op", "t0", "stages", "subs", "trace_id")

    def __init__(self, op: str, t0: Optional[float] = None):
        self.op = op
        self.t0 = time.monotonic() if t0 is None else t0
        self.stages: Dict[str, float] = {}
        # sub-stage slices (inside device_dispatch / server_other):
        # never part of measured_ms()
        self.subs: Dict[str, float] = {}
        self.trace_id: Optional[str] = None

    def record(self, stage: str, ms: float) -> None:
        if ms <= 0.0:
            return
        cur = self.stages.get(stage)
        self.stages[stage] = ms if cur is None else cur + ms

    def record_sub(self, sub: str, ms: float) -> None:
        if ms <= 0.0:
            return
        cur = self.subs.get(sub)
        self.subs[sub] = ms if cur is None else cur + ms

    def sub_ms(self, stage: str) -> float:
        """Milliseconds the named sub-stages of `stage` hold so far."""
        return sum(ms for sub, ms in self.subs.items()
                   if _SUB_OF.get(sub) == stage)

    def merge(self, stage_map) -> None:
        """Fold a wire-carried stage map (the response's `lat` value)
        into this budget. Wire data: tolerate any malformed entry."""
        if not isinstance(stage_map, dict):
            return
        for k, v in stage_map.items():
            if k == SUB_WIRE_KEY and isinstance(v, dict):
                for sk, sv in v.items():
                    if isinstance(sk, str) and isinstance(sv, (int, float)) \
                            and not isinstance(sv, bool):
                        self.record_sub(sk, float(sv))
            elif isinstance(k, str) and isinstance(v, (int, float)) \
                    and not isinstance(v, bool):
                self.record(k, float(v))

    def elapsed_ms(self) -> float:
        return (time.monotonic() - self.t0) * 1e3

    def measured_ms(self) -> float:
        return sum(self.stages.values())

    def to_wire(self) -> Dict[str, object]:
        wire: Dict[str, object] = {k: round(v, 4)
                                   for k, v in self.stages.items()}
        if self.subs:
            wire[SUB_WIRE_KEY] = {k: round(v, 4)
                                  for k, v in self.subs.items()}
        return wire


_BUDGET_VAR: "contextvars.ContextVar[Optional[LatencyBudget]]" = \
    contextvars.ContextVar("ybtpu_latency_budget", default=None)


def current_budget() -> Optional[LatencyBudget]:
    return _BUDGET_VAR.get()


def record_stage(stage: str, ms: float) -> None:
    """Record into the ambient budget, if any. The no-budget fast path
    is one contextvar read + an is-None check."""
    b = _BUDGET_VAR.get()
    if b is not None:
        b.record(stage, ms)


class _StageSink:
    """A serve-path span's sink: into the AMBIENT budget at exit (one
    contextvar read; nothing without a budget), inclusive time under a
    stage and/or self time under a sub-stage."""

    __slots__ = ("stage", "sub")

    def __init__(self, stage: Optional[str], sub: Optional[str]):
        self.stage = stage
        self.sub = sub

    def __call__(self, inclusive_ms: float, self_ms: float) -> None:
        b = _BUDGET_VAR.get()
        if b is not None:
            if self.stage is not None:
                b.record(self.stage, inclusive_ms)
            if self.sub is not None:
                b.record_sub(self.sub, self_ms)


# (span name, sink) per stage / sub-stage, built once
_STAGE_SPANS = {
    stage: ("serve/" + stage, _StageSink(stage, other))
    for stage, other in ((STAGE_DEVICE_DISPATCH, SUB_DISPATCH_OTHER),
                         (STAGE_HOST_FALLBACK, None),
                         (STAGE_WIRE_ENCODE, None))}
_SUB_SPANS = {sub: ("serve/" + sub, _StageSink(None, sub))
              for sub in _SUB_OF}


def stage_span(stage: str) -> span:
    """The span "yb/serve/<stage>" of one stage site: inclusive time
    under the stage (and, for device_dispatch, self time under
    device_dispatch_other)."""
    return span(*_STAGE_SPANS[stage])


def sub_span(sub: str) -> span:
    """The span "yb/serve/<sub>" of one sub-stage slice (self time)."""
    return span(*_SUB_SPANS[sub])


def use_budget(budget: Optional[LatencyBudget]):
    """Install `budget` as the ambient budget; returns the reset token.
    (The server handler path, which must NOT finalize — the budget's
    stage map rides the response back to the owning client.)"""
    return _BUDGET_VAR.set(budget)


def clear_budget(token) -> None:
    _BUDGET_VAR.reset(token)


class budget_scope:
    """Client-side scope: installs a fresh LatencyBudget for the with
    block and, on SUCCESSFUL exit, closes the decomposition and feeds
    the serve_path histograms. A failed op (exception propagating)
    records nothing — its wall time includes retry/timeout semantics
    the stage vocabulary does not describe."""

    __slots__ = ("budget", "_token", "_span")

    def __init__(self, op: str, t0: Optional[float] = None,
                 parent=AMBIENT):
        self.budget = LatencyBudget(op, t0)
        # the client's whole call on the profiler: "yb/client/<op>";
        # `parent` is the batch's span when a fan-out thread runs the call
        self._span = span("client/" + op, parent=parent)

    def __enter__(self) -> LatencyBudget:
        self._span.__enter__()
        self._token = _BUDGET_VAR.set(self.budget)
        return self.budget

    def __exit__(self, exc_type, exc, tb):
        _BUDGET_VAR.reset(self._token)
        self._span.__exit__(exc_type, exc, tb)
        if exc_type is None:
            finalize_budget(self.budget)
        return False


_STAGE_HELP = ("serve-path attribution: milliseconds this op spent in "
               "the stage (see README 'Telemetry timebase')")
_SUB_HELP = ("serve-path attribution: milliseconds this op spent in the "
             "sub-stage, a slice inside device_dispatch or server_other "
             "(see README 'Telemetry timebase')")


def finalize_budget(budget: LatencyBudget) -> None:
    """Close the decomposition (wire_transfer residual) and aggregate
    the budget into the per-stage serve_path histograms; the e2e
    observation carries the op's trace id as exemplar."""
    table = _STAGE_TABLES.get(budget.op)
    if table is None:
        return
    e2e = budget.elapsed_ms()
    if e2e <= 0.0:
        return
    residual = e2e - budget.measured_ms()
    if residual > 0.0:
        budget.record(STAGE_WIRE_TRANSFER, residual)
    ent = _metrics.serve_path_metrics()
    for stage, ms in budget.stages.items():
        name = table.get(stage)
        if name is not None:
            ent.histogram(name, _STAGE_HELP).increment(ms)
    subs = _SUB_TABLES[budget.op]
    for sub, ms in budget.subs.items():
        name = subs.get(sub)
        if name is not None:
            ent.histogram(name, _SUB_HELP).increment(ms)
    ent.histogram(_E2E_HISTOGRAMS[budget.op],
                  "serve-path attribution: measured end-to-end op wall "
                  "time; sums the per-stage histograms within clamp "
                  "slack").increment(e2e, exemplar=budget.trace_id)


def serve_path_attribution_page() -> Dict[str, object]:
    """The /servez attribution block: per op, the e2e summary plus each
    stage's share of total e2e time (percentages computed from the
    histogram sums, so they answer 'where did the path's time go' over
    the server's lifetime) with trace-id exemplars on e2e."""
    ent = _metrics.serve_path_metrics()
    out: Dict[str, object] = {}
    for op, table in _STAGE_TABLES.items():
        e2e_h = ent.histogram(_E2E_HISTOGRAMS[op])
        e2e = e2e_h.snapshot_dict()
        total = float(e2e.get("sum") or 0.0)
        stages = {}
        for stage, name in table.items():
            h = ent.histogram(name, _STAGE_HELP)
            snap = h.snapshot_dict()
            snap.pop("exemplars", None)
            snap["pct_of_e2e"] = (round(100.0 * float(snap["sum"]) / total, 2)
                                  if total > 0 else 0.0)
            stages[stage] = snap
        for sub, name in _SUB_TABLES[op].items():
            # a slice inside the stage `of` names: shares of e2e overlap
            # that stage's own share
            snap = ent.histogram(name, _SUB_HELP).snapshot_dict()
            snap.pop("exemplars", None)
            snap["pct_of_e2e"] = (round(100.0 * float(snap["sum"]) / total, 2)
                                  if total > 0 else 0.0)
            snap["of"] = _SUB_OF[sub]
            stages[sub] = snap
        out[op] = {"e2e": e2e, "stages": stages}
    return out
