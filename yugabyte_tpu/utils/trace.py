"""Per-request tracing with cross-node propagation, and the stage-timing
rail (`span`, further down: named host intervals on the profiler's clock
that feed the compaction and serve-path stage counters).

Capability parity with yb::Trace (ref: src/yb/util/trace.h:62-137): a Trace
collects timestamped messages for one request; traces dump on slow operations
(ref: LongOperationTracker usage, tserver/read_query.cc:500). A contextvar
carries the current trace, so deep call stacks need no plumbing.

Distributed propagation: every Trace is a SPAN of a distributed trace,
identified by (trace_id, span_id, parent_span_id, sampled). The RPC layer
(rpc/messenger.py) attaches the current span's context to outbound calls and
adopts it on the inbound handler path, so a multi-hop request (client ->
tserver -> raft peers) stitches into one trace_id visible in /tracez. A
Trace opened while another is current inherits that trace's id and parents
itself under it automatically — nested local spans need no plumbing either.
"""

from __future__ import annotations

import contextvars
import random
import threading
import time
from typing import Dict, List, Optional, Tuple

_current_trace: contextvars.ContextVar[Optional["Trace"]] = contextvars.ContextVar(
    "ybtpu_trace", default=None)

_id_rng = random.Random()


def _new_id(bits: int) -> str:
    return f"{_id_rng.getrandbits(bits):0{bits // 4}x}"


class Trace:
    __slots__ = ("entries", "start", "children", "name", "record",
                 "trace_id", "span_id", "parent_span_id", "sampled",
                 "_token")

    def __init__(self, name: str = "", record: bool = True,
                 trace_id: Optional[str] = None,
                 parent_span_id: Optional[str] = None,
                 sampled: bool = True):
        self.entries: List[Tuple[float, str]] = []
        self.start = time.monotonic()
        self.children: List["Trace"] = []
        self.name = name
        # record=False: a child attached to a parent trace — it renders
        # inside the parent's /tracez entry, not as its own
        self.record = record
        # Span identity: explicit ids come from an adopted wire context;
        # otherwise inherit the ambient trace (nested local span) or mint a
        # fresh root trace id.
        if trace_id is None:
            ambient = _current_trace.get()
            if ambient is not None:
                trace_id = ambient.trace_id
                parent_span_id = ambient.span_id
                sampled = ambient.sampled
            else:
                trace_id = _new_id(64)
        self.trace_id = trace_id
        self.span_id = _new_id(32)
        self.parent_span_id = parent_span_id
        self.sampled = sampled

    def message(self, msg: str) -> None:
        self.entries.append((time.monotonic() - self.start, msg))

    def dump(self) -> str:
        lines = [f"{dt * 1e3:10.3f}ms {msg}" for dt, msg in self.entries]
        for child in self.children:
            lines.append("  [child trace]")
            lines.extend("  " + l for l in child.dump().splitlines())
        return "\n".join(lines)

    def wire_context(self) -> Dict[str, object]:
        """The propagation header this span stamps on outbound RPCs
        (rpc/codec.trace_to_wire normalizes it for the wire)."""
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "sampled": self.sampled}

    @classmethod
    def from_wire_context(cls, ctx: Optional[dict], name: str = "",
                          record: bool = True) -> "Trace":
        """Adopt an inbound RPC's trace header: the new span continues the
        sender's trace_id and parents under the sender's span. A missing /
        malformed header (old peer) starts a fresh root trace."""
        if not isinstance(ctx, dict) or not ctx.get("trace_id"):
            return cls(name, record=record)
        return cls(name, record=record, trace_id=str(ctx["trace_id"]),
                   parent_span_id=(str(ctx["span_id"])
                                   if ctx.get("span_id") else None),
                   sampled=bool(ctx.get("sampled", True)))

    def __enter__(self) -> "Trace":
        self._token = _current_trace.set(self)
        return self

    def __exit__(self, *exc) -> None:
        _current_trace.reset(self._token)
        # children count as content: a request whose only activity is a
        # nested local-bypass call must still appear in /tracez
        if self.record and self.sampled and (self.entries or self.children):
            _record_tracez(self)


# ---------------------------------------------------------------- spans
# The one stage-timing rail. A `span` is a named host interval that (a)
# shows on the profiler's clock as a `jax.profiler.TraceAnnotation`
# "yb/<name>" — the same clock the device planes of a trace use, so a
# device idle gap can be laid against what the host was doing — and (b)
# hands its inclusive and self time to a sink, which adds them to the
# registry counters of its rail (utils/metrics.pipeline_span for the
# compaction job, utils/latency.stage_span / sub_span for the serve
# path). Nothing is appended to any list: always on, sums and counts
# only. Keep spans out of per-row, per-key and per-block loops.

_current_span: "contextvars.ContextVar[Optional[span]]" = \
    contextvars.ContextVar("ybtpu_span", default=None)

AMBIENT = object()   # `parent=AMBIENT`: the enclosing span of this context
SPAN_PREFIX = "yb/"  # never "bench/": the benchmark attributes idle time
                     # to its own spans and must keep reading only those

_annotation = None   # jax.profiler.TraceAnnotation, or False without JAX
_now_ns = time.monotonic_ns


def _resolve_annotation():
    global _annotation
    try:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    except ImportError:      # a process without JAX: counters alone
        _annotation = False
    return _annotation


class span:
    """`with span(name, sink):` — one timed, profiler-visible interval.

    On exit the span's duration is added to its parent's child time; its
    self time is the duration minus its own children's (so the self times
    of a tree of spans are disjoint and sum to the root's duration). The
    sink, when given, is called as ``sink(inclusive_ms, self_ms)``.
    `ns` / `self_ns` (and `ms`) hold both after exit; `elapsed_ms()` reads
    the clock while the span is open.

    parent: AMBIENT (default) takes the span open in this context; a new
    thread starts with none, so work handed to another thread passes the
    waiting span explicitly (`parent=that_span`), as LatencyBudget is
    passed across the same hand-offs. None makes a root. An exception
    leaves the span like any other exit: timed, recorded, re-raised.
    """

    __slots__ = ("name", "sink", "parent", "child_ns", "ns", "self_ns",
                 "_t0", "_ann", "_token")

    def __init__(self, name: str, sink=None, parent=AMBIENT):
        self.name = name
        self.sink = sink
        self.parent = parent
        self.child_ns = 0
        self.ns = self.self_ns = 0

    def elapsed_ms(self) -> float:
        return (_now_ns() - self._t0) / 1e6

    @property
    def ms(self) -> float:
        """Inclusive milliseconds, once the span has exited."""
        return self.ns / 1e6

    def __enter__(self) -> "span":
        if self.parent is AMBIENT:
            self.parent = _current_span.get()
        self._token = _current_span.set(self)
        ann = _annotation if _annotation is not None \
            else _resolve_annotation()
        # the annotation object only while a profiler session is on: with
        # none it would be a third of the span's cost, for nothing
        if ann and ann.is_enabled():
            self._ann = ann(SPAN_PREFIX + self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = _now_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.ns = ns = _now_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _current_span.reset(self._token)
        child_ns = self.child_ns
        self.self_ns = self_ns = ns - child_ns if ns > child_ns else 0
        parent = self.parent
        if parent is not None:
            parent.child_ns += ns
        if self.sink is not None:
            self.sink(ns / 1e6, self_ns / 1e6)
        return False


def current_span() -> Optional[span]:
    return _current_span.get()


def TRACE(msg: str, *args) -> None:
    """Append to the current request trace, if any (ref: TRACE() macro, trace.h)."""
    t = _current_trace.get()
    if t is not None:
        t.message(msg % args if args else msg)


def current_trace() -> Optional[Trace]:
    return _current_trace.get()


def current_trace_context() -> Optional[Dict[str, object]]:
    """Wire context of the current span, or None outside any trace."""
    t = _current_trace.get()
    return t.wire_context() if t is not None else None


# ------------------------------------------------------------- /tracez
# Ring of recently completed traces (ref: the reference's /tracez page
# over yb::Trace sampling). Completed scoped Traces with any entries
# land here; the webserver serves them as JSON.
_tracez_lock = threading.Lock()
_TRACEZ: List[dict] = []
_TRACEZ_CAP = 256


def _span_entry(t: Trace, duration_ms: Optional[float] = None) -> dict:
    if duration_ms is None:
        duration_ms = round((time.monotonic() - t.start) * 1e3, 3)
    return {"name": t.name or "request",
            "wall_ts": time.time(),
            "duration_ms": duration_ms,
            "trace_id": t.trace_id,
            "span_id": t.span_id,
            "parent_span_id": t.parent_span_id,
            "dump": t.dump()}


def _record_tracez(t: Trace) -> None:
    entry = _span_entry(t)
    with _tracez_lock:
        _TRACEZ.append(entry)
        if len(_TRACEZ) > _TRACEZ_CAP:
            del _TRACEZ[: len(_TRACEZ) - _TRACEZ_CAP]


def tracez() -> List[dict]:
    with _tracez_lock:
        return list(reversed(_TRACEZ))


def tracez_grouped() -> List[dict]:
    """Spans grouped by trace_id with per-hop timings — the multi-hop view
    of /tracez: one entry per distributed trace, its spans (hops) oldest
    first, so a slow client -> tserver -> raft-peer write reads as one
    tree instead of fragments on every server."""
    groups: Dict[str, List[dict]] = {}
    order: List[str] = []
    for span in reversed(tracez()):        # oldest first within a trace
        tid = span.get("trace_id") or "untraced"
        if tid not in groups:
            groups[tid] = []
            order.append(tid)
        groups[tid].append(span)
    out = []
    for tid in order:
        spans = groups[tid]
        out.append({
            "trace_id": tid,
            "n_spans": len(spans),
            "wall_ts": spans[0]["wall_ts"],
            "total_duration_ms": round(
                sum(s["duration_ms"] for s in spans), 3),
            "spans": [{k: s[k] for k in
                       ("name", "wall_ts", "duration_ms", "span_id",
                        "parent_span_id", "dump")} for s in spans],
        })
    out.reverse()                          # newest trace first
    return out


def tracez_page() -> dict:
    """The /tracez payload: flat span ring + the grouped-by-trace view."""
    return {"spans": tracez(), "traces": tracez_grouped()}


def threadz() -> List[dict]:
    """Live thread stack dump (the reference exposes /pprof + /threadz
    from the stack-trace collector, util/debug-util.cc)."""
    import sys
    import threading as _t
    import traceback
    frames = sys._current_frames()
    out = []
    for th in _t.enumerate():
        fr = frames.get(th.ident)
        out.append({
            "name": th.name,
            "ident": th.ident,
            "daemon": th.daemon,
            "stack": traceback.format_stack(fr) if fr is not None else [],
        })
    return out


class LongOperationTracker:
    """Warns (collects) when an operation exceeds a threshold (ref:
    util/long_operation_tracker.h). On exceed it TRACEs into the current
    request trace AND dumps the stitched trace-so-far into the /tracez
    ring as a `slow-op:<name>` span, so a slow WAL fsync or raft
    replication is explainable after the fact even if the enclosing
    request ultimately succeeds."""

    def __init__(self, name: str, threshold_ms: float = 1000.0):
        self.name = name
        self.threshold_ms = threshold_ms

    def __enter__(self):
        self._start = time.monotonic()
        return self

    def __exit__(self, *exc):
        elapsed_ms = (time.monotonic() - self._start) * 1e3
        if elapsed_ms > self.threshold_ms:
            TRACE("LongOperation %s took %.1fms (threshold %.1fms)",
                  self.name, elapsed_ms, self.threshold_ms)
            self._dump_slow_op(elapsed_ms)

    def _dump_slow_op(self, elapsed_ms: float) -> None:
        from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
        ROOT_REGISTRY.entity("server", "slow_ops").counter(
            "long_operation_exceeded_total",
            "operations that overran their LongOperationTracker "
            "threshold").increment()
        t = _current_trace.get()
        entry = {"name": f"slow-op:{self.name}",
                 "wall_ts": time.time(),
                 "duration_ms": round(elapsed_ms, 3),
                 # a child span of the still-open enclosing request span,
                 # so the grouped view hangs the dump under the right hop
                 "trace_id": t.trace_id if t is not None else _new_id(64),
                 "span_id": _new_id(32),
                 "parent_span_id": t.span_id if t is not None else None,
                 "dump": t.dump() if t is not None else ""}
        with _tracez_lock:
            _TRACEZ.append(entry)
            if len(_TRACEZ) > _TRACEZ_CAP:
                del _TRACEZ[: len(_TRACEZ) - _TRACEZ_CAP]
