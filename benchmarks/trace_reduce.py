"""Reduction of one `jax.profiler` trace to the benchmark's device numbers.

Two steps, kept apart so the arithmetic is testable on a hand-made list:

  load(path)    -> Events: device operations per device, and the host spans
                   the benchmark's own `TraceAnnotation`s wrote ("bench/...")
  reduce(events)-> busy union, idle share, the operations that took most
                   device time, and idle time by what the host was doing

Times are nanoseconds on the profiler's clock (host and device planes share
it). Device operations are the events of a device plane's "XLA Ops" line
(leaf operations; "XLA Modules" and "Steps" enclose them and would double
the union). Off the TPU (`--rehearse`, selftest) the CPU client's events
that carry an `hlo_module` stand in, as one pseudo device.
"""

import bisect
import glob
import os
import re
from collections import defaultdict, namedtuple

SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/trace_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 20_000
SHORT_GAPS = "gaps_under_20us"
Events = namedtuple("Events", "device_ops host_spans")
# device_ops: {device: [(name, start_ns, dur_ns)]}; host_spans: same tuples


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def short_op(text: str) -> str:
    """'%fusion.3 = pred[...] fusion(...)' -> 'fusion.3': on a TPU an op's
    event name is its whole HLO line."""
    return text.split(" = ", 1)[0].lstrip("%")[:64]


def short_module(text: str) -> str:
    """'jit__block_encode_impl(15534449245221468849)' -> without the id."""
    return re.sub(r"\(\d+\)$", "", text)


def name_ops(ops, modules) -> list:
    """Give each device op the XLA module that was running when it began:
    (text, start, dur) + [(module, start, dur)] -> ('module/op', start, dur).
    The "XLA Ops" events carry no module of their own."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for text, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s < modules[i][1] + modules[i][2]
        module = short_module(modules[i][0]) if inside else "no_module"
        out.append((f"{module}/{short_op(text)}", s, d))
    return out


def load(path: str) -> Events:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops = {}
    cpu_ops = []
    host_spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            if lines.get(OPS_LINE):
                device_ops[plane.name] = name_ops(
                    lines[OPS_LINE], lines.get(MODULES_LINE, []))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    host_spans.append((e.name, e.start_ns, e.duration_ns))
                elif e.duration_ns > 0 and not device_ops:
                    module = _stat(e, "hlo_module")
                    if module:          # CPU backend's executed HLO ops
                        cpu_ops.append((f"{module}/{short_op(e.name)}",
                                        e.start_ns, e.duration_ns))
    if not device_ops and cpu_ops:
        device_ops["/host:cpu-as-device"] = cpu_ops
    return Events(device_ops, host_spans)


def busy_union(intervals) -> list:
    """Merged [start, end) intervals of (start, dur) pairs."""
    merged = []
    for start, end in sorted((s, s + d) for s, d in intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(merged, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in merged
            if min(e, hi) > max(s, lo)]


def reduce(events: Events, top: int = 10) -> dict:
    """busy_s and idle share are averaged over the devices that ran
    anything; a trace with no device operation gives busy_s 0."""
    spans = [s for s in events.host_spans if s[0] != WINDOW_SPAN]
    windows = [s for s in events.host_spans if s[0] == WINDOW_SPAN]
    every = [(s, d) for ops in events.device_ops.values()
             for _n, s, d in ops]
    if windows:
        lo = min(s for _n, s, _d in windows)
        hi = max(s + d for _n, s, d in windows)
    elif every or spans:
        both = every + [(s, d) for _n, s, d in spans]
        lo, hi = min(s for s, _d in both), max(s + d for s, d in both)
    else:
        lo = hi = 0
    window_ns = hi - lo
    busy_ns, op_ns, idle_by = [], defaultdict(float), defaultdict(float)
    module_ns = defaultdict(float)
    gaps_all = []
    for ops in events.device_ops.values():
        merged = _clip(busy_union((s, d) for _n, s, d in ops), lo, hi)
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, d in ops:
            inside = max(0, min(s + d, hi) - max(s, lo))
            op_ns[name] += inside
            module_ns[name.split("/", 1)[0]] += inside
        edges = [lo] + [t for se in merged for t in se] + [hi]
        gaps_all += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    n_dev = max(len(busy_ns), 1)
    # a gap goes to the host spans that overlap it, in proportion to their
    # overlap (driver threads run side by side); gaps under MIN_GAP_NS
    # between back-to-back operations are lumped together
    spans.sort(key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max((s[2] for s in spans), default=0)
    for g0, g1 in gaps_all:
        if g1 - g0 < MIN_GAP_NS:
            idle_by[SHORT_GAPS] += (g1 - g0) / n_dev
            continue
        near = spans[bisect.bisect_left(starts, g0 - longest):
                     bisect.bisect_right(starts, g1)]
        overlaps = [(name, min(g1, s + d) - max(g0, s))
                    for name, s, d in near]
        overlaps = [(name, ov) for name, ov in overlaps if ov > 0]
        total = sum(ov for _n, ov in overlaps)
        if not overlaps:
            idle_by["unattributed"] += (g1 - g0) / n_dev
        for name, ov in overlaps:
            idle_by[name] += (g1 - g0) * ov / total / n_dev
    busy_s = sum(busy_ns) / n_dev / 1e9
    window_s = window_ns / 1e9

    def ranked(d, scale):
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / scale] for k, v in rows if v > 0]

    return {
        "window_s": window_s, "busy_s": busy_s, "devices": len(busy_ns),
        "idle_share": (1.0 - busy_s / window_s) if window_s > 0 and busy_ns
        else None,
        "device_ops": ranked(op_ns, 1e9 * n_dev),
        "device_modules": ranked(module_ns, 1e9 * n_dev),
        "idle_gaps": ranked(idle_by, 1e9),
        "longest_gap_s": max((g1 - g0 for g0, g1 in gaps_all),
                             default=0) / 1e9,
    }
