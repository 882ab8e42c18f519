"""`span_idle`: of the traced window's device idle time, the share that
falls under no named span of the program ("yb/..." host spans, which
`yugabyte_tpu/utils/trace.py::span` writes on the profiler's clock).

The idle gaps are those of `trace_reduce.reduce` (same window, same busy
union per device, same MIN_GAP_NS below which a gap is the device's own
cadence and is left out, here of both sides of the share). Within a gap
each host thread is cut into the segments of its INNERMOST `yb/` span. A
segment whose innermost span is one of the spec's `roots` (a whole-job or
whole-call span: the program was there, but no stage names what it did) is
unnamed time, like host time under no `yb/` span at all. Threads run side
by side, so the part of a gap that some `yb/` span covers is split between
named and unnamed in proportion to their overlaps with it, as `reduce`
splits a gap between the `bench/` spans; the part no thread covers is
unnamed outright.

`observed` carries no path, so the reader finds the run's `.xplane.pb`
itself: `run.py` keeps one work directory a process under `.bench_work/`
until the per-layer metrics are read, and the trace whose window is the
reduction's is this run's. A trace without `yb/` spans (a program from
before the span rail), without an idle gap or without a device operation
gives None, never 0."""

import bisect
import glob
import os

from benchmarks import trace_reduce as tr

PREFIX = "yb/"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_threads(path: str) -> dict:
    """{thread: [(name, start_ns, dur_ns)]} of the host planes' `yb/`
    events; a thread is one line of a host plane."""
    from jax.profiler import ProfileData
    threads = {}
    for pi, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            spans = [(e.name, e.start_ns, e.duration_ns)
                     for e in line.events if e.name.startswith(PREFIX)]
            if spans:
                threads[(pi, li)] = spans
    return threads


def innermost_segments(spans) -> list:
    """One thread's nested spans -> disjoint [(name, start, end)], each
    stretch under the name of the innermost span open there."""
    out, stack, t = [], [], 0       # stack: (name, end)

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][1] <= limit:
            name, end = stack.pop()
            if end > t:
                out.append((name, t, end))
                t = end

    for name, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
        close_until(s)
        end = s + d
        if stack:
            if s > t:
                out.append((stack[-1][0], t, s))
            end = min(end, stack[-1][1])    # a child ends with its parent
        stack.append((name, end))
        t = s
    close_until(float("inf"))
    return out


def window(events: tr.Events):
    """(lo, hi) of the traced window in ns, as `trace_reduce.reduce`
    takes it: the window span, else all that was recorded."""
    windows = [s for s in events.host_spans if s[0] == tr.WINDOW_SPAN]
    if windows:
        return (min(s for _n, s, _d in windows),
                max(s + d for _n, s, d in windows))
    both = [(s, d) for ops in events.device_ops.values()
            for _n, s, d in ops] + [(s, d) for _n, s, d in events.host_spans]
    if both:
        return min(s for s, _d in both), max(s + d for s, d in both)
    return 0, 0


def idle_gaps(events: tr.Events) -> list:
    """The [(start, end)] device idle gaps of the traced window, every
    device's, as `trace_reduce.reduce` lists them."""
    lo, hi = window(events)
    gaps = []
    for ops in events.device_ops.values():
        merged = tr._clip(tr.busy_union((s, d) for _n, s, d in ops), lo, hi)
        edges = [lo] + [t for se in merged for t in se] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] - edges[i] >= tr.MIN_GAP_NS]
    return gaps


def unspanned_share(events: tr.Events, threads: dict, roots) -> float:
    """Unnamed idle nanoseconds over idle nanoseconds, or None."""
    if not threads or not events.device_ops:
        return None
    roots = set(roots)
    per_thread = []
    for spans in threads.values():
        segs = innermost_segments(spans)
        per_thread.append((segs, [e for _n, _s, e in segs]))
    idle = unnamed = 0.0
    for g0, g1 in idle_gaps(events):
        named_ov = root_ov = 0
        covered = []
        for segs, ends in per_thread:
            for name, s, e in segs[bisect.bisect_right(ends, g0):]:
                if s >= g1:
                    break
                ov = min(e, g1) - max(s, g0)
                if name in roots:
                    root_ov += ov
                else:
                    named_ov += ov
                covered.append((max(s, g0), ov))
        cover = sum(e - s for s, e in tr.busy_union(covered))
        idle += g1 - g0
        unnamed += (g1 - g0) - cover
        if cover:
            unnamed += cover * root_ov / (named_ov + root_ov)
    return unnamed / idle if idle > 0 else None


def find_trace(window_s: float):
    """The `.xplane.pb` under `.bench_work/*/trace` whose window is
    `window_s`: (path, its Events), newest first; None when there is none."""
    dirs = sorted(glob.glob(os.path.join(ROOT, ".bench_work", "*", "trace")),
                  key=os.path.getmtime, reverse=True)
    for d in dirs:
        try:
            path = tr.find_xplane(d)
        except RuntimeError:
            continue
        events = tr.load(path)
        lo, hi = window(events)
        if abs((hi - lo) / 1e9 - window_s) < 1e-9:
            return path, events
    return None


def read(spec: dict, observed: dict):
    red = observed.get("trace")
    if red is None or red["idle_share"] is None:
        return None
    found = find_trace(red["window_s"])
    if found is None:
        return None
    path, events = found
    share = unspanned_share(events, load_threads(path), spec["roots"])
    return None if share is None else 100.0 * share
