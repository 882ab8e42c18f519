"""`span_idle` on a hand-made event list: nested spans, two threads, a gap
under a root span only, a gap under no span, and a trace without `yb/`
spans (a program from before the span rail) -> None."""

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.readers import span_idle as si

MS = 1_000_000
ROOTS = ["yb/compact/job", "yb/rpc/handler"]


def events(ops, window=(0, 100 * MS)):
    return tr.Events({"/device:TPU:0": ops},
                     [(tr.WINDOW_SPAN, window[0], window[1])])


def test_innermost_segments_of_nested_spans():
    spans = [("yb/compact/job", 0, 100), ("yb/compact/ingest", 10, 40),
             ("yb/compact/raw_read", 20, 10), ("yb/compact/write", 60, 50)]
    assert si.innermost_segments(spans) == [
        ("yb/compact/job", 0, 10), ("yb/compact/ingest", 10, 20),
        ("yb/compact/raw_read", 20, 30), ("yb/compact/ingest", 30, 50),
        ("yb/compact/job", 50, 60),
        ("yb/compact/write", 60, 100)]     # a child ends with its parent


def test_one_thread_named_root_and_no_span():
    # busy 0..10 and 90..100: one idle gap 10..90
    ops = [("m/a", 0, 10 * MS), ("m/a", 90 * MS, 10 * MS)]
    threads = {0: [("yb/compact/job", 20 * MS, 60 * MS),       # 20..80
                   ("yb/compact/value_gather", 30 * MS, 30 * MS)]}  # 30..60
    # gap 80 ms: 30 named, 30 under the root only, 20 under no span
    assert si.unspanned_share(events(ops), threads, ROOTS) == \
        pytest.approx(50 / 80)
    # every span a root: all of it unnamed
    assert si.unspanned_share(
        events(ops), {0: [("yb/compact/job", 20 * MS, 60 * MS)]},
        ROOTS) == pytest.approx(1.0)


def test_two_threads_split_the_covered_part_by_overlap():
    ops = [("m/a", 0, 10 * MS), ("m/a", 50 * MS, 50 * MS)]   # gap 10..50
    threads = {
        0: [("yb/rpc/handler", 10 * MS, 40 * MS),            # root, 10..50
            ("yb/serve/device_wait", 10 * MS, 20 * MS)],     # named 10..30
        1: [("yb/rpc/handler", 30 * MS, 20 * MS)]}           # root, 30..50
    # named 20, root-only 20 + 20, all 40 ms covered: 40 * 40 / 60 unnamed
    assert si.unspanned_share(events(ops), threads, ROOTS) == \
        pytest.approx((40 * 40 / 60) / 40)


def test_gaps_are_those_of_the_reduction():
    ops = [("k", 0, 10 * MS), ("k", 10 * MS + 5_000, 10 * MS),   # 5 us gap
           ("k", 60 * MS, 40 * MS)]
    e = events(ops)
    assert si.idle_gaps(e) == [(20 * MS + 5_000, 60 * MS)]
    red = tr.reduce(e)
    assert sum(g1 - g0 for g0, g1 in si.idle_gaps(e)) / 1e9 == \
        pytest.approx(red["window_s"] - red["busy_s"]
                      - dict(red["idle_gaps"])[tr.SHORT_GAPS])


def test_nothing_to_read_gives_none():
    ops = [("m/a", 0, 10 * MS), ("m/a", 90 * MS, 10 * MS)]
    assert si.unspanned_share(events(ops), {}, ROOTS) is None
    assert si.unspanned_share(tr.Events({}, []), {0: [("yb/x", 0, 5)]},
                              ROOTS) is None
    busy = [("m/a", 0, 100 * MS)]                     # no idle gap at all
    assert si.unspanned_share(events(busy), {0: [("yb/x", 0, 5)]},
                              ROOTS) is None
    assert si.read({"roots": ROOTS}, {"trace": None}) is None


def test_reader_finds_the_runs_trace_by_its_window(tmp_path, monkeypatch):
    """On a small trace recorded on the CPU backend, under a work directory
    laid out as run.py lays it out."""
    import jax
    import jax.numpy as jnp
    import jax.profiler
    from yugabyte_tpu.utils.trace import span
    trace_dir = tmp_path / ".bench_work" / "cell-abc" / "trace"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with span("compact/job"):
            for _ in range(3):
                with span("compact/value_gather"):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    monkeypatch.setattr(si, "ROOT", str(tmp_path))
    red = tr.reduce(tr.load(tr.find_xplane(str(trace_dir))))
    value = si.read({"roots": ROOTS}, {"trace": red})
    assert value is not None and 0.0 <= value <= 100.0
    # another run's window finds no trace: None, not a raise
    other = dict(red, window_s=red["window_s"] + 1.0)
    assert si.read({"roots": ROOTS}, {"trace": other}) is None
