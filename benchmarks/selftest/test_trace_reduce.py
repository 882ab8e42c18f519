"""The trace reduction on a hand-made event list and on a small trace
recorded on the CPU backend."""

import time

import pytest

from benchmarks import trace_reduce as tr

MS = 1_000_000


def events(ops, spans):
    return tr.Events({"/device:TPU:0": ops}, spans)


def test_busy_union_merges_overlaps():
    assert tr.busy_union([(0, 10), (5, 10), (30, 5), (35, 1)]) == \
        [[0, 15], [30, 36]]


def test_ops_take_the_module_that_was_running():
    hlo = "%fusion.3 = pred[1048576]{0:T(1024)} fusion(pred[8] %mk.1), kind=kCustom"
    ops = [(hlo, 12, 5), ("%copy.1 = u32[4] copy(u32[4] %x)", 40, 2),
           ("%stray = u32[] constant(0)", 90, 1)]
    modules = [("jit__block_encode_impl(15534449245221468849)", 30, 20),
               ("jit__pallas_merge_gc_fused(108)", 10, 10)]
    assert tr.name_ops(ops, modules) == [
        ("jit__pallas_merge_gc_fused/fusion.3", 12, 5),
        ("jit__block_encode_impl/copy.1", 40, 2),
        ("no_module/stray", 90, 1)]


def test_idle_share_and_gap_attribution():
    ops = [("m/a", 10 * MS, 20 * MS), ("m/b", 20 * MS, 20 * MS),   # 10..40
           ("m/a", 70 * MS, 10 * MS)]                             # 70..80
    spans = [(tr.WINDOW_SPAN, 0, 100 * MS),
             ("bench/job_open", 0, 10 * MS),
             ("bench/job_body", 10 * MS, 70 * MS),
             ("bench/job_close", 80 * MS, 20 * MS)]
    red = tr.reduce(events(ops, spans))
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.040)
    assert red["idle_share"] == pytest.approx(0.60)
    assert dict(red["device_ops"]) == pytest.approx(
        {"m/a": 0.030, "m/b": 0.020})
    assert dict(red["device_modules"]) == pytest.approx({"m": 0.050})
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"bench/job_body": 0.030, "bench/job_close": 0.020,
         "bench/job_open": 0.010})
    assert red["longest_gap_s"] == pytest.approx(0.030)


def test_concurrent_spans_share_a_gap_and_short_gaps_are_lumped():
    ops = [("k", 0, 10 * MS), ("k", 10 * MS + 5_000, 10 * MS),
           ("k", 60 * MS, 40 * MS)]
    spans = [(tr.WINDOW_SPAN, 0, 100 * MS),
             ("bench/multi_read", 20 * MS, 40 * MS),
             ("bench/session_flush", 20 * MS, 20 * MS)]
    red = tr.reduce(events(ops, spans))
    gaps = dict(red["idle_gaps"])
    total = 60 * MS - (20 * MS + 5_000)
    assert gaps[tr.SHORT_GAPS] == pytest.approx(5e-6)
    assert gaps["bench/multi_read"] + gaps["bench/session_flush"] == \
        pytest.approx(total / 1e9)
    assert gaps["bench/multi_read"] > gaps["bench/session_flush"]


def test_ops_outside_the_window_are_clipped_and_devices_averaged():
    e = tr.Events({"/device:TPU:0": [("k", -5 * MS, 10 * MS)],
                   "/device:TPU:1": [("k", 0, 15 * MS)]},
                  [(tr.WINDOW_SPAN, 0, 20 * MS)])
    red = tr.reduce(e)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(0.010)      # (5 + 15) / 2 ms
    assert red["idle_share"] == pytest.approx(0.5)


def test_no_device_operation_reads_nothing():
    red = tr.reduce(tr.Events({}, [(tr.WINDOW_SPAN, 0, 10 * MS)]))
    assert red["busy_s"] == 0 and red["idle_share"] is None


def test_a_trace_recorded_on_the_cpu_backend(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/job_body"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench/job_close"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    ev = tr.load(tr.find_xplane(str(tmp_path)))
    assert sum(1 for s in ev.host_spans if s[0] == "bench/job_body") == 3
    red = tr.reduce(ev)
    assert 0.06 <= red["window_s"] < 2.0
    assert 0 < red["busy_s"] < red["window_s"]
    assert any("dot" in name for name, _s in red["device_ops"])
    assert dict(red["idle_gaps"])["bench/job_close"] >= 0.05
