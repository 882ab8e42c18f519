"""Stage-overlapped compaction offload pipeline (PR: perf_opt).

Covers the three-stage pipeline (host decode -> async chunked device
merge -> streaming native SST writer), the shape-bucketed compile cache
and the greedy run-packing of small runs into shared m-slots:

  - pipelined device jobs produce byte-identical SSTs to the unpipelined
    device path AND to the CPU/native fallback (the repo's standing
    equivalence bar, extended to the chunked + streaming writer);
  - shape-bucket quantization lands distinct widths/compare schedules on
    the canonical lattice, and the bucket hit counter increments when a
    second job reuses the executable;
  - run-packing with mixed-size runs preserves the exact survivor set;
  - the streaming survivor injection (append_survivors) equals the
    one-shot set_survivors.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_run_merge import _make_run  # noqa: E402

from yugabyte_tpu.ops import run_merge  # noqa: E402
from yugabyte_tpu.ops.merge_gc import GCParams  # noqa: E402
from yugabyte_tpu.ops.slabs import ValueArray, concat_slabs  # noqa: E402
from yugabyte_tpu.storage import compaction as compaction_mod  # noqa: E402
from yugabyte_tpu.storage import native_engine  # noqa: E402
from yugabyte_tpu.storage.cpu_baseline import compact_cpu_baseline  # noqa: E402
from yugabyte_tpu.storage.device_cache import DeviceSlabCache  # noqa: E402
from yugabyte_tpu.storage.sst import Frontier, SSTReader, SSTWriter  # noqa: E402
from yugabyte_tpu.utils import flags  # noqa: E402

CUTOFF = (10_000_000 << 12)


def _device():
    import jax
    return jax.devices()[0]


def _mk_run(rng, n, key_space, value_bytes=16, ttl_frac=0.0):
    slab = _make_run(rng, n, key_space, ttl_frac=ttl_frac)
    data = rng.integers(0, 256, size=n * value_bytes, dtype=np.uint8)
    offs = np.arange(n + 1, dtype=np.int64) * value_bytes
    slab.values = ValueArray(data, offs)
    return slab


def _write_runs(workdir, runs):
    readers = []
    for i, slab in enumerate(runs):
        p = os.path.join(workdir, f"in{i:03d}.sst")
        SSTWriter(p).write(slab, Frontier())
        readers.append(SSTReader(p))
    return readers


def _sst_bytes(outputs):
    """data-file bytes per output, in output order."""
    out = []
    for _fid, base_path, _props in outputs:
        with open(base_path + ".sblock.0", "rb") as f:
            out.append(f.read())
    return out


def _run_device_native(readers, out_dir, first_id=100, is_major=True):
    os.makedirs(out_dir, exist_ok=True)
    cache = DeviceSlabCache(device=_device())
    ids = list(range(len(readers)))
    for fid, r in zip(ids, readers):
        cache.stage(fid, r.read_all())
    gen = iter(range(first_id, first_id + 500))
    return compaction_mod.run_compaction_job_device_native(
        readers, out_dir, lambda: next(gen), CUTOFF, is_major,
        device=_device(), device_cache=cache, input_ids=ids)


# ---------------------------------------------------------------- pipeline


@pytest.mark.requires_native("compaction_engine")
def test_pipeline_vs_sequential_vs_cpu_byte_identical(tmp_path, monkeypatch):
    """The headline equivalence: chunked pipelined device job ==
    unpipelined device job == native CPU fallback, byte for byte,
    across a multi-file split."""
    rng = np.random.default_rng(21)
    runs = [_mk_run(rng, 1500, 6000) for _ in range(4)]
    readers = _write_runs(str(tmp_path), runs)
    old = flags.get_flag("compaction_max_output_entries_per_sst")
    flags.set_flag("compaction_max_output_entries_per_sst", 1000)
    monkeypatch.setenv("YBTPU_MERGE_CHUNK_ROWS", "2048")  # force chunking
    try:
        monkeypatch.setenv("YBTPU_PIPELINE", "1")
        res_pipe = _run_device_native(readers, str(tmp_path / "pipe"),
                                      first_id=100)
        monkeypatch.setenv("YBTPU_PIPELINE", "0")
        res_seq = _run_device_native(readers, str(tmp_path / "seq"),
                                     first_id=100)
        monkeypatch.delenv("YBTPU_PIPELINE")
        ids = iter(range(100, 600))
        os.makedirs(str(tmp_path / "cpu"))
        res_cpu = compaction_mod.run_compaction_job(
            readers, str(tmp_path / "cpu"), lambda: next(ids), CUTOFF,
            True, device="native")
    finally:
        flags.set_flag("compaction_max_output_entries_per_sst", old)
    assert res_pipe.rows_out == res_seq.rows_out == res_cpu.rows_out
    assert len(res_pipe.outputs) >= 2, "expected a multi-file split"
    assert _sst_bytes(res_pipe.outputs) == _sst_bytes(res_seq.outputs)
    assert _sst_bytes(res_pipe.outputs) == _sst_bytes(res_cpu.outputs)
    assert res_pipe.tombstones_written == res_seq.tombstones_written
    for r in readers:
        r.close()


@pytest.mark.requires_native("compaction_engine")
def test_streaming_writer_overlaps_chunks(tmp_path, monkeypatch):
    """With chunking + a small file split, the streaming writer must
    emit at least one complete file BEFORE the last chunk's decisions
    are consumed (the actual overlap, not just the same outputs)."""
    rng = np.random.default_rng(22)
    runs = [_mk_run(rng, 1500, 8000) for _ in range(4)]
    readers = _write_runs(str(tmp_path), runs)
    # this test observes the SHELL's streaming stage C specifically; the
    # device codec writes outputs through its own writer, so pin it off
    monkeypatch.setenv("YBTPU_DEVICE_CODEC", "0")
    monkeypatch.setenv("YBTPU_MERGE_CHUNK_ROWS", "2048")
    old = flags.get_flag("compaction_max_output_entries_per_sst")
    flags.set_flag("compaction_max_output_entries_per_sst", 700)

    events = []
    orig_feed = compaction_mod._StreamingNativeWriter._write_span
    orig_iter = run_merge._ChunkedMergeGCHandle.result_iter

    def span_spy(self, start, end, more_coming):
        events.append(("write", start, end))
        return orig_feed(self, start, end, more_coming)

    def iter_spy(self):
        for x in orig_iter(self):
            events.append(("chunk",))
            yield x

    monkeypatch.setattr(compaction_mod._StreamingNativeWriter,
                        "_write_span", span_spy)
    monkeypatch.setattr(run_merge._ChunkedMergeGCHandle,
                        "result_iter", iter_spy)
    try:
        res = _run_device_native(readers, str(tmp_path / "out"))
    finally:
        flags.set_flag("compaction_max_output_entries_per_sst", old)
    n_chunks = sum(1 for e in events if e[0] == "chunk")
    assert n_chunks >= 2, "chunked launch did not engage"
    first_write = next(i for i, e in enumerate(events) if e[0] == "write")
    last_chunk = max(i for i, e in enumerate(events) if e[0] == "chunk")
    assert first_write < last_chunk, (
        "no output file was written before the final chunk's decisions "
        f"were consumed: {events}")
    assert len(res.outputs) >= 2
    for r in readers:
        r.close()


@pytest.mark.requires_native("compaction_engine")
def test_append_survivors_equals_set_survivors(tmp_path):
    """The C++ streaming injection: appending chunk survivor spans must
    leave the job in exactly the state one set_survivors produces."""
    rng = np.random.default_rng(23)
    runs = [_mk_run(rng, 400, 300) for _ in range(2)]
    readers = _write_runs(str(tmp_path), runs)
    params = GCParams(CUTOFF, True, False)
    perm, keep, mk = run_merge.merge_and_gc_runs(
        [r.read_all() for r in readers], params)
    surv, mk_s = perm[keep], mk[keep]
    tomb = b"\x00"

    def ingest(job):
        for r in readers:
            with open(r.data_path, "rb") as f:
                job.add_input(f.read(), r.block_handles)
        job.prepare()

    with native_engine.NativeCompactionJob() as j1, \
            native_engine.NativeCompactionJob() as j2:
        ingest(j1)
        ingest(j2)
        j1.set_survivors(surv, mk_s)
        cut = len(surv) // 3
        j2.append_survivors(surv[:cut], mk_s[:cut])
        j2.append_survivors(surv[cut:], mk_s[cut:])
        assert j1.n_survivors == j2.n_survivors == len(surv)
        o1 = j1.write_output(0, len(surv), str(tmp_path / "a.dat"), 128,
                             compress=False, tombstone_value=tomb)
        o2 = j2.write_output(0, len(surv), str(tmp_path / "b.dat"), 128,
                             compress=False, tombstone_value=tomb)
        assert o1[0] == o2[0]
    with open(tmp_path / "a.dat", "rb") as fa, \
            open(tmp_path / "b.dat", "rb") as fb:
        assert fa.read() == fb.read()
    for r in readers:
        r.close()


# ----------------------------------------------------------- shape buckets


def test_quantize_width_lattice():
    assert run_merge.quantize_width(1) == 4
    assert run_merge.quantize_width(3) == 4
    assert run_merge.quantize_width(4) == 4
    assert run_merge.quantize_width(5) == 8
    assert run_merge.quantize_width(8) == 8
    assert run_merge.quantize_width(9) == 16


def test_cmp_schedule_lands_on_lattice():
    """Distinct pruned-comparator lengths quantize onto the n_cmp
    lattice, with the pad repeating the last row (a no-op compare)."""
    for n_live in range(1, 17):
        is_const = np.ones(64, dtype=bool)
        # leave exactly n_live key-word rows non-constant
        for j in range(n_live):
            is_const[run_merge._ROW_WORDS + j] = False
        rows, n_cmp = run_merge._cmp_schedule(w=32, is_const=is_const)
        assert n_cmp in run_merge._CMP_LATTICE
        assert n_cmp >= n_live
        assert len(rows) == n_cmp
        # padding repeats the final real row
        assert (rows[n_live:] == rows[n_live - 1]).all()


def test_staged_widths_share_bucket():
    """Runs of width 3 and width 4 must stage into the SAME (w) bucket
    so one executable serves both."""
    rng = np.random.default_rng(24)
    a = run_merge.stage_runs_from_slabs(
        [_make_run(rng, 300, 200, w=3) for _ in range(2)])
    b = run_merge.stage_runs_from_slabs(
        [_make_run(rng, 300, 200, w=4) for _ in range(2)])
    assert a.w == b.w == 4
    assert a.n_cmp in run_merge._CMP_LATTICE
    assert (a.m, a.k_pad) == (b.m, b.k_pad)


def test_bucket_hit_counter_increments():
    """Second job with the same quantized shape = a bucket hit."""
    from yugabyte_tpu.utils.metrics import kernel_metrics
    hits = kernel_metrics().counter(
        "kernel_compile_bucket_hits_total",
        "kernel launches that reused an already-compiled shape bucket")
    rng = np.random.default_rng(25)
    params = GCParams(CUTOFF, True, False)
    runs1 = [_make_run(rng, 300, 200) for _ in range(2)]
    runs2 = [_make_run(rng, 300, 200) for _ in range(2)]  # same shapes
    run_merge.merge_and_gc_runs(runs1, params)
    before = hits.value()
    run_merge.merge_and_gc_runs(runs2, params)
    assert hits.value() > before, (
        "identical-shape second job did not record a bucket hit")


def test_prewarm_buckets_compiles_and_marks_seen():
    """Prewarm compiles the requested buckets; the next real launch of
    that bucket is a recorded hit."""
    from yugabyte_tpu.utils.metrics import kernel_metrics
    hits = kernel_metrics().counter(
        "kernel_compile_bucket_hits_total",
        "kernel launches that reused an already-compiled shape bucket")
    rng = np.random.default_rng(26)
    runs = [_make_run(rng, 400, 300) for _ in range(2)]  # -> m=512, w->4
    staged = run_merge.stage_runs_from_slabs(runs)
    assert (staged.k_pad, staged.m, staged.w) == (2, 512, 4)
    assert staged.n_cmp in run_merge._CMP_LATTICE
    # prewarm the exact bucket this staging produced (staging records no
    # bucket; only launches do) — the real launch below must then be the
    # bucket's second sighting, i.e. a hit
    pw = run_merge.prewarm_buckets(
        [(staged.k_pad, staged.m, staged.w, staged.n_cmp)])
    # both is_major variants of the one merge shape, plus the chained
    # write-through programs (survivor scan, span gather, restage concat)
    assert pw.compiled == 5 and pw.failed == []
    before = hits.value()
    run_merge.merge_and_gc_runs(runs, GCParams(CUTOFF, True, False),
                                staged=staged)
    assert hits.value() > before


def test_prewarm_maintenance_op_one_shot():
    from yugabyte_tpu.tserver.maintenance_manager import (
        MaintenanceOpStats, PrewarmKernelsOp)
    op = PrewarmKernelsOp(shapes=[(2, 512, 4, 8)], enabled_fn=lambda: True)
    s = MaintenanceOpStats()
    op.update_stats(s)
    assert s.runnable and s.perf_improvement > 0
    op.perform()
    s2 = MaintenanceOpStats()
    op.update_stats(s2)
    assert not s2.runnable, "prewarm op must be one-shot"


def test_prewarm_op_does_not_mark_refused_shapes(monkeypatch):
    """A shape whose compile the compiler refuses stays COLD on the
    board and is named in op.failed; its neighbour is marked warmed."""
    from yugabyte_tpu.storage import bucket_health
    from yugabyte_tpu.tserver.maintenance_manager import PrewarmKernelsOp
    board = bucket_health.health_board()
    board.reset()
    real = run_merge._gather_staged_output.lower

    class _Refusing:
        def lower(self, *a, n_out_pad, **kw):
            if n_out_pad == 1024:
                raise RuntimeError("compiler refused")
            return real(*a, n_out_pad=n_out_pad, **kw)

    monkeypatch.setattr(run_merge, "_gather_staged_output", _Refusing())
    op = PrewarmKernelsOp(shapes=[(2, 512, 4, 8), (2, 1024, 4, 8)],
                          enabled_fn=lambda: True)
    try:
        op.perform()
        assert op.done and len(op.failed) == 1 and "1024" in op.failed[0]
        assert board.state("run_merge_fused", (2, 512)) == \
            bucket_health.WARMING
        assert board.state("run_merge_fused", (2, 1024)) == \
            bucket_health.COLD
    finally:
        board.reset()


def test_serve_path_prewarms_report_what_compiled(monkeypatch):
    """The scan-pushdown and block-codec prewarms return what compiled
    and name nothing refused (lattices cut to one tiny n_pad: the
    declared ones are PrewarmKernelsOp's full-mode bill)."""
    from yugabyte_tpu.ops import block_codec, scan
    monkeypatch.setattr(scan, "_PREWARM_NPADS", (256,))
    monkeypatch.setattr(block_codec, "_PREWARM_DECODE", ((256, 4),))
    pw = scan.prewarm_scan_pushdown()
    n_filtered = len(scan.PRED_SLOTS) * 2
    assert pw.compiled == n_filtered * (1 + len(scan.AGG_SLOTS)) + 1
    assert pw.failed == []
    pw = block_codec.prewarm_block_codec()
    assert (pw.compiled, pw.failed) == (2, [])


# ------------------------------------------------------------ run packing


def test_plan_run_packing_mixed_sizes():
    """One big run + small ones: smalls pack into shared slots and k_pad
    shrinks; evenly sized runs do not pack (no k_pad win)."""
    plan = run_merge.plan_run_packing([4000, 100, 90, 80, 70])  # k_pad 8
    assert plan is not None
    packed = run_merge.packed_run_ns([4000, 100, 90, 80, 70])
    m = run_merge.run_bucket(4000)
    assert all(s <= m for s in packed)
    assert len(packed) < 5
    k_pad_new = 1 << max(0, (len(packed) - 1).bit_length())
    assert k_pad_new < 8
    # every input run appears in exactly one bin
    flat = sorted(i for b in plan for i in b)
    assert flat == [0, 1, 2, 3, 4]
    assert run_merge.plan_run_packing([1000, 1000, 900, 950]) is None
    assert run_merge.plan_run_packing([500]) is None


def test_run_packing_survivors_match_unpacked():
    """Packed staging must keep exactly the survivors (input-row indexed)
    of the unpacked staging AND of the CPU baseline."""
    rng = np.random.default_rng(27)
    sizes = [3000, 200, 150, 120, 100]
    runs = [_make_run(rng, n, 800) for n in sizes]
    params = GCParams(CUTOFF, True, False)

    staged_packed = run_merge.stage_runs_from_slabs(runs, pack_runs=True)
    assert staged_packed.run_maps is not None, "packing did not engage"
    assert staged_packed.k_pad < 8
    p1, k1, m1 = run_merge.launch_merge_gc(staged_packed, params).result()

    staged_plain = run_merge.stage_runs_from_slabs(runs, pack_runs=False)
    p2, k2, m2 = run_merge.launch_merge_gc(staged_plain, params).result()

    assert np.array_equal(p1[k1], p2[k2])
    assert np.array_equal(p1[m1], p2[m2])

    merged = concat_slabs(runs)
    offsets = np.concatenate(([0], np.cumsum(sizes))).tolist()
    oc, kc, mc = compact_cpu_baseline(merged, offsets, CUTOFF, True, False)
    assert np.array_equal(p1[k1], oc[kc])


def test_run_packing_env_disable(monkeypatch):
    monkeypatch.setenv("YBTPU_RUN_PACKING", "0")
    rng = np.random.default_rng(28)
    runs = [_make_run(rng, n, 500) for n in (2000, 100, 90, 80)]
    staged = run_merge.stage_runs_from_slabs(runs)
    assert staged.run_maps is None
    assert staged.k_pad == 4


# -------------------------------------------------------- stage metrics


def test_pipeline_stage_totals_accumulate():
    from yugabyte_tpu.utils.metrics import (pipeline_stage_totals,
                                            record_pipeline_stage)
    before = pipeline_stage_totals()
    record_pipeline_stage("host", 5.0)
    record_pipeline_stage("device", 2.5)
    record_pipeline_stage("write", 1.0)
    after = pipeline_stage_totals()
    assert after["host"] >= before["host"] + 5.0 - 1e-6
    assert after["device"] >= before["device"] + 2.5 - 1e-6
    assert after["write"] >= before["write"] + 1.0 - 1e-6
