#!/usr/bin/env python
"""North-star benchmark: L0->L1 compaction merge+GC rows/sec on TPU.

Measures the fused TPU merge+MVCC-GC kernel (ops/merge_gc.py) against the
native C++ CPU baseline (native/compaction_baseline.cc) which implements the
reference's stock CompactionJob architecture — binary-heap k-way merge
(ref: rocksdb/table/merger.cc:51) + sequential per-entry GC filter
(ref: docdb/docdb_compaction_filter.cc) — on one core, i.e. one
subcompaction thread (ref: compaction_job.cc:456-468).

Workload: YCSB-A-shaped tablet — K_RUNS overlapping sorted runs (L0 SSTs)
of uniform-random row updates plus row tombstones, major-compacted with the
history cutoff above all writes (pure dedup-to-latest + tombstone GC).

Robustness contract (round-2 hardening): the parent process NEVER touches a
JAX backend. All device work runs in child processes under a watchdog
timeout with retries; if the TPU backend cannot be initialized, the
benchmark still emits a full JSON line using the CPU-JAX kernel rate, so a number is ALWAYS
recorded.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
value       = device end-to-end rows/s (host pack + transfer + kernel + fetch)
vs_baseline = value / native-C++-baseline rows/s
Extra keys record platform, device-resident rate, scan rate, and baseline.
"""

import json
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def _surface_counts_for_report():
    """Per-family declared compile-surface executable counts from the
    committed kernel manifest — reported next to compile_bucket_* so a
    run proves the warm cache covers exactly the reviewed surface."""
    from yugabyte_tpu.storage.offload_policy import declared_surface_counts
    return declared_surface_counts()


def _shadow_sample_for_report():
    from yugabyte_tpu.storage.integrity import shadow_snapshot
    return shadow_snapshot()["sample"]


def _shadow_jobs_for_report():
    from yugabyte_tpu.storage.integrity import shadow_snapshot
    return shadow_snapshot()["jobs_verified"]


def _shadow_mismatches_for_report():
    from yugabyte_tpu.storage.integrity import shadow_snapshot
    return shadow_snapshot()["mismatches"]


def _round_meta(backend: str, round_label: str = "") -> dict:
    """The round identity stamp every bench JSON carries: what backend
    produced the numbers, on how many devices / host cores, from which
    source revision — the keys tools/bench_compare.py refuses to diff
    across (CPU-vs-TPU rounds are different experiments, not
    regressions)."""
    meta = {
        "backend": backend,
        "device_count": 0,
        "host_cores": os.cpu_count() or 0,
        "git_rev": "",
        "round_label": round_label
        or os.environ.get("YBTPU_BENCH_ROUND_LABEL", ""),
    }
    try:
        meta["git_rev"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip()
    except Exception:  # noqa: BLE001 — identity stamp, never fatal
        pass
    try:
        # parent-safe: only count devices if a backend is ALREADY up in
        # this process (children measure; the parent must not init one)
        if "jax" in sys.modules:
            meta["device_count"] = len(sys.modules["jax"].devices())
    except Exception:  # noqa: BLE001 — identity stamp, never fatal
        pass
    return meta


def _bucket_health_for_report():
    """Transition counters + per-state bucket counts from the live
    bucket-health board — reported next to compile_bucket_* so a run
    shows whether any shape bucket demoted/quarantined mid-bench (a
    demotion silently shifts rows to the native path, which would
    otherwise read as an unexplained device-rate regression)."""
    from yugabyte_tpu.storage.bucket_health import health_board
    snap = health_board().snapshot()
    return ({f"bucket_health_{k}": v
             for k, v in snap.get("counters", {}).items()},
            dict(snap.get("states", {})))


def log(msg):
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def synth_ycsb_runs(n_total: int, n_runs: int, key_space: int, seed: int = 42,
                    tombstone_frac: float = 0.05):
    """Vectorized YCSB-A-like slab: n_runs sorted runs of row writes.

    Key layout (DocDB encoding, docdb/doc_key.py): root = 'S' 'user%08d'
    00 00 '!' (16B); column write = root + 'K' + 2B col id (19B).
    """
    from yugabyte_tpu.ops.slabs import KVSlab, FLAG_TOMBSTONE, ValueArray

    rng = np.random.default_rng(seed)
    per_run = n_total // n_runs
    stride = 20  # 19B padded to 4B words -> w=5
    all_parts = []
    offsets = [0]
    for g in range(n_runs):
        ids = rng.integers(0, key_space, size=per_run)
        is_tomb = rng.random(per_run) < tombstone_frac
        keys = np.zeros((per_run, stride), dtype=np.uint8)
        keys[:, 0] = ord("S")
        keys[:, 1:5] = np.frombuffer(b"user", dtype=np.uint8)
        digits = ids[:, None] // (10 ** np.arange(7, -1, -1)[None, :]) % 10
        keys[:, 5:13] = (digits + ord("0")).astype(np.uint8)
        keys[:, 13] = 0
        keys[:, 14] = 0
        keys[:, 15] = ord("!")
        # column writes address col 0; tombstones hit the row root
        col_part = np.where(is_tomb[:, None],
                            np.zeros((per_run, 3), np.uint8),
                            np.array([[ord("K"), 0, 0]], np.uint8))
        keys[:, 16:19] = col_part
        key_len = np.where(is_tomb, 16, 19).astype(np.int32)
        dkl = np.full(per_run, 16, dtype=np.int32)
        ht = (1_000_000 * (g + 1) + rng.permutation(per_run)).astype(np.uint64) << 12
        flags = np.where(is_tomb, FLAG_TOMBSTONE, 0).astype(np.uint32)
        # sort run by (key, ht desc): lexsort minor->major
        sort_cols = [~ht] + [keys[:, j] for j in range(stride - 1, -1, -1)]
        order = np.lexsort(sort_cols)
        all_parts.append((keys[order], key_len[order], dkl[order], ht[order],
                          flags[order]))
        offsets.append(offsets[-1] + per_run)
    keys = np.concatenate([p[0] for p in all_parts])
    n = keys.shape[0]
    kw = keys.reshape(n, stride // 4, 4)
    key_words = ((kw[:, :, 0].astype(np.uint32) << 24)
                 | (kw[:, :, 1].astype(np.uint32) << 16)
                 | (kw[:, :, 2].astype(np.uint32) << 8)
                 | kw[:, :, 3].astype(np.uint32))
    ht = np.concatenate([p[3] for p in all_parts])
    slab = KVSlab(
        key_words=key_words,
        key_len=np.concatenate([p[1] for p in all_parts]),
        doc_key_len=np.concatenate([p[2] for p in all_parts]),
        ht_hi=(ht >> 32).astype(np.uint32),
        ht_lo=(ht & 0xFFFFFFFF).astype(np.uint32),
        write_id=np.zeros(n, dtype=np.uint32),
        flags=np.concatenate([p[4] for p in all_parts]),
        ttl_ms=np.zeros(n, dtype=np.int64),
        value_idx=np.arange(n, dtype=np.int32),
        values=ValueArray.empty_rows(n),
    )
    return slab, offsets


def _attach_values(slab, value_bytes: int):
    """Give every row a value payload (uniform stride — one big buffer)."""
    from yugabyte_tpu.ops.slabs import ValueArray
    n = slab.n
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=n * value_bytes, dtype=np.uint8)
    offsets = (np.arange(n + 1, dtype=np.int64) * value_bytes)
    slab.values = ValueArray(data, offsets)
    return slab


def _write_input_ssts(slab, offsets, workdir: str):
    """Materialize the L0 input runs as real split-SST files on disk."""
    from yugabyte_tpu.storage.sst import Frontier, SSTWriter
    in_dir = os.path.join(workdir, "in")
    os.makedirs(in_dir, exist_ok=True)
    paths = []
    for r in range(len(offsets) - 1):
        sub = _slice_slab(slab, offsets[r], offsets[r + 1])
        path = os.path.join(in_dir, f"{r:06d}.sst")
        SSTWriter(path).write(sub, Frontier())
        paths.append(path)
    return paths


def _e2e_compaction(paths, n_total, cutoff, device, out_dir: str):
    """End-to-end L0->L1 compaction: SSTs on disk -> read -> merge+GC ->
    output SSTs on disk (the FULL CompactionJob, ref compaction_job.cc:442,
    including hot loop ③ block encode). device='native' is the stock
    CPU architecture doing the same full job over the same files."""
    import shutil
    from yugabyte_tpu.storage.compaction import run_compaction_job
    from yugabyte_tpu.storage.sst import SSTReader

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    ids = iter(range(1, 1 << 30))
    readers = [SSTReader(p) for p in paths]
    t0 = time.time()
    result = run_compaction_job(readers, out_dir, lambda: next(ids),
                                cutoff, True, device=device)
    dt = time.time() - t0
    for r in readers:
        r.close()
    return n_total / dt, result.rows_out


def _slice_slab(slab, lo, hi):
    from yugabyte_tpu.ops.slabs import KVSlab, ValueArray
    va = slab.values
    sel = slab.value_idx[lo:hi]
    return KVSlab(
        key_words=slab.key_words[lo:hi], key_len=slab.key_len[lo:hi],
        doc_key_len=slab.doc_key_len[lo:hi], ht_hi=slab.ht_hi[lo:hi],
        ht_lo=slab.ht_lo[lo:hi], write_id=slab.write_id[lo:hi],
        flags=slab.flags[lo:hi], ttl_ms=slab.ttl_ms[lo:hi],
        value_idx=np.arange(hi - lo, dtype=np.int32),
        values=va.gather(sel))


def _cpu_cxx_baseline(slab, offsets, cutoff, n_total):
    """Native C++ baseline: stock CompactionJob architecture, one core."""
    from yugabyte_tpu.storage.cpu_baseline import compact_cpu_baseline
    t0 = time.time()
    _, keep_cpu, _ = compact_cpu_baseline(slab, offsets, cutoff, True)
    cpu_s = time.time() - t0
    cpu_rate = n_total / cpu_s
    log(f"  C++ baseline: {cpu_s:.2f}s = {cpu_rate/1e6:.2f}M rows/s "
        f"(kept {int(keep_cpu.sum())})")
    return cpu_rate, int(keep_cpu.sum())


def _save_workload(path, slab, offsets, n_total, cutoff, cpu_rate, cpu_kept):
    np.savez(path, key_words=slab.key_words, key_len=slab.key_len,
             doc_key_len=slab.doc_key_len, ht_hi=slab.ht_hi, ht_lo=slab.ht_lo,
             write_id=slab.write_id, flags=slab.flags, ttl_ms=slab.ttl_ms,
             value_idx=slab.value_idx, offsets=np.asarray(offsets),
             meta=np.asarray([n_total, cutoff, cpu_kept], dtype=np.int64),
             cpu_rate=np.asarray([cpu_rate]))


def _load_workload(path):
    from yugabyte_tpu.ops.slabs import KVSlab, ValueArray
    z = np.load(path)
    n_total, cutoff, cpu_kept = (int(x) for x in z["meta"])
    slab = KVSlab(key_words=z["key_words"], key_len=z["key_len"],
                  doc_key_len=z["doc_key_len"], ht_hi=z["ht_hi"],
                  ht_lo=z["ht_lo"], write_id=z["write_id"], flags=z["flags"],
                  ttl_ms=z["ttl_ms"], value_idx=z["value_idx"],
                  values=ValueArray.empty_rows(n_total))
    return slab, list(z["offsets"]), n_total, cutoff, float(z["cpu_rate"][0]), cpu_kept


def _split_runs(slab, offsets):
    return [_slice_slab(slab, offsets[r], offsets[r + 1])
            for r in range(len(offsets) - 1)]


def run_probe_child(platform: str) -> None:
    """Init-only child: succeeds iff the backend comes up as `platform`."""
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    if platform == "tpu" and dev.platform == "cpu":
        sys.exit(3)
    print(json.dumps({"probe": str(dev)}), flush=True)


def run_warm_child(platform: str, workload_path: str) -> None:
    """Compile-cache warmer: run the kernel once at the target shape so the
    persistent compilation cache (utils/jax_setup.py) holds the executables
    before the measuring child starts.  A timeout here still keeps whatever
    finished compiling — the measure child resumes from the cache."""
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    slab, offsets, n_total, cutoff, _, cpu_kept = _load_workload(workload_path)
    runs = _split_runs(slab, offsets)
    from yugabyte_tpu.ops import run_merge
    from yugabyte_tpu.ops.merge_gc import GCParams
    dev = jax.devices()[0]
    if platform == "tpu" and dev.platform == "cpu":
        sys.exit(3)
    t0 = time.time()
    _, keep, _ = run_merge.merge_and_gc_runs(runs, GCParams(cutoff, True),
                                             device=dev)
    first_s = time.time() - t0
    assert int(keep.sum()) == cpu_kept
    # isolate compile from run: the second call reuses the in-process jit
    # cache, so first - second ~= trace + compile (or persistent-cache
    # load). This is the cache proof the parent records as compile2_s —
    # a FRESH process over already-cached buckets must land in seconds,
    # not re-pay the first child's full XLA compile.
    t0 = time.time()
    _, keep2, _ = run_merge.merge_and_gc_runs(runs, GCParams(cutoff, True),
                                              device=dev)
    second_s = time.time() - t0
    assert int(keep2.sum()) == cpu_kept
    compile_s = max(0.0, first_s - second_s)
    log(f"  warm: first call {first_s:.1f}s, second {second_s:.1f}s -> "
        f"compile ~{compile_s:.1f}s on {dev} (kept {int(keep.sum())}, "
        f"expected {cpu_kept})")
    print(json.dumps({"warmed": n_total,
                      "compile_s": round(compile_s, 2)}), flush=True)


def run_points_child(platform: str, db_dir: str, n_str: str) -> None:
    """Batched point-read rung (ROADMAP item 4): multi_get through the
    device bloom/locate/gather kernels over the scan-stage DB, batch
    sizes 64/1024, hit + bloom-rejected miss mixes, with learned-index
    hit/fallback counters. Runs as a child so the platform choice (TPU
    when one is attached, else the CPU fallback) never hangs the
    parent."""
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    if platform == "tpu" and dev.platform == "cpu":
        sys.exit(3)
    n = int(n_str)
    from yugabyte_tpu.ops.point_read import point_read_metrics
    from yugabyte_tpu.ops.slabs import _doc_key_len
    from yugabyte_tpu.storage.db import DB, DBOptions
    from yugabyte_tpu.storage.device_cache import DeviceSlabCache
    from yugabyte_tpu.storage.sst import BlockCache

    rng = np.random.default_rng(17)
    db = DB(db_dir, DBOptions(device=dev,
                              device_cache=DeviceSlabCache(device=dev),
                              auto_compact=False,
                              block_cache=BlockCache(256 << 20)))
    out = {"points_device": str(dev)}

    def key_of(i: int) -> bytes:
        return b"Suser%08d\x00\x00!" % i

    try:
        dkl = _doc_key_len(key_of(0))
        m = point_read_metrics()
        lh0 = m["learned_hits"].value()
        lf0 = m["learned_fallbacks"].value()
        sk0 = m["bloom_skips"].value()
        # correctness gate before any rate ships: batched == sequential
        spot = [key_of(int(i)) for i in rng.integers(0, n + 64, size=256)]
        assert db.multi_get(spot, doc_key_lens=[dkl] * len(spot)) == \
            [db.get(k) for k in spot], "multi_get != sequential gets"
        for bs in (64, 1024):
            mq = 40_960 if bs == 1024 else 8_192
            hit_keys = [key_of(int(i))
                        for i in rng.integers(0, n, size=mq)]
            db.multi_get(hit_keys[:bs], doc_key_lens=[dkl] * bs)  # warm
            t0 = time.time()
            found = 0
            for s in range(0, mq, bs):
                chunk = hit_keys[s: s + bs]
                res = db.multi_get(chunk,
                                   doc_key_lens=[dkl] * len(chunk))
                found += sum(r is not None for r in res)
            dt = time.time() - t0
            assert found == mq, f"batched hits: {found}/{mq}"
            out[f"point_reads_batched_b{bs}_per_sec"] = round(mq / dt, 1)
            # bloom-rejected misses: keys outside the loaded range
            miss_keys = [key_of(n + 10 + i) for i in range(mq)]
            t0 = time.time()
            for s in range(0, mq, bs):
                chunk = miss_keys[s: s + bs]
                if any(r is not None for r in db.multi_get(
                        chunk, doc_key_lens=[dkl] * len(chunk))):
                    raise AssertionError("phantom batched read")
            out[f"point_miss_batched_b{bs}_per_sec"] = round(
                mq / (time.time() - t0), 1)
            log(f"  batched point reads (B={bs}): "
                f"{out[f'point_reads_batched_b{bs}_per_sec']:.0f}/s hit, "
                f"{out[f'point_miss_batched_b{bs}_per_sec']:.0f}/s miss")
        out["point_reads_batched_per_sec"] = \
            out["point_reads_batched_b1024_per_sec"]
        out["point_miss_batched_per_sec"] = \
            out["point_miss_batched_b1024_per_sec"]
        m = point_read_metrics()
        out["point_read_learned_hits"] = int(m["learned_hits"].value()
                                             - lh0)
        out["point_read_learned_fallbacks"] = int(
            m["learned_fallbacks"].value() - lf0)
        out["point_read_bloom_skipped_ssts"] = int(
            m["bloom_skips"].value() - sk0)
    finally:
        db.close()
    print(json.dumps(out), flush=True)


def run_codec_child(platform: str, n_str: str) -> None:
    """Block-codec micro rung (ROADMAP item 2): device block decode /
    encode vs the host codec baselines over one n-row SST.

    Decode: raw-byte parse + block_decode_fused into staged cols, vs the
    host path (SSTReader.read_all + stage_slab: threaded decode_block +
    pack_cols) and, when available, the native shell's threaded block
    decode (add_input + prepare).  Encode: block_encode_fused + host
    value splice + CRC + file write, vs SSTWriter's per-block
    encode_block loop and the native shell's threaded write_output.
    Correctness gates run before any rate ships: the device-decoded cols
    must equal the host staging bit-for-bit and the device-encoded data
    file must equal the host-encoded one byte-for-byte."""
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    if platform == "tpu" and dev.platform == "cpu":
        sys.exit(3)
    import shutil
    import tempfile

    import numpy as _np

    from yugabyte_tpu.ops import block_codec
    from yugabyte_tpu.ops.merge_gc import stage_slab
    from yugabyte_tpu.storage import native_engine
    from yugabyte_tpu.storage.sst import (Frontier, SSTReader, SSTWriter,
                                          data_file_name, write_base_file)
    from yugabyte_tpu.utils.env import get_env

    n = int(n_str)
    slab, _offsets = synth_ycsb_runs(n, 1, max(1, n // 2))
    root = tempfile.mkdtemp(prefix="ybtpu-bench-codec-")
    out = {"codec_device": str(dev), "codec_rows": n}
    try:
        path = os.path.join(root, "in.sst")
        SSTWriter(path, fit_lindex=False).write(slab, Frontier())
        r = SSTReader(path)

        # ---- decode: host / native / device --------------------------
        def host_decode():
            return stage_slab(r.read_all(), dev)

        def best_of_pair(fa, fb, reps=5):
            """Interleaved best-of-N for two contenders: alternating the
            measurements cancels background drift on a shared box (a
            sequential pair hands whichever runs second the noisier
            machine)."""
            ta, tb = [], []
            for _ in range(reps):
                t0 = time.time()
                fa()
                ta.append(time.time() - t0)
                t0 = time.time()
                fb()
                tb.append(time.time() - t0)
            return min(ta), min(tb)

        ref = host_decode()   # warm + reference
        rfb = block_codec.parse_raw_file(r.read_raw(), r.block_handles)
        st = block_codec.decode_file_to_staged(rfb, dev)   # compile
        assert _np.array_equal(_np.asarray(st.cols_dev),
                               _np.asarray(ref.cols_dev)), \
            "device decode != host staging"
        import jax as _jax

        def device_decode():
            nonlocal rfb, st
            rfb = block_codec.parse_raw_file(r.read_raw(), r.block_handles)
            st = block_codec.decode_file_to_staged(rfb, dev)
            _jax.block_until_ready(st.cols_dev)

        host_s, dev_s = best_of_pair(host_decode, device_decode)
        dec_host_s, dec_dev_s = host_s, dev_s
        out["block_decode_rows_per_sec"] = round(n / dev_s, 1)
        out["block_decode_host_rows_per_sec"] = round(n / host_s, 1)
        out["block_decode_vs_host"] = round(host_s / dev_s, 2)
        log(f"  block decode: device {n/dev_s/1e6:.2f}M rows/s vs host "
            f"{n/host_s/1e6:.2f}M rows/s = {host_s/dev_s:.1f}x")
        if native_engine.available():
            with open(r.data_path, "rb") as f:
                raw = f.read()
            def native_decode():
                with native_engine.NativeCompactionJob() as job:
                    job.add_input(raw, r.block_handles)
                    job.prepare()

            native_decode()   # warm the threads
            nat_s, _ = best_of_pair(native_decode, lambda: None, reps=3)
            out["block_decode_native_rows_per_sec"] = round(n / nat_s, 1)
            log(f"  block decode (native shell): {n/nat_s/1e6:.2f}M rows/s")

        # ---- encode: host / native / device --------------------------
        def host_encode(tag):
            p = os.path.join(root, f"host-{tag}.sst")
            SSTWriter(p, fit_lindex=False).write(slab, Frontier())
            return p

        def device_encode(tag):
            p = os.path.join(root, f"dev-{tag}.sst")
            blocks, index, hashes, fk, lk = block_codec.encode_span(
                st, n, rfb.w, rfb.values, r.block_handles[0][2]
                if r.block_handles else 4096, compress=False)
            dp = data_file_name(p)
            df = get_env().open_append(dp)
            try:
                size = 0
                for blk in blocks:
                    df.append(blk)
                    size += len(blk)
                df.flush(fsync=True)
            finally:
                df.close()
            write_base_file(p, index, n, hashes, fk, lk, Frontier(), size)
            return p

        hp = host_encode("warm")
        dp = device_encode("warm")
        with open(data_file_name(hp), "rb") as f1, \
                open(data_file_name(dp), "rb") as f2:
            assert f1.read() == f2.read(), "device encode != host encode"
        host_s, dev_s = best_of_pair(lambda: host_encode("t"),
                                     lambda: device_encode("t"))
        out["block_encode_rows_per_sec"] = round(n / dev_s, 1)
        out["block_encode_host_rows_per_sec"] = round(n / host_s, 1)
        out["block_encode_vs_host"] = round(host_s / dev_s, 2)
        log(f"  block encode: device {n/dev_s/1e6:.2f}M rows/s vs host "
            f"{n/host_s/1e6:.2f}M rows/s = {host_s/dev_s:.1f}x")
        # the codec as a whole (the stage-A + stage-C byte shell one
        # compaction pays): decode + encode round trip vs the host codec
        out["block_codec_rows_per_sec"] = round(
            n / (dec_dev_s + dev_s), 1)
        out["block_codec_host_rows_per_sec"] = round(
            n / (dec_host_s + host_s), 1)
        out["block_codec_vs_host"] = round(
            (dec_host_s + host_s) / (dec_dev_s + dev_s), 2)
        log(f"  block codec (decode+encode): device "
            f"{n/(dec_dev_s+dev_s)/1e6:.2f}M rows/s vs host "
            f"{n/(dec_host_s+host_s)/1e6:.2f}M rows/s = "
            f"{(dec_host_s+host_s)/(dec_dev_s+dev_s):.2f}x")
        if native_engine.available():
            tomb = b"X"

            def native_encode(tag):
                p = os.path.join(root, f"nat-{tag}.dat")
                with native_engine.NativeCompactionJob() as job:
                    job.add_input(raw, r.block_handles)
                    job.prepare()
                    surv = _np.arange(n, dtype=_np.int64)
                    job.set_survivors(surv, _np.zeros(n, dtype=_np.uint8))
                    job.write_output(0, n, p,
                                     r.block_handles[0][2]
                                     if r.block_handles else 4096,
                                     compress=False, tombstone_value=tomb)
                return p

            native_encode("warm")
            nat_s, _ = best_of_pair(lambda: native_encode("t"),
                                    lambda: None, reps=3)
            out["block_encode_native_rows_per_sec"] = round(n / nat_s, 1)
            log(f"  block encode (native shell, incl. threaded decode "
                f"ingest): {n/nat_s/1e6:.2f}M rows/s")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps(out), flush=True)


def run_analytics_child(platform: str, n_str: str) -> None:
    """Analytics rung (ROADMAP item 5): fused filtered/aggregating scans
    vs the per-row host path, over one tablet's resident slabs.

    The host baseline is the exact work the query layer does without
    pushdown — assemble every row, evaluate the predicate in Python,
    aggregate in Python. The fused numbers ride tablet.scan_pushdown /
    tablet.scan_aggregate (one device dispatch + winner-block decode /
    scalar download). Correctness gates run before any rate ships:
    fused results must equal the host results exactly."""
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    if platform == "tpu" and dev.platform == "cpu":
        sys.exit(3)
    import shutil
    import tempfile

    from yugabyte_tpu.common.hybrid_time import HybridTime
    from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema
    from yugabyte_tpu.docdb import scan_spec as SS
    from yugabyte_tpu.docdb.doc_key import DocKey
    from yugabyte_tpu.docdb.doc_operations import column_key_suffix
    from yugabyte_tpu.docdb.value import Value
    from yugabyte_tpu.ops.scan import pushdown_snapshot
    from yugabyte_tpu.storage.device_cache import DeviceSlabCache
    from yugabyte_tpu.storage.sst import BlockCache
    from yugabyte_tpu.tablet.tablet import Tablet, TabletOptions
    from yugabyte_tpu.utils import flags as _flags

    schema = Schema(columns=[ColumnSchema("k", DataType.INT64),
                             ColumnSchema("v", DataType.INT64),
                             ColumnSchema("b", DataType.BOOL)],
                    num_hash_key_columns=1)
    n = int(n_str)
    _flags.set_flag("scan_pushdown_min_rows", 0)
    rng = np.random.default_rng(23)
    root = tempfile.mkdtemp(prefix="ybtpu-bench-analytics-")
    out = {"analytics_device": str(dev), "analytics_rows": n}
    t = Tablet("t-analytics", root, schema,
               options=TabletOptions(
                   auto_compact=False, device=dev,
                   device_cache=DeviceSlabCache(device=dev),
                   block_cache=BlockCache(256 << 20)))
    try:
        vcid = schema.column_id("v")
        bcid = schema.column_id("b")
        vsuf = column_key_suffix(vcid)
        bsuf = column_key_suffix(bcid)
        lsuf = column_key_suffix(-1)
        vals = rng.integers(0, 10_000, size=n)
        bools = rng.random(n) < 0.5
        t0 = time.time()
        per_flush = n // 2
        for f in range(2):
            keys = []
            values = []
            for i in range(f * per_flush, (f + 1) * per_flush):
                dk_enc = DocKey(hash_components=(int(i),)).encode()
                keys.append(dk_enc + lsuf)
                values.append(Value(primitive=None).encode())
                keys.append(dk_enc + vsuf)
                values.append(Value(primitive=int(vals[i])).encode())
                keys.append(dk_enc + bsuf)
                values.append(Value(primitive=bool(bools[i])).encode())
            m = len(keys)
            ht = ((np.arange(m, dtype=np.uint64) // 3
                   + np.uint64(1000 + f * per_flush)) << np.uint64(12))
            wid = (np.arange(m, dtype=np.uint32) % 3)
            t.regular_db.write_batch_columns(keys, ht, wid, values,
                                             op_id=(1, f + 1))
            t.regular_db.flush()
        # compact to ONE sorted SST: the analytics steady state — a
        # single resident source rides the presorted kernel variant
        # (no merge sort, no permutation gather)
        t.regular_db.compact_all()
        log(f"  analytics load: {n} rows ({3 * n} entries) in "
            f"{time.time() - t0:.1f}s "
            f"({len(t.regular_db.versions.live_files())} SSTs)")

        threshold = 100   # ~1% selectivity — the analytics WHERE shape
        pred = SS.compile_predicate(schema, "v", "<", threshold)
        spec_f = SS.ScanSpec(predicates=(pred,))
        spec_a = SS.ScanSpec(
            predicates=(pred,),
            aggregates=(SS.compile_aggregate(schema, "count", None),
                        SS.compile_aggregate(schema, "sum", "v"),
                        SS.compile_aggregate(schema, "min", "v"),
                        SS.compile_aggregate(schema, "max", "v")))
        read_ht = t.clock.now()

        def host_filtered():
            got = []
            for row in t.scan(read_ht, use_device=False):
                d = row.to_dict(schema)
                hv = d.get("v")
                if hv is not None and hv < threshold:
                    got.append((d["k"], hv, d["b"]))
            return got

        def fused_filtered():
            it = t.scan_pushdown(read_ht, spec=spec_f)
            assert it is not None, "pushdown fell back"
            got = []
            for row in it:
                d = row.to_dict(schema)
                got.append((d["k"], d["v"], d["b"]))
            return got

        # warm (compile) + correctness gate, then measure
        want = host_filtered()
        assert sorted(fused_filtered()) == sorted(want), \
            "fused filtered != host"
        t0 = time.time()
        got = fused_filtered()
        fused_s = time.time() - t0
        t0 = time.time()
        host_filtered()
        host_s = time.time() - t0
        out["filtered_scan_rows_per_sec"] = round(n / fused_s, 1)
        out["filtered_scan_host_rows_per_sec"] = round(n / host_s, 1)
        out["filtered_scan_vs_host"] = round(host_s / fused_s, 1)
        out["filtered_scan_survivors"] = len(got)
        log(f"  filtered scan (v < {threshold}, {len(got)} survivors): "
            f"fused {n/fused_s/1e3:.0f}K rows/s vs host "
            f"{n/host_s/1e3:.0f}K rows/s = {host_s/fused_s:.1f}x")

        def host_agg():
            cnt = 0
            sv = 0
            mn = None
            mx = None
            for row in t.scan(read_ht, use_device=False):
                d = row.to_dict(schema)
                hv = d.get("v")
                if hv is None or hv >= threshold:
                    continue
                cnt += 1
                sv += hv
                mn = hv if mn is None else min(mn, hv)
                mx = hv if mx is None else max(mx, hv)
            return cnt, sv, mn, mx

        def fused_agg():
            p = t.scan_aggregate(read_ht, spec=spec_a)
            assert p is not None, "aggregate pushdown fell back"
            st = p["cols"][vcid]
            return p["rows"], st["sum"], st["min"], st["max"]

        want = host_agg()
        assert fused_agg() == want, "fused aggregate != host"
        t0 = time.time()
        fused_agg()
        fused_s = time.time() - t0
        t0 = time.time()
        host_agg()
        host_s = time.time() - t0
        out["agg_scan_rows_per_sec"] = round(n / fused_s, 1)
        out["agg_scan_host_rows_per_sec"] = round(n / host_s, 1)
        out["agg_scan_vs_host"] = round(host_s / fused_s, 1)
        log(f"  aggregate scan (count/sum/min/max WHERE): fused "
            f"{n/fused_s/1e3:.0f}K rows/s vs host {n/host_s/1e3:.0f}K "
            f"rows/s = {host_s/fused_s:.1f}x")
        snap = pushdown_snapshot()
        out["analytics_pushdown_fallbacks"] = snap["fallbacks"]
        out["analytics_blocks_decoded_p50"] = \
            snap["blocks_decoded_per_scan"]["p50"]
    finally:
        t.close()
        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps(out), flush=True)


class StageLog:
    """Per-stage checkpoint file: the parent assembles a partial result if
    the child dies late (VERDICT r3: a 480s all-or-nothing budget threw away
    every completed stage when the final one blew it)."""

    def __init__(self, path):
        self.path = path

    def put(self, **kv):
        if not self.path:
            return
        with open(self.path, "a") as f:
            f.write(json.dumps(kv) + "\n")
            f.flush()


def run_device_child(platform: str, workload_path: str,
                     stages_path: str = None) -> None:
    """Child-process body: all JAX backend work happens here.

    Round-4 shape: the flagship kernel is the pallas merge-path tournament
    (ops/pallas_merge.py; jnp network fallback elsewhere) with packed
    ~0.5-byte/row decision downloads. Measured stages:
      cold            pack + upload + kernel + decisions + host perm
      device-resident staged inputs (HBM slab cache steady state)
      pipelined       overlapping launches (sustained compaction stream)
      e2e steady      disk->disk full job: device decisions + native C++
                      byte shell, inputs pre-staged (write-through cache)
    """
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    stages = StageLog(stages_path)

    slab, offsets, n_total, cutoff, cpu_rate, cpu_kept = \
        _load_workload(workload_path)
    runs = _split_runs(slab, offsets)

    from yugabyte_tpu.ops.merge_gc import GCParams, stage_slab
    from yugabyte_tpu.ops import run_merge
    t0 = time.time()
    dev = jax.devices()[0]
    log(f"  device: {dev} (backend init {time.time()-t0:.1f}s)")
    if platform == "tpu" and dev.platform == "cpu":
        # a fast-failing TPU plugin can silently fall back to CPU; refuse
        # so the parent's fallback path labels the number honestly
        log("  requested TPU but got a CPU device — failing child")
        sys.exit(3)
    platform = dev.platform
    stages.put(stage="init", platform=platform, device=str(dev))
    params = GCParams(cutoff, True)

    # ---- cold: pack + upload + kernel + decision download ----------------
    t0 = time.time()
    perm, keep, mk = run_merge.merge_and_gc_runs(runs, params, device=dev)
    compile_s = time.time() - t0
    log(f"  first call (compile+run): {compile_s:.1f}s")
    assert int(keep.sum()) == cpu_kept, (
        f"survivor mismatch: device {int(keep.sum())} cpu {cpu_kept}")
    t0 = time.time()
    perm, keep, _ = run_merge.merge_and_gc_runs(runs, params, device=dev)
    cold_s = time.time() - t0
    log(f"  cold end-to-end: {cold_s:.2f}s = {n_total/cold_s/1e6:.2f}M "
        f"rows/s (kept {int(keep.sum())})")
    stages.put(stage="cold", cold_s=cold_s, compile_s=compile_s)

    # ---- device-resident: HBM slab cache steady state --------------------
    # A production server compacts CONTINUOUSLY: decisions for job i
    # download while job i+1 computes. The sustained per-job cost is the
    # slope of a pipelined stream (k=8 minus k=2 over 6 jobs), which
    # removes the fixed per-call round-trip that a single timed call
    # would charge to the device.
    staged_list = [stage_slab(r, dev) for r in runs]
    staged = run_merge.stage_runs_from_staged(staged_list)
    jax.block_until_ready(staged.cols_dev)

    def run_stream(k: int) -> float:
        t0 = time.time()
        hs = [run_merge.launch_merge_gc(staged, params)]
        for i in range(1, k):
            hs.append(run_merge.launch_merge_gc(staged, params))
            hs[i - 1].result()
        hs[-1].result()
        return time.time() - t0

    run_stream(2)                      # warm
    t0 = time.time()
    run_merge.launch_merge_gc(staged, params).result()
    single_s = time.time() - t0        # one launch+fetch incl. link RTT
    t2 = run_stream(2)
    t8 = run_stream(8)
    if t8 > t2:
        sustained_s = (t8 - t2) / 6
    else:
        # jitter/recompile made the slope meaningless — fall back to the
        # conservative mean rather than emitting an absurd rate
        log(f"  WARNING: stream slope invalid (t2={t2:.3f}s t8={t8:.3f}s); "
            f"using mean")
        sustained_s = t8 / 8
    res_s = sustained_s
    log(f"  device-resident sustained: {sustained_s:.3f}s/job = "
        f"{n_total/sustained_s/1e6:.2f}M rows/s "
        f"(single call incl. link latency: {single_s:.3f}s)")
    pipe_s = t8 / 8
    log(f"  pipelined: {pipe_s:.3f}s/job = {n_total/pipe_s/1e6:.2f}M rows/s")
    # host<->device link round-trip: a 4-byte transfer is pure latency.
    # Reported so the e2e number is interpretable: every decision download
    # pays this per round-trip.
    rtts = []
    for i in range(3):
        # a FRESH device array per probe: jax caches the host copy on
        # the array object, so re-reading one array is a cache hit
        tiny = jax.device_put(np.full(1, i, dtype=np.uint8), dev)
        jax.block_until_ready(tiny)
        t0 = time.time()
        np.asarray(tiny)
        rtts.append(time.time() - t0)
    link_rtt_s = sorted(rtts)[1]
    log(f"  link round-trip (4B D2H): {link_rtt_s*1e3:.0f}ms")
    stages.put(stage="device_resident", sustained_s=res_s, single_s=single_s,
               pipelined_s=pipe_s, link_rtt_s=link_rtt_s)

    # ---- e2e disk->disk: device decisions + native C++ byte shell --------
    # Runs BEFORE the snapshot-scan stage: this is the flagship number, and
    # its chunked merge reuses the executable the stages above compiled,
    # while the scan kernel needs its own multi-minute Mosaic compile — a
    # tight budget must kill scan, not e2e (r5: a 480s child died compiling
    # the 4M scan with the e2e stage still queued behind it).
    import tempfile
    from yugabyte_tpu.storage import compaction as compaction_mod
    from yugabyte_tpu.storage import native_engine
    from yugabyte_tpu.storage.device_cache import DeviceSlabCache
    from yugabyte_tpu.storage.sst import SSTReader

    e2e_n = int(os.environ.get("YBTPU_BENCH_E2E_N", min(n_total, 1 << 22)))
    e2e_slab, e2e_offsets = synth_ycsb_runs(e2e_n, 4, max(1, e2e_n // 2))
    _attach_values(e2e_slab, 64)
    workdir = tempfile.mkdtemp(prefix="ybtpu-bench-")
    e2e_steady = e2e_steady2 = e2e_cold = 0.0
    resident_chain = 0.0
    cache_hit_ratio = 0.0
    e2e_rows = -1
    stage_ms = {}
    bucket_hits = bucket_misses = 0
    try:
        paths = _write_input_ssts(e2e_slab, e2e_offsets, workdir)
        readers = [SSTReader(p) for p in paths]
        ids = iter(range(1, 1 << 20))
        if native_engine.available():
            cache = DeviceSlabCache(device=dev)
            # id space disjoint from output file ids (the write-through
            # REPLACES cache entries — an output landing on an input's id
            # would silently corrupt the next run's decisions; production
            # ids are VersionSet-unique per namespaced DB)
            input_ids = [10**9 + i for i in range(len(readers))]
            # steady state: inputs staged by flush write-through
            for fid, r in zip(input_ids, readers):
                cache.stage(fid, r.read_all())
            # ... and retained in the host packed-run cache, exactly as
            # flush write-through does (write_sst_from_packed): the
            # steady-state shell never re-reads or re-decodes inputs
            from yugabyte_tpu.storage.run_cache import (NamespacedRunCache,
                                                        NativeRunCache,
                                                        export_reader)
            rc = NamespacedRunCache(NativeRunCache(capacity_bytes=8 << 30),
                                    "bench")
            for fid, r in zip(input_ids, readers):
                export_reader(rc, fid, r)

            def run_dn(out_name, use_cache, job_readers=None,
                       job_ids=None, n_rows=None):
                out = os.path.join(workdir, out_name)
                os.makedirs(out, exist_ok=True)
                t0 = time.time()
                res = compaction_mod.run_compaction_job_device_native(
                    job_readers or readers, out, lambda: next(ids),
                    cutoff, True, device=dev,
                    device_cache=cache if use_cache else None,
                    input_ids=(job_ids or input_ids) if use_cache
                    else None,
                    run_cache=rc if use_cache else None)
                return (n_rows or e2e_n) / (time.time() - t0), res

            run_dn("warm", True)  # compile/warm
            from yugabyte_tpu.utils.metrics import (kernel_metrics,
                                                    pipeline_stage_totals)
            stage_before = pipeline_stage_totals()
            e2e_steady, _res_steady = run_dn("steady", True)
            e2e_rows = _res_steady.rows_out
            log(f"  e2e steady ({platform}+native shell): "
                f"{e2e_steady/1e6:.2f}M rows/s ({e2e_rows} rows out)")
            # 2-worker compaction stream: job i+1's device merge overlaps
            # job i's decision download + native write — the production
            # shape (the server's compaction pool runs concurrent jobs;
            # the device path leaves the CPU free, which is the thesis).
            import threading as _th
            sem = _th.Semaphore(2)
            errs = []

            def _wk(i):
                try:
                    run_dn(f"p{i}", True)
                except Exception as e:  # noqa: BLE001 — fail the stage
                    errs.append(e)
                finally:
                    sem.release()

            jobs2 = 4
            t0 = time.time()
            ths = []
            for i in range(jobs2):
                sem.acquire()
                t = _th.Thread(target=_wk, args=(i,))
                t.start()
                ths.append(t)
            for t in ths:
                t.join()
            if errs:
                raise errs[0]
            e2e_steady2 = e2e_n * jobs2 / (time.time() - t0)
            log(f"  e2e steady x2 workers: {e2e_steady2/1e6:.2f}M rows/s")
            # where the pipelined jobs' wall time went (stage A host
            # decode/pack, stage B device compute+transfer, stage C
            # native SST write) + shape-bucket executable reuse
            stage_after = pipeline_stage_totals()
            stage_ms = {s: round(stage_after[s] - stage_before[s], 1)
                        for s in stage_after}
            ke = kernel_metrics()
            bucket_hits = ke.counter(
                "kernel_compile_bucket_hits_total", "").value()
            bucket_misses = ke.counter(
                "kernel_compile_bucket_misses_total", "").value()
            # declared compile surface (committed kernel manifest) next
            # to the hit/miss counters: a warm run's misses must stay
            # within the manifest's executable count, proving the cache
            # covers exactly the reviewed surface
            from yugabyte_tpu.storage.offload_policy import (
                declared_surface_counts)
            from yugabyte_tpu.utils.metrics import publish_compile_surface
            surface_counts = declared_surface_counts()
            publish_compile_surface(surface_counts)
            surface_total = sum(surface_counts.values())
            # shadow verification rode the steady jobs at the DEFAULT
            # sampling rate (acceptance: <=5% steady regression): report
            # its cost + coverage next to the stage timings
            from yugabyte_tpu.storage.integrity import shadow_snapshot
            shadow = shadow_snapshot()
            bh_counters, bh_states = _bucket_health_for_report()
            log(f"  pipeline stages over steady jobs: "
                f"host {stage_ms.get('host', 0):.0f}ms / device "
                f"{stage_ms.get('device', 0):.0f}ms / write "
                f"{stage_ms.get('write', 0):.0f}ms / shadow "
                f"{stage_ms.get('shadow', 0):.0f}ms; compile buckets "
                f"{bucket_hits} hits / {bucket_misses} misses "
                f"(manifest surface: {surface_total} executables); "
                f"shadow verify sample={shadow['sample']} "
                f"jobs={shadow['jobs_verified']} "
                f"mismatches={shadow['mismatches']}; bucket health "
                f"states={bh_states or 'none'} "
                f"demotions={bh_counters.get('bucket_health_demotions', 0)} "
                f"promotions="
                f"{bh_counters.get('bucket_health_promotions', 0)}")
            stages.put(stage="e2e_steady", e2e_steady=e2e_steady,
                       e2e_steady2=e2e_steady2,
                       e2e_rows=e2e_rows, e2e_n=e2e_n,
                       stage_host_ms=stage_ms.get("host", 0.0),
                       stage_device_ms=stage_ms.get("device", 0.0),
                       stage_write_ms=stage_ms.get("write", 0.0),
                       stage_shadow_ms=stage_ms.get("shadow", 0.0),
                       stage_decode_ms=stage_ms.get("decode", 0.0),
                       stage_encode_ms=stage_ms.get("encode", 0.0),
                       compile_bucket_hits=bucket_hits,
                       compile_bucket_misses=bucket_misses,
                       compile_surface_buckets=surface_total,
                       shadow_verify_sample=shadow["sample"],
                       shadow_verify_jobs=shadow["jobs_verified"],
                       shadow_verify_mismatches=shadow["mismatches"],
                       bucket_health_states=bh_states,
                       **bh_counters)
            # chained L0->L1->L2: two L0->L1 jobs' outputs stay resident
            # (per-span write-through) and feed an L1->L2 job whose
            # inputs never leave HBM — the ROADMAP item-1 configuration
            _, res_c1 = run_dn("c1", True)
            _, res_c2 = run_dn("c2", True)
            chain_outs = res_c1.outputs + res_c2.outputs
            chain_readers = [SSTReader(p) for _f, p, _pr in chain_outs]
            chain_ids = [fid for fid, _p, _pr in chain_outs]
            chain_rows = sum(pr.n_entries for _f, _p, pr in chain_outs)
            resident_chain, _res_l2 = run_dn(
                "l2chain", True, job_readers=chain_readers,
                job_ids=chain_ids, n_rows=chain_rows)
            for r in chain_readers:
                r.close()
            cache_hit_ratio = cache.hits / max(1, cache.hits
                                               + cache.misses)
            log(f"  resident chain (L1->L2 from HBM, {chain_rows} rows): "
                f"{resident_chain/1e6:.2f}M rows/s; device-cache hit "
                f"ratio {cache_hit_ratio:.3f} "
                f"({cache.hits}h/{cache.misses}m)")
            stages.put(stage="resident_chain",
                       resident_chain=resident_chain,
                       chain_rows=chain_rows,
                       cache_hit_ratio=cache_hit_ratio)
            e2e_cold, _ = run_dn("cold", False)
            log(f"  e2e cold ({platform}+native shell): "
                f"{e2e_cold/1e6:.2f}M rows/s")
            stages.put(stage="e2e_cold", e2e_cold=e2e_cold)
            # correctness cross-check: the device+native path must keep
            # exactly what the pure-native reference job keeps
            nat_out = os.path.join(workdir, "natcheck")
            os.makedirs(nat_out, exist_ok=True)
            nat_res = compaction_mod.run_compaction_job(
                readers, nat_out, lambda: next(ids), cutoff, True,
                device="native")
            assert nat_res.rows_out == e2e_rows, (
                f"e2e survivor mismatch: device+native {e2e_rows} "
                f"vs native {nat_res.rows_out}")
        for r in readers:
            r.close()
    finally:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)

    from yugabyte_tpu.ops.scan import scan_visible
    from yugabyte_tpu.storage.device_cache import concat_staged
    # one staged run, not the 4M concat: same kernel, bounded compile
    # (the full-shape Mosaic compile costs minutes)
    scan_staged = concat_staged(staged_list[:1])
    scan_n = scan_staged.n
    scan_visible(scan_staged, cutoff)  # compile
    t0 = time.time()
    _, keep_scan = scan_visible(scan_staged, cutoff)
    scan_s = time.time() - t0
    log(f"  snapshot scan: {scan_s:.2f}s = {scan_n/scan_s/1e6:.2f}M rows/s "
        f"over {scan_n} rows ({int(keep_scan.sum())} visible)")
    stages.put(stage="scan", scan_s=scan_s, scan_n=scan_n)

    headline = max(e2e_steady2, e2e_steady) or n_total / res_s
    bh_counters, bh_states = _bucket_health_for_report()
    print(json.dumps({
        "metric": "l0_compaction_merge_gc_rows_per_sec",
        "value": round(headline, 1),
        "unit": "rows/s",
        # the parent overwrites vs_baseline + vs_baseline_basis with the
        # like-for-like disk-to-disk comparison (value / e2e_native) when
        # the native shell is available; until then the basis label below
        # keeps this number honestly described
        "vs_baseline": round(headline / cpu_rate, 3),
        "vs_baseline_basis": "single-core IN-MEMORY C++ merge+GC "
                             "(native e2e unavailable in child)",
        "platform": platform,
        "device": str(dev),
        "note": "value = steady-state disk-to-disk compaction stream (device "
                "decisions from HBM slab cache + native C++ byte shell; "
                "e2e_steady2 = 2 concurrent jobs, the compaction-pool "
                "shape - device merge overlaps decision download + "
                "native write); "
                "vs_baseline basis is vs_baseline_basis; "
                "kernel_vs_cpu_core = sustained device merge+GC / "
                "single-core IN-MEMORY C++ merge+GC",
        "cpu_cxx_baseline_rows_per_sec": round(cpu_rate, 1),
        "kernel_vs_cpu_core": round((n_total / res_s) / cpu_rate, 3),
        "cold_rows_per_sec": round(n_total / cold_s, 1),
        "device_resident_rows_per_sec": round(n_total / res_s, 1),
        "device_single_call_rows_per_sec": round(n_total / single_s, 1),
        "pipelined_rows_per_sec": round(n_total / pipe_s, 1),
        "link_roundtrip_ms": round(link_rtt_s * 1e3, 1),
        "scan_rows_per_sec": round(scan_n / scan_s, 1),
        "e2e_steady_rows_per_sec": round(e2e_steady, 1),
        "e2e_steady2_rows_per_sec": round(e2e_steady2, 1),
        # chained L0->L1->L2: an L1->L2 job whose inputs are the prior
        # jobs' write-through-resident outputs (zero re-decode), next to
        # the overall HBM slab-cache hit ratio of the steady stream
        "resident_chain_rows_per_sec": round(resident_chain, 1),
        "device_cache_hit_ratio": round(cache_hit_ratio, 4),
        "e2e_cold_rows_per_sec": round(e2e_cold, 1),
        "e2e_native_rows_per_sec": 0.0,   # parent overwrites (JAX-free)
        "compile_s": round(compile_s, 1),
        # per-stage pipeline wall time over the steady e2e jobs (stage A
        # host decode/pack, stage B device compute + transfer waits,
        # stage C native SST write) — the /compactionz stall view,
        # snapshotted into the artifact
        "stage_host_ms": stage_ms.get("host", 0.0),
        "stage_device_ms": stage_ms.get("device", 0.0),
        "stage_write_ms": stage_ms.get("write", 0.0),
        # shadow verification cost + coverage over the steady jobs at
        # the DEFAULT --shadow_verify_sample (acceptance: <=5% steady
        # regression with sampling on)
        "stage_shadow_ms": stage_ms.get("shadow", 0.0),
        # device block-codec stages (ops/block_codec.py): raw-word upload
        # + decode dispatch (stage A) and span encode + download (stage C)
        "stage_decode_ms": stage_ms.get("decode", 0.0),
        "stage_encode_ms": stage_ms.get("encode", 0.0),
        "shadow_verify_sample": _shadow_sample_for_report(),
        "shadow_verify_jobs": _shadow_jobs_for_report(),
        "shadow_verify_mismatches": _shadow_mismatches_for_report(),
        "compile_bucket_hits": bucket_hits,
        "compile_bucket_misses": bucket_misses,
        # per-family declared compile-surface counts (committed kernel
        # manifest; also exported as kernel_compile_surface gauges)
        "compile_surface_buckets": _surface_counts_for_report(),
        # live routing-authority telemetry (storage/bucket_health.py):
        # lifetime transition counters + the end-of-run state histogram
        # — a mid-bench demotion explains a device-rate dip honestly
        **bh_counters,
        "bucket_health_states": bh_states,
        "e2e_n_rows": e2e_n,
        "n_rows": n_total,
    }), flush=True)


def run_pool_child(platform: str, mesh_n_str: str) -> None:
    """One rung of the compaction-pool ladder: aggregate multi-tablet
    merge+GC decision throughput at one mesh size (ROADMAP item 3 — the
    headline is aggregate rows/s across N concurrent tablet jobs, not
    single-job latency).

    Mesh size 1 measures the INLINE single-device dispatch
    (ops/run_merge.merge_and_gc_runs per job) because that is what the
    system actually runs there — the server only builds a CompactionPool
    over a >1-device mesh. Mesh sizes >= 2 measure the pool's batch-slot
    waves (parallel/dist_compact.pooled_merge_gc) over the same jobs.
    Inputs are pre-staged (the steady-state regime: flush/compaction
    write-through keeps them resident); SST I/O is excluded here and
    covered by the identity phase, which runs FULL pooled jobs through
    tserver/compaction_pool.CompactionPool and proves the outputs
    byte-identical to sequential runs with zero leaked pins/leases."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    mesh_n = int(mesh_n_str)
    assert len(jax.devices()) >= mesh_n, (len(jax.devices()), mesh_n)

    from yugabyte_tpu.ops import run_merge
    from yugabyte_tpu.ops.merge_gc import GCParams
    from yugabyte_tpu.parallel import dist_compact as dist_mod
    from yugabyte_tpu.parallel.mesh import make_mesh

    cutoff = 10_000_000 << 12
    params = GCParams(cutoff, True)
    out = {"pool_mesh_devices": mesh_n,
           "pool_platform": jax.devices()[0].platform}

    def _jobs(n_jobs, rows, k):
        jobs = []
        for j in range(n_jobs):
            slab, offsets = synth_ycsb_runs(rows, k, max(2, rows // 2),
                                            seed=j)
            jobs.append(_split_runs(slab, offsets))
        return jobs

    def _measure(jobs, mesh):
        staged = []
        for runs in jobs:
            b = dist_mod.pool_slot_bucket(runs)
            staged.append(dist_mod.stage_pool_slot(runs, *b))
        if mesh is None:
            run_merge.merge_and_gc_runs(jobs[0], params)   # warm/compile
            t0 = time.time()
            done = 0
            for runs, st in zip(jobs, staged):
                run_merge.merge_and_gc_runs(runs, params)
                done += st.n
            return done / max(time.time() - t0, 1e-9), 0
        dist_mod.pooled_merge_gc(mesh, [(staged[0], params)])  # warm
        t0 = time.time()
        done = waves = 0
        i = 0
        n_slots = mesh.devices.size
        while i < len(staged):
            wave = [(s, params) for s in staged[i:i + n_slots]]
            dist_mod.pooled_merge_gc(mesh, wave)
            done += sum(s.n for s, _p in wave)
            waves += 1
            i += n_slots
        return done / max(time.time() - t0, 1e-9), waves

    # headline series: small multi-tablet jobs (the overhead-dominated
    # regime where pooling matters most on a 1-core CPU mesh; a TPU
    # round adds real per-slot device parallelism on top)
    small = _jobs(96, 256, 2)
    mesh = make_mesh(mesh_n) if mesh_n > 1 else None
    rate, waves = _measure(small, mesh)
    out["pool_rows_per_sec"] = round(rate, 1)
    out["pool_jobs"] = len(small)
    out["pool_job_rows"] = 256
    out["pool_waves"] = waves
    # context series: mid-size jobs (compute-dominated on CPU — shows
    # the amortization win shrinking as compute takes over)
    mid = _jobs(32, 4096, 4)
    rate_mid, _w = _measure(mid, mesh)
    out["pool_mid_rows_per_sec"] = round(rate_mid, 1)
    out["pool_mid_job_rows"] = 4096

    if mesh_n == len(jax.devices()):
        out.update(_pool_identity_phase(cutoff))
    # routing-authority events over this rung: a wave-fault demotion or
    # a probe re-promotion mid-ladder changes what the rows/s above
    # actually measured (devices vs the native completion path)
    bh_counters, bh_states = _bucket_health_for_report()
    out["pool_bucket_demotions"] = \
        bh_counters.get("bucket_health_demotions", 0)
    out["pool_bucket_repromotions"] = \
        bh_counters.get("bucket_health_promotions", 0)
    out["pool_bucket_quarantines"] = \
        bh_counters.get("bucket_health_quarantines", 0)
    out["pool_bucket_states"] = bh_states
    print(json.dumps(out), flush=True)


def _pool_identity_phase(cutoff: int) -> dict:
    """Full pooled compaction jobs through the REAL scheduler vs
    sequential single-device runs: byte-identical outputs, zero leaked
    pins, zero leaked staging leases."""
    import shutil
    import tempfile as _tf

    import jax
    from yugabyte_tpu.parallel.mesh import make_mesh
    from yugabyte_tpu.storage.compaction import run_compaction_job
    from yugabyte_tpu.storage.device_cache import (DeviceSlabCache,
                                                   host_staging_pool)
    from yugabyte_tpu.storage.sst import (Frontier, SSTReader, SSTWriter,
                                          data_file_name)
    from yugabyte_tpu.tserver.compaction_pool import (CompactionPool,
                                                      PoolRequest)

    root = _tf.mkdtemp(prefix="ybtpu-bench-pool-")
    pool = CompactionPool(make_mesh(8))
    shared = DeviceSlabCache(jax.devices()[0], capacity_bytes=1 << 30)
    identical = True
    try:
        tablets = {}
        for t in range(4):
            n = 20000
            slab, offsets = synth_ycsb_runs(n, 4, n // 2, seed=50 + t)
            _attach_values(slab, 16)
            runs = _split_runs(slab, offsets)
            d = os.path.join(root, f"in{t}")
            os.makedirs(d)
            paths = []
            for i, sub in enumerate(runs):
                p = os.path.join(d, f"{i:06d}.sst")
                SSTWriter(p).write(sub, Frontier())
                paths.append(p)
            tablets[f"t{t}"] = paths
        handles = {}
        for tid, paths in tablets.items():
            readers = [SSTReader(p) for p in paths]
            cache = pool.partition_for(shared, f"db-{tid}", tid)
            for fid, r in enumerate(readers):
                cache.stage(fid, r.read_all())
            outd = os.path.join(root, f"pool_out_{tid}")
            os.makedirs(outd)
            ids = iter(range(100, 10_000))
            handles[tid] = (pool.submit(tid, PoolRequest(
                inputs=readers, out_dir=outd,
                new_file_id=lambda it=ids: next(it),
                history_cutoff_ht=cutoff, is_major=True,
                input_ids=list(range(len(readers))),
                device_cache=cache)), readers)
        results = {}
        for tid, (h, readers) in handles.items():
            results[tid] = h.result(timeout=300)
            for r in readers:
                r.close()
        for tid, paths in tablets.items():
            readers = [SSTReader(p) for p in paths]
            outd = os.path.join(root, f"seq_out_{tid}")
            os.makedirs(outd)
            ids = iter(range(100, 10_000))
            res = run_compaction_job(readers, outd,
                                     lambda it=ids: next(it), cutoff,
                                     True, device=jax.devices()[0])
            for r in readers:
                r.close()
            for (f1, p1, _a), (f2, p2, _b) in zip(res.outputs,
                                                  results[tid].outputs):
                for fn in (lambda p: p, data_file_name):
                    with open(fn(p1), "rb") as fa, open(fn(p2), "rb") as fb:
                        if fa.read() != fb.read():
                            identical = False
        return {
            "pool_identical_to_sequential": identical,
            "pool_leaked_pins": shared.pinned_count(),
            "pool_leaked_leases": host_staging_pool().outstanding(),
        }
    finally:
        pool.shutdown()
        shutil.rmtree(root, ignore_errors=True)


def run_pool_parent() -> None:
    """`bench.py --compaction_pool`: the MULTICHIP pool ladder — one
    child per mesh size {1, 2, 4, 8} (fresh process each, so the virtual
    CPU mesh and the jit caches are per-rung), recorded with the scaling
    ratio and every knob."""
    budget = float(os.environ.get("YBTPU_BENCH_POOL_TIMEOUT", 600))
    mesh_sizes = [1, 2, 4, 8]
    per_mesh = {}
    for n in mesh_sizes:
        child = _spawn_child("cpu", budget, str(n), mode="--pool_child")
        if child is None:
            log(f"pool child mesh={n} failed")
            continue
        per_mesh[str(n)] = child
        log(f"pool mesh={n}: {child.get('pool_rows_per_sec'):,} rows/s "
            f"aggregate")
    result = {"rung": "compaction_pool", "mesh": per_mesh}
    r1 = (per_mesh.get("1") or {}).get("pool_rows_per_sec")
    r8 = (per_mesh.get("8") or {}).get("pool_rows_per_sec")
    for k in mesh_sizes:
        v = (per_mesh.get(str(k)) or {}).get("pool_rows_per_sec")
        if v is not None:
            result[f"pool_rows_per_sec_m{k}"] = v
    if r1 and r8:
        result["pool_scaling_8_over_1"] = round(r8 / r1, 2)
    ident = per_mesh.get("8") or {}
    for k in ("pool_identical_to_sequential", "pool_leaked_pins",
              "pool_leaked_leases", "pool_bucket_demotions",
              "pool_bucket_repromotions", "pool_bucket_quarantines",
              "pool_bucket_states"):
        if k in ident:
            result[k] = ident[k]
    result["platform"] = "cpu"
    result["meta"] = _round_meta("cpu", round_label="compaction_pool")
    result["knobs"] = {
        "devices": "virtual 8-device CPU mesh "
                   "(xla_force_host_platform_device_count; "
                   "CPU-labeled, single core)",
        "basis": "aggregate merge+GC decision-service rows/s across "
                 "concurrent tablet jobs, inputs pre-staged (steady-"
                 "state write-through regime); SST I/O measured "
                 "separately by the identity phase",
        "mesh_1_basis": "inline single-device dispatch per job — the "
                        "server builds no pool over a 1-device mesh",
        "pool_job_rows": 256,
        "mechanism_note": "on one CPU core the scaling comes from wave "
                          "batching amortizing per-job dispatch/"
                          "transfer/host overhead (compute serializes); "
                          "a real TPU mesh adds per-slot device "
                          "parallelism on top — TPU re-measure pending",
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "MULTICHIP_r06.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    log(f"wrote {path}")
    print(json.dumps(result), flush=True)


def _spawn_child(platform: str, timeout_s: float, *args, mode="--child"):
    """Run `bench.py <mode> <platform> [args...]` under a hard watchdog.

    Returns the parsed JSON result dict, or None on failure/timeout. The
    child gets its own process group so a hung backend thread can't
    outlive the kill."""
    cmd = [sys.executable, os.path.abspath(__file__), mode, platform,
           *args]
    log(f"spawning {platform} child (timeout {timeout_s:.0f}s): {' '.join(cmd)}")
    t0 = time.time()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=sys.stderr,
                            start_new_session=True, text=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        log(f"{platform} child TIMED OUT after {time.time()-t0:.0f}s — killing "
            f"process group")
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
        return None
    if proc.returncode != 0:
        log(f"{platform} child exited rc={proc.returncode} "
            f"after {time.time()-t0:.0f}s")
        return None
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    log(f"{platform} child produced no JSON result")
    return None


_BASIS = ("stock-architecture CompactionJob reimplementation "
          "(native/compaction_engine.cc: heap merge + per-entry filter + "
          "block encode), full disk-to-disk job over the same files on the "
          "same machine")


def _native_e2e_rate(n_rows: int, cutoff: int, n_runs: int = 3):
    """Full-native disk->disk e2e (the CPU production path; JAX-free).

    The baseline is PINNED (VERDICT r4 weak #3: the denominator moved
    1.45M -> 0.89M between rounds and polluted the trend): fixed seed and
    shapes, one warm-up, then n_runs measured runs — the MEDIAN is the
    baseline and the individual runs ship in the artifact so spread is
    auditable. Returns (median_rate, [run rates])."""
    import statistics
    import shutil
    import tempfile as _tf
    e2e_slab, e2e_offsets = synth_ycsb_runs(n_rows, 4, max(1, n_rows // 2))
    _attach_values(e2e_slab, 64)
    nat_dir = _tf.mkdtemp(prefix="ybtpu-bench-nat-")
    try:
        paths = _write_input_ssts(e2e_slab, e2e_offsets, nat_dir)
        _e2e_compaction(paths, n_rows, cutoff, "native",
                        os.path.join(nat_dir, "w"))  # warm (build .so)
        rates = []
        for i in range(n_runs):
            rate, _rows = _e2e_compaction(
                paths, n_rows, cutoff, "native",
                os.path.join(nat_dir, f"out{i}"))
            rates.append(round(rate, 1))
        median = statistics.median(rates)
        spread = (max(rates) - min(rates)) / median if median else 0.0
        log(f"  e2e (native C++ full job, {n_rows} rows): "
            f"median {median/1e6:.2f}M rows/s, runs "
            f"{[round(r/1e6, 2) for r in rates]} (spread {spread:.1%})")
        return median, rates
    finally:
        shutil.rmtree(nat_dir, ignore_errors=True)


def _scan_point_stages(n_rows: int, tpu_ok: bool = False) -> dict:
    """BASELINE configs 3-4 (VERDICT r3 #7 / r4 next #2+#5): full-tablet
    seq-scan MB/s, bloom-gated point reads, and the write/ingest path —
    all through the PRODUCTION serving paths (native read engine + native
    flush encoder, native/read_engine.cc + compaction_engine.cc), with the
    pure-Python paths measured alongside as the baseline columns the
    artifact ships.

    ref: rocksdb/table/block_based_table_reader.cc:1144-1286 (seek +
    bloom gate), table/merger.cc:51, db/db_impl.cc Get."""
    import shutil
    import tempfile

    from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime
    from yugabyte_tpu.storage.db import DB, DBOptions
    from yugabyte_tpu.storage.sst import BlockCache
    from yugabyte_tpu.utils import flags as _flags

    n = min(n_rows, 1 << 20)
    rng = np.random.default_rng(11)
    workdir = tempfile.mkdtemp(prefix="ybtpu-bench-scan-")
    out: dict = {}
    try:
        # block cache as on a real server (tserver/server_context.py)
        db = DB(os.path.join(workdir, "db"),
                DBOptions(device="native", auto_compact=False,
                          block_cache=BlockCache(256 << 20)))
        value = b"v" * 64
        t0 = time.time()
        per_flush = n // 4
        for f in range(4):
            base = f * per_flush
            # columnar bulk write: the batched-RPC apply / bulk-load shape
            # (native memtable arena, native/memtable_arena.cc — ref
            # db/memtable.cc Add)
            keys = [b"Suser%08d\x00\x00!" % (base + i)
                    for i in range(per_flush)]
            ht = ((np.arange(per_flush, dtype=np.uint64)
                   + np.uint64(1000 + base)) << np.uint64(12))
            wid = np.zeros(per_flush, dtype=np.uint32)
            db.write_batch_columns(keys, ht, wid, [value] * per_flush,
                                   op_id=(1, f + 1))
            db.flush()
        load_s = time.time() - t0
        out["load_rows_per_sec"] = round(n / load_s, 1)
        log(f"  scan-stage load (columnar write_batch + native flush): "
            f"{n} rows in {load_s:.1f}s = {n/load_s/1e3:.0f}K rows/s "
            f"({len(db.versions.live_files())} SSTs)")
        # secondary: the per-row tuple write path (replication apply shape)
        tup_dir = os.path.join(workdir, "tup")
        db_t = DB(tup_dir, DBOptions(device="native", auto_compact=False))
        nt = min(n, 1 << 18)
        t0 = time.time()
        items = [(b"Suser%08d\x00\x00!" % i,
                  DocHybridTime(HybridTime.from_micros(1000 + i), 0), value)
                 for i in range(nt)]
        db_t.write_batch(items, op_id=(1, 1))
        db_t.flush()
        out["load_tuple_rows_per_sec"] = round(nt / (time.time() - t0), 1)
        log(f"  tuple write path: {out['load_tuple_rows_per_sec']/1e3:.0f}K "
            f"rows/s")
        db_t.close()

        # ---- bulk ingest (the reference's bulk-load / SST-ingestion path,
        # ref src/yb/tools/yb_bulk_load.cc): packed arrays -> native encode
        try:
            ing_dir = os.path.join(workdir, "ing")
            db2 = DB(ing_dir, DBOptions(device="native", auto_compact=False))
            t0 = time.time()
            keys_blob = b"".join(b"Suser%08d\x00\x00!" % i for i in range(n))
            koffs = np.arange(n + 1, dtype=np.int64) * 16
            ht = ((np.arange(n, dtype=np.uint64) + 1000) << np.uint64(12))
            wid = np.zeros(n, dtype=np.uint32)
            vals_blob = value * n
            voffs = np.arange(n + 1, dtype=np.int64) * len(value)
            db2.ingest_packed(keys_blob, koffs, ht, wid, vals_blob, voffs,
                              op_id=(1, 1))
            ing_s = time.time() - t0
            out["ingest_rows_per_sec"] = round(n / ing_s, 1)
            log(f"  bulk ingest (packed -> native SST): {n} rows in "
                f"{ing_s:.2f}s = {n/ing_s/1e6:.2f}M rows/s")
            db2.close()
        except Exception as e:  # noqa: BLE001
            log(f"  bulk ingest stage skipped: {e}")

        # ---- full seq scan: native batch interface (the storage-level
        # scan the CQL row iterator consumes; counts come from the packed
        # buffers, like db_bench readseq) ---------------------------------
        scan = db.scan_native(internal_keys=True)
        if scan is not None:
            t0 = time.time()
            rows = 0
            nbytes = 0
            for b in scan.batches():
                rows += b.n
                nbytes += b.key_bytes_total + b.val_bytes_total
            dt = time.time() - t0
            out["seq_scan_rows_per_sec"] = round(rows / dt, 1)
            out["seq_scan_mb_per_sec"] = round(nbytes / dt / 1e6, 1)
            assert rows == n, f"native scan row count: {rows}/{n}"
            log(f"  seq scan (native): {rows} rows in {dt:.2f}s = "
                f"{out['seq_scan_rows_per_sec']/1e6:.2f}M rows/s, "
                f"{out['seq_scan_mb_per_sec']:.0f} MB/s")
        # baseline column: the pure-Python merged iterator over the same DB
        prior_native = _flags.get_flag("read_native")
        _flags.set_flag("read_native", False)
        try:
            t0 = time.time()
            rows = 0
            nbytes = 0
            for ikey, val in db.iter_from(b""):
                rows += 1
                nbytes += len(ikey) + len(val)
                if time.time() - t0 > 60:  # cap the slow baseline's cost
                    break
            dt = time.time() - t0
            py_rate = rows / dt
            out["seq_scan_py_rows_per_sec"] = round(py_rate, 1)
            out["seq_scan_py_mb_per_sec"] = round(nbytes / dt / 1e6, 1)
        finally:
            _flags.set_flag("read_native", prior_native)
        if "seq_scan_rows_per_sec" not in out:
            # no native engine: the Python number IS the scan number
            out["seq_scan_rows_per_sec"] = out["seq_scan_py_rows_per_sec"]
            out["seq_scan_mb_per_sec"] = out["seq_scan_py_mb_per_sec"]
        log(f"  seq scan (python baseline): "
            f"{out['seq_scan_py_rows_per_sec']/1e6:.2f}M rows/s, "
            f"{out['seq_scan_py_mb_per_sec']:.0f} MB/s")

        # ---- bloom-gated point reads (native get + python baseline) -----
        m = 20_000
        hit_ids = rng.integers(0, n, size=m)
        t0 = time.time()
        found = 0
        for i in hit_ids:
            if db.get(b"Suser%08d\x00\x00!" % i) is not None:
                found += 1
        dt = time.time() - t0
        out["point_reads_per_sec"] = round(m / dt, 1)
        assert found == m, f"point reads missed rows: {found}/{m}"
        # misses: keys outside the loaded range — the bloom filters gate
        # out every SST probe (the reference's bloom-before-seek path)
        t0 = time.time()
        for i in range(m):
            if db.get(b"Suser%08d\x00\x00!" % (n + 10 + i)) is not None:
                raise AssertionError("phantom point read")
        dt = time.time() - t0
        out["point_miss_per_sec"] = round(m / dt, 1)
        # baseline column: the Python heap-merge get over the same DB —
        # both mixes, so the batched-vs-python comparison covers the
        # bloom-rejected miss path too (not just hit-path reads)
        prior_native = _flags.get_flag("read_native")
        _flags.set_flag("read_native", False)
        try:
            mp = 2_000
            t0 = time.time()
            for i in hit_ids[:mp]:
                assert db.get(b"Suser%08d\x00\x00!" % i) is not None
            out["point_reads_py_per_sec"] = round(mp / (time.time() - t0), 1)
            t0 = time.time()
            for i in range(mp):
                if db.get(b"Suser%08d\x00\x00!" % (n + 10 + i)) is not None:
                    raise AssertionError("phantom python point read")
            out["point_miss_py_per_sec"] = round(mp / (time.time() - t0), 1)
        finally:
            _flags.set_flag("read_native", prior_native)
        log(f"  point reads: {out['point_reads_per_sec']:.0f}/s hit "
            f"(python baseline {out['point_reads_py_per_sec']:.0f}/s), "
            f"{out['point_miss_per_sec']:.0f}/s bloom-gated miss "
            f"(python {out['point_miss_py_per_sec']:.0f}/s)")
        db.close()

        # ---- batched point reads (ROADMAP item 4): multi_get through
        # the device bloom/locate/gather kernels + learned index, in a
        # child so an absent TPU degrades to the CPU fallback
        # instead of hanging the parent's jax runtime
        plat = "tpu" if tpu_ok else "cpu"
        pts = _spawn_child(plat, 600, os.path.join(workdir, "db"),
                           str(n), mode="--points")
        if pts is None and plat == "tpu":
            log("  TPU points child failed — retrying on the CPU fallback")
            pts = _spawn_child("cpu", 600, os.path.join(workdir, "db"),
                               str(n), mode="--points")
        if pts:
            out.update(pts)
            batched = pts.get("point_reads_batched_per_sec", 0)
            if batched and out.get("point_reads_py_per_sec"):
                out["point_batched_vs_py"] = round(
                    batched / out["point_reads_py_per_sec"], 1)
            if batched and out.get("point_reads_per_sec"):
                out["point_batched_vs_per_call"] = round(
                    batched / out["point_reads_per_sec"], 2)
    except Exception as e:  # noqa: BLE001 — stage is best-effort
        log(f"scan/point stage failed: {e}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return out


def _cluster_soak_stage() -> dict:
    """BASELINE config 5 (VERDICT r4 next #6): 3-node RF=3 real-process
    cluster, unpaced YCSB-A at the highest sustainable rate, with
    background compaction plus one kill -9 + restart and one tablet
    split mid-run. Records the measured ops/s and p99 — whatever they
    are — instead of asserting a target.

    ref: yb-perf-v1.0.7.md:6-8 (the 3-node YCSB-A configuration),
    src/yb/integration-tests/linked_list-test.cc (the churn shape)."""
    import shutil
    import tempfile

    from yugabyte_tpu.integration.external_mini_cluster import (
        ExternalMiniCluster)
    from yugabyte_tpu.integration.load_generator import (
        YCSB_SCHEMA, YcsbALoadGenerator)

    seconds = float(os.environ.get("YBTPU_BENCH_SOAK_SECONDS", 60))
    root = tempfile.mkdtemp(prefix="ybtpu-bench-soak-")
    out: dict = {}
    c = None
    gen = None
    client = None
    try:
        c = ExternalMiniCluster(os.path.join(root, "cluster"),
                                num_tservers=3, rf=3).start()
        c.wait_tservers_alive(3)
        client = c.new_client()
        client.create_namespace("soak")
        table = client.create_table("soak", "ycsb", YCSB_SCHEMA,
                                    num_tablets=4)
        # workload must not race the fresh tablets' first elections
        c.wait_table_leaders(client, table.table_id)
        gen = YcsbALoadGenerator(client, table, n_threads=8).start()
        third = seconds / 3.0
        time.sleep(third)
        c.tservers[1].kill9()           # churn: node loss mid-load
        time.sleep(third / 2)
        c.tservers[1].start()           # recovery: bootstrap/catch-up
        c.wait_tservers_alive(3)
        time.sleep(third / 2)
        locs = client._master_call("get_table_locations",
                                   table_id=table.table_id)
        client._master_call("split_tablet",
                            tablet_id=locs[0]["tablet_id"])
        time.sleep(third)
        rep = gen.stop()
        gen = None  # stopped cleanly; finally must not re-stop
        out["cluster_ops_per_sec"] = rep.ops_per_sec
        out["cluster_p50_ms"] = rep.p50_ms
        out["cluster_p99_ms"] = rep.p99_ms
        out["cluster_soak_seconds"] = rep.seconds
        out["cluster_soak_errors"] = rep.errors
        out["cluster_soak_ops"] = rep.ops
        log(f"  cluster soak (3-node RF=3 YCSB-A + kill -9 + split): "
            f"{rep.ops_per_sec:.0f} ops/s over {rep.seconds:.0f}s, "
            f"p50 {rep.p50_ms}ms p99 {rep.p99_ms}ms, "
            f"{rep.errors} errors")
    except Exception as e:  # noqa: BLE001 — stage is best-effort
        log(f"cluster soak stage failed: {e}")
    finally:
        # stop workers BEFORE tearing the cluster down — leaked unpaced
        # threads would hammer dead sockets through retry backoff for the
        # rest of the process (and destabilize later pytest stages)
        if gen is not None:
            try:
                gen.stop()
            except Exception:  # noqa: BLE001
                pass
        if client is not None:
            try:
                client.close()
            except Exception:  # noqa: BLE001
                pass
        if c is not None:
            try:
                c.shutdown()
            except Exception:  # noqa: BLE001
                pass
        shutil.rmtree(root, ignore_errors=True)
    return out


def _ycsb_stage() -> dict:
    """Serve-path rung (ROADMAP item 1): batched YCSB mixes A-F on the
    SAME 3-process RF3 external cluster shape as the soak baseline, but
    riding the PR-11 serve path — multi_read batches for reads, the
    session batcher's per-tablet group commits for writes, the scan RPC
    page path for E. Per-op completion latency is its batch's wall time
    (op-weighted percentiles).

    Tserver flags: native offload + relaxed election timing — on a
    CPU-only (often single-core) bench host the serve rung measures the
    RPC/raft/storage batching, not jax-CPU kernel compile stalls; the
    device read path's own numbers are the --points rung and the TPU
    re-measure."""
    import shutil
    import tempfile

    from yugabyte_tpu.integration.external_mini_cluster import (
        ExternalMiniCluster)
    from yugabyte_tpu.integration.load_generator import (
        YCSB_SCHEMA, YcsbLoadGenerator)

    seconds = float(os.environ.get("YBTPU_BENCH_YCSB_SECONDS", 15))
    mixes = os.environ.get("YBTPU_BENCH_YCSB_MIXES", "abcdef")
    key_space = int(os.environ.get("YBTPU_BENCH_YCSB_KEYS", 10_000))
    root = tempfile.mkdtemp(prefix="ybtpu-bench-ycsb-")
    out: dict = {}
    c = None
    client = None
    gen = None
    try:
        c = ExternalMiniCluster(
            os.path.join(root, "cluster"), num_tservers=3, rf=3,
            default_flags={
                "device_offload_mode": "native",
                "point_read_batched": False,
                "raft_heartbeat_interval_ms": 100,
                "leader_failure_max_missed_heartbeat_periods": 20,
                # overload-protection knobs (PR 12) pinned explicitly so
                # the trajectory measures a known shedding config: the
                # bounded RPC queue and write-pressure limits are ACTIVE
                # during the mixes and their counters are recorded below
                "rpc_service_queue_depth": 512,
                "wal_backlog_soft_entries": 512,
                "wal_backlog_hard_entries": 4096,
                "memstore_reject_fraction": 0.95,
                # query-pushdown routing for the E mix (ROADMAP item 5):
                # predicate-free scan pages ride the fused device scan
                # over resident slabs once a tablet is big enough; the
                # ratio served that way is recorded below
                "scan_pushdown_pages": os.environ.get(
                    "YBTPU_BENCH_E_PUSHDOWN", "1") == "1",
                "scan_pushdown_min_rows": 1024,
            }).start()
        c.wait_tservers_alive(3)
        client = c.new_client()
        client.create_namespace("ycsb")
        table = client.create_table("ycsb", "usertable", YCSB_SCHEMA,
                                    num_tablets=6)
        c.wait_table_leaders(client, table.table_id)
        t0 = time.time()
        YcsbLoadGenerator(client, table, key_space=key_space).load()
        out["ycsb_load_rows_per_sec"] = round(
            key_space / (time.time() - t0), 1)
        for mix in mixes:
            batch = 128 if mix == "e" else 1024
            gen = YcsbLoadGenerator(client, table, mix=mix, n_threads=2,
                                    key_space=key_space,
                                    batch_size=batch).start()
            time.sleep(seconds)
            rep = gen.stop()
            gen = None
            out[f"ycsb_{mix}_ops_per_sec"] = rep.ops_per_sec
            out[f"ycsb_{mix}_p50_ms"] = rep.p50_ms
            out[f"ycsb_{mix}_p99_ms"] = rep.p99_ms
            out[f"ycsb_{mix}_errors"] = rep.errors
            if mix == "e":
                out["ycsb_e_scan_rows_per_sec"] = round(
                    rep.scan_rows / rep.seconds, 1) if rep.seconds else 0
                # scan-page routing: what fraction of E's pages the
                # fused filtered path actually served (per-tserver
                # scan_pushdown_status scrape; cumulative counters, but
                # only the E mix issues scan RPCs)
                pages = pushed = 0
                for ts in c.tservers:
                    try:
                        sc = client._messenger.call(
                            ts.address, "tserver", "scan_pushdown_status",
                            timeout_s=10.0)["scans"]
                    except Exception as e:  # noqa: BLE001 — best-effort
                        log(f"  pushdown scrape of {ts.address} "
                            f"failed: {e}")
                        continue
                    pages += sc.get("scan_rpc_pages_total", 0)
                    pushed += sc.get("scan_rpc_pages_pushdown_total", 0)
                out["ycsb_e_pushdown_ratio"] = round(
                    pushed / pages, 3) if pages else 0.0
                log(f"  ycsb-e pushdown ratio: "
                    f"{out['ycsb_e_pushdown_ratio']} "
                    f"({pushed}/{pages} pages)")
            log(f"  ycsb-{mix}: {rep.ops_per_sec:.0f} ops/s over "
                f"{rep.seconds:.0f}s, p50 {rep.p50_ms}ms "
                f"p99 {rep.p99_ms}ms, {rep.errors} errors")
        # headline keys: the read-heavy B mix (the acceptance rung)
        if "ycsb_b_ops_per_sec" in out:
            out["ycsb_p50_ms"] = out["ycsb_b_p50_ms"]
            out["ycsb_p99_ms"] = out["ycsb_b_p99_ms"]
        # overload counters (PR 12): scrape every tserver's /servez
        # overload block over the overload_status RPC and record the
        # shedding totals, so throttling is VISIBLE in the trajectory —
        # a future rung whose ops/s rises while rejections explode is
        # shedding its way to the number, not serving it
        shed = {"write_throttle_rejections_total": 0,
                "rpc_queue_overflow_total": 0,
                "rpc_calls_expired_in_queue_total": 0}
        for ts in c.tservers:
            try:
                ov = client._messenger.call(
                    ts.address, "tserver", "overload_status",
                    timeout_s=10.0)["overload"]
            except Exception as e:  # noqa: BLE001 — scrape is best-effort
                log(f"  overload scrape of {ts.address} failed: {e}")
                continue
            shed["write_throttle_rejections_total"] += ov.get(
                "write_throttle_rejections_total", 0)
            rpc = ov.get("rpc", {})
            shed["rpc_queue_overflow_total"] += rpc.get(
                "rpc_queue_overflow_total", 0)
            shed["rpc_calls_expired_in_queue_total"] += rpc.get(
                "rpc_calls_expired_in_queue_total", 0)
        for k, v in shed.items():
            out[f"ycsb_{k}"] = v
        out["ycsb_retry_budget_exhaustions_total"] = \
            client.retry_budget.exhausted_total
        out["ycsb_retries_spent_total"] = client.retry_budget.spent_total
        log(f"  overload: {shed}, retry_budget_exhaustions="
            f"{client.retry_budget.exhausted_total}")
    except Exception as e:  # noqa: BLE001 — stage is best-effort
        log(f"ycsb stage failed: {e}")
    finally:
        if gen is not None:
            try:
                gen.stop()
            except Exception:  # noqa: BLE001
                pass
        if client is not None:
            try:
                client.close()
            except Exception:  # noqa: BLE001
                pass
        if c is not None:
            try:
                c.shutdown()
            except Exception:  # noqa: BLE001
                pass
        shutil.rmtree(root, ignore_errors=True)
    return out


def _partial_from_stages(stages_path: str, n_total: int, cpu_rate: float):
    """Assemble a result dict from whatever stages a dead child finished."""
    recs = {}
    try:
        with open(stages_path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                    recs[rec.pop("stage")] = rec
                except (json.JSONDecodeError, KeyError):
                    continue
    except OSError:
        return None
    if "device_resident" not in recs:
        return None
    res_s = recs["device_resident"]["sustained_s"]
    out = {
        "metric": "l0_compaction_merge_gc_rows_per_sec",
        "value": round(n_total / res_s, 1),
        "unit": "rows/s",
        "vs_baseline": round((n_total / res_s) / cpu_rate, 3),
        "vs_baseline_basis": "single-core IN-MEMORY C++ merge+GC "
                             "(child died before the disk-to-disk stage)",
        "platform": recs.get("init", {}).get("platform", "tpu"),
        "device": recs.get("init", {}).get("device", "?"),
        "note": "PARTIAL: assembled from stage checkpoints of a child that "
                "exceeded its budget; value = device-resident sustained "
                "merge+GC",
        "partial": True,
        "cpu_cxx_baseline_rows_per_sec": round(cpu_rate, 1),
        "kernel_vs_cpu_core": round((n_total / res_s) / cpu_rate, 3),
        "device_resident_rows_per_sec": round(n_total / res_s, 1),
        "n_rows": n_total,
    }
    if "link_rtt_s" in recs.get("device_resident", {}):
        out["link_roundtrip_ms"] = round(
            recs["device_resident"]["link_rtt_s"] * 1e3, 1)
    if "cold" in recs:
        out["cold_rows_per_sec"] = round(n_total / recs["cold"]["cold_s"], 1)
        out["compile_s"] = round(recs["cold"]["compile_s"], 1)
    if "scan" in recs:
        out["scan_rows_per_sec"] = round(
            recs["scan"].get("scan_n", n_total) / recs["scan"]["scan_s"], 1)
    if "resident_chain" in recs:
        out["resident_chain_rows_per_sec"] = round(
            recs["resident_chain"]["resident_chain"], 1)
        out["device_cache_hit_ratio"] = round(
            recs["resident_chain"].get("cache_hit_ratio", 0.0), 4)
    if "e2e_steady" in recs:
        out["e2e_steady_rows_per_sec"] = round(
            recs["e2e_steady"]["e2e_steady"], 1)
        out["e2e_steady2_rows_per_sec"] = round(
            recs["e2e_steady"].get("e2e_steady2", 0.0), 1)
        out["e2e_n_rows"] = recs["e2e_steady"]["e2e_n"]
        for k in ("stage_host_ms", "stage_device_ms", "stage_write_ms",
                  "stage_shadow_ms", "stage_decode_ms", "stage_encode_ms",
                  "compile_bucket_hits",
                  "compile_bucket_misses", "compile_surface_buckets",
                  "shadow_verify_sample", "shadow_verify_jobs",
                  "shadow_verify_mismatches", "bucket_health_states",
                  "bucket_health_promotions", "bucket_health_demotions",
                  "bucket_health_quarantines", "bucket_health_probes",
                  "bucket_health_probe_failures",
                  "bucket_health_mismatch"):
            if k in recs["e2e_steady"]:
                out[k] = recs["e2e_steady"][k]
        out["value"] = max(out["e2e_steady_rows_per_sec"],
                           out["e2e_steady2_rows_per_sec"])
        out["vs_baseline"] = round(out["value"] / cpu_rate, 3)
        out["vs_baseline_basis"] = (
            "single-core IN-MEMORY C++ merge+GC (the parent replaces this "
            "with the disk-to-disk basis when the native e2e baseline ran)")
        out["note"] = ("PARTIAL: child died after the disk-to-disk steady "
                       "stage; value = e2e steady disk-to-disk compaction")
    return out


class _Rung:
    """Workload + JAX-free baselines for one ladder size; the file outlives
    the rung so the CPU fallback can reuse it instead of regenerating."""

    def __init__(self, n_total: int):
        import tempfile
        self.n = n_total
        slab, offsets, _, self.cutoff = _workload_at(n_total)
        self.cpu_rate, cpu_kept = _cpu_cxx_baseline(slab, offsets,
                                                    self.cutoff, n_total)
        # e2e baseline at the SAME size formula the device child uses for
        # its disk-to-disk stage — vs_baseline must compare equal workloads
        self.e2e_n = int(os.environ.get("YBTPU_BENCH_E2E_N",
                                        min(n_total, 1 << 22)))
        try:
            self.native_rate, self.native_runs = _native_e2e_rate(
                self.e2e_n, self.cutoff)
        except Exception as e:  # noqa: BLE001 — native shell optional
            log(f"native e2e unavailable: {e}")
            self.native_rate = 0.0
            self.native_runs = []
        wl = tempfile.NamedTemporaryFile(suffix=".npz", delete=False)
        self.wl_path = wl.name
        _save_workload(self.wl_path, slab, offsets, n_total, self.cutoff,
                       self.cpu_rate, cpu_kept)

    def cleanup(self):
        try:
            os.unlink(self.wl_path)
        except OSError:
            pass


def _measure_rung(rung: _Rung, warm_budget: float, measure_budget: float):
    """One ladder rung on TPU: warm child + measure child."""
    import tempfile
    stages_f = tempfile.NamedTemporaryFile(suffix=".stages", delete=False)
    try:
        warmed = _spawn_child("tpu", warm_budget, rung.wl_path, mode="--warm")
        if warmed is None:
            log(f"warm child failed at n={rung.n} — measuring anyway "
                f"(compile cache holds whatever finished)")
        result = _spawn_child("tpu", measure_budget, rung.wl_path,
                              stages_f.name)
        if result is None:
            result = _partial_from_stages(stages_f.name, rung.n,
                                          rung.cpu_rate)
            if result is not None:
                log(f"assembled PARTIAL result from stage checkpoints at "
                    f"n={rung.n}")
    finally:
        os.unlink(stages_f.name)
    return result


def _workload_at(n_total: int):
    n_runs = 4
    key_space = max(1, n_total // 2)
    cutoff = (10_000_000 << 12)  # above all writes
    log(f"generating {n_total} rows in {n_runs} sorted runs ...")
    t0 = time.time()
    slab, offsets = synth_ycsb_runs(n_total, n_runs, key_space)
    log(f"  gen: {time.time()-t0:.1f}s")
    return slab, offsets, n_total, cutoff


def _last_tpu_keys() -> dict:
    """When no TPU answers at capture time, surface the most recent
    COMMITTED TPU measurements (clearly labeled last_tpu_*, with their
    capture file) so a CPU-fallback artifact is not blind to the real
    hardware results this round already recorded."""
    here = os.path.dirname(os.path.abspath(__file__))
    best = None
    # recency by mtime, not filename (lexicographic breaks across digit
    # boundaries, e.g. r99 vs r100)
    def _mtime(n):
        try:
            return os.path.getmtime(os.path.join(here, n))
        except OSError:
            return 0.0
    for name in sorted(os.listdir(here), key=_mtime):
        if not (name.startswith("BENCH_SELF") and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(here, name)) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    rec = json.loads(line)
                    if rec.get("platform") == "tpu":
                        best = (name, rec)
        except Exception:  # noqa: BLE001 — artifact scan is best-effort
            continue
    if best is None:
        return {}
    name, rec = best
    out = {"last_tpu_source": name}
    for k in ("value", "vs_baseline", "kernel_vs_cpu_core",
              "e2e_steady_rows_per_sec", "e2e_native_rows_per_sec",
              "device_resident_rows_per_sec", "seq_scan_rows_per_sec",
              "point_reads_per_sec", "compile_s", "n_rows", "device"):
        if k in rec:
            out[f"last_tpu_{k}"] = rec[k]
    return out


def main():
    if len(sys.argv) >= 2 and sys.argv[1] == "--compaction_pool":
        run_pool_parent()
        return
    if len(sys.argv) >= 4 and sys.argv[1] == "--pool_child":
        run_pool_child(sys.argv[2], sys.argv[3])
        return
    if len(sys.argv) >= 3 and sys.argv[1] == "--probe":
        run_probe_child(sys.argv[2])
        return
    if len(sys.argv) >= 4 and sys.argv[1] == "--warm":
        run_warm_child(sys.argv[2], sys.argv[3])
        return
    if len(sys.argv) >= 5 and sys.argv[1] == "--points":
        run_points_child(sys.argv[2], sys.argv[3], sys.argv[4])
        return
    if len(sys.argv) >= 4 and sys.argv[1] == "--analytics":
        run_analytics_child(sys.argv[2], sys.argv[3])
        return
    if len(sys.argv) >= 4 and sys.argv[1] == "--codec":
        run_codec_child(sys.argv[2], sys.argv[3])
        return
    if len(sys.argv) >= 4 and sys.argv[1] == "--child":
        run_device_child(sys.argv[2], sys.argv[3],
                         sys.argv[4] if len(sys.argv) > 4 else None)
        return

    # telemetry timebase: sample the parent process (the cluster-soak
    # and YCSB stages run in-parent) through the round so the emitted
    # JSON carries rate history, not just end-state counters
    from yugabyte_tpu.utils.timeseries import timeseries_store
    _ts = timeseries_store()
    _ts.start(interval_s=1.0)

    # Budgets are per-phase (VERDICT r3: one all-or-nothing 480s budget for
    # init+compile+run produced no TPU datapoint at all).  On timeout the
    # ladder degrades SHAPE (4M -> 1M -> 256K), never platform.
    probe_budget = float(os.environ.get("YBTPU_BENCH_PROBE_TIMEOUT", 420))
    warm_budget = float(os.environ.get("YBTPU_BENCH_WARM_TIMEOUT", 600))
    measure_budget = float(os.environ.get("YBTPU_BENCH_TIMEOUT", 900))
    n_top = int(os.environ.get("YBTPU_BENCH_N", 1 << 22))

    result = None
    rung = None
    rungs = []
    probe = _spawn_child("tpu", probe_budget, mode="--probe")
    if probe is None:
        log("TPU init probe failed once — retrying")
        probe = _spawn_child("tpu", probe_budget, mode="--probe")
    try:
        if probe is not None:
            log(f"TPU probe ok: {probe.get('probe')}")
            for i, n in enumerate([n_top, n_top // 4, n_top // 16]):
                if n < (1 << 16):
                    break
                log(f"=== ladder rung {i}: n={n} (tpu) ===")
                rung = _Rung(n)
                rungs.append(rung)
                shrink = 0.75 ** i
                result = _measure_rung(rung, warm_budget * shrink,
                                       measure_budget * shrink)
                if result is not None:
                    break
        else:
            log("TPU backend unavailable after two probes")

        if result is None:
            log("no TPU datapoint possible — falling back to CPU-JAX so a "
                "number is still recorded (reusing the last rung's "
                "workload and baselines)")
            if rung is None:
                rung = _Rung(n_top)
                rungs.append(rung)
            result = _spawn_child("cpu", measure_budget * 2, rung.wl_path)
            if result is not None:
                result.update(_last_tpu_keys())
        if result is not None and rung is not None:
            # persistent-compilation-cache proof: a FRESH process hitting
            # the same shape buckets must compile from the cache dir in
            # seconds, not re-pay the full XLA compile (compile_s). The
            # measuring child above populated the cache; this second
            # process's first-call time is compile2_s.
            plat2 = "tpu" if result.get("platform") == "tpu" else "cpu"
            warm2 = _spawn_child(plat2, warm_budget, rung.wl_path,
                                 mode="--warm")
            if warm2 and "compile_s" in warm2:
                result["compile2_s"] = warm2["compile_s"]
                log(f"second-process first call (persistent cache): "
                    f"{warm2['compile_s']:.1f}s vs cold compile "
                    f"{result.get('compile_s', '?')}s")
        native_rate = rung.native_rate if rung else 0.0
        cpu_rate = rung.cpu_rate if rung else 0.0
    finally:
        for r in rungs:
            r.cleanup()

    if result is None:
        # last resort: still emit a JSON line with the native full-job rate
        log("CPU-JAX child also failed; emitting native rates only")
        result = {
            "metric": "l0_compaction_merge_gc_rows_per_sec",
            "value": round(native_rate or cpu_rate, 1),
            "unit": "rows/s",
            "vs_baseline": round((native_rate or cpu_rate)
                                 / max(cpu_rate, 1), 3),
            "platform": "native-cxx-only",
            "n_rows": n_top,
        }
    # scan-path stages (BASELINE configs 3-4): storage-level CPU numbers,
    # independent of the device child's fate
    result.update(_scan_point_stages(
        int(result.get("n_rows") or n_top),
        tpu_ok=result.get("platform") == "tpu"))
    # analytics rung (ROADMAP item 5): fused filtered/aggregating scans
    # vs the per-row host query path (TPU when one is attached, else
    # CPU-labeled — same child-watchdog discipline as --points)
    if os.environ.get("YBTPU_BENCH_SKIP_ANALYTICS", "") != "1":
        plat = "tpu" if result.get("platform") == "tpu" else "cpu"
        n_an = str(min(int(result.get("n_rows") or n_top), 1 << 18))
        ana = _spawn_child(plat, 600, n_an, mode="--analytics")
        if ana is None and plat == "tpu":
            log("TPU analytics child failed — retrying on CPU fallback")
            ana = _spawn_child("cpu", 600, n_an, mode="--analytics")
        if ana:
            result.update(ana)
    # block-codec micro rung (ROADMAP item 2): device block decode/encode
    # vs the host and native-shell codecs over one SST
    if os.environ.get("YBTPU_BENCH_SKIP_CODEC", "") != "1":
        plat = "tpu" if result.get("platform") == "tpu" else "cpu"
        n_c = str(min(int(result.get("n_rows") or n_top), 1 << 18))
        cod = _spawn_child(plat, 600, n_c, mode="--codec")
        if cod is None and plat == "tpu":
            log("TPU codec child failed — retrying on CPU fallback")
            cod = _spawn_child("cpu", 600, n_c, mode="--codec")
        if cod:
            result.update(cod)
    # BASELINE config 5: the 3-node RF=3 cluster soak with churn
    if os.environ.get("YBTPU_BENCH_SKIP_SOAK", "") != "1":
        result.update(_cluster_soak_stage())
    # serve-path rung (ROADMAP item 1): batched YCSB A-F on the same
    # RF3 cluster shape, riding the PR-11 batcher + multi_read path
    if os.environ.get("YBTPU_BENCH_SKIP_YCSB", "") != "1":
        result.update(_ycsb_stage())
        b = result.get("ycsb_b_ops_per_sec")
        soak = result.get("cluster_ops_per_sec")
        if b and soak:
            # batched serve path vs the per-op soak on the same cluster
            result["ycsb_b_vs_cluster_soak"] = round(b / soak, 1)

    if native_rate:
        result["e2e_native_rows_per_sec"] = round(native_rate, 1)
        result["e2e_native_runs"] = rung.native_runs if rung else []
        steady = result.get("e2e_steady_rows_per_sec") or 0
        # (the static offload-calibration artifact is gone: production
        # device-vs-native routing is the live bucket-health board's
        # measured EWMA rate race — storage/bucket_health.py, PR 16)
        if steady:
            result["e2e_vs_native"] = round(steady / native_rate, 3)
            # the headline comparison: OUR full job vs the stock-CPU-
            # architecture full job over the same files on the same disk
            # (BASELINE.md: ">=3x rows/sec on L0->L1 compaction ... vs the
            # stock CPU CompactionJob" — which also pays disk I/O)
            result["vs_baseline"] = round(steady / native_rate, 3)
            result["vs_baseline_basis"] = _BASIS
    result["meta"] = _round_meta(str(result.get("platform") or "cpu"))
    _ts.sample_once()  # final tick so short stages land in the window
    _ts.stop()
    result["timeseries"] = _ts.bench_snapshot()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
