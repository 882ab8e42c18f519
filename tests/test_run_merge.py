"""Differential tests for the pre-sorted-run merge kernel (ops/run_merge.py).

The round-3 compaction hot path: bitonic merge network over K sorted runs +
shared GC filter + packed decision buffer. Every case cross-checks survivors
(in merged order) and make-tombstone decisions against the native C++
baseline (reference architecture: heap merge + sequential filter) and, where
cheap, the radix kernel — three independent implementations must agree.
"""

import numpy as np
import pytest

from yugabyte_tpu.ops import run_merge
from yugabyte_tpu.ops.merge_gc import GCParams, merge_and_gc_device
from yugabyte_tpu.ops.slabs import (
    FLAG_HAS_TTL, FLAG_TOMBSTONE, KVSlab, ValueArray, concat_slabs)
from yugabyte_tpu.storage.cpu_baseline import compact_cpu_baseline


def _make_run(rng, n, key_space, w=3, tomb_frac=0.1, ttl_frac=0.0,
              ht_lo_bits=20):
    """One sorted run of synthetic entries with duplicate keys across runs."""
    kid = rng.integers(0, key_space, size=n).astype(np.uint32)
    key_words = np.zeros((n, w), dtype=np.uint32)
    key_words[:, 0] = 0x53000000 | (kid >> 16)
    key_words[:, 1] = (kid << 16) | 0x2100
    key_len = np.full(n, 7, dtype=np.int32)   # 7 bytes -> word2 zero pad
    dkl = np.full(n, 7, dtype=np.int32)
    is_col = rng.random(n) < 0.5              # half root writes, half column
    key_words[is_col, 1] |= 0x4B              # 'K' subkey marker byte
    key_len[is_col] = 10
    ht = rng.integers(1, 1 << ht_lo_bits, size=n).astype(np.uint64) << 12
    flags = np.where(rng.random(n) < tomb_frac, FLAG_TOMBSTONE, 0).astype(np.uint32)
    ttl_ms = np.zeros(n, dtype=np.int64)
    if ttl_frac:
        has = rng.random(n) < ttl_frac
        flags[has] |= FLAG_HAS_TTL
        ttl_ms[has] = rng.integers(1, 1000, size=int(has.sum()))
    wid = rng.integers(0, 4, size=n).astype(np.uint32)
    # full internal-key order incl. wid desc: a (key, ht) collision within a
    # run must still leave the run ascending under the merge comparator
    order = np.lexsort((~wid, ~ht, key_len) + tuple(
        key_words[:, j] for j in range(w - 1, -1, -1)))
    return KVSlab(
        key_words=key_words[order], key_len=key_len[order],
        doc_key_len=dkl[order],
        ht_hi=(ht[order] >> 32).astype(np.uint32),
        ht_lo=(ht[order] & 0xFFFFFFFF).astype(np.uint32),
        write_id=wid[order], flags=flags[order], ttl_ms=ttl_ms[order],
        value_idx=np.arange(n, dtype=np.int32),
        values=ValueArray.empty_rows(n))


def _check_against_baseline(runs, cutoff, is_major, retain_deletes=False):
    params = GCParams(cutoff, is_major, retain_deletes)
    perm, keep, mk = run_merge.merge_and_gc_runs(runs, params)
    merged = concat_slabs(runs)
    offsets = np.concatenate(([0], np.cumsum([r.n for r in runs]))).tolist()
    order_c, keep_c, mk_c = compact_cpu_baseline(
        merged, offsets, cutoff, is_major, retain_deletes)
    surv = perm[keep]
    surv_c = order_c[keep_c]
    assert np.array_equal(surv, surv_c), (
        f"survivor mismatch: {len(surv)} vs {len(surv_c)}")
    assert np.array_equal(perm[mk], order_c[mk_c])
    return surv


@pytest.mark.parametrize("k,seed", [(2, 0), (3, 1), (4, 2), (5, 3), (8, 4)])
def test_differential_multi_run(k, seed):
    rng = np.random.default_rng(seed)
    runs = [_make_run(rng, int(rng.integers(50, 400)), key_space=60)
            for _ in range(k)]
    _check_against_baseline(runs, cutoff=(1 << 21) << 12, is_major=True)
    _check_against_baseline(runs, cutoff=(1 << 19) << 12, is_major=False)


def test_single_run_is_gc_only():
    rng = np.random.default_rng(7)
    runs = [_make_run(rng, 300, key_space=40)]
    surv = _check_against_baseline(runs, cutoff=(1 << 19) << 12,
                                   is_major=True)
    assert len(surv) > 0


def test_unequal_run_sizes():
    rng = np.random.default_rng(11)
    runs = [_make_run(rng, n, key_space=100) for n in (1000, 17, 3, 260)]
    _check_against_baseline(runs, cutoff=(1 << 20) << 12, is_major=True)


def test_ttl_expiry_paths():
    rng = np.random.default_rng(13)
    runs = [_make_run(rng, 200, key_space=30, ttl_frac=0.4)
            for _ in range(3)]
    # minor compaction: expired values become tombstones (mk set)
    params = GCParams((1 << 22) << 12, False)
    perm, keep, mk = run_merge.merge_and_gc_runs(runs, params)
    merged = concat_slabs(runs)
    offsets = np.concatenate(([0], np.cumsum([r.n for r in runs]))).tolist()
    order_c, keep_c, mk_c = compact_cpu_baseline(
        merged, offsets, (1 << 22) << 12, False)
    assert np.array_equal(perm[keep], order_c[keep_c])
    assert np.array_equal(perm[mk], order_c[mk_c])
    assert mk.sum() > 0  # the workload must actually exercise expiry
    # major: expired + visible tombstones vanish
    _check_against_baseline(runs, cutoff=(1 << 22) << 12, is_major=True)


def test_retain_deletes():
    rng = np.random.default_rng(17)
    runs = [_make_run(rng, 150, key_space=25, tomb_frac=0.5)
            for _ in range(2)]
    _check_against_baseline(runs, cutoff=(1 << 21) << 12, is_major=True,
                            retain_deletes=True)


def test_matches_radix_kernel():
    """Three-way agreement: run-merge == radix kernel == C++ baseline."""
    rng = np.random.default_rng(23)
    runs = [_make_run(rng, 256, key_space=50) for _ in range(4)]
    cutoff = (1 << 20) << 12
    surv = _check_against_baseline(runs, cutoff, is_major=True)
    merged = concat_slabs(runs)
    perm_r, keep_r, _ = merge_and_gc_device(merged, GCParams(cutoff, True))
    assert np.array_equal(np.sort(surv), np.sort(perm_r[keep_r]))


def test_staged_runs_reuse_matches_fresh_upload():
    """Device-resident path: per-run staged cols re-laid out on device must
    produce identical decisions to a fresh run-major upload."""
    from yugabyte_tpu.ops.merge_gc import stage_slab

    rng = np.random.default_rng(29)
    runs = [_make_run(rng, int(rng.integers(100, 300)), key_space=40)
            for _ in range(3)]
    params = GCParams((1 << 20) << 12, True)
    staged_list = [stage_slab(r) for r in runs]
    staged = run_merge.stage_runs_from_staged(staged_list)
    perm_a, keep_a, mk_a = run_merge.merge_and_gc_runs(
        runs, params, staged=staged)
    perm_b, keep_b, mk_b = run_merge.merge_and_gc_runs(runs, params)
    assert np.array_equal(perm_a[keep_a], perm_b[keep_b])
    assert np.array_equal(perm_a[mk_a], perm_b[mk_b])


def test_write_id_tiebreak():
    """Same key+ht, different write ids: wid descends within the version
    stack and the overwrite check uses it (ref docdb_compaction_filter.cc
    DocHybridTime ordering)."""
    w = 2
    n = 6
    key_words = np.zeros((n, w), dtype=np.uint32)
    key_words[:, 0] = 0x41414141
    key_len = np.array([4, 4, 4, 4, 4, 4], dtype=np.int32)
    dkl = key_len.copy()
    ht = np.array([100, 100, 100, 50, 50, 10], dtype=np.uint64) << 12
    wid = np.array([2, 1, 0, 1, 0, 0], dtype=np.uint32)
    run = KVSlab(key_words=key_words, key_len=key_len, doc_key_len=dkl,
                 ht_hi=(ht >> 32).astype(np.uint32),
                 ht_lo=(ht & 0xFFFFFFFF).astype(np.uint32),
                 write_id=wid, flags=np.zeros(n, np.uint32),
                 ttl_ms=np.zeros(n, np.int64),
                 value_idx=np.arange(n, dtype=np.int32),
                 values=ValueArray.empty_rows(n))
    half = KVSlab(key_words=key_words[::2], key_len=key_len[::2],
                  doc_key_len=dkl[::2],
                  ht_hi=(ht[::2] >> 32).astype(np.uint32),
                  ht_lo=(ht[::2] & 0xFFFFFFFF).astype(np.uint32),
                  write_id=wid[::2], flags=np.zeros(3, np.uint32),
                  ttl_ms=np.zeros(3, np.int64),
                  value_idx=np.arange(3, dtype=np.int32),
                  values=ValueArray.empty_rows(3))
    other = KVSlab(key_words=key_words[1::2], key_len=key_len[1::2],
                   doc_key_len=dkl[1::2],
                   ht_hi=(ht[1::2] >> 32).astype(np.uint32),
                   ht_lo=(ht[1::2] & 0xFFFFFFFF).astype(np.uint32),
                   write_id=wid[1::2], flags=np.zeros(3, np.uint32),
                   ttl_ms=np.zeros(3, np.int64),
                   value_idx=np.arange(3, dtype=np.int32),
                   values=ValueArray.empty_rows(3))
    _check_against_baseline([half, other], cutoff=(200 << 12),
                            is_major=True)
    _check_against_baseline([run], cutoff=(60 << 12), is_major=False)


def test_pallas_failure_degrades_to_network(monkeypatch):
    """A Mosaic lowering/runtime failure on the first real-TPU run must
    degrade to the jnp network, not kill the compaction/bench process."""
    from yugabyte_tpu.integration.synth import split_runs, synth_ycsb_runs
    from yugabyte_tpu.ops import pallas_merge, run_merge
    from yugabyte_tpu.ops.merge_gc import GCParams

    slab, offsets = synth_ycsb_runs(1 << 12, 4, 1 << 11, seed=3)
    staged = run_merge.stage_runs_from_slabs(split_runs(slab, offsets))
    params = GCParams((10_000_000 << 12), True)
    expect = run_merge.launch_merge_gc(staged, params).result()

    calls = {"n": 0}

    def boom(*a, **k):
        calls["n"] += 1
        raise RuntimeError("mosaic lowering exploded")

    monkeypatch.setattr(pallas_merge, "launch_merge_gc_pallas", boom)
    monkeypatch.setattr(run_merge, "_pallas_broken", False)
    monkeypatch.setattr(run_merge, "_pick_impl", lambda s: "pallas")
    got = run_merge.launch_merge_gc(staged, params).result()
    assert calls["n"] == 1
    # process-wide circuit breaker: no second pallas attempt
    got2 = run_merge.launch_merge_gc(staged, params).result()
    assert calls["n"] == 1
    import numpy as np
    for a, b in zip(expect, got):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(expect, got2):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------- chunking

def _chunk_equal(runs, cutoff, is_major, monkeypatch, target,
                 expect_chunked=None):
    """Chunked launch must produce BIT-IDENTICAL (perm, keep, mk) to the
    unchunked launch: chunks are route-partitioned in key order and the
    per-chunk tiebreak preserves run-major order, so even the merged
    order matches exactly."""
    params = GCParams(cutoff, is_major)
    staged = run_merge.stage_runs_from_slabs(runs)
    monkeypatch.setenv("YBTPU_MERGE_CHUNK_ROWS", "0")
    p0, k0, m0 = run_merge.launch_merge_gc(staged, params).result()
    monkeypatch.setenv("YBTPU_MERGE_CHUNK_ROWS", str(target))
    h = run_merge.launch_merge_gc(staged, params)
    if expect_chunked is not None:
        assert isinstance(h, run_merge._ChunkedMergeGCHandle) \
            == expect_chunked, type(h).__name__
    p1, k1, m1 = h.result()
    assert np.array_equal(p0, p1)
    assert np.array_equal(k0, k1)
    assert np.array_equal(m0, m1)
    return h


@pytest.mark.parametrize("k,seed", [(2, 10), (3, 11), (4, 12)])
def test_chunked_matches_unchunked(k, seed, monkeypatch):
    rng = np.random.default_rng(seed)
    runs = [_make_run(rng, int(rng.integers(1500, 2049)), key_space=500)
            for _ in range(k)]
    h = _chunk_equal(runs, (1 << 19) << 12, True, monkeypatch,
                     target=2048, expect_chunked=True)
    # subcompactions really happened, on bounded shapes
    assert len(h._handles) >= 2
    assert all(hh._staged.m < 2048 for hh in h._handles)


def test_chunked_doc_atomicity_under_hot_docs(monkeypatch):
    """A handful of doc keys with thousands of versions each: route
    boundaries must keep every doc whole (the GC overwrite logic depends
    on it). With this much skew the chunker may legitimately refuse
    (bucket would not shrink) — equality must hold either way."""
    rng = np.random.default_rng(13)
    runs = [_make_run(rng, 2000, key_space=6) for _ in range(4)]
    _chunk_equal(runs, (1 << 19) << 12, True, monkeypatch, target=2048)
    _chunk_equal(runs, (1 << 18) << 12, False, monkeypatch, target=2048)


def test_chunked_against_native_baseline(monkeypatch):
    rng = np.random.default_rng(14)
    runs = [_make_run(rng, 1800, key_space=300, ttl_frac=0.1)
            for _ in range(4)]
    monkeypatch.setenv("YBTPU_MERGE_CHUNK_ROWS", "2048")
    staged = run_merge.stage_runs_from_slabs(runs)
    params = GCParams((1 << 19) << 12, True)
    h = run_merge.launch_merge_gc(staged, params)
    assert isinstance(h, run_merge._ChunkedMergeGCHandle)
    perm, keep, mk = h.result()
    merged = concat_slabs(runs)
    offsets = np.concatenate(([0], np.cumsum([r.n for r in runs]))).tolist()
    order_c, keep_c, mk_c = compact_cpu_baseline(
        merged, offsets, (1 << 19) << 12, True, False)
    assert np.array_equal(perm[keep], order_c[keep_c])
    assert np.array_equal(perm[mk], order_c[mk_c])


def test_chunked_disabled_below_threshold(monkeypatch):
    rng = np.random.default_rng(15)
    runs = [_make_run(rng, 300, key_space=60) for _ in range(4)]
    monkeypatch.setenv("YBTPU_MERGE_CHUNK_ROWS", "1048576")
    staged = run_merge.stage_runs_from_slabs(runs)
    h = run_merge.launch_merge_gc(staged, GCParams((1 << 19) << 12, True))
    assert not isinstance(h, run_merge._ChunkedMergeGCHandle)
