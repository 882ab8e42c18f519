"""Batched device point-read kernels: bloom probe + block locate + gather.

ROADMAP item 4: TPU sequential scan runs at 12.4M rows/s while point reads
do ~87k/s, because every `DB.get` walks the block index in host Python one
key at a time — even though the key columns it searches increasingly sit in
HBM already (the device-resident slab cache, storage/device_cache.py).
This module batches the SST half of a point read into three fused device
programs over a padded key batch:

  1. `_fnv64_fused` — FNV-1a over the doc-key prefix of every query, in
     two uint32 limbs (int64 is avoided on device, like the hybrid-time
     limbs in ops/merge_gc.py). The exact twin of
     `storage/bloom.fnv64_masked`, which that module documents as the CPU
     path of this kernel.
  2. `_bloom_probe_fused` — double-hashed probe of one SST's bloom bits
     for the whole batch (ref: the reference's bloom-before-seek,
     rocksdb/table/block_based_table_reader.cc:1144): an SST none of the
     batch's keys can hit never pays a locate dispatch.
  3. `_locate_gather_fused` — vectorized binary seek over the RESIDENT
     staged column matrix (ops/merge_gc.StagedCols): for each query, the
     first entry in internal-key order with key == q and ht <= read_ht
     (the newest visible version — `DB.get`'s seek semantics), gathered
     with its (ht, wid) so the host only decodes the winner's block for
     value bytes. Optionally seeded by a learned per-SST index.

Learned per-SST index ("A Pragmatic Approach to Learned Indexing in
RocksDB", PAPERS.md): a tiny piecewise-linear model over the first 8 key
bytes, fit at flush/compaction time — `_index_fit_fused` runs over the
staged columns when they are already in HBM for free; the numpy twin in
storage/learned_index.py covers host-written SSTs. The model only narrows
the search window (static `_LG_WINDOW` steps instead of log2(n_pad)); a
misprediction beyond the recorded error bound is DETECTED by the binary-
search invariant check and the key falls back to the exact per-key path —
correctness never depends on the model.

Shapes bucket like every other kernel family: batches pad to
`BATCH_BUCKETS`, widths are `quantize_width` points, matrices are
`bucket_size` lattices — all registered in the compile-surface manifest
(tools/analysis/kernel_manifest.json) under the PR 7 budget/prewarm
discipline.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from yugabyte_tpu.ops.merge_gc import (
    _ROW_HT_HI, _ROW_HT_LO, _ROW_KEY_LEN, _ROW_WID, _ROW_WORDS, StagedCols,
    bucket_size)
from yugabyte_tpu.utils.jax_setup import Prewarm  # also: compilation cache

# Learned-index lattice: segment count is a single static (the anchors
# array shape), and the error bound must fit the fixed window search —
# 2*err+1 candidate positions resolved in _LG_WINDOW halvings. The
# canonical constants live in storage/learned_index.py (jax-free, every
# flush imports it); the assert pins the window/bound lock-step.
from yugabyte_tpu.storage.learned_index import (  # noqa: E402
    LINDEX_MAX_ERR, LINDEX_MIN_ENTRIES, LINDEX_SEGMENTS)

_LG_WINDOW = 15
assert LINDEX_MAX_ERR == (1 << (_LG_WINDOW - 1)) - 2

_K_MAX = 12                 # BloomFilterBuilder clamps k to [1, 12]
# the u32 probe arithmetic needs i*(h2 % m) < 2^32 for i < _K_MAX
BLOOM_PROBE_MAX_BITS = 1 << 28

BATCH_BUCKETS = (64, 1024)


def batch_bucket(n: int) -> int:
    """Padded batch size: the two-point lattice keeps the compile surface
    at two executables per (kernel, shape) instead of one per batch."""
    return BATCH_BUCKETS[0] if n <= BATCH_BUCKETS[0] else BATCH_BUCKETS[1]


def point_read_metrics():
    """Process-wide batched-read observability (satellite: batch size
    histogram, learned-index hit/fallback counters, device fallbacks)."""
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    e = ROOT_REGISTRY.entity("server", "point_read")
    return {
        "batches": e.counter(
            "point_read_batches_total",
            "multi_get batches resolved through the device kernels"),
        "keys": e.counter(
            "point_read_batched_keys_total",
            "keys resolved through the batched device path"),
        "batch_rows": e.histogram(
            "point_read_batch_rows",
            "multi_get batch sizes reaching the device path"),
        "bloom_skips": e.counter(
            "point_read_bloom_skipped_sst_total",
            "per-SST locate dispatches skipped because the bloom probe "
            "rejected every key in the batch"),
        "learned_hits": e.counter(
            "point_read_learned_hit_total",
            "locate dispatches that used a learned per-SST index"),
        "learned_fallbacks": e.counter(
            "point_read_learned_fallback_total",
            "keys re-resolved exactly after a learned-index "
            "misprediction beyond the recorded error bound"),
        "device_fallbacks": e.counter(
            "point_read_device_fallback_total",
            "multi_get batches completed via the native per-key path "
            "after a device fault"),
        "value_fetch_rows": e.counter(
            "point_read_value_fetch_rows_total",
            "SST winners whose value the batched path fetched"),
        "value_fetch_blocks": e.counter(
            "point_read_value_fetch_blocks_total",
            "distinct blocks read for those winners, chunk by chunk: "
            "rows over blocks is how many winners one read_block serves"),
        "max_error": e.gauge(
            "learned_index_max_error_rows",
            "recorded max-error bound (entry positions) of the most "
            "recently fitted learned per-SST index"),
    }


# ---------------------------------------------------------------------------
# FNV-1a in two uint32 limbs (exact twin of storage/bloom.fnv64_masked)
# ---------------------------------------------------------------------------

_FNV_OFFSET_HI = 0xCBF29CE4
_FNV_OFFSET_LO = 0x84222325
# FNV prime 0x100000001B3 = 2^40 + 0x1B3; the multiply below decomposes
# h*P mod 2^64 into shift/add limbs so no intermediate needs 64 bits
_FNV_PRIME_LOW = 0x1B3


def _mul64_by_prime(hi, lo):
    """(hi, lo) * 0x100000001B3 mod 2^64, in uint32 limb arithmetic.

    h*P = h*2^40 + h*0x1B3 (mod 2^64):
      h*2^40 contributes (lo << 8) to the high limb (everything above
      2^64 drops); h*0x1B3 is computed via a 16-bit split of `lo` so no
      partial product exceeds 2^25.
    """
    p = jnp.uint32(_FNV_PRIME_LOW)
    a = lo >> jnp.uint32(16)
    b = lo & jnp.uint32(0xFFFF)
    t = a * p                      # < 2^25
    u = b * p                      # < 2^25
    s1 = t << jnp.uint32(16)       # == (t & 0xFFFF) << 16 (wrapping)
    new_lo = s1 + u                # wrapping u32
    carry = (new_lo < s1).astype(jnp.uint32)
    new_hi = ((lo << jnp.uint32(8)) + hi * p
              + (t >> jnp.uint32(16)) + carry)
    return new_hi, new_lo


@functools.partial(jax.jit, static_argnames=("w",))
def _fnv64_fused(qwords, qlens, w: int):
    """FNV-1a over the first qlens[i] bytes of each query key.

    qwords: uint32 [B, w] big-endian packed key words (ops/slabs.py
    layout); qlens: int32 [B]. Returns (h1, h2) uint32 [B]: the double-
    hash pair the bloom builder/prober derive from the 64-bit hash
    (h1 = low word, h2 = high word | 1)."""
    b = qwords.shape[0]
    hi = jnp.full((b,), jnp.uint32(_FNV_OFFSET_HI))
    lo = jnp.full((b,), jnp.uint32(_FNV_OFFSET_LO))
    for j in range(w * 4):
        word = qwords[:, j // 4]
        byte = (word >> jnp.uint32(8 * (3 - (j % 4)))) & jnp.uint32(0xFF)
        active = qlens > j
        nhi, nlo = _mul64_by_prime(hi, lo ^ byte)
        hi = jnp.where(active, nhi, hi)
        lo = jnp.where(active, nlo, lo)
    return lo, hi | jnp.uint32(1)


@jax.jit
def _bloom_probe_fused(h1, h2, bloom_words, m_bits, k):
    """Double-hashed bloom probe of one SST for a whole key batch.

    h1/h2: uint32 [B]; bloom_words: uint32 [m_words_pad] little-endian
    bit words (the builder's byte layout viewed as '<u4'); m_bits uint32
    scalar (true filter size — padding words are never addressed);
    k int32 scalar. Position arithmetic matches the uint64 CPU path via
    modular identities: (h1 + i*h2) % m == ((h1%m) + (i*(h2%m)) % m) % m,
    every intermediate < 2^32 while m < BLOOM_PROBE_MAX_BITS."""
    m = m_bits
    h1m = h1 % m
    h2m = h2 % m
    ok = jnp.ones(h1.shape, bool)
    for i in range(_K_MAX):
        pos = (h1m + (jnp.uint32(i) * h2m) % m) % m
        word = bloom_words[pos >> jnp.uint32(5)]
        bit = (word >> (pos & jnp.uint32(31))) & jnp.uint32(1)
        ok = ok & ((bit == jnp.uint32(1)) | (jnp.int32(i) >= k))
    return ok


# ---------------------------------------------------------------------------
# Learned-index prediction (shared by fit and inference so the recorded
# error bound is measured with the inference arithmetic)
#
# The key coordinate is the two uint32 words at the file's common-prefix
# word offset p (word-aligned prefix skip: tablets share long key
# prefixes, and a coordinate that starts inside the shared bytes would
# collapse every key onto a handful of values). Anchors persist as EXACT
# uint32 limb pairs — segment selection and the (x - a0) differences are
# integer-exact; float32 enters only for the final interpolation of a
# difference, whose relative error is absorbed by the measured bound.
# ---------------------------------------------------------------------------

def _sub64(x_hi, x_lo, y_hi, y_lo):
    """(x - y) as two uint32 limbs (callers guarantee x >= y or mask)."""
    lo = x_lo - y_lo
    borrow = (x_lo < y_lo).astype(jnp.uint32)
    return x_hi - y_hi - borrow, lo


def _f64ish(hi, lo):
    """float32 value of a two-limb difference (exact compares happened
    already; only the interpolation ratio rides this)."""
    return (hi.astype(jnp.float32) * jnp.float32(4294967296.0)
            + lo.astype(jnp.float32))


def _ge64(x_hi, x_lo, y_hi, y_lo):
    return (x_hi > y_hi) | ((x_hi == y_hi) & (x_lo >= y_lo))


def _predict_pos(x_hi, x_lo, a_hi, a_lo, anchor_pos):
    """Piecewise-linear position prediction from exact two-limb anchors.
    a_hi/a_lo: uint32 [S+1] anchor coordinates at anchor_pos (int32
    [S+1], positions 0..n-1). Returns float32 predictions."""
    s = a_hi.shape[0] - 1
    seg = jnp.zeros(x_hi.shape, jnp.int32)
    for i in range(1, s):
        seg = seg + _ge64(x_hi, x_lo, a_hi[i], a_lo[i]).astype(jnp.int32)
    a0h, a0l = a_hi[seg], a_lo[seg]
    a1h, a1l = a_hi[seg + 1], a_lo[seg + 1]
    p0 = anchor_pos[seg].astype(jnp.float32)
    p1 = anchor_pos[seg + 1].astype(jnp.float32)
    ge0 = _ge64(x_hi, x_lo, a0h, a0l)
    dx = _f64ish(*_sub64(x_hi, x_lo, a0h, a0l))
    da = _f64ish(*_sub64(a1h, a1l, a0h, a0l))
    t = jnp.where(ge0 & (da > 0), dx / jnp.where(da > 0, da,
                                                 jnp.float32(1.0)),
                  jnp.float32(0.0))
    t = jnp.clip(t, 0.0, 1.0)
    return p0 + t * (p1 - p0)


def _x_words(words_by_row, p, w: int):
    """The coordinate limbs: key words p and p+1, p clamped to [0, w-2].
    words_by_row: callable j -> the j-th key-word vector (rows of a cols
    matrix or columns of a query batch)."""
    pp = jnp.clip(p, 0, w - 2)
    stacked_hi = jnp.stack([words_by_row(j) for j in range(w)])
    x_hi = jnp.take(stacked_hi, pp, axis=0)
    x_lo = jnp.take(stacked_hi, pp + 1, axis=0)
    return x_hi, x_lo


@functools.partial(jax.jit, static_argnames=("n_segments", "w"))
def _index_fit_fused(cols, n, n_segments: int, w: int):
    """Fit the per-SST model over an already-staged (sorted) cols matrix
    — the flush/compaction write-through path, where the sorted key
    columns are in HBM for free. Computes the prefix-skip offset p from
    the first/last entry in-kernel (no D2H), gathers exact anchor limbs,
    and measures max_err by predicting every real entry with the
    inference arithmetic — the bound is self-consistent by construction.
    Returns (a_hi u32 [S+1], a_lo u32 [S+1], p i32, max_err i32)."""
    from yugabyte_tpu.storage.learned_index import LINDEX_MAX_P
    n_pad = cols.shape[1]
    last = jnp.clip(n - 1, 0, n_pad - 1)
    # leading key words shared by the first and last entry — by
    # sortedness, shared by every entry in between. Capped at
    # LINDEX_MAX_P so the model depends only on the first 16 key bytes
    # (byte-identical to the host twins regardless of staged width).
    run = jnp.int32(1)
    p = jnp.int32(0)
    for j in range(min(w - 2, LINDEX_MAX_P)):
        eqj = (cols[_ROW_WORDS + j, 0]
               == cols[_ROW_WORDS + j, last]).astype(jnp.int32)
        run = run * eqj
        p = p + run
    x_hi, x_lo = _x_words(lambda j: cols[_ROW_WORDS + j], p, w)
    anchor_pos = (jnp.arange(n_segments + 1, dtype=jnp.int32)
                  * (n - jnp.int32(1))) // jnp.int32(n_segments)
    a_hi = x_hi[anchor_pos]
    a_lo = x_lo[anchor_pos]
    pred = _predict_pos(x_hi, x_lo, a_hi, a_lo, anchor_pos)
    idx = jnp.arange(n_pad, dtype=jnp.int32)
    err = jnp.abs(jnp.round(pred).astype(jnp.int32) - idx)
    max_err = jnp.max(jnp.where(idx < n, err, 0))
    return a_hi, a_lo, p, max_err


# ---------------------------------------------------------------------------
# Locate + gather
# ---------------------------------------------------------------------------

def _seek_pred(cols, i, n, qwords, qlens_u, rhi, rlo, w: int):
    """P(i) [B]: entry i is at-or-after query's seek point — key_i > q,
    or key_i == q with ht_i <= read_ht (versions sort HT-descending, so
    the FIRST true position is the newest visible version). Padding
    columns (all-0xFF words, sentinel len) evaluate key > q. P(n) := True."""
    ii = jnp.clip(i, 0, cols.shape[1] - 1)
    gt = jnp.zeros(i.shape, bool)
    eq = jnp.ones(i.shape, bool)
    for j in range(w):
        c = cols[_ROW_WORDS + j][ii]
        gt = gt | (eq & (c > qwords[:, j]))
        eq = eq & (c == qwords[:, j])
    klen = cols[_ROW_KEY_LEN][ii]
    gt = gt | (eq & (klen > qlens_u))
    eq = eq & (klen == qlens_u)
    ht_hi = cols[_ROW_HT_HI][ii]
    ht_lo = cols[_ROW_HT_LO][ii]
    le = (ht_hi < rhi) | ((ht_hi == rhi) & (ht_lo <= rlo))
    return jnp.where(i >= n, True, gt | (eq & le))


@functools.partial(jax.jit, static_argnames=("w", "use_model"))
def _locate_gather_fused(cols, n, qwords, qlens, rhi, rlo,
                         a_hi, a_lo, anchor_pos, p, max_err,
                         w: int, use_model: bool):
    """Batched point locate over one staged SST + survivor field gather.

    cols: uint32 [8+w, n_pad] resident slab matrix (sorted); n: int32
    real-entry count; qwords/qlens: the padded query batch; rhi/rlo: the
    read_ht limbs; a_hi/a_lo/anchor_pos/p/max_err: learned-index
    operands (ignored when use_model=False — the exact full seek runs).

    Returns (idx, hit, ht_hi, ht_lo, wid, miss) over [B]: idx is the
    seek position; hit means an exact key match visible at read_ht (its
    ht/wid gathered); miss flags a learned-index misprediction the
    binary-search invariant check caught — the caller must re-resolve
    those keys exactly (correctness never rides the model)."""
    n_pad = cols.shape[1]
    b = qwords.shape[0]
    qlens_u = qlens.astype(jnp.uint32)

    def pred(i):
        return _seek_pred(cols, i, n, qwords, qlens_u, rhi, rlo, w)

    if use_model:
        x_hi, x_lo = _x_words(lambda j: qwords[:, j], p, w)
        pi = jnp.round(_predict_pos(x_hi, x_lo, a_hi, a_lo, anchor_pos)
                       ).astype(jnp.int32)
        lo = jnp.clip(pi - max_err, 0, n)
        hi = jnp.clip(pi + max_err + jnp.int32(1), 0, n)
        steps = _LG_WINDOW
    else:
        lo = jnp.zeros((b,), jnp.int32)
        hi = jnp.zeros((b,), jnp.int32) + n
        steps = int(n_pad).bit_length()
    for _ in range(steps):
        active = lo < hi
        mid = (lo + hi) >> 1
        p = pred(mid)
        lo = jnp.where(active & ~p, mid + jnp.int32(1), lo)
        hi = jnp.where(active & p, mid, hi)
    r = lo
    # binary-search invariant: the true seek point satisfies
    # (r == 0 or not P(r-1)) and (r == n or P(r)); a learned window that
    # excluded the answer fails one side and flags the key for exact
    # re-resolution. In exact mode the invariant holds by construction.
    if use_model:
        ok_left = (r == 0) | ~pred(jnp.maximum(r - 1, 0))
        ok_right = (r >= n) | pred(r)
        miss = ~(ok_left & ok_right)
    else:
        miss = jnp.zeros((b,), bool)
    rr = jnp.clip(r, 0, n_pad - 1)
    eq = jnp.ones((b,), bool)
    for j in range(w):
        eq = eq & (cols[_ROW_WORDS + j][rr] == qwords[:, j])
    eq = eq & (cols[_ROW_KEY_LEN][rr] == qlens_u)
    ht_hi = cols[_ROW_HT_HI][rr]
    ht_lo = cols[_ROW_HT_LO][rr]
    le = (ht_hi < rhi) | ((ht_hi == rhi) & (ht_lo <= rlo))
    hit = (r < n) & eq & le & ~miss
    wid = cols[_ROW_WID][rr]
    return r, hit, ht_hi, ht_lo, wid, miss


# ---------------------------------------------------------------------------
# Host wrappers (padding, per-reader bloom residency, dispatch metrics,
# device-fault injection sites)
# ---------------------------------------------------------------------------

def pack_query_batch(keys: Sequence[bytes], w: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Pad a key batch to (batch_bucket(B), w) uint32 words + int32 lens.
    Keys longer than w*4 bytes are truncated in the word matrix but keep
    their true length, so the exact-match compare can never accept them
    (no entry of a w-wide SST has key_len > w*4). The first w' columns of
    the result ARE the batch packed at a narrower width w' (big-endian
    words, zero padded): a chunk is packed once, at its widest width."""
    n = len(keys)
    stride = w * 4
    lens = np.fromiter(map(len, keys), dtype=np.int64, count=n)
    clens = lens
    if n and int(lens.max()) > stride:
        keys = [k[:stride] for k in keys]
        clens = np.minimum(lens, stride)
    rows = np.zeros((n, stride), dtype=np.uint8)
    flat = np.frombuffer(b"".join(keys), dtype=np.uint8)
    # byte i of the joined keys lands at its row's start + its offset
    # within the key: i + (row * stride - start of the row's key)
    shift = np.arange(n, dtype=np.int64) * stride
    shift[1:] -= np.cumsum(clens[:-1])
    rows.reshape(-1)[np.arange(len(flat), dtype=np.int64)
                     + np.repeat(shift, clens)] = flat
    b_pad = batch_bucket(n)
    out_w = np.zeros((b_pad, w), dtype=np.uint32)
    out_w[:n] = rows.view(">u4")
    out_l = np.zeros(b_pad, dtype=np.int32)
    out_l[:n] = lens
    return out_w, out_l


# Operands that depend on the file alone are put on the device once and
# kept on the reader (dropped by its close()). The scalars and the
# model's arrays go UNCOMMITTED (no device named), as the arrays that
# jnp.asarray / jnp.int32 made of them on every call were: a committed
# operand lowers with a sharding on its argument, which is another
# program to the compiler than the one the compile cache holds.

def bloom_device_words(reader, device=None):
    """The SST's bloom bit array as a padded device uint32 vector with
    its size and probe count as device scalars (uint32 m_bits, int32 k:
    the probe program's avals), kept on the reader until it closes
    (blooms are ~1.25 bytes/key — tiny next to the staged key columns).
    None when the filter is too large for the u32 probe arithmetic."""
    cached = reader._bloom_dev
    if cached is not None:
        return cached
    bloom = reader.bloom
    if bloom.m_bits >= BLOOM_PROBE_MAX_BITS or bloom.m_bits == 0:
        return None
    words = np.frombuffer(bloom.bits.tobytes(), dtype="<u4")
    padded = np.zeros(bucket_size(len(words)), dtype=np.uint32)
    padded[: len(words)] = words
    reader._bloom_dev = (
        jax.device_put(padded, device),
        *jax.device_put((np.uint32(bloom.m_bits), np.int32(bloom.k))))
    return reader._bloom_dev


@functools.cache
def _no_model_operands():
    """The locate program's model operands where no model serves (the
    exact full seek ignores them): zeros of the model's shapes, put on
    the device once for the life of the process."""
    z = np.zeros(LINDEX_SEGMENTS + 1, dtype=np.uint32)
    return jax.device_put(
        (z, z, z.astype(np.int32), np.int32(0), np.int32(0)))


def locate_device_operands(reader):
    """What the locate program needs of one SST besides its staged
    columns: (n, model) on the device, kept on the reader until it
    closes. n is the int32 entry count; model is the validated learned
    index (a_hi, a_lo, anchor_pos, p, max_err: storage/learned_index.
    model_operands) or None where the file carries none that serves."""
    cached = reader._locate_dev
    if cached is not None:
        return cached
    from yugabyte_tpu.storage import learned_index
    n = int(reader.props.n_entries)
    model = learned_index.model_operands(reader.props.lindex, n)
    if model is not None:
        a_hi, a_lo, anchor_pos, p, max_err = model
        model = (a_hi, a_lo, anchor_pos, np.int32(p), np.int32(max_err))
    reader._locate_dev = jax.device_put((np.int32(n), model))
    return reader._locate_dev


def hash_batch(qwords: np.ndarray, dkls: np.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Device FNV over the doc-key prefix of each padded query (uint32
    [B, w] words, int32 [B] doc-key lengths, as numpy: the program's own
    argument path uploads them)."""
    from yugabyte_tpu.ops.run_merge import quantize_width
    from yugabyte_tpu.utils.latency import sub_span
    from yugabyte_tpu.utils.metrics import record_kernel_dispatch
    # the batch is packed at a quantize_width point already; re-routing
    # the static through the quantizer keeps the lattice explicit
    with sub_span("device_enqueue"):
        h1, h2 = _fnv64_fused(qwords, dkls,
                              w=quantize_width(int(qwords.shape[1])))
    record_kernel_dispatch("kernel_point_hash", int(qwords.shape[0]),
                           int(qwords.shape[0]))
    return h1, h2


def probe_bloom(reader, h1, h2, device=None) -> Optional[np.ndarray]:
    """Probe one SST's bloom for the batch; None = no usable filter
    (treat every key as a maybe — the bloom is advisory)."""
    from yugabyte_tpu.utils.latency import SUB_DEVICE_WAIT, sub_span
    with sub_span("device_enqueue"):
        bd = bloom_device_words(reader, device)
        if bd is None:
            return None
        ok = _bloom_probe_fused(h1, h2, *bd)
    with sub_span(SUB_DEVICE_WAIT):
        return np.asarray(ok)


def locate_batch(staged: StagedCols, qwords: np.ndarray,
                 qlens: np.ndarray, rhi: np.uint32, rlo: np.uint32,
                 n_dev, model_dev=None):
    """Run the locate+gather kernel over one staged SST.

    qwords uint32 [B, staged.w] / qlens int32 [B] / rhi, rlo (the read
    time's halves, numpy uint32 scalars): the chunk's operands, as
    numpy. n_dev, model_dev: the file's own, resident
    (locate_device_operands); model_dev None runs the exact full binary
    seek. Returns numpy (idx, hit, ht_hi, ht_lo, wid, miss).
    """
    from yugabyte_tpu.ops import device_faults
    from yugabyte_tpu.utils.latency import SUB_DEVICE_WAIT, sub_span
    from yugabyte_tpu.utils.metrics import record_kernel_dispatch
    b = int(qwords.shape[0])
    use_model = model_dev is not None
    device_faults.maybe_fault("dispatch")
    with sub_span("device_enqueue"):
        out = _locate_gather_fused(
            staged.cols_dev, n_dev, qwords, qlens, rhi, rlo,
            *(model_dev if use_model else _no_model_operands()),
            w=staged.w, use_model=use_model)
    device_faults.maybe_fault("result")
    with sub_span(SUB_DEVICE_WAIT):
        idx, hit, ht_hi, ht_lo, wid, miss = (np.asarray(x) for x in out)
    record_kernel_dispatch("kernel_point_locate", b, b)
    return idx, hit, ht_hi, ht_lo, wid, miss


def fit_learned_index_device(staged: StagedCols) -> Optional[dict]:
    """Fit the learned index over an already-staged cols matrix (the
    device write-through path: compaction outputs' sorted keys are in
    HBM for free). Returns the persistable model dict, or None when the
    span is too small or the bound too loose to help."""
    from yugabyte_tpu.storage import learned_index
    if staged.n < LINDEX_MIN_ENTRIES or staged.w < 2:
        return None
    a_hi, a_lo, p, max_err = _index_fit_fused(
        staged.cols_dev, jnp.int32(staged.n),
        n_segments=LINDEX_SEGMENTS, w=staged.w)
    return learned_index.finish_model(np.asarray(a_hi), np.asarray(a_lo),
                                      int(np.asarray(p)),
                                      int(np.asarray(max_err)),
                                      staged.n)


# ---------------------------------------------------------------------------
# Prewarm (PrewarmKernelsOp folds this into the startup compile pass)
# ---------------------------------------------------------------------------

# (n_pad, w) lattice points the manifest declares for locate/fit; the
# probe/hash programs warm over (B, m_words) / (B, w) from the same sets
_PREWARM_NPADS = (1 << 16, 1 << 20)
_PREWARM_WIDTHS = (4, 8)
_PREWARM_MWORDS = (1 << 14, 1 << 18)


def prewarm_point_read() -> Prewarm:
    """Ahead-of-traffic compile of the declared point-read buckets
    (mirrors ops/run_merge.prewarm_buckets; called by PrewarmKernelsOp).
    Returns what compiled and what the compiler refused."""
    pw = Prewarm("point_read")

    def _warm(what, lower_fn):
        pw.warm(what, lambda: lower_fn().compile())

    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    u32 = jax.ShapeDtypeStruct((), jnp.uint32)
    sdt = jax.ShapeDtypeStruct
    for b in BATCH_BUCKETS:
        for w in _PREWARM_WIDTHS:
            _warm(f"fnv64 (B={b} w={w})",
                  lambda: _fnv64_fused.lower(
                      sdt((b, w), jnp.uint32), sdt((b,), jnp.int32), w=w))
        for mw in _PREWARM_MWORDS:
            _warm(f"bloom_probe (B={b} m_words={mw})",
                  lambda: _bloom_probe_fused.lower(
                      sdt((b,), jnp.uint32), sdt((b,), jnp.uint32),
                      sdt((mw,), jnp.uint32), u32, i32))
        for w in _PREWARM_WIDTHS:
            for n_pad in _PREWARM_NPADS:
                for use_model in (False, True):
                    _warm(f"locate (B={b} w={w} n_pad={n_pad} "
                          f"model={use_model})",
                          lambda: _locate_gather_fused.lower(
                              sdt((8 + w, n_pad), jnp.uint32), i32,
                              sdt((b, w), jnp.uint32),
                              sdt((b,), jnp.int32), u32, u32,
                              sdt((LINDEX_SEGMENTS + 1,), jnp.uint32),
                              sdt((LINDEX_SEGMENTS + 1,), jnp.uint32),
                              sdt((LINDEX_SEGMENTS + 1,), jnp.int32),
                              i32, i32, w=w, use_model=use_model))
    for w in _PREWARM_WIDTHS:
        for n_pad in _PREWARM_NPADS:
            _warm(f"index_fit (n_pad={n_pad} w={w})",
                  lambda: _index_fit_fused.lower(
                      sdt((8 + w, n_pad), jnp.uint32), i32,
                      n_segments=LINDEX_SEGMENTS, w=w))
    return pw


def point_read_snapshot() -> dict:
    """Batched point-read block for /compactionz."""
    m = point_read_metrics()
    return {
        "batches": m["batches"].value(),
        "batched_keys": m["keys"].value(),
        "bloom_skipped_ssts": m["bloom_skips"].value(),
        "learned_index_hits": m["learned_hits"].value(),
        "learned_index_fallbacks": m["learned_fallbacks"].value(),
        "device_fallbacks": m["device_fallbacks"].value(),
        "value_fetch_rows": m["value_fetch_rows"].value(),
        "value_fetch_blocks": m["value_fetch_blocks"].value(),
        "learned_index_max_error": m["max_error"].value(),
    }
