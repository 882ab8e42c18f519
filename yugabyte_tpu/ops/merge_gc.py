"""TPU merge + MVCC-GC kernel: the north-star compaction hot path.

Replaces the reference's three sequential hot loops (SURVEY.md section 3.4):
 1. k-way MergingIterator min-heap merge   (ref: rocksdb/table/merger.cc:51)
 2. CompactionIterator seqno/version dedup (ref: rocksdb/db/compaction_iterator.cc:97)
 3. DocDBCompactionFilter MVCC GC          (ref: docdb/docdb_compaction_filter.cc:74-320)

with ONE fused device program per call:

 - merge: LSD radix sort over key columns — a `lax.fori_loop` whose body is a
   single 2-operand STABLE `lax.sort` pass over a dynamically-selected column.
   One sort op in the HLO (fast compile; a W+5-operand lexicographic sort
   costs minutes of XLA compile on TPU), one device dispatch total. Keys
   sort in exact memcmp order (see ops/slabs.py).
 - version GC: segmented prefix ops (cumsum/cummax). Within each full-key
   segment (versions sorted HT-descending), every version with
   ht > history_cutoff is retained history; among versions <= cutoff only the
   FIRST (visible at cutoff) survives (docdb_compaction_filter.cc:166).
 - subtree overwrite: a root-level (DocKey, no subkeys) write visible at the
   cutoff overwrites every deeper entry with DocHybridTime <= its own
   (overwrite-stack truncation, docdb_compaction_filter.cc:104-123,
   restricted to depth-2 documents: row + column entries; deeper docs take
   the CPU semantic path). At most one such root version exists per doc
   segment, so propagation is cummax over flagged positions + gathers.
 - TTL expiry -> tombstone conversion / drop at major compactions
   (docdb_compaction_filter.cc:260-279); visible tombstones dropped at major
   compactions (:316-319).

I/O is transfer-optimized: all inputs ship as ONE
contiguous uint32 matrix `cols[R, n_pad]`; outputs are the permutation plus
keep/make-tombstone as packed bitmasks. Shapes bucket to powers of two so XLA
compiles once per bucket; the persistent compilation cache
(utils/jax_setup.py) amortizes across processes. int64 is avoided: hybrid
times travel as two uint32 limbs, TTL arithmetic is two-limb 20/32-bit.

Fixed row layout of `cols` (rows R = 8 + W):
    0 key_len | 1 doc_key_len | 2 ht_hi | 3 ht_lo | 4 write_id
    5 entry_flags | 6 ttl_hi | 7 ttl_lo | 8.. key words 0..W-1
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from yugabyte_tpu.ops.slabs import (
    FLAG_HAS_TTL, FLAG_OBJECT_INIT, FLAG_TOMBSTONE, KVSlab)
from yugabyte_tpu.utils import jax_setup  # noqa: F401  (compilation cache)

_ROW_KEY_LEN, _ROW_DKL, _ROW_HT_HI, _ROW_HT_LO, _ROW_WID = 0, 1, 2, 3, 4
_ROW_FLAGS, _ROW_TTL_HI, _ROW_TTL_LO, _ROW_WORDS = 5, 6, 7, 8


@dataclass(frozen=True)
class GCParams:
    history_cutoff_ht: int      # HybridTime.value; versions above stay
    is_major_compaction: bool   # bottommost level: tombstones can vanish
    retain_deletes: bool = False  # e.g. during index backfill (ref :288)


def _le_u64(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def _last_valid_combine(a, b):
    """Associative combine for 'value at the last flagged position':
    (valid, *vals) pairs where the right side wins if it has seen a
    flagged element. Classic last-write-wins segment combine — associative
    because the rightmost valid element determines the result regardless
    of grouping."""
    av = a[0]
    bv = b[0]
    out = [av | bv]
    for x, y in zip(a[1:], b[1:]):
        out.append(jnp.where(bv, y, x))
    return tuple(out)


def gc_over_sorted(s, w: int, cutoff_hi, cutoff_lo,
                   cutoff_phys_hi, cutoff_phys_lo,
                   is_major: bool, retain_deletes: bool,
                   snapshot: bool = False):
    """MVCC-GC decisions over an ALREADY-MERGED cols matrix `s` [R, n].

    The traceable GC half shared by every merge strategy: the radix path
    below (sort_and_gc), the pre-sorted-run bitonic merge (ops/run_merge.py)
    and the distributed per-shard path all produce a key-sorted `s` and then
    apply this identical filter, so keep/make-tombstone decisions are
    byte-identical across paths (differential-tested).

    Semantics (ref: docdb/docdb_compaction_filter.cc):
      - version visibility within full-key segments (:166)
      - TTL expiry -> tombstone conversion / drop at major (:260-279)
      - root-subtree overwrite truncation, depth-2 (:104-123)
      - visible-tombstone drop at major compactions (:316-319)
    Returns (keep, make_tombstone) bool arrays [n].
    """
    n = s.shape[1]
    u32max = jnp.uint32(0xFFFFFFFF)
    s_len = s[_ROW_KEY_LEN].astype(jnp.int32)
    s_dkl = s[_ROW_DKL].astype(jnp.int32)
    s_ht_hi, s_ht_lo, s_wid = s[_ROW_HT_HI], s[_ROW_HT_LO], s[_ROW_WID]
    s_flags = s[_ROW_FLAGS]
    s_ttl_hi, s_ttl_lo = s[_ROW_TTL_HI], s[_ROW_TTL_LO]
    s_words = s[_ROW_WORDS:]                 # [w, n]

    # ---- segment structure ------------------------------------------------
    prev_words = jnp.concatenate([jnp.zeros((w, 1), s_words.dtype), s_words[:, :-1]], axis=1)
    prev_len = jnp.concatenate([jnp.full((1,), -1, s_len.dtype), s_len[:-1]])
    same_key = jnp.all(s_words == prev_words, axis=0) & (s_len == prev_len)
    new_seg = ~same_key.at[0].set(False)

    word_idx = jnp.arange(w, dtype=jnp.int32)[:, None]
    nbytes = jnp.clip(s_dkl[None, :] - word_idx * 4, 0, 4)
    mask = jnp.where(nbytes >= 4, u32max,
                     jnp.where(nbytes == 0, jnp.uint32(0),
                               (u32max << ((4 - nbytes).astype(jnp.uint32) * 8)) & u32max))
    doc_words = s_words & mask
    prev_doc_words = jnp.concatenate([jnp.zeros((w, 1), s_words.dtype), doc_words[:, :-1]], axis=1)
    prev_dkl = jnp.concatenate([jnp.full((1,), -1, s_dkl.dtype), s_dkl[:-1]])
    same_doc = jnp.all(doc_words == prev_doc_words, axis=0) & (s_dkl == prev_dkl)
    new_doc = ~same_doc.at[0].set(False)
    doc_seg_id = jnp.cumsum(new_doc.astype(jnp.int32))

    # ---- version visibility within full-key segments ----------------------
    c = _le_u64(s_ht_hi, s_ht_lo, cutoff_hi, cutoff_lo)
    c_i = c.astype(jnp.int32)
    total = jnp.cumsum(c_i)
    base = jax.lax.cummax(jnp.where(new_seg, total - c_i, 0))
    within_c = total - base
    visible_slot = c & (within_c == 1)
    keep_version = ~c | visible_slot

    # ---- TTL expiry -------------------------------------------------------
    has_ttl = (s_flags & FLAG_HAS_TTL) != 0
    sum_lo = (s_ht_lo >> 12) + s_ttl_lo
    carry = sum_lo >> 20
    sum_hi = s_ht_hi + s_ttl_hi + carry
    sum_lo = sum_lo & jnp.uint32(0xFFFFF)
    expired = has_ttl & ((sum_hi < cutoff_phys_hi) |
                         ((sum_hi == cutoff_phys_hi) & (sum_lo <= cutoff_phys_lo)))
    already_tomb = (s_flags & FLAG_TOMBSTONE) != 0
    is_tomb = already_tomb | (expired & c)

    # ---- root-subtree overwrite ------------------------------------------
    is_root = s_len == s_dkl
    ov_flag = is_root & visible_slot
    # forward-fill the overwrite point's (ht, wid, doc segment) from the
    # last ov_flag position via an associative scan. The obvious gather
    # formulation — cummax the flagged index, then x[safe_pos] — costs
    # 4 element-serial 1-D gathers (~77ms of a 136ms kernel at 1M rows,
    # profiled on v5e: TPU lane-axis gathers run ~180MB/s); the last-valid
    # scan is log-depth elementwise and keeps the kernel gather-free.
    ov_valid, ov_hi, ov_lo, ov_wid, ov_doc = jax.lax.associative_scan(
        _last_valid_combine,
        (ov_flag, s_ht_hi, s_ht_lo, s_wid, doc_seg_id))
    in_same_doc = ov_valid & (ov_doc == doc_seg_id)
    # strict <, matching the reference's obsolete check (ref :166 `ht <
    # prev_overwrite_ht`): an exact DocHybridTime tie is NOT covered
    dht_lt = (s_ht_hi < ov_hi) | ((s_ht_hi == ov_hi) & (
        (s_ht_lo < ov_lo) | ((s_ht_lo == ov_lo) & (s_wid < ov_wid))))
    covered = (~is_root) & in_same_doc & dht_lt

    # ---- tombstone GC + result -------------------------------------------
    if snapshot:
        keep = visible_slot & ~covered & ~is_tomb
        return keep, jnp.zeros_like(keep)
    drop_tomb = (visible_slot & is_tomb & jnp.bool_(is_major)
                 & jnp.bool_(not retain_deletes))
    keep = keep_version & ~covered & ~drop_tomb
    make_tombstone = expired & keep & c & ~already_tomb & jnp.bool_(not is_major)
    return keep, make_tombstone


def sort_and_gc(cols, cutoff_hi, cutoff_lo, cutoff_phys_hi, cutoff_phys_lo,
                w: int, is_major: bool, retain_deletes: bool,
                sort_rows=None, n_sort=None, snapshot: bool = False):
    """Traceable core: radix merge + GC over one cols matrix.

    Reused by the single-chip jit wrapper below and by the distributed
    per-shard path (parallel/dist_compact.py) inside shard_map.
    Returns (perm, keep, make_tombstone) as unpacked device arrays.

    sort_rows/n_sort: optional column-pruned radix schedule (see
    build_sort_schedule) — constant columns carry no ordering information,
    so the host drops their passes. Row indices >= _ROW_WORDS sort
    ascending; the ht/wid rows sort descending (complemented in the body).

    snapshot: SCAN mode — the cutoff is a read time and keep marks exactly
    the version set visible AT that time: one version per key (the first
    with dht <= read_ht), minus tombstones, TTL-expired values and
    root-overwrite-covered entries; versions above the read time are
    excluded rather than retained as history. This turns the same fused
    program into the MVCC-resolution half of the scan path (ref: the
    visibility logic of docdb/intent_aware_iterator.cc +
    doc_rowwise_iterator.cc done per-iterator-step in the reference).
    """
    n = cols.shape[1]
    u32max = jnp.uint32(0xFFFFFFFF)

    # ---- merge: LSD radix passes, least-significant column first ----------
    # full sequence: wid desc, ht_lo desc, ht_hi desc, key_len asc, words
    # W-1..0 asc; pruned schedules drop constant columns.
    if sort_rows is None:
        sort_rows = jnp.asarray(
            [_ROW_WID, _ROW_HT_LO, _ROW_HT_HI, _ROW_KEY_LEN]
            + [_ROW_WORDS + j for j in range(w - 1, -1, -1)], dtype=jnp.int32)
        n_sort = 4 + w

    def body(k, perm):
        row = sort_rows[k]
        invert = jnp.where((row >= _ROW_HT_HI) & (row <= _ROW_WID),
                           u32max, jnp.uint32(0))
        col = jax.lax.dynamic_index_in_dim(cols, row, axis=0,
                                           keepdims=False) ^ invert
        _, new_perm = jax.lax.sort([col[perm], perm], num_keys=1, is_stable=True)
        return new_perm

    # (the `cols[0,:1]*0` term imprints cols' varying-axes type on the carry,
    # required when tracing inside shard_map)
    perm0 = jnp.arange(n, dtype=jnp.int32) + cols[0, :1].astype(jnp.int32) * 0
    perm = jax.lax.fori_loop(0, n_sort, body, perm0)

    s = cols[:, perm]                        # gather all rows once
    keep, make_tombstone = gc_over_sorted(
        s, w, cutoff_hi, cutoff_lo, cutoff_phys_hi, cutoff_phys_lo,
        is_major=is_major, retain_deletes=retain_deletes, snapshot=snapshot)
    return perm, keep, make_tombstone


PAD_SENTINEL = 0xFFFFFFFF  # key_len/dkl value marking padding rows


def route_word_mask(dkl, w_route: int, leading: bool = True):
    """Per-word doc-key mask for route prefixes: word i keeps
    clip(dkl - 4*i, 0, 4) leading bytes (big-endian packed keys).

    THE single definition of route masking — chunk boundaries
    (ops/run_merge), host-side splitter sampling, and mesh shard routing
    (parallel/dist_compact) must agree bit-for-bit or documents split
    across partitions.  dkl: int32 [...]; returns u32 mask broadcast
    against the word index on the LEADING axis (leading=True: shape
    [w_route, *dkl.shape]) or the TRAILING axis ([..., w_route])."""
    u32max = jnp.uint32(0xFFFFFFFF)
    wi = jnp.arange(w_route, dtype=jnp.int32)
    nb = (jnp.clip(dkl[None, ...] - wi.reshape(
              (w_route,) + (1,) * dkl.ndim) * 4, 0, 4) if leading
          else jnp.clip(dkl[..., None] - wi * 4, 0, 4))
    return jnp.where(
        nb >= 4, u32max,
        jnp.where(nb == 0, jnp.uint32(0),
                  (u32max << ((4 - nb).astype(jnp.uint32) * 8)) & u32max))


def bucket_size(n: int) -> int:
    """Power-of-two shape bucket (one XLA compile per bucket)."""
    return 1 << max(8, (n - 1).bit_length() if n > 1 else 1)


def pad_template(r: int) -> np.ndarray:
    """One padding column for a cols matrix with r rows: all-0xFF key words
    (sort after every real key — real keys zero-pad their final word),
    PAD_SENTINEL lens, zero ht/wid/flags/ttl."""
    col = np.zeros(r, dtype=np.uint32)
    col[_ROW_KEY_LEN] = PAD_SENTINEL
    col[_ROW_DKL] = PAD_SENTINEL
    col[_ROW_WORDS:] = 0xFFFFFFFF
    return col


def full_sort_sequence(w: int) -> list:
    """The complete LSD radix schedule for key width w (least-sig first)."""
    return [_ROW_WID, _ROW_HT_LO, _ROW_HT_HI, _ROW_KEY_LEN] + \
        [_ROW_WORDS + j for j in range(w - 1, -1, -1)]


def column_stats(cols: np.ndarray, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """(is_const[R], first_val[R]) over the real rows of a cols matrix."""
    r = cols.shape[0]
    if n == 0:
        return np.ones(r, bool), np.zeros(r, np.uint32)
    first = cols[:, 0].copy()
    is_const = (cols[:, :n] == first[:, None]).all(axis=1)
    return is_const, first


def build_sort_schedule(w: int, is_const: np.ndarray) -> Tuple[np.ndarray, int]:
    """Prune constant columns from the radix schedule (host side).

    A column whose value is identical across all real rows contributes no
    ordering information; skipping its pass saves a full sort+gather on
    device. Returns (sort_rows padded to 4+w, n_sort)."""
    full = full_sort_sequence(w)
    used = [row for row in full if not is_const[row]]
    n_sort = len(used)
    padded = np.asarray(used + [0] * (len(full) - n_sort), dtype=np.int32)
    return padded, n_sort


def pack_bits_u32(bits, n: int):
    """bool [n] -> uint32 [n//32], little-endian lanes (np.unpackbits'
    bitorder='little' inverse). Shared by every kernel that ships decision
    masks over the (slow) device->host link."""
    b32 = bits.reshape(n // 32, 32).astype(jnp.uint32)
    return (b32 << jnp.arange(32, dtype=jnp.uint32)[None, :]).sum(
        axis=1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("w", "is_major", "retain_deletes"))
def _merge_gc_fused(cols, sort_rows, n_sort,
                    cutoff_hi, cutoff_lo, cutoff_phys_hi, cutoff_phys_lo,
                    w: int, is_major: bool, retain_deletes: bool):
    n = cols.shape[1]
    perm, keep, make_tombstone = sort_and_gc(
        cols, cutoff_hi, cutoff_lo, cutoff_phys_hi, cutoff_phys_lo,
        w=w, is_major=is_major, retain_deletes=retain_deletes,
        sort_rows=sort_rows, n_sort=n_sort)
    return perm, pack_bits_u32(keep, n), pack_bits_u32(make_tombstone, n)


def _unpack_bits(packed: np.ndarray, n: int) -> np.ndarray:
    return np.unpackbits(packed.view(np.uint8), bitorder="little")[:n].astype(bool)


@dataclass
class StagedCols:
    """A slab staged on device: the device-resident block-cache unit."""
    cols_dev: object
    sort_rows: np.ndarray
    n_sort: int
    n: int
    n_pad: int
    w: int
    col_const: Optional[np.ndarray] = None   # is_const per row (real rows)
    col_first: Optional[np.ndarray] = None   # first value per row
    # value-payload words [1 + VAL_WORDS, n_pad] for the pushdown scan
    # kernels (ops/scan.py); staged lazily on the first filtered/
    # aggregating scan that needs column values, then resident
    vals_dev: object = None

    @property
    def nbytes(self) -> int:
        n = int(self.cols_dev.size) * 4
        if self.vals_dev is not None:
            n += int(self.vals_dev.size) * 4
        return n


def stage_slab(slab: KVSlab, device=None) -> StagedCols:
    """Pack + upload a slab once; reuse across compactions (HBM block cache)."""
    cols, n, n_pad, w = pack_cols(slab)
    is_const, first = column_stats(cols, n)
    sort_rows, n_sort = build_sort_schedule(w, is_const)
    cols_dev = jax.device_put(cols, device) if device is not None else jnp.asarray(cols)
    return StagedCols(cols_dev, sort_rows, n_sort, n, n_pad, w, is_const, first)


def merge_and_gc_device(slab: Optional[KVSlab], params: GCParams, device=None,
                        staged: Optional[StagedCols] = None
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Run the fused merge+GC program on `device`.

    Returns (perm, keep, make_tombstone) as host numpy arrays (padded length
    n_pad; padding rows sort after all real rows and have keep=False):
      perm[i]  = input index of the i-th entry in merged order
      keep[i]  = survives compaction
      make_tombstone[i] = value must be rewritten as a tombstone (TTL expiry
                          at a non-major compaction)

    staged: pre-staged device cols (device-resident slab cache path) —
    skips the host pack + upload entirely.
    """
    from yugabyte_tpu.utils.metrics import record_kernel_dispatch
    if staged is None:
        if slab.n == 0:
            z = np.zeros(0, dtype=np.int32)
            zb = np.zeros(0, dtype=bool)
            return z, zb, zb
        staged = stage_slab(slab, device)
    cols_dev, sort_rows, n_sort = staged.cols_dev, staged.sort_rows, staged.n_sort
    n, n_pad, w = staged.n, staged.n_pad, staged.w
    cutoff = params.history_cutoff_ht
    cutoff_phys = cutoff >> 12
    perm, keep_p, mk_p = _merge_gc_fused(
        cols_dev, jnp.asarray(sort_rows), jnp.int32(n_sort),
        jnp.uint32(cutoff >> 32), jnp.uint32(cutoff & 0xFFFFFFFF),
        jnp.uint32(cutoff_phys >> 20), jnp.uint32(cutoff_phys & 0xFFFFF),
        w=w, is_major=params.is_major_compaction,
        retain_deletes=params.retain_deletes)
    perm = np.asarray(perm)
    keep = _unpack_bits(np.asarray(keep_p), n_pad) & (perm < n)
    mk = _unpack_bits(np.asarray(mk_p), n_pad)
    record_kernel_dispatch("kernel_merge_gc", n, n_pad)
    return perm, keep, mk


def pack_cols(slab: KVSlab, n_pad_override: Optional[int] = None,
              w_pad_override: Optional[int] = None
              ) -> Tuple[np.ndarray, int, int, int]:
    """Pack a slab into the kernel's contiguous cols matrix (host side).

    Padding rows carry all-0xFF keys (greater than any real key: real keys
    zero-pad their final word) so they sort to the tail.

    n_pad_override / w_pad_override: callers building a composite layout
    (ops/run_merge.py run-major packing) pick their own padded dimensions.
    """
    n = slab.n
    n_pad = n_pad_override if n_pad_override is not None else bucket_size(n)
    w = slab.width_words
    if w_pad_override is not None:
        w_pad = w_pad_override
    else:
        w_pad = 1 << max(2, (w - 1).bit_length() if w > 1 else 1)
    ttl_us = slab.ttl_ms * 1000
    cols = np.empty((_ROW_WORDS + w_pad, n_pad), dtype=np.uint32)
    cols[:, n:] = pad_template(_ROW_WORDS + w_pad)[:, None]
    cols[_ROW_KEY_LEN, :n] = slab.key_len
    cols[_ROW_DKL, :n] = slab.doc_key_len
    cols[_ROW_HT_HI, :n] = slab.ht_hi
    cols[_ROW_HT_LO, :n] = slab.ht_lo
    cols[_ROW_WID, :n] = slab.write_id
    cols[_ROW_FLAGS, :n] = slab.flags
    cols[_ROW_TTL_HI, :n] = (ttl_us >> 20).astype(np.uint32)
    cols[_ROW_TTL_LO, :n] = (ttl_us & 0xFFFFF).astype(np.uint32)
    cols[_ROW_WORDS: _ROW_WORDS + w, :n] = slab.key_words.T
    cols[_ROW_WORDS + w:, :n] = 0
    return cols, n, n_pad, w_pad
