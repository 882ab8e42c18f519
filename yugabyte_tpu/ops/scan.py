"""TPU scan/filter kernel: batched MVCC snapshot resolution + range filter.

The scan-path half of the north star (SURVEY.md section 7 stage 4): where the
reference resolves MVCC visibility one iterator step at a time — min-heap
MergingIterator (ref: rocksdb/table/merger.cc:51) over block iterators
(ref: rocksdb/table/block_based_table_reader.cc:1168) with per-key seeks in
DocRowwiseIterator — this kernel resolves an ENTIRE key range in one fused
device program:

  1. radix merge of all input runs (memtable + SSTs), reusing the compaction
     sort (ops/merge_gc.sort_and_gc)
  2. snapshot GC with cutoff = read_ht: exactly one surviving version per
     key — the one visible at the read time — with tombstones, TTL-expired
     values and root-overwrite-covered entries dropped (snapshot=True mode)
  3. lexicographic range mask over the sorted key words (the block-index +
     seek equivalent, done as a vectorized compare)

The output is a bit-packed keep mask over the merged order; the host gathers
surviving (key, value) pairs — values never cross to the device (slabs.py).
"""

from __future__ import annotations

import functools
from typing import Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from yugabyte_tpu.ops import merge_gc
from yugabyte_tpu.ops.merge_gc import (
    _ROW_DKL, _ROW_KEY_LEN, _ROW_WORDS, PAD_SENTINEL, StagedCols,
    pack_bits_u32, sort_and_gc)
from yugabyte_tpu.ops.slabs import KVSlab, _pad_keys_to_words
from yugabyte_tpu.utils.jax_setup import Prewarm


def _pack_bound(key: Optional[bytes], w: int) -> Tuple[np.ndarray, int]:
    if not key:
        return np.zeros(w, dtype=np.uint32), 0
    words, lens = _pad_keys_to_words([key], width_words=w)
    return words[0], int(lens[0])


@functools.partial(jax.jit, static_argnames=(
    "w", "has_lower", "has_upper", "upper_truncated"))
def _scan_fused(cols, sort_rows, n_sort, cutoff_hi, cutoff_lo, cph, cpl,
                lo_words, lo_len, hi_words, hi_len,
                w: int, has_lower: bool, has_upper: bool,
                upper_truncated: bool = False):
    n = cols.shape[1]
    perm, keep, _ = sort_and_gc(
        cols, cutoff_hi, cutoff_lo, cph, cpl,
        w=w, is_major=True, retain_deletes=False,
        sort_rows=sort_rows, n_sort=n_sort, snapshot=True)
    s_words = cols[_ROW_WORDS:, :][:, perm]
    s_len = cols[_ROW_KEY_LEN][perm].astype(jnp.int32)

    # lexicographic (words, byte-length) compare == memcmp on the raw keys:
    # zero-padded words tie exactly when one key is a prefix of the other,
    # and then the shorter key sorts first
    def cmp_bound(b_words, b_len):
        lt = jnp.zeros(n, bool)
        eq = jnp.ones(n, bool)
        for i in range(w):
            bw = b_words[i]
            lt = lt | (eq & (s_words[i] < bw))
            eq = eq & (s_words[i] == bw)
        lt = lt | (eq & (s_len < b_len))
        eq = eq & (s_len == b_len)
        return lt, eq  # key < bound, key == bound

    if has_lower:
        lt, _ = cmp_bound(lo_words, lo_len)
        keep = keep & ~lt
    if has_upper:
        lt, eq = cmp_bound(hi_words, hi_len)
        # A truncated bound (full upper longer than the key stride) must
        # keep keys EQUAL to the truncated prefix: their full bytes can
        # still be < the full bound; the host re-checks them exactly.
        keep = keep & ((lt | eq) if upper_truncated else lt)

    def pack_bits(b):
        b32 = b.reshape(n // 32, 32).astype(jnp.uint32)
        return (b32 << jnp.arange(32, dtype=jnp.uint32)[None, :]).sum(
            axis=1, dtype=jnp.uint32)

    return perm, pack_bits(keep)


def scan_visible(staged: StagedCols, read_ht_value: int,
                 lower_key: Optional[bytes] = None,
                 upper_key: Optional[bytes] = None,
                 upper_truncated: bool = False
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Run the scan kernel over a staged cols matrix.

    Returns (perm, keep) as host arrays over the merged order: entry
    perm[i] of the staged input survives iff keep[i]; surviving entries are
    exactly the versions visible at read_ht within [lower_key, upper_key).
    """
    from yugabyte_tpu.utils.metrics import record_kernel_dispatch
    w_bytes_cap = staged.w  # key words available
    lo_w, lo_l = _pack_bound(lower_key, w_bytes_cap)
    hi_w, hi_l = _pack_bound(upper_key, w_bytes_cap)
    cutoff = read_ht_value
    cutoff_phys = cutoff >> 12
    perm, keep_p = _scan_fused(
        staged.cols_dev, jnp.asarray(staged.sort_rows), jnp.int32(staged.n_sort),
        jnp.uint32(cutoff >> 32), jnp.uint32(cutoff & 0xFFFFFFFF),
        jnp.uint32(cutoff_phys >> 20), jnp.uint32(cutoff_phys & 0xFFFFF),
        jnp.asarray(lo_w), jnp.int32(lo_l), jnp.asarray(hi_w), jnp.int32(hi_l),
        w=staged.w, has_lower=lower_key is not None,
        has_upper=upper_key is not None, upper_truncated=upper_truncated)
    perm = np.asarray(perm)
    keep = merge_gc._unpack_bits(np.asarray(keep_p), staged.n_pad)
    keep = keep & (perm < staged.n)
    record_kernel_dispatch("kernel_scan", staged.n, staged.n_pad)
    return perm, keep


class SlabSource:
    """Scan input backed by a decoded host slab (memtables, cache-miss
    SSTs): keys/values come straight from the slab arrays."""

    def __init__(self, slab: KVSlab, staged: Optional[StagedCols] = None,
                 sorted_source: bool = False):
        self.slab = slab
        self.staged = staged
        self.n = slab.n
        # True when the slab came from a SORTED on-disk file (SST): a
        # single sorted source lets the pushdown kernels skip the merge
        # sort + permutation gather entirely (presorted fast path)
        self.sorted_source = sorted_source

    def to_slab(self) -> KVSlab:
        return self.slab

    def entry(self, i: int) -> Tuple[bytes, bytes, int]:
        sl = self.slab
        ht = (int(sl.ht_hi[i]) << 32) | int(sl.ht_lo[i])
        return sl.key_bytes(i), sl.values[int(sl.value_idx[i])], ht


class ResidentSource:
    """Scan input served from the HBM slab cache: the device filter runs
    over the RESIDENT column matrix — no host block decode to stage the
    scan — and keys/values of SURVIVORS are fetched lazily from the SST
    reader's blocks, so decode happens only for blocks that actually
    hold visible entries (a narrow range scan touches one block of a
    fully resident file instead of all of them).

    Caller contract: the file must not hold deep documents (the resident
    kernel path is depth-2 only — check reader.props.has_deep)."""

    def __init__(self, reader, staged: StagedCols):
        self.slab = None
        self.reader = reader
        self.staged = staged
        self.n = staged.n
        self.sorted_source = True   # SSTs are sorted by construction
        # per-block first-row offsets: block handles record their entry
        # counts (storage/sst.py index format)
        self._row_offs = np.concatenate(
            ([0], np.cumsum([h[2] for h in reader.block_handles])))
        self._blk_idx = -1
        self._blk = None
        self.decoded_blocks = 0   # winner-block decodes this scan

    def to_slab(self) -> KVSlab:
        return self.reader.read_all()

    def entry(self, i: int) -> Tuple[bytes, bytes, int]:
        b = int(np.searchsorted(self._row_offs, i, side="right") - 1)
        if b != self._blk_idx:
            self._blk = self.reader.read_block(b)
            self._blk_idx = b
            self.decoded_blocks += 1
        sl = self._blk
        j = i - int(self._row_offs[b])
        ht = (int(sl.ht_hi[j]) << 32) | int(sl.ht_lo[j])
        return sl.key_bytes(j), sl.values[int(sl.value_idx[j])], ht


def visible_entries_sources(sources, read_ht_value: int,
                            lower_key: Optional[bytes] = None,
                            upper_key: Optional[bytes] = None,
                            device=None
                            ) -> Iterator[Tuple[bytes, bytes, int]]:
    """Yield (key_prefix, value_bytes, ht_value) for every entry visible
    at read_ht in [lower_key, upper_key), in key order, over a mixed list
    of SlabSource / ResidentSource inputs — the merged+resolved scan
    stream, with resident inputs never decoded to stage the filter."""
    from yugabyte_tpu.ops.merge_gc import stage_slab
    from yugabyte_tpu.ops.slabs import FLAG_DEEP
    from yugabyte_tpu.storage.device_cache import concat_staged

    live = [s for s in sources if s.n]
    if not live:
        return
    if any(s.slab is not None and bool((s.slab.flags & FLAG_DEEP).any())
           for s in live):
        # Deep documents: the kernel's snapshot mode is depth-2 only —
        # resolve visibility on the host with the full overwrite stack.
        # (Resident sources only reach here for depth-2 files, but the
        # host path needs every input as a slab.)
        yield from _visible_entries_host([s.to_slab() for s in live],
                                         read_ht_value, lower_key,
                                         upper_key)
        return
    staged_list = [s.staged if s.staged is not None
                   else stage_slab(s.slab, device) for s in live]
    staged = (staged_list[0] if len(staged_list) == 1
              else concat_staged(staged_list))
    # the device compare sees only the first w*4 key bytes; longer bounds are
    # truncated there and enforced exactly on the host below
    stride = staged.w * 4
    lo_exact = lower_key if lower_key and len(lower_key) > stride else None
    hi_exact = upper_key if upper_key and len(upper_key) > stride else None
    perm, keep = scan_visible(staged, read_ht_value,
                              lower_key[:stride] if lower_key else None,
                              upper_key[:stride] if upper_key else None,
                              upper_truncated=hi_exact is not None)
    # map merged indices back to (source, local index)
    offsets = np.cumsum([0] + [s.n for s in live])
    sel = perm[keep]
    src_idx = np.searchsorted(offsets, sel, side="right") - 1
    local_idx = sel - offsets[src_idx]
    for j, li in zip(src_idx, local_idx):
        key, value, ht = live[int(j)].entry(int(li))
        if lo_exact is not None and key < lo_exact:
            continue
        if hi_exact is not None and key >= hi_exact:
            continue
        yield key, value, ht


def visible_entries(slabs: Sequence[KVSlab], read_ht_value: int,
                    lower_key: Optional[bytes] = None,
                    upper_key: Optional[bytes] = None,
                    device=None,
                    staged_inputs: Optional[Sequence[StagedCols]] = None,
                    ) -> Iterator[Tuple[bytes, bytes, int]]:
    """Slab-list form of visible_entries_sources (every input decoded on
    the host; staged_inputs, when given, skip the per-slab upload)."""
    staged_inputs = (list(staged_inputs) if staged_inputs is not None
                     else [None] * len(slabs))
    sources = [SlabSource(sl, st) for sl, st in zip(slabs, staged_inputs)]
    yield from visible_entries_sources(sources, read_ht_value, lower_key,
                                       upper_key, device=device)


# ---------------------------------------------------------------------------
# Query pushdown: fused filtered / aggregating scans (ROADMAP item 5).
#
# The scan_filtered / scan_agg kernel families extend the snapshot scan
# with row-level predicate evaluation and segment-reduce aggregation ON
# DEVICE, over the resident cols matrices plus a small per-entry VALUE
# word matrix (vals: [1 + VAL_WORDS, n_pad] — payload byte length and the
# first 12 payload bytes, control fields stripped).  The compilable
# predicate subset (docdb/scan_spec.py) is chosen so the encoded-byte
# comparison is provably identical to the host path's decoded-Python
# comparison; SUM rides exact per-byte-column u32 sums reconstructed to
# arbitrary-precision host ints, MIN/MAX ride the biased two-limb
# encoding directly.  Predicates and aggregate column selectors are
# OPERAND DATA (padded to small static slot lattices), so the compile
# surface stays a handful of executables per shape bucket.
# ---------------------------------------------------------------------------

VAL_WORDS = 3                       # value payload words staged per entry
_VAL_ROWS = 1 + VAL_WORDS           # + the payload byte-length row
PRED_SLOTS = (1, 2, 4)              # static predicate-slot lattice
AGG_SLOTS = (1, 2)                  # static aggregate-column-slot lattice
# byte-column SUM accumulators are exact only while n * 255 < 2^32
PUSHDOWN_MAX_NPAD = 1 << 24

_TAG_COLUMN_ID = 0x4B               # ValueType.kColumnId
_TAG_SYS_COLUMN_ID = 0x4A           # ValueType.kSystemColumnId
_TAG_MERGE_FLAGS = 0x6B             # ValueType.kMergeFlags
_TAG_TTL = 0x74                     # ValueType.kTTL


def pred_slot_bucket(n: int) -> Optional[int]:
    """Smallest predicate-slot lattice point holding n predicates, or
    None when the conjunction is too wide for the kernel."""
    for p in PRED_SLOTS:
        if n <= p:
            return p
    return None


def agg_slot_bucket(n: int) -> Optional[int]:
    for c in AGG_SLOTS:
        if n <= c:
            return c
    return None


def pushdown_metrics():
    """Process-wide pushdown observability (the /compactionz "scans"
    block): hit counters, per-reason fallbacks, blocks-decoded and
    batch-size histograms."""
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    e = ROOT_REGISTRY.entity("server", "scan_pushdown")
    return {
        "filtered": e.counter(
            "scan_pushdown_filtered_total",
            "row scans served by the fused filtered kernel"),
        "agg": e.counter(
            "scan_pushdown_agg_total",
            "aggregating scans served by the fused segment-reduce "
            "kernel"),
        "rows": e.counter(
            "scan_pushdown_rows_total",
            "input entries resolved by the pushdown kernels"),
        "vals_staged": e.counter(
            "scan_pushdown_vals_staged_total",
            "value-word matrices staged on a residency miss (write-"
            "through keeps later pushdown scans fully resident)"),
        "blocks": e.histogram(
            "scan_pushdown_decoded_blocks",
            "SST blocks decoded per fused filtered scan (winner blocks "
            "only — a selective predicate over resident slabs decodes "
            "a handful of blocks, not the file)"),
        "batch": e.histogram(
            "scan_pushdown_batch_rows",
            "real entries per pushdown kernel dispatch"),
    }


def count_pushdown_fallback(reason: str) -> None:
    """scan_pushdown_fallback_<reason>_total: one counter per fallback
    reason, so the offload policy can see WHY queries leave the device
    path (the RESYSTANCE measure-then-steer discipline)."""
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    e = ROOT_REGISTRY.entity("server", "scan_pushdown")
    e.counter(f"scan_pushdown_fallback_{reason}_total",
              f"pushdown-eligible scans served by the host path "
              f"({reason})").increment()


def _record_bucket_dispatch(kind: str, n_pad: int) -> None:
    """Per-shape-bucket dispatch counter (the manifest's lattice is the
    vocabulary; one counter per (kernel, n_pad) point)."""
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    e = ROOT_REGISTRY.entity("server", "scan_pushdown")
    e.counter(f"scan_pushdown_{kind}_n{n_pad}_dispatch_total",
              f"{kind} kernel dispatches over the n_pad={n_pad} shape "
              "bucket").increment()


# ------------------------------------------------------------- vals staging

def pack_vals(slab: KVSlab, n_pad: int) -> np.ndarray:
    """Pack a slab's value payloads into the [1+VAL_WORDS, n_pad] uint32
    vals matrix: row 0 = payload byte length (control fields stripped),
    rows 1.. = the first VAL_WORDS*4 payload bytes as big-endian words.
    Fully vectorized — one pass over the contiguous ValueArray blob."""
    from yugabyte_tpu.ops.slabs import ValueArray
    va = slab.values if isinstance(slab.values, ValueArray) \
        else ValueArray.from_list(list(slab.values))
    n = slab.n
    stride = VAL_WORDS * 4
    out = np.zeros((_VAL_ROWS, n_pad), dtype=np.uint32)
    if n == 0:
        return out
    idx = slab.value_idx.astype(np.int64)
    starts = va.offsets[idx]
    ends = va.offsets[idx + 1]
    # guard-padded blob: every speculative gather below stays in bounds
    data = np.concatenate([va.data, np.zeros(stride, dtype=np.uint8)])
    limit = len(data) - 1
    first = np.where(starts < ends, data[np.minimum(starts, limit)], 0)
    skip = np.where(first == _TAG_MERGE_FLAGS, 5, 0).astype(np.int64)
    p2 = starts + skip
    second = np.where(p2 < ends, data[np.minimum(p2, limit)], 0)
    skip += np.where(second == _TAG_TTL, 9, 0)
    pstart = starts + skip
    plen = np.maximum(ends - pstart, 0)
    take = np.minimum(plen, stride)
    pos2d = pstart[:, None] + np.arange(stride, dtype=np.int64)[None, :]
    valid = pos2d < (pstart + take)[:, None]
    b = np.where(valid, data[np.minimum(pos2d, limit)], 0).astype(np.uint32)
    w4 = b.reshape(n, VAL_WORDS, 4)
    words = (w4[:, :, 0] << 24) | (w4[:, :, 1] << 16) \
        | (w4[:, :, 2] << 8) | w4[:, :, 3]
    out[0, :n] = plen.astype(np.uint32)
    out[1:, :n] = words.T
    return out


@functools.partial(jax.jit, static_argnames=("n_pad",))
def _concat_vals_fused(parts, ns, n_pad: int):
    """Per-source vals matrices -> one contiguous [1+VAL_WORDS, n_pad]
    matrix, laid out with EXACTLY the same real-row placement as
    device_cache.concat_staged lays the cols — the two matrices must
    stay row-aligned through the shared sort permutation."""
    out = jnp.zeros((_VAL_ROWS, n_pad), jnp.uint32)
    lane = jnp.arange(n_pad, dtype=jnp.int32)
    off = jnp.int32(0)
    for i, v in enumerate(parts):
        idx = lane - off
        sub = v[:, jnp.clip(idx, 0, v.shape[1] - 1)]
        valid = (idx >= 0) & (idx < ns[i])
        out = jnp.where(valid[None, :], sub, out)
        off = off + ns[i]
    return out


def concat_vals(vals_list, ns: Sequence[int], n_pad: int):
    """Host wrapper: single-source vals pass through untouched."""
    if len(vals_list) == 1:
        return vals_list[0]
    return _concat_vals_fused(tuple(vals_list),
                              jnp.asarray(ns, dtype=jnp.int32),
                              n_pad=n_pad)


# --------------------------------------------------------- traced helpers

def _seg_or_combine(a, b):
    """Segmented-OR scan combine: (new_seg_flag, value) elements; the
    right side resets accumulation at its segment boundary. Associative
    (the standard segmented-scan construction)."""
    af, av = a
    bf, bv = b
    return af | bf, bv | (av & ~bf)


def _segment_any(flag, new_seg, end_seg):
    """Per-entry 'any(flag) within my doc segment', gather-free: a
    forward segmented-OR scan (covering segment-start..i) OR'd with a
    backward one (covering i..segment-end)."""
    _, fwd = jax.lax.associative_scan(_seg_or_combine, (new_seg, flag))
    _, rev = jax.lax.associative_scan(
        _seg_or_combine, (jnp.flip(end_seg), jnp.flip(flag)))
    return fwd | jnp.flip(rev)


def _doc_segments(s, w: int):
    """(new_doc, end_doc) over a SORTED cols matrix: doc-key boundaries
    computed from the dkl-masked key words (the same masking
    gc_over_sorted uses for the overwrite logic)."""
    u32max = jnp.uint32(0xFFFFFFFF)
    s_dkl = s[_ROW_DKL].astype(jnp.int32)
    s_words = s[_ROW_WORDS:]
    word_idx = jnp.arange(w, dtype=jnp.int32)[:, None]
    nbytes = jnp.clip(s_dkl[None, :] - word_idx * 4, 0, 4)
    mask = jnp.where(nbytes >= 4, u32max,
                     jnp.where(nbytes == 0, jnp.uint32(0),
                               (u32max << ((4 - nbytes).astype(jnp.uint32)
                                           * 8)) & u32max))
    doc_words = s_words & mask
    prev_doc = jnp.concatenate(
        [jnp.zeros((w, 1), s_words.dtype), doc_words[:, :-1]], axis=1)
    prev_dkl = jnp.concatenate(
        [jnp.full((1,), -1, s_dkl.dtype), s_dkl[:-1]])
    same_doc = jnp.all(doc_words == prev_doc, axis=0) & (s_dkl == prev_dkl)
    new_doc = ~same_doc.at[0].set(False)
    end_doc = jnp.concatenate([new_doc[1:],
                               jnp.ones((1,), jnp.bool_)])
    return new_doc, end_doc


def _key_byte_at(s_words, off, w: int):
    """Byte of the packed big-endian key at a per-entry byte offset
    (gather-free: a w-way masked select over the word rows)."""
    wi = off >> 2
    sh = ((3 - (off & 3)) * 8).astype(jnp.uint32)
    b = jnp.zeros(off.shape, jnp.uint32)
    for j in range(w):
        b = jnp.where(wi == j, s_words[j], b)
    return (b >> sh) & jnp.uint32(0xFF)


def _cmp_words(v_words, v_len, b_words, b_len, nw: int):
    """Lexicographic (words, byte-length) compare of per-entry word
    vectors against one broadcast bound: returns (lt, eq)."""
    n = v_len.shape[0]
    lt = jnp.zeros(n, bool)
    eq = jnp.ones(n, bool)
    for j in range(nw):
        bw = b_words[j]
        lt = lt | (eq & (v_words[j] < bw))
        eq = eq & (v_words[j] == bw)
    lt = lt | (eq & (v_len < b_len))
    eq = eq & (v_len == b_len)
    return lt, eq


def _pushdown_base(cols, sort_rows, n_sort, cutoff_hi, cutoff_lo, cph, cpl,
                   lo_words, lo_len, hi_words, hi_len, up_inf, up_trunc,
                   w: int, presorted: bool):
    """Shared front half of both pushdown kernels: snapshot-resolve,
    bound-mask (bounds are OPERANDS — empty lower / up_inf sentinel
    upper cover the no-bound cases with the same executable), and the
    structural per-entry fields the predicate/aggregate logic needs.

    presorted (static): a SINGLE SST source is already in exact internal-
    key order (writers emit sorted files; padding rows carry all-0xFF
    keys at the tail), so the radix merge AND the [R, n] permutation
    gather both drop out — on a single-core CPU backend that is ~30x of
    the dispatch (the sort+gather dominate; the GC/filter half is a few
    linear passes). Multi-source scans take the merge path."""
    if presorted:
        perm = jnp.arange(cols.shape[1], dtype=jnp.int32)
        s = cols
        keep, _ = merge_gc.gc_over_sorted(
            s, w, cutoff_hi, cutoff_lo, cph, cpl,
            is_major=True, retain_deletes=False, snapshot=True)
    else:
        perm, keep, _ = sort_and_gc(
            cols, cutoff_hi, cutoff_lo, cph, cpl,
            w=w, is_major=True, retain_deletes=False,
            sort_rows=sort_rows, n_sort=n_sort, snapshot=True)
        s = cols[:, perm]
    s_len_u = s[_ROW_KEY_LEN]
    s_len = s_len_u.astype(jnp.int32)
    s_dkl = s[_ROW_DKL].astype(jnp.int32)
    s_words = s[_ROW_WORDS:]
    real = s_len_u != jnp.uint32(PAD_SENTINEL)
    lo_lt, _ = _cmp_words(s_words, s_len, lo_words, lo_len, w)
    hi_lt, hi_eq = _cmp_words(s_words, s_len, hi_words, hi_len, w)
    in_hi = up_inf | jnp.where(up_trunc, hi_lt | hi_eq, hi_lt)
    base = keep & real & ~lo_lt & in_hi
    new_doc, end_doc = _doc_segments(s, w)
    sub_len = s_len - s_dkl
    b0 = _key_byte_at(s_words, s_dkl, w)
    b1 = _key_byte_at(s_words, s_dkl + 1, w)
    b2 = _key_byte_at(s_words, s_dkl + 2, w)
    sub3 = (b0 << jnp.uint32(16)) | (b1 << jnp.uint32(8)) | b2
    is_len3 = sub_len == 3
    is_bare = s_len == s_dkl
    is_colkey = is_len3 & ((b0 == jnp.uint32(_TAG_COLUMN_ID))
                           | (b0 == jnp.uint32(_TAG_SYS_COLUMN_ID)))
    return perm, s, base, new_doc, end_doc, sub3, is_len3, is_bare, \
        is_colkey


def _row_pass(base, new_doc, end_doc, is_len3, sub3, sv, p_sub, p_op,
              p_neg, p_tag_a, p_tag_b, p_words, p_len, p_pad: int):
    """Per-entry broadcast of 'this entry's row satisfies every active
    predicate slot'. A row satisfies slot i iff SOME visible entry is
    the predicate's column, carries an acceptable payload tag (NULL and
    wrong-type payloads never match) and its encoded payload bytes
    compare true against the literal — with the slot's verdict
    optionally NEGATED (p_neg).

    Negation is how the two NULL contracts share one kernel: the CQL
    executor's _match fails a NULL column on EVERY operator (aggregate
    mode packs != directly — exists a non-equal entry), while the wire
    filter contract (common/wire.FILTER_OPS, the pgsql pushdown) lets
    NULL pass != — row-scan mode packs != as NOT(exists an equal
    entry), so absent/NULL columns pass exactly like row_matches."""
    n = base.shape[0]
    v_len = sv[0].astype(jnp.int32)
    v_words = [sv[1 + j] for j in range(VAL_WORDS)]
    v_tag = v_words[0] >> jnp.uint32(24)
    rowpass = jnp.ones(n, bool)
    for i in range(p_pad):
        code = p_op[i]
        lt, eq = _cmp_words(v_words, v_len, p_words[i], p_len[i],
                            VAL_WORDS)
        m = jnp.where(
            code == 1, eq,
            jnp.where(code == 2, ~eq,
                      jnp.where(code == 3, lt,
                                jnp.where(code == 4, lt | eq,
                                          jnp.where(code == 5, ~(lt | eq),
                                                    ~lt)))))
        tag_ok = (v_tag == p_tag_a[i]) | (v_tag == p_tag_b[i])
        match = base & is_len3 & (sub3 == p_sub[i]) & tag_ok & m
        passed = _segment_any(match, new_doc, end_doc)
        passed = jnp.where(p_neg[i] == 1, ~passed, passed)
        rowpass = rowpass & ((code == 0) | passed)
    return rowpass


@functools.partial(jax.jit, static_argnames=("w", "p_pad", "presorted"))
def _scan_filtered_fused(cols, vals, sort_rows, n_sort,
                         cutoff_hi, cutoff_lo, cph, cpl,
                         lo_words, lo_len, hi_words, hi_len,
                         up_inf, up_trunc,
                         p_sub, p_op, p_neg, p_tag_a, p_tag_b, p_words,
                         p_len,
                         w: int, p_pad: int, presorted: bool = False):
    """Fused filtered scan: snapshot resolution + range mask + row-level
    predicate filter in one program. The keep mask marks EVERY visible
    entry of the rows that pass (the host assembles full rows from the
    winners, decoding only their blocks)."""
    n = cols.shape[1]
    (perm, _s, base, new_doc, end_doc, sub3, is_len3, _is_bare,
     _is_colkey) = _pushdown_base(
        cols, sort_rows, n_sort, cutoff_hi, cutoff_lo, cph, cpl,
        lo_words, lo_len, hi_words, hi_len, up_inf, up_trunc, w,
        presorted)
    sv = vals if presorted else vals[:, perm]
    rowpass = _row_pass(base, new_doc, end_doc, is_len3, sub3, sv,
                        p_sub, p_op, p_neg, p_tag_a, p_tag_b, p_words,
                        p_len, p_pad)
    keep = base & rowpass
    return perm, pack_bits_u32(keep, n)


@functools.partial(jax.jit, static_argnames=("w", "p_pad", "c_pad",
                                             "has_vals", "presorted"))
def _scan_agg_fused(cols, vals, sort_rows, n_sort,
                    cutoff_hi, cutoff_lo, cph, cpl,
                    lo_words, lo_len, hi_words, hi_len, up_inf, up_trunc,
                    p_sub, p_op, p_neg, p_tag_a, p_tag_b, p_words, p_len,
                    a_sub, a_tag_a, a_tag_b,
                    w: int, p_pad: int, c_pad: int, has_vals: bool,
                    presorted: bool = False):
    """Fused aggregating scan: one dispatch answers COUNT/SUM/MIN/MAX
    over the filtered row set — a SELECT count(*) ... WHERE touches
    host memory once per RESULT.

    Per aggregate-column slot c (selector a_sub[c]; slot 0 disabled via
    a_sub == 0) the program reduces, over entries of passing rows whose
    payload tag is acceptable (NULLs excluded, the executor's
    d.get(col)-is-None rule):
      - nonnull count,
      - 8 per-byte-column u32 sums of the biased big-endian int payload
        (exact while n < 2^24; the host reconstructs the arbitrary-
        precision signed sum),
      - min/max of the biased payload as two u32 limbs (order-preserving
        encoding: limb order == numeric order).
    Row liveness matches VisibleEntryRowAssembler: a row exists iff a
    visible bare-DocKey marker or column entry survives."""
    (perm, _s, base, new_doc, end_doc, sub3, is_len3, is_bare,
     is_colkey) = _pushdown_base(
        cols, sort_rows, n_sort, cutoff_hi, cutoff_lo, cph, cpl,
        lo_words, lo_len, hi_words, hi_len, up_inf, up_trunc, w,
        presorted)
    if has_vals:
        sv = vals if presorted else vals[:, perm]
        rowpass = _row_pass(base, new_doc, end_doc, is_len3, sub3, sv,
                            p_sub, p_op, p_neg, p_tag_a, p_tag_b,
                            p_words, p_len, p_pad)
        v_words = [sv[1 + j] for j in range(VAL_WORDS)]
        v_tag = v_words[0] >> jnp.uint32(24)
    else:
        rowpass = jnp.ones(base.shape, bool)
        v_words = None
        v_tag = None
    live_e = base & (is_bare | is_colkey)
    live = _segment_any(live_e, new_doc, end_doc)
    rows_count = jnp.sum((new_doc & live & rowpass).astype(jnp.int32))
    u32max = jnp.uint32(0xFFFFFFFF)
    nonnull = []
    sums = []
    mins_hi, mins_lo, maxs_hi, maxs_lo = [], [], [], []
    for c in range(c_pad):
        if v_words is None:
            z32 = jnp.int32(0)
            zu = jnp.uint32(0)
            nonnull.append(z32)
            sums.append(jnp.zeros(8, jnp.uint32))
            mins_hi.append(zu)
            mins_lo.append(zu)
            maxs_hi.append(zu)
            maxs_lo.append(zu)
            continue
        tag_ok = (v_tag == a_tag_a[c]) | (v_tag == a_tag_b[c])
        qual = base & rowpass & is_len3 & (sub3 == a_sub[c]) & tag_ok
        nonnull.append(jnp.sum(qual.astype(jnp.int32)))
        # biased u64 payload limbs: bytes 1..8 after the kInt64 tag
        hi = ((v_words[0] & jnp.uint32(0xFFFFFF)) << jnp.uint32(8)) \
            | (v_words[1] >> jnp.uint32(24))
        lo = (v_words[1] << jnp.uint32(8)) | (v_words[2] >> jnp.uint32(24))
        byte_sums = []
        for j in range(8):
            pos = 1 + j
            word = v_words[pos // 4]
            byte = (word >> jnp.uint32(8 * (3 - (pos % 4)))) \
                & jnp.uint32(0xFF)
            byte_sums.append(jnp.sum(jnp.where(qual, byte, jnp.uint32(0)),
                                     dtype=jnp.uint32))
        sums.append(jnp.stack(byte_sums))
        mins_hi.append(jnp.min(jnp.where(qual, hi, u32max)))
        min_hi = mins_hi[-1]
        mins_lo.append(jnp.min(jnp.where(qual & (hi == min_hi), lo,
                                         u32max)))
        maxs_hi.append(jnp.max(jnp.where(qual, hi, jnp.uint32(0))))
        max_hi = maxs_hi[-1]
        maxs_lo.append(jnp.max(jnp.where(qual & (hi == max_hi), lo,
                                         jnp.uint32(0))))
    return (rows_count, jnp.stack(nonnull), jnp.stack(sums),
            jnp.stack(mins_hi), jnp.stack(mins_lo),
            jnp.stack(maxs_hi), jnp.stack(maxs_lo))


# ----------------------------------------------------- host-side drivers

def _check_pushdown_bucket(n_pad: int, family: str):
    """Pre-dispatch health gate: a shape bucket the board parked
    (recent fault, sticky mismatch, measured demotion without a probe
    slot) routes straight to the host path (no re-fault). Returns the
    bucket key for the fault-time report. The (1, n_pad) vocabulary is
    the same one scan_fused/merge_gc declare in the kernel manifest."""
    from yugabyte_tpu.docdb.scan_spec import PushdownUnsupported
    from yugabyte_tpu.storage.bucket_health import health_board
    from yugabyte_tpu.storage.offload_policy import point_read_bucket_key
    bkey = point_read_bucket_key(n_pad)
    if not health_board().allow_device(family, bkey):
        raise PushdownUnsupported("quarantined")
    return bkey


def _contain_pushdown_fault(e: BaseException, bkey, family: str) -> None:
    """Fault-time half of the compaction containment mirror: a device
    fault parks the shape bucket on the health board and converts to
    PushdownUnsupported so the caller serves the SAME query through the
    host path; anything else propagates unchanged."""
    from yugabyte_tpu.docdb.scan_spec import PushdownUnsupported
    from yugabyte_tpu.ops.device_faults import is_device_fault
    from yugabyte_tpu.storage.bucket_health import health_board
    if is_device_fault(e):
        health_board().record_fault(
            family, bkey, f"scan_pushdown:{e.__class__.__name__}")
        raise PushdownUnsupported("fault") from e


def _pack_predicate_operands(spec, p_pad: int,
                             wire_ne_semantics: bool = False):
    """wire_ne_semantics: pack != as NOT(exists equal entry) — the
    common/wire.FILTER_OPS contract where NULL/absent columns PASS !=
    (row-scan mode; the executor re-checks with its own rules). False =
    the CQL _match contract (exists a non-equal entry; NULL fails) —
    the aggregate mode, which has no per-row re-check."""
    from yugabyte_tpu.docdb.doc_operations import column_key_suffix
    from yugabyte_tpu.docdb.scan_spec import OP_CODES
    p_sub = np.zeros(p_pad, np.uint32)
    p_op = np.zeros(p_pad, np.int32)
    p_neg = np.zeros(p_pad, np.int32)
    p_ta = np.zeros(p_pad, np.uint32)
    p_tb = np.zeros(p_pad, np.uint32)
    p_words = np.zeros((p_pad, VAL_WORDS), np.uint32)
    p_len = np.zeros(p_pad, np.int32)
    for i, p in enumerate(spec.predicates):
        suf = column_key_suffix(p.cid)
        assert len(suf) == 3 and len(p.enc) <= VAL_WORDS * 4
        p_sub[i] = (suf[0] << 16) | (suf[1] << 8) | suf[2]
        if wire_ne_semantics and p.op == "!=":
            p_op[i] = OP_CODES["="]
            p_neg[i] = 1
        else:
            p_op[i] = OP_CODES[p.op]
        p_ta[i] = p.tag_a
        p_tb[i] = p.tag_b
        w4 = np.zeros(VAL_WORDS * 4, np.uint8)
        w4[: len(p.enc)] = np.frombuffer(p.enc, dtype=np.uint8)
        w4 = w4.reshape(VAL_WORDS, 4).astype(np.uint32)
        p_words[i] = (w4[:, 0] << 24) | (w4[:, 1] << 16) \
            | (w4[:, 2] << 8) | w4[:, 3]
        p_len[i] = len(p.enc)
    return p_sub, p_op, p_neg, p_ta, p_tb, p_words, p_len


def _pack_agg_operands(spec, c_pad: int):
    from yugabyte_tpu.docdb.doc_operations import column_key_suffix
    a_sub = np.zeros(c_pad, np.uint32)
    a_ta = np.zeros(c_pad, np.uint32)
    a_tb = np.zeros(c_pad, np.uint32)
    by_cid = {a.cid: a for a in spec.aggregates if a.cid is not None}
    for c, cid in enumerate(spec.agg_cids):
        suf = column_key_suffix(cid)
        a_sub[c] = (suf[0] << 16) | (suf[1] << 8) | suf[2]
        a_ta[c] = by_cid[cid].tag_a
        a_tb[c] = by_cid[cid].tag_b
    return a_sub, a_ta, a_tb


def _bound_operands(staged: StagedCols, lower_key, upper_key):
    """Kernel bound operands + the exact host re-check residue. Bounds
    longer than the key stride are truncated for the device compare; the
    caller re-checks winners against the exact bytes (filtered mode) or
    must refuse (aggregate mode)."""
    stride = staged.w * 4
    lo_exact = lower_key if lower_key and len(lower_key) > stride else None
    hi_exact = upper_key if upper_key and len(upper_key) > stride else None
    lo_w, lo_l = _pack_bound(lower_key[:stride] if lower_key else None,
                             staged.w)
    hi_w, hi_l = _pack_bound(upper_key[:stride] if upper_key else None,
                             staged.w)
    return (jnp.asarray(lo_w), jnp.int32(lo_l),
            jnp.asarray(hi_w), jnp.int32(hi_l),
            jnp.bool_(upper_key is None), jnp.bool_(hi_exact is not None),
            lo_exact, hi_exact)


def _cutoff_operands(read_ht_value: int):
    cutoff_phys = read_ht_value >> 12
    return (jnp.uint32(read_ht_value >> 32),
            jnp.uint32(read_ht_value & 0xFFFFFFFF),
            jnp.uint32(cutoff_phys >> 20),
            jnp.uint32(cutoff_phys & 0xFFFFF))


def _stage_pushdown(sources, spec, device):
    """Stage (cols, vals) for a mixed source list: one merged matrix
    pair, row-aligned, resident inputs untouched in HBM. Raises
    PushdownUnsupported on deep documents, slot overflow, or an
    oversized batch (callers fall back host-side, counted)."""
    from yugabyte_tpu.docdb.scan_spec import PushdownUnsupported
    from yugabyte_tpu.ops.merge_gc import stage_slab
    from yugabyte_tpu.ops.slabs import FLAG_DEEP
    from yugabyte_tpu.storage.device_cache import concat_staged

    live = [s for s in sources if s.n]
    if not live:
        return None, None, [], False
    if any(s.slab is not None and bool((s.slab.flags & FLAG_DEEP).any())
           for s in live):
        raise PushdownUnsupported("deep")
    from yugabyte_tpu.docdb.scan_spec import GroupAggSpec
    grouped = isinstance(spec, GroupAggSpec)    # scan_group.py sizes its own
    if not grouped and pred_slot_bucket(len(spec.predicates)) is None:
        raise PushdownUnsupported("predicates")
    if not grouped and spec.agg_cids \
            and agg_slot_bucket(len(spec.agg_cids)) is None:
        raise PushdownUnsupported("agg_width")
    staged_list = []
    vals_list = []
    for s in live:
        st = s.staged if s.staged is not None \
            else stage_slab(s.slab, device)
        staged_list.append(st)
        if not spec.needs_vals:
            continue
        vals = getattr(st, "vals_dev", None)
        if vals is None:
            if s.slab is None:
                # a resident source without staged value words: the DB
                # layer re-stages with vals before building the source
                raise PushdownUnsupported("vals")
            packed = pack_vals(s.slab, st.n_pad)
            vals = (jax.device_put(packed, device) if device is not None
                    else jnp.asarray(packed))
            st.vals_dev = vals
        vals_list.append(vals)
    staged = (staged_list[0] if len(staged_list) == 1
              else concat_staged(staged_list))
    if staged.n_pad > PUSHDOWN_MAX_NPAD:
        raise PushdownUnsupported("batch_size")
    vals = None
    if spec.needs_vals:
        vals = concat_vals(vals_list, [s.n for s in staged_list],
                           staged.n_pad)
    presorted = (len(live) == 1
                 and getattr(live[0], "sorted_source", False))
    return staged, vals, live, presorted


def filtered_entries_sources(sources, read_ht_value: int, spec,
                             lower_key: Optional[bytes] = None,
                             upper_key: Optional[bytes] = None,
                             device=None,
                             stats: Optional[dict] = None
                             ) -> Iterator[Tuple[bytes, bytes, int]]:
    """Pushdown twin of visible_entries_sources: yields the visible
    entries of exactly the rows satisfying spec.predicates, resolved in
    ONE fused dispatch. The dispatch (and its decision download) happens
    EAGERLY, before the first yield — a device fault surfaces here,
    where the caller can still fall back to the host path without having
    emitted a single row."""
    from yugabyte_tpu.ops import device_faults
    from yugabyte_tpu.utils.metrics import record_kernel_dispatch

    staged, vals, live, presorted = _stage_pushdown(sources, spec, device)
    if staged is None:
        return iter(())
    p_pad = pred_slot_bucket(len(spec.predicates))
    p_ops = _pack_predicate_operands(spec, p_pad, wire_ne_semantics=True)
    (lo_w, lo_l, hi_w, hi_l, up_inf, up_trunc,
     lo_exact, hi_exact) = _bound_operands(staged, lower_key, upper_key)
    bkey = _check_pushdown_bucket(staged.n_pad, "scan_filtered")
    try:
        device_faults.maybe_fault("dispatch")
        perm, keep_p = _scan_filtered_fused(
            staged.cols_dev, vals, jnp.asarray(staged.sort_rows),
            jnp.int32(staged.n_sort), *_cutoff_operands(read_ht_value),
            lo_w, lo_l, hi_w, hi_l, up_inf, up_trunc,
            *(jnp.asarray(a) for a in p_ops),
            w=staged.w, p_pad=p_pad, presorted=presorted)
        device_faults.maybe_fault("result")
        perm = np.asarray(perm)
        keep_p = np.asarray(keep_p)
    except Exception as e:  # noqa: BLE001 — classified below
        _contain_pushdown_fault(e, bkey, "scan_filtered")
        raise
    keep = merge_gc._unpack_bits(keep_p, staged.n_pad)
    keep = keep & (perm < staged.n)
    record_kernel_dispatch("kernel_scan_filtered", staged.n, staged.n_pad)
    _record_bucket_dispatch("filtered", staged.n_pad)
    m = pushdown_metrics()
    m["filtered"].increment()
    m["rows"].increment(staged.n)
    m["batch"].increment(staged.n)
    if stats is not None:
        stats["n"] = staged.n

    def entries():
        offsets = np.cumsum([0] + [s.n for s in live])
        sel = perm[keep]
        src_idx = np.searchsorted(offsets, sel, side="right") - 1
        local_idx = sel - offsets[src_idx]
        for j, li in zip(src_idx, local_idx):
            key, value, ht = live[int(j)].entry(int(li))
            if lo_exact is not None and key < lo_exact:
                continue
            if hi_exact is not None and key >= hi_exact:
                continue
            yield key, value, ht

    return entries()


def aggregate_sources(sources, read_ht_value: int, spec,
                      lower_key: Optional[bytes] = None,
                      upper_key: Optional[bytes] = None,
                      device=None) -> dict:
    """One fused dispatch -> the aggregate partial for this source set:
    {"rows": <count of passing rows>, "cols": {cid: {"nonnull", "sum",
    "min", "max"}}}. Sums/extremes are exact arbitrary-precision ints
    reconstructed from the device's byte-column sums / biased limbs."""
    from yugabyte_tpu.docdb.scan_spec import (GroupAggSpec,
                                              PushdownUnsupported)
    from yugabyte_tpu.ops import device_faults
    from yugabyte_tpu.utils.metrics import record_kernel_dispatch

    if isinstance(spec, GroupAggSpec):
        # the typed, grouped aggregate has a kernel of its own
        from yugabyte_tpu.ops.scan_group import group_aggregate_sources
        return group_aggregate_sources(sources, read_ht_value, spec,
                                       lower_key, upper_key, device=device)
    staged, vals, _live, presorted = _stage_pushdown(sources, spec, device)
    if staged is None:
        return {"rows": 0,
                "cols": {cid: {"nonnull": 0, "sum": 0, "min": None,
                               "max": None} for cid in spec.agg_cids}}
    stride = staged.w * 4
    if (lower_key and len(lower_key) > stride) or \
            (upper_key and len(upper_key) > stride):
        # no per-row host re-check exists for a scalar result: refuse
        # bounds the device compare cannot represent exactly
        raise PushdownUnsupported("bound_width")
    p_pad = pred_slot_bucket(len(spec.predicates))
    c_pad = agg_slot_bucket(max(len(spec.agg_cids), 1))
    p_ops = _pack_predicate_operands(spec, p_pad)
    a_ops = _pack_agg_operands(spec, c_pad)
    has_vals = spec.needs_vals
    if not has_vals:
        vals = jnp.zeros((_VAL_ROWS, 1), jnp.uint32)
    (lo_w, lo_l, hi_w, hi_l, up_inf, up_trunc,
     _lo_exact, _hi_exact) = _bound_operands(staged, lower_key, upper_key)
    bkey = _check_pushdown_bucket(staged.n_pad, "scan_agg")
    try:
        device_faults.maybe_fault("dispatch")
        out = _scan_agg_fused(
            staged.cols_dev, vals, jnp.asarray(staged.sort_rows),
            jnp.int32(staged.n_sort), *_cutoff_operands(read_ht_value),
            lo_w, lo_l, hi_w, hi_l, up_inf, up_trunc,
            *(jnp.asarray(a) for a in p_ops),
            *(jnp.asarray(a) for a in a_ops),
            w=staged.w, p_pad=p_pad, c_pad=c_pad, has_vals=has_vals,
            presorted=presorted)
        device_faults.maybe_fault("result")
        rows_count, nonnull, sums, min_hi, min_lo, max_hi, max_lo = \
            (np.asarray(x) for x in out)
    except Exception as e:  # noqa: BLE001 — classified below
        _contain_pushdown_fault(e, bkey, "scan_agg")
        raise
    record_kernel_dispatch("kernel_scan_agg", staged.n, staged.n_pad)
    _record_bucket_dispatch("agg", staged.n_pad)
    m = pushdown_metrics()
    m["agg"].increment()
    m["rows"].increment(staged.n)
    m["batch"].increment(staged.n)
    bias = 1 << 63
    cols = {}
    for c, cid in enumerate(spec.agg_cids):
        nn = int(nonnull[c])
        total = sum(int(sums[c][j]) << (8 * (7 - j)) for j in range(8))
        cols[cid] = {
            "nonnull": nn,
            "sum": total - nn * bias,
            "min": None if nn == 0 else
            (((int(min_hi[c]) << 32) | int(min_lo[c])) - bias),
            "max": None if nn == 0 else
            (((int(max_hi[c]) << 32) | int(max_lo[c])) - bias),
        }
    return {"rows": int(rows_count), "cols": cols}


def _visible_entries_host(slabs: Sequence[KVSlab], read_ht_value: int,
                          lower_key: Optional[bytes],
                          upper_key: Optional[bytes]
                          ) -> Iterator[Tuple[bytes, bytes, int]]:
    """Host-side snapshot resolution with FULL overwrite-stack semantics
    (deep documents). Uses the native merge+GC in snapshot shape: a major
    compaction at cutoff=read_ht keeps exactly one surviving version per
    visible key (plus retained history above the read time, filtered
    here), with tombstones dropped and subtree overwrites applied."""
    from yugabyte_tpu.ops.slabs import concat_slabs
    from yugabyte_tpu.storage.cpu_baseline import compact_cpu_baseline

    merged = concat_slabs(slabs)
    offsets = np.cumsum([0] + [s.n for s in slabs]).tolist()
    order, keep, _ = compact_cpu_baseline(merged, offsets, read_ht_value,
                                          True)
    read_ht = np.uint64(read_ht_value)
    for i, k in zip(order, keep):
        if not k:
            continue
        i = int(i)
        ht = (int(merged.ht_hi[i]) << 32) | int(merged.ht_lo[i])
        if ht > int(read_ht):
            continue  # history above the read time is not visible
        key = merged.key_bytes(i)
        if lower_key is not None and key < lower_key:
            continue
        if upper_key is not None and key >= upper_key:
            break
        yield key, merged.values[int(merged.value_idx[i])], ht


# ---------------------------------------------------------------------------
# Prewarm + observability snapshot (PrewarmKernelsOp folds the pushdown
# buckets into the startup compile pass; /compactionz renders the block)
# ---------------------------------------------------------------------------

# declared (n_pad, w) lattice of the pushdown families — the same two
# n_pad points every scan-shaped family declares in the manifest
_PREWARM_NPADS = (1 << 16, 1 << 20)
_PREWARM_W = 4


def prewarm_scan_pushdown() -> Prewarm:
    """Ahead-of-traffic compile of the declared scan_filtered/scan_agg
    buckets (mirrors ops/point_read.prewarm_point_read). Returns
    what compiled and what the compiler refused."""
    pw = Prewarm("scan_pushdown")

    def _warm(what, lower_fn):
        pw.warm(what, lambda: lower_fn().compile())

    sdt = jax.ShapeDtypeStruct
    w = _PREWARM_W
    i32 = sdt((), jnp.int32)
    u32 = sdt((), jnp.uint32)
    b1 = sdt((), jnp.bool_)
    for n_pad in _PREWARM_NPADS:
        common = (sdt((_ROW_WORDS + w, n_pad), jnp.uint32),)
        mid = (sdt((4 + w,), jnp.int32), i32, u32, u32, u32, u32,
               sdt((w,), jnp.uint32), i32, sdt((w,), jnp.uint32), i32,
               b1, b1)
        for p_pad in PRED_SLOTS:
            preds = (sdt((p_pad,), jnp.uint32), sdt((p_pad,), jnp.int32),
                     sdt((p_pad,), jnp.int32),
                     sdt((p_pad,), jnp.uint32), sdt((p_pad,), jnp.uint32),
                     sdt((p_pad, VAL_WORDS), jnp.uint32),
                     sdt((p_pad,), jnp.int32))
            args = common + (sdt((_VAL_ROWS, n_pad), jnp.uint32),) \
                + mid + preds
            for ps in (False, True):
                _warm(f"scan_filtered (n_pad={n_pad} p={p_pad} "
                      f"presorted={ps})",
                      lambda a=args, p=p_pad, q=ps:
                      _scan_filtered_fused.lower(*a, w=w, p_pad=p,
                                                 presorted=q))
                for c_pad in AGG_SLOTS:
                    aggs = (sdt((c_pad,), jnp.uint32),
                            sdt((c_pad,), jnp.uint32),
                            sdt((c_pad,), jnp.uint32))
                    _warm(f"scan_agg (n_pad={n_pad} p={p_pad} c={c_pad} "
                          f"presorted={ps})",
                          lambda a=args, g=aggs, p=p_pad, c=c_pad, q=ps:
                          _scan_agg_fused.lower(*a, *g, w=w, p_pad=p,
                                                c_pad=c, has_vals=True,
                                                presorted=q))
        # the valless variant (COUNT(*) with key-bound-only predicates)
        args = common + (sdt((_VAL_ROWS, 1), jnp.uint32),) + mid + (
            sdt((1,), jnp.uint32), sdt((1,), jnp.int32),
            sdt((1,), jnp.int32),
            sdt((1,), jnp.uint32), sdt((1,), jnp.uint32),
            sdt((1, VAL_WORDS), jnp.uint32), sdt((1,), jnp.int32))
        _warm(f"scan_agg novals (n_pad={n_pad})",
              lambda a=args: _scan_agg_fused.lower(
                  *a, sdt((1,), jnp.uint32), sdt((1,), jnp.uint32),
                  sdt((1,), jnp.uint32), w=w, p_pad=1, c_pad=1,
                  has_vals=False))
    return pw


def pushdown_snapshot() -> dict:
    """The /compactionz "scans" block: pushdown hit/fallback counters by
    reason, per-bucket dispatch counts and the blocks-decoded-per-scan
    histogram (RESYSTANCE: the fused path reports where its time and its
    fallbacks go so the offload policy can steer it)."""
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    e = ROOT_REGISTRY.entity("server", "scan_pushdown")
    m = pushdown_metrics()
    fallbacks = {}
    buckets = {}
    for name, c in sorted(e.metrics_snapshot().items()):
        if not hasattr(c, "value"):
            continue
        if name.startswith("scan_pushdown_fallback_"):
            reason = name[len("scan_pushdown_fallback_"):-len("_total")]
            fallbacks[reason] = c.value()
        elif "_dispatch_total" in name and "_n" in name:
            buckets[name[len("scan_pushdown_"):-len("_dispatch_total")]] \
                = c.value()
    blocks = m["blocks"]
    return {
        "filtered_scans": m["filtered"].value(),
        "agg_scans": m["agg"].value(),
        "rows_resolved": m["rows"].value(),
        "vals_staged": m["vals_staged"].value(),
        "fallbacks": fallbacks,
        "bucket_dispatches": buckets,
        "blocks_decoded_per_scan": {
            "count": blocks.count(),
            "p50": round(blocks.percentile(50), 1),
            "p99": round(blocks.percentile(99), 1),
            "max": blocks.max(),
        },
    }
