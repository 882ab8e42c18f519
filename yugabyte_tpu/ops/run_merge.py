"""Pre-sorted-run K-way merge + MVCC-GC: the round-3 compaction kernel.

Compaction inputs are NOT random rows — they are K already-sorted runs
(L0 SSTs / flush outputs). The round-2 kernel ignored that and re-sorted
everything with a 7-pass LSD radix (O(passes x sort(N)) where the reference
does an O(N log K) heap merge, ref: rocksdb/table/merger.cc:51). This module
replaces the re-sort with a *bitonic merge network over the pre-sorted runs*:

  - lay the K runs out as [K_pad, m] (each run padded to a common power-of-two
    length m with all-0xFF sentinel columns that sort to the tail; K_pad runs
    padded with all-sentinel runs),
  - merge pairwise, log2(K_pad) levels. One level: concat(A, reverse(B)) is
    bitonic, and log2(2L) half-cleaner stages sort it. Every stage is a
    static reshape + vectorized lexicographic compare-exchange — regular
    HBM-friendly access, no gathers, no data-dependent control flow.
    Total work: O(N log N) *stage-passes of elementwise ops* vs the radix
    path's O(passes) full bitonic SORTS (each internally ~log^2 N stages):
    ~40x fewer compare-exchange stages at K=4, N=4M.
  - the comparator is the internal-key order (key words asc, key_len asc,
    hybrid time desc, write id desc — ops/slabs.py) over the host-pruned
    non-constant columns, with the global index as final tiebreak, making the
    order total and the network deterministic & run-stable.

The merged permutation then feeds the SAME segmented GC filter as every other
path (ops/merge_gc.gc_over_sorted), so survivors are byte-identical to the
radix kernel, the native C++ baseline and the Python model.

Transfer design: instead of fetching the 4-byte-per-row
permutation (16 MB at 4M rows), the kernel returns ONE packed decision
buffer: per 32 merged positions, a keep-bit word, a make-tombstone word and
ceil(log2 K_pad) source-run-code words (~0.5 byte/row total). Because the
merge consumes each run in order, the host (or the native C++ shell)
reconstructs the exact permutation from the source codes with a trivial
counting pass. This cuts device->host bytes ~10x.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from yugabyte_tpu.ops.merge_gc import (
    _ROW_DKL, _ROW_FLAGS, _ROW_HT_HI, _ROW_HT_LO, _ROW_KEY_LEN, _ROW_TTL_HI,
    _ROW_TTL_LO, _ROW_WID, _ROW_WORDS, GCParams, PAD_SENTINEL, StagedCols,
    column_stats, gc_over_sorted, pack_cols, pad_template,
    route_word_mask, pack_bits_u32 as _pack_group_bits)
from yugabyte_tpu.ops.slabs import KVSlab
from yugabyte_tpu.utils.jax_setup import Prewarm  # also: compilation cache


def _lex_gt(lo, hi, n_rows: int):
    """Strict lexicographic greater-than over the leading axis (u32 rows)."""
    gt = jnp.zeros(lo.shape[1:], dtype=bool)
    eq = jnp.ones(lo.shape[1:], dtype=bool)
    for i in range(n_rows):
        gt = gt | (eq & (lo[i] > hi[i]))
        eq = eq & (lo[i] == hi[i])
    return gt


def merge_network(x, k_pad: int, m: int, pos=None):
    """Bitonic merge tree over [C, k_pad, m] (each run ascending).

    Returns the fully merged [C, k_pad*m]. All C rows form the comparator;
    the LAST row must be a unique tiebreak (the global index) so the
    order is total.

    Stage formulation (profiled on v5e): every half-cleaner runs on the
    FLAT [C, n] array — the partner of position i at stride s is i^s,
    fetched with two lane rotations (jnp.roll) and a parity select
    instead of reshape(..., 2, s) slicing. The reshape form forced a
    tiled-layout copy per stage (~half the merge wall time); rolls keep
    one fixed layout for the whole network. Only the per-level reverse of
    the B runs still reshapes.

    pos must be a RUNTIME int32 iota [k_pad*m] (the caller's jit takes it
    as an operand): written as jnp.arange inside the trace, every stage's
    `pos & s` parity mask is a compile-time constant and XLA folds ~40
    multi-MB literals — at 4M rows that blew the compile past 10 minutes.
    """
    c = x.shape[0]
    n_cmp = c
    n = k_pad * m
    if pos is None:   # convenience for tests; production passes it in
        pos = jnp.arange(n, dtype=jnp.int32)
    z = x.reshape(c, n)
    k, length = k_pad, m
    while k > 1:
        # reverse every odd run: concat(A, reverse(B)) is bitonic
        y = z.reshape(c, k // 2, 2, length)
        z = jnp.concatenate([y[:, :, 0, :], y[:, :, 1, ::-1]],
                            axis=-1).reshape(c, n)
        s = length
        while s >= 1:
            hi_half = (pos & s) != 0
            # partner = z[i ^ s]; XOR never crosses a 2s block, so the
            # roll's wrap-around values are never selected
            p = jnp.where(hi_half[None], jnp.roll(z, s, axis=1),
                          jnp.roll(z, -s, axis=1))
            gt = _lex_gt(z[:n_cmp], p[:n_cmp], n_cmp)   # strict, total
            take_p = jnp.where(hi_half, ~gt, gt)        # lo keeps min
            z = jnp.where(take_p[None], p, z)
            s //= 2
        k //= 2
        length *= 2
    return z




def _merge_gc_runs_impl(cols, cmp_rows, pos,
                        cutoff_hi, cutoff_lo, cutoff_phys_hi, cutoff_phys_lo,
                        k_pad: int, m: int, w: int, n_cmp: int,
                        is_major: bool, retain_deletes: bool,
                        snapshot: bool, lexsort: bool = False):
    """One device program: run-merge + GC + packed decision buffer.

    cols: [8+w, k_pad*m] run-major layout. cmp_rows: int32 [n_cmp] row ids of
    the non-constant compare columns in most-significant-first order (host
    prunes constants; WHICH rows is dynamic so the compile key is only the
    shape tuple). Output: uint32 [N//32, 2+b] packed groups (keep bits,
    make-tombstone bits, b source-code bit-planes), b = log2(k_pad).

    lexsort (static): merge with ONE multi-key `lax.sort` instead of the
    bitonic network. The comparator short-circuits per comparison, so it is
    the clear winner everywhere a real comparison sort runs fast and
    multi-operand sorts compile quickly — i.e. every non-TPU backend (the
    CPU fallback path ran ~15x faster in measurement); on TPU the
    multi-operand sort costs minutes of XLA compile and the network/pallas
    paths stay the default. Both impls produce bit-identical decisions:
    the comparator (pruned rows + global-index tiebreak) is the same total
    order.
    """
    n = k_pad * m
    u32max = jnp.uint32(0xFFFFFFFF)

    # compare matrix: gather the pruned rows, complement the descending ones
    # (ht_hi/ht_lo/write_id), append the global index as total-order tiebreak
    invert = ((cmp_rows >= _ROW_HT_HI) & (cmp_rows <= _ROW_WID))
    cmp = cols[cmp_rows, :] ^ jnp.where(invert, u32max, jnp.uint32(0))[:, None]
    idx = pos.astype(jnp.uint32)

    if k_pad > 1 and lexsort:
        ops = [cmp[i] for i in range(n_cmp)] + [idx]
        perm = jax.lax.sort(ops, num_keys=n_cmp + 1)[-1].astype(jnp.int32)
        s = cols[:, perm]
    elif k_pad > 1:
        x = jnp.concatenate([cmp, idx[None]], axis=0)
        merged = merge_network(x.reshape(n_cmp + 1, k_pad, m), k_pad, m,
                               pos=pos)
        perm = merged[-1].astype(jnp.int32)
        s = cols[:, perm]
    else:
        perm = pos
        s = cols

    keep, make_tomb = gc_over_sorted(
        s, w, cutoff_hi, cutoff_lo, cutoff_phys_hi, cutoff_phys_lo,
        is_major=is_major, retain_deletes=retain_deletes, snapshot=snapshot)
    keep = keep & (s[_ROW_KEY_LEN] != jnp.uint32(PAD_SENTINEL))

    groups = [_pack_group_bits(keep, n), _pack_group_bits(make_tomb, n)]
    b = max(1, (k_pad - 1).bit_length())
    if k_pad > 1:
        src = (perm >> int(m).bit_length() - 1).astype(jnp.uint32)  # run id
        for t in range(b):
            groups.append(_pack_group_bits((src >> t) & 1, n))
    else:
        zeros = jnp.zeros_like(groups[0])
        for _ in range(b):
            groups.append(zeros)
    # perm/keep/make_tomb stay DEVICE-resident: only `packed` is ever
    # downloaded; the others feed the zero-transfer output staging gather
    # (_gather_staged_output) so write-through never re-uploads columns
    return jnp.stack(groups, axis=1), perm, keep, make_tomb


_FUSED_STATICS = ("k_pad", "m", "w", "n_cmp", "is_major", "retain_deletes",
                  "snapshot", "lexsort")

_merge_gc_runs_fused = functools.partial(
    jax.jit, static_argnames=_FUSED_STATICS)(_merge_gc_runs_impl)

# Donated variant for TRANSIENT column buffers (carved subcompaction
# chunks, per-chunk host uploads): XLA reuses the input's HBM for the
# merge scratch instead of holding input + working set live together.
# Never used on buffers that outlive the launch (HBM slab-cache entries,
# the chunked parent matrix that write-through staging gathers from).
_merge_gc_runs_fused_donated = functools.partial(
    jax.jit, static_argnames=_FUSED_STATICS,
    donate_argnums=(0,))(_merge_gc_runs_impl)


class _DonatedBuffer:
    """Poison placeholder installed over StagedRuns.cols_dev once the
    buffer was donated to XLA: any later touch (the write-through gather
    in gather_staged_outputs, a re-dispatch) raises with the launch that
    consumed it instead of silently reading reused HBM."""

    __slots__ = ("_what",)

    def __init__(self, what: str):
        self._what = what

    def _die(self, *_a, **_k):
        raise RuntimeError(
            f"cols_dev was donated to {self._what}: XLA reuses its HBM "
            "in place, so this buffer no longer holds the staged "
            "columns. Launch without donate=True if anything (e.g. "
            "device write-through staging) must read it afterwards.")

    __getattr__ = __getitem__ = __array__ = _die


def _donation_supported() -> bool:
    """Buffer donation is a no-op (with a per-call warning) on the CPU
    backend — only donate where the runtime honors it. Doubles as the
    "H2D really copies" predicate: the CPU backend may alias host numpy
    memory, so staging arrays are only pooled for reuse on tpu/gpu."""
    return jax.default_backend() in ("tpu", "gpu")


def _use_lexsort() -> bool:
    """Merge-impl selector for the fused program's `lexsort` static (see
    _merge_gc_runs_impl): YBTPU_MERGE_LEXSORT=1/0 forces it; auto uses the
    multi-key lax.sort everywhere except TPU (where its compile takes
    minutes and the network/pallas paths win)."""
    env = os.environ.get("YBTPU_MERGE_LEXSORT", "auto").lower()
    if env in ("1", "true", "on"):
        return True
    if env in ("0", "false", "off"):
        return False
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# Shape-bucket lattice: every static piece of the fused program's compile
# key is quantized so one tablet's whole compaction lifetime hits a small
# fixed set of executables (k_pad and m are powers of two by construction;
# w and n_cmp are quantized here), and the persistent compilation cache
# (utils/jax_setup.py) makes each bucket a one-time cost per node.

_CMP_LATTICE = (2, 4, 6, 8, 12, 16, 24, 32)


def quantize_width(w: int) -> int:
    """Key-word width bucket: power of two, >= 4 (matches pack_cols'
    default w_pad so slab-staged and run-staged layouts share buckets)."""
    return 1 << max(2, (w - 1).bit_length() if w > 1 else 1)


def _quantize_cmp(used: List[int]) -> List[int]:
    """Pad the compare schedule to the next lattice point by repeating its
    last row. A duplicated compare row is a no-op for the lexicographic
    comparator (gt/eq are already resolved at the first occurrence), so
    only the static n_cmp changes — onto ~8 values instead of any int."""
    for q in _CMP_LATTICE:
        if len(used) <= q:
            return used + [used[-1]] * (q - len(used))
    return used


_bucket_keys_seen = set()  # guarded-by: _bucket_lock
_bucket_lock = __import__("threading").Lock()


def _record_bucket(key) -> None:
    """Executable-bucket hit/miss counters: a 'miss' is the first launch of
    a (impl, shape, params) bucket in this process — the jit cache compiles
    (or loads from the persistent cache); every later launch is a hit."""
    from yugabyte_tpu.utils.metrics import kernel_metrics
    with _bucket_lock:
        hit = key in _bucket_keys_seen
        if not hit:
            _bucket_keys_seen.add(key)
    if hit:
        kernel_metrics().counter(
            "kernel_compile_bucket_hits_total",
            "kernel launches that reused an already-compiled shape "
            "bucket").increment()
    else:
        kernel_metrics().counter(
            "kernel_compile_bucket_misses_total",
            "first launches of a shape bucket (compile or persistent-"
            "cache load)").increment()


# The shape buckets steady-state universal compaction actually produces:
# 2/4-slot merges of flush-sized (64k-row) through once-compacted (256k-row)
# runs at the default 4-word quantized key width, whose full compare
# schedule (4 words + key_len/ht_hi/ht_lo/write_id) lands on the n_cmp=8
# lattice point.
_PREWARM_SHAPES = (
    (2, 1 << 16, 4, 8),
    (4, 1 << 16, 4, 8),
    (2, 1 << 18, 4, 8),
    (4, 1 << 18, 4, 8),
)


def prewarm_buckets(shapes: Optional[Sequence[Tuple[int, int, int, int]]]
                    = None) -> Prewarm:
    """Ahead-of-traffic compile of the common fused-kernel buckets.

    Each (k_pad, m, w, n_cmp) bucket lowers + compiles against
    ShapeDtypeStructs (no device memory touched), populating the
    persistent compilation cache (utils/jax_setup.py) so the first REAL
    compaction of each bucket loads a cached executable instead of paying
    the full XLA compile. Run by the tserver maintenance manager at
    startup (flag-gated). Returns what compiled; `.failed` holds the
    (k_pad, m, w, n_cmp) shapes with an executable the compiler refused.

    Coverage matches the committed compile-surface manifest
    (tools/analysis/kernel_manifest.json): BOTH is_major variants per
    shape (minor compactions are the common case — warming only the
    major twin left half the steady surface cold), and on TPU the pallas
    tournament kernel too, with the full unpruned compare schedule —
    auto impl routing launches pallas there, so warming only the jnp
    program cached an executable the TPU path never runs."""
    shapes = tuple(shapes) if shapes is not None else _PREWARM_SHAPES
    lexsort = _use_lexsort()
    donate = _donation_supported()
    fn = _merge_gc_runs_fused_donated if donate else _merge_gc_runs_fused
    on_tpu = jax.default_backend() == "tpu"
    pw = Prewarm("run_merge")

    for shape in shapes:
        k_pad, m, w, n_cmp = shape

        def _warm(what: str, lower_fn) -> bool:
            return pw.warm(what, lambda: lower_fn().compile(), key=shape)

        r = _ROW_WORDS + w
        n = k_pad * m
        u32 = jax.ShapeDtypeStruct((), jnp.uint32)
        fused_args = (
            jax.ShapeDtypeStruct((r, n), jnp.uint32),
            jax.ShapeDtypeStruct((n_cmp,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            u32, u32, u32, u32)
        for is_major in (True, False):
            got = _warm(
                f"bucket (k_pad={k_pad} m={m} w={w} n_cmp={n_cmp} "
                f"is_major={is_major})",
                lambda: fn.lower(
                    *fused_args, k_pad=k_pad, m=m, w=w, n_cmp=n_cmp,
                    is_major=is_major, retain_deletes=False,
                    snapshot=False, lexsort=lexsort))
            if got:
                _record_bucket(("lexsort" if lexsort else "network",
                                k_pad, m, w, n_cmp, is_major, False,
                                False, donate))
        # the chained-compaction write-through programs launch right after
        # every merge of this bucket (restage of cache-resident inputs,
        # survivor scan, per-span output gather) — tiny compiles, warmed
        # so the first chained L0->L1->L2 job is entirely cache-hot
        pos_fn = (_survivor_positions_donated if donate
                  else _survivor_positions)
        _warm(
            f"survivor_positions (n_pad={n})",
            lambda: pos_fn.lower(jax.ShapeDtypeStruct((n,), jnp.bool_)))
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        _warm(
            f"gather_staged_output (n_pad={n} n_out_pad={m})",
            lambda: _gather_staged_output.lower(
                jax.ShapeDtypeStruct((r, n), jnp.uint32),
                jax.ShapeDtypeStruct((n,), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.bool_),
                i32, i32, n_out_pad=m))
        _warm(
            f"restage_concat (k_pad={k_pad} m={m} w={w})",
            lambda: _restage_concat.lower(
                tuple(jax.ShapeDtypeStruct((r, m), jnp.uint32)
                      for _ in range(k_pad)),
                jax.ShapeDtypeStruct((k_pad,), jnp.int32),
                w=w, m=m, k_pad=k_pad))
        if on_tpu:
            from yugabyte_tpu.ops import pallas_merge
            cmp_rows, n_cmp_full = _cmp_schedule(w, np.zeros(r, dtype=bool))
            cmp_rows_t = tuple(int(x) for x in cmp_rows)
            rp = ((r + 1 + 7) // 8) * 8
            tile = min(pallas_merge.default_tile(rp), m)
            for is_major in (True, False):
                got = _warm(
                    f"pallas bucket (k_pad={k_pad} m={m} w={w} "
                    f"is_major={is_major})",
                    lambda: pallas_merge._pallas_merge_gc_fused.lower(
                        jax.ShapeDtypeStruct((r, n), jnp.uint32),
                        jax.ShapeDtypeStruct((n,), jnp.int32),
                        u32, u32, u32, u32,
                        k_pad=k_pad, m=m, w=w, cmp_rows_t=cmp_rows_t,
                        tile=tile, is_major=is_major, retain_deletes=False,
                        snapshot=False, interpret=False))
                if got:
                    _record_bucket(("pallas", k_pad, m, w, n_cmp_full,
                                    is_major, False, False))
    return pw


@dataclass
class StagedRuns:
    """K sorted runs laid out run-major on device: [8+w, k_pad*m]."""
    cols_dev: object
    m: int                 # per-run padded length (power of two)
    k_pad: int             # run slots (power of two)
    w: int                 # key words
    run_ns: List[int]      # real rows per run (len = real run count)
    cmp_rows: np.ndarray   # pruned compare row ids, MSB-first, + int32
    n_cmp: int
    # greedy run-packing (pack_runs_greedy): slot i's rows map to input
    # rows run_maps[i][slot_position] over the concatenation of the
    # ORIGINAL live runs; None = identity (slot == run)
    run_maps: Optional[List[np.ndarray]] = None

    @property
    def n(self) -> int:
        return int(sum(self.run_ns))

    @property
    def n_pad(self) -> int:
        return self.m * self.k_pad

    @property
    def nbytes(self) -> int:
        return int(self.cols_dev.size) * 4


def _merge_const_stats(per_run: Sequence[Tuple[np.ndarray, np.ndarray]],
                       r: int) -> np.ndarray:
    """Merge per-run (is_const, first_val) column stats into the cross-run
    is_const vector: a row is prunable from the comparator only if it is
    constant WITH THE SAME VALUE across every input — constant-per-run with
    differing values still orders the merge. Vectorized: first values of
    non-constant runs never matter (the all-const mask already excludes
    their rows)."""
    consts = np.stack([c for c, _f in per_run]).astype(bool)
    firsts = np.stack([f for _c, f in per_run]).astype(np.uint32)
    return consts.all(axis=0) & (firsts == firsts[0:1]).all(axis=0)


def _cmp_schedule(w: int, is_const: np.ndarray) -> Tuple[np.ndarray, int]:
    """Most-significant-first compare rows with constants pruned, padded to
    the n_cmp lattice (see _quantize_cmp — n_cmp is a static jit arg).

    Order: key words 0..w-1, key_len, ht_hi, ht_lo, write_id (the merge
    comparator; complements for the descending rows are applied on device).
    """
    full = [_ROW_WORDS + j for j in range(w)] + [
        _ROW_KEY_LEN, _ROW_HT_HI, _ROW_HT_LO, _ROW_WID]
    used = [r for r in full if not is_const[r]]
    if not used:
        used = [_ROW_KEY_LEN]  # degenerate: all constant; any row works
    used = _quantize_cmp(used)
    return np.asarray(used, dtype=np.int32), len(used)


def run_bucket(n: int) -> int:
    """Per-run padded length: power of two, >= 256 (lane-tile friendly)."""
    return 1 << max(8, (n - 1).bit_length() if n > 1 else 1)


def plan_run_packing(run_ns: Sequence[int]) -> Optional[List[List[int]]]:
    """Greedy (first-fit-decreasing) packing of small runs into shared
    m-slots: bins of combined size <= m (the largest run's bucket).

    The run-major layout pads EVERY run to m; a pick of one big run plus
    several small ones wastes most of its padded slots (the pad-waste
    gauges record it). Packing several small runs into one slot cuts the
    slot count — and often k_pad, halving device work. Returns the bins
    (lists of run indices, input order preserved within a bin), or None
    when packing would not shrink k_pad (same padded layout, extra host
    pre-merge for nothing)."""
    k = len(run_ns)
    if k < 2:
        return None
    m = max(run_bucket(n) for n in run_ns)
    order = sorted(range(k), key=lambda i: -run_ns[i])
    bins: List[List[object]] = []          # [free_slots, [run indices]]
    for i in order:
        for b in bins:
            if b[0] >= run_ns[i]:
                b[0] -= run_ns[i]
                b[1].append(i)
                break
        else:
            bins.append([m - run_ns[i], [i]])
    k_pad_orig = 1 << max(0, (k - 1).bit_length())
    k_new = len(bins)
    k_pad_new = 1 << max(0, (k_new - 1).bit_length()) if k_new > 1 else 1
    if k_pad_new >= k_pad_orig:
        return None
    return [sorted(b[1]) for b in bins]


def packed_run_ns(run_ns: Sequence[int]) -> List[int]:
    """Slot sizes after greedy run-packing (the layout-inflation gates
    score the layout that would ACTUALLY be staged)."""
    bins = plan_run_packing(run_ns)
    if bins is None:
        return list(run_ns)
    return [sum(run_ns[i] for i in b) for b in bins]


def _slab_sort_order(slab: KVSlab) -> np.ndarray:
    """Merged order of a concatenated slab under the kernel comparator
    (key words asc, key_len asc, ht desc, write_id desc; stable — ties
    keep concatenation order, matching the kernel's global-index
    tiebreak over the slot layout)."""
    inv = np.uint32(0xFFFFFFFF)
    keys = [slab.write_id ^ inv, slab.ht_lo ^ inv, slab.ht_hi ^ inv,
            slab.key_len.astype(np.uint32)]
    for j in range(slab.width_words - 1, -1, -1):
        keys.append(slab.key_words[:, j])
    return np.lexsort(tuple(keys))


def _gather_slab_keys(slab: KVSlab, order: np.ndarray) -> KVSlab:
    """Key-column gather of a slab (values untouched: staging only reads
    key columns; survivors gather values via the GLOBAL perm later)."""
    from yugabyte_tpu.ops.slabs import ValueArray
    return KVSlab(
        key_words=slab.key_words[order], key_len=slab.key_len[order],
        doc_key_len=slab.doc_key_len[order], ht_hi=slab.ht_hi[order],
        ht_lo=slab.ht_lo[order], write_id=slab.write_id[order],
        flags=slab.flags[order], ttl_ms=slab.ttl_ms[order],
        value_idx=np.arange(len(order), dtype=np.int32),
        values=ValueArray.empty_rows(len(order)))


def pack_runs_greedy(live: Sequence[KVSlab]
                     ) -> Tuple[List[KVSlab], Optional[List[np.ndarray]]]:
    """Apply plan_run_packing to live slabs: bins with >1 run are
    pre-merged on the host (sorted merge of sorted runs — cheap, they are
    the SMALL runs) into one sorted slot slab, with a per-slot map from
    slot position to global input row so the decoded permutation still
    indexes the original input concatenation."""
    from yugabyte_tpu.ops.slabs import concat_slabs
    if os.environ.get("YBTPU_RUN_PACKING", "1") == "0":
        return list(live), None
    bins = plan_run_packing([s.n for s in live])
    if bins is None:
        return list(live), None
    bases = np.concatenate(([0], np.cumsum([s.n for s in live])))
    slot_slabs: List[KVSlab] = []
    run_maps: List[np.ndarray] = []
    for idxs in bins:
        if len(idxs) == 1:
            i = idxs[0]
            slot_slabs.append(live[i])
            run_maps.append(np.arange(bases[i], bases[i] + live[i].n,
                                      dtype=np.int64))
            continue
        cat = concat_slabs([live[i] for i in idxs])
        gidx = np.concatenate([np.arange(bases[i], bases[i] + live[i].n,
                                         dtype=np.int64) for i in idxs])
        order = _slab_sort_order(cat)
        slot_slabs.append(_gather_slab_keys(cat, order))
        run_maps.append(gidx[order])
    from yugabyte_tpu.utils.metrics import kernel_metrics
    kernel_metrics().counter(
        "kernel_run_packing_total",
        "staging calls that packed small runs into shared "
        "m-slots").increment()
    return slot_slabs, run_maps


def stage_runs_from_slabs(slabs: Sequence[KVSlab], device=None,
                          pack_runs: bool = True) -> StagedRuns:
    """Pack K sorted slabs into the run-major layout with ONE upload.

    pack_runs: greedily pack small runs into shared m-slots first
    (pack_runs_greedy) — cuts the pad waste the kernel gauges expose."""
    from yugabyte_tpu.storage.device_cache import host_staging_pool
    live = [s for s in slabs if s.n]
    run_maps = None
    if pack_runs:
        live, run_maps = pack_runs_greedy(live)
    k = len(live)
    k_pad = 1 << max(0, (k - 1).bit_length()) if k > 1 else 1
    m = max(run_bucket(s.n) for s in live)
    w = quantize_width(max(int(s.width_words) for s in live))
    r = _ROW_WORDS + w
    pool = host_staging_pool()
    cols = pool.acquire((r, k_pad * m))
    try:
        cols[:] = pad_template(r)[:, None]
        stats = []
        for i, s in enumerate(live):
            sub, n_s, _, _ = pack_cols(s, n_pad_override=s.n,
                                       w_pad_override=w)
            cols[:, i * m: i * m + n_s] = sub
            stats.append(column_stats(sub, n_s))
        cmp_rows, n_cmp = _cmp_schedule(w, _merge_const_stats(stats, r))
    except BaseException:
        # the upload below never started, so no device buffer can alias
        # these pages on ANY backend — recycle instead of leaking the
        # lease (an unwinding pipeline stage would otherwise degrade the
        # pool to one-shot allocations)
        pool.release(cols)
        raise
    cols_dev = (jax.device_put(cols, device) if device is not None
                else jnp.asarray(cols))
    if _donation_supported():
        # the accelerator H2D copy owns its bytes once the put completes;
        # block for it, then recycle the staging array (the next chunk's
        # stage-A pack reuses these pages instead of allocating). The CPU
        # backend may alias host memory, so there the array just drops —
        # forget() ends the lease without recycling, so the outstanding-
        # lease gauge (the chaos soak's leak detector) still drains.
        jax.block_until_ready(cols_dev)
        pool.release(cols)
    else:
        pool.forget(cols)
    return StagedRuns(cols_dev, m, k_pad, w, [s.n for s in live],
                      cmp_rows, n_cmp, run_maps=run_maps)


# --------------------------------------------------------------------------
# Device-side re-staging (the restage_concat kernel family): cache-resident
# per-SST cols re-laid into merge inputs with ONE cached jitted program per
# shape bucket, instead of a stream of small un-jitted slice/pad/concat ops
# per input per job. Both layouts appear in the compile-surface manifest;
# all inputs are LIVE slab-cache entries, so nothing here may donate.

@functools.partial(jax.jit, static_argnames=("w", "m", "k_pad"))
def _restage_concat(parts, ns, w: int, m: int, k_pad: int):
    """Per-SST staged cols -> the run-major [8+w, k_pad*m] merge layout.

    parts: tuple of device cols matrices [r_i, n_pad_i] (r_i <= 8+w,
    n_pad_i <= m — both lattice-quantized, so the compile key is bounded);
    ns[i] is the real row count of part i. Real rows land at the head of
    slot i, narrow inputs expose their extra word rows as zero, and every
    padding lane (slot tails + the k_pad-k empty slots) carries the pad
    template so it sorts to the tail."""
    r = _ROW_WORDS + w
    pad_col = jnp.asarray(pad_template(r))
    lane = jnp.arange(m, dtype=jnp.int32)
    outs = []
    for i in range(k_pad):
        if i < len(parts):
            cols = parts[i]
            sub = cols[:, jnp.clip(lane, 0, cols.shape[1] - 1)]
            if cols.shape[0] < r:
                sub = jnp.concatenate(
                    [sub, jnp.zeros((r - cols.shape[0], m), jnp.uint32)],
                    axis=0)
            outs.append(jnp.where((lane < ns[i])[None, :], sub,
                                  pad_col[:, None]))
        else:
            outs.append(jnp.broadcast_to(pad_col[:, None], (r, m)))
    return jnp.concatenate(outs, axis=1) if k_pad > 1 else outs[0]


@functools.partial(jax.jit, static_argnames=("w", "n_pad"))
def _concat_staged_fused(parts, ns, w: int, n_pad: int):
    """Per-SST staged cols -> ONE contiguous padded cols matrix [8+w,
    n_pad] (the radix kernel's input layout, storage/device_cache.py
    concat_staged): real rows of every input laid out back to back, tail
    padded with the template."""
    r = _ROW_WORDS + w
    pad_col = jnp.asarray(pad_template(r))
    out = jnp.broadcast_to(pad_col[:, None], (r, n_pad))
    lane = jnp.arange(n_pad, dtype=jnp.int32)
    off = jnp.int32(0)
    for i, cols in enumerate(parts):
        idx = lane - off
        sub = cols[:, jnp.clip(idx, 0, cols.shape[1] - 1)]
        if cols.shape[0] < r:
            sub = jnp.concatenate(
                [sub, jnp.zeros((r - cols.shape[0], n_pad), jnp.uint32)],
                axis=0)
        valid = (idx >= 0) & (idx < ns[i])
        out = jnp.where(valid[None, :], sub, out)
        off = off + ns[i]
    return out


def stage_runs_from_staged(staged_list: Sequence[StagedCols]) -> StagedRuns:
    """Device-side re-layout of per-SST staged cols (HBM slab cache hits)
    into the run-major matrix — no host->device transfer at all, and one
    jitted dispatch (_restage_concat) instead of per-input slice/pad/concat
    chains."""
    live = [s for s in staged_list if s.n]
    k = len(live)
    k_pad = 1 << max(0, (k - 1).bit_length()) if k > 1 else 1
    m = max(run_bucket(s.n) for s in live)
    # staged widths are already pack_cols-quantized; the explicit
    # quantize_width keeps this layout on the lattice even if a caller
    # ever stages an odd width (idempotent on lattice points)
    w = quantize_width(max(s.w for s in live))
    r = _ROW_WORDS + w
    cat = _restage_concat(tuple(s.cols_dev for s in live),
                          jnp.asarray([s.n for s in live], dtype=jnp.int32),
                          w=w, m=m, k_pad=k_pad)
    stats = []
    for s in live:
        c_i = np.zeros(r, dtype=bool)
        f_i = np.zeros(r, dtype=np.uint32)
        rs = min(_ROW_WORDS + s.w, r)
        c_i[rs:] = True                  # implicit zero-pad word rows
        if s.col_const is not None:
            c_i[:rs] = s.col_const[:rs]
            f_i[:rs] = s.col_first[:rs]
        stats.append((c_i, f_i))
    cmp_rows, n_cmp = _cmp_schedule(w, _merge_const_stats(stats, r))
    return StagedRuns(cat, m, k_pad, w, [s.n for s in live], cmp_rows, n_cmp)


class DeviceFaultError(Exception):
    """A device-path failure that survived its retry: the kernel path of
    this job is broken (XLA compile error, HBM OOM, runtime dispatch
    fault). Carries the shape-bucket key so the containment layer
    (storage/compaction.py) can quarantine the bucket before taking the
    byte-identical native fallback."""

    def __init__(self, bucket: Tuple[int, int], cause: BaseException):
        super().__init__(f"device merge failed after retry "
                         f"(bucket k_pad={bucket[0]} m={bucket[1]}): "
                         f"{cause!r}")
        self.bucket = bucket
        self.cause = cause


def _chunk_retry_counter():
    from yugabyte_tpu.utils.metrics import kernel_metrics
    return kernel_metrics().counter(
        "kernel_chunk_retry_total",
        "per-chunk kernel retries after a device fault")


class MergeGCHandle:
    """In-flight merge+GC launch: packed decisions transferring async.

    Pipelining hook: launch job i+1 while job i's (small) decision buffer
    downloads, so sustained compaction throughput is bounded by
    max(compute, transfer), not their sum.
    """

    def __init__(self, packed_dev, staged: StagedRuns,
                 perm_dev=None, keep_dev=None, mk_dev=None,
                 host_async: bool = True, relaunch=None):
        self._packed_dev = packed_dev
        self._staged = staged
        self._result = None
        # device-resident merge products for zero-transfer output staging
        self._perm_dev = perm_dev
        self._keep_dev = keep_dev
        self._mk_dev = mk_dev
        # retry-once hook: a closure re-dispatching the SAME launch (only
        # set when the input buffer was not donated, so re-reading it is
        # legal) — a transient device fault at download time gets one
        # more attempt before the caller's native fallback
        self._relaunch = relaunch
        if host_async:
            try:
                packed_dev.copy_to_host_async()
            except (AttributeError, NotImplementedError):  # yblint: contained(backend lacks async D2H; result() falls back to the sync download)
                pass
        # (a chunked parent fuses every chunk's packed buffer into ONE
        # device concat + download instead of calling result() per chunk —
        # each separate np.asarray pays a full device round-trip)

    def _download(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        from yugabyte_tpu.utils.metrics import pipeline_span
        with pipeline_span("device"):   # the host blocked on the device
            packed = np.asarray(self._packed_dev)  # [n_pad//32, 2+b]
        with pipeline_span("decision_unpack", inclusive="host"):
            return _decode_packed(packed, self._staged)

    def result(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(perm, keep, make_tombstone) host arrays over the merged order.

        perm indexes the CONCATENATION of the live runs in input order
        (padding excluded): merged position i came from input row perm[i].
        Arrays cover exactly the real rows (length n = sum(run_ns)).
        """
        if self._result is not None:
            return self._result
        from yugabyte_tpu.ops import device_faults
        try:
            device_faults.maybe_fault("result")
            self._result = self._download()
        except Exception as e:  # noqa: BLE001 — device-fault containment
            if self._relaunch is None or not device_faults.is_device_fault(e):
                raise
            # one retry of the same launch (jit-cached: re-dispatch is
            # cheap); a second failure surfaces to the caller, which
            # quarantines the bucket and falls back to the native merge
            _chunk_retry_counter().increment()
            from yugabyte_tpu.utils.trace import TRACE
            TRACE("run_merge: device fault at download (%r) — retrying "
                  "the launch once", e)
            self._packed_dev, self._perm_dev, self._keep_dev, \
                self._mk_dev = self._relaunch()
            device_faults.maybe_fault("result")
            self._result = self._download()
        return self._result

    def result_iter(self):
        """Streaming form of result(): yields (perm, keep, make_tombstone)
        once — the single-launch degenerate case of the chunked handle's
        per-chunk stream, so pipeline consumers handle both uniformly."""
        yield self.result()


def _decode_packed(packed: np.ndarray, staged: StagedRuns
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host decode of one launch's packed decision words -> (perm, keep,
    make_tombstone) over the merged order (see MergeGCHandle.result)."""
    n = staged.n
    n_grp = (n + 31) // 32
    grp = packed[:n_grp]
    keep = _unpack_words(grp[:, 0], n)
    mk = _unpack_words(grp[:, 1], n)
    if staged.k_pad == 1:
        if staged.run_maps is not None:
            return staged.run_maps[0][:n].copy(), keep, mk
        return np.arange(n, dtype=np.int64), keep, mk
    b = max(1, (staged.k_pad - 1).bit_length())
    src = np.zeros(n, dtype=np.uint32)
    for t in range(b):
        src |= _unpack_words(grp[:, 2 + t], n).astype(np.uint32) << t
    # reconstruct the permutation: the merge consumes each run in order,
    # so output position i with source run r maps to the next unconsumed
    # row of r. Padding sorts after every real key, so positions [0, n)
    # are exactly the real rows. Packed slots (run_maps) translate slot
    # consumption order to the original input rows.
    perm = np.zeros(n, dtype=np.int64)
    base = np.concatenate(([0], np.cumsum(staged.run_ns)))
    for r_i in range(len(staged.run_ns)):
        sel = src == r_i
        cnt = int(sel.sum())
        if staged.run_maps is not None:
            perm[sel] = staged.run_maps[r_i][:cnt]
        else:
            perm[sel] = base[r_i] + np.arange(cnt, dtype=np.int64)
    return perm, keep, mk


def _unpack_words(words: np.ndarray, n: int) -> np.ndarray:
    from yugabyte_tpu.ops.merge_gc import _unpack_bits
    return _unpack_bits(np.ascontiguousarray(words), n)


def _survivor_positions_impl(keep):
    """Merged positions of all survivors, padded with n_pad-1 (a padding
    row: padding sorts to the tail and is never kept, so n_pad-1 is only a
    real row when NOTHING was padded AND it survived — in which case it is
    a valid filler that sits beyond every real survivor index anyway)."""
    n_pad = keep.shape[0]
    return jnp.nonzero(keep, size=n_pad, fill_value=n_pad - 1)[0]


_survivor_positions = jax.jit(_survivor_positions_impl)

# Donated variant for the CHAINED-buffer handoff: the keep mask is dead
# once its survivor positions are scanned (the span gathers below read
# only perm/mk/pos), so on backends that honor donation XLA reuses its
# HBM in place. The caller (survivor_positions) poisons the handle's
# _keep_dev afterwards so any late reader fails loudly instead of seeing
# reused memory.
_survivor_positions_donated = functools.partial(
    jax.jit, donate_argnums=(0,))(_survivor_positions_impl)


def survivor_positions(handle: "MergeGCHandle"):
    """Device survivor-position scan over a finished merge's keep mask —
    the first half of write-through staging. Donates the keep mask where
    the backend honors donation (it is the last reader)."""
    keep = handle._keep_dev
    if _donation_supported():
        pos = _survivor_positions_donated(keep)
        handle._keep_dev = _DonatedBuffer("_survivor_positions_donated")
    else:
        pos = _survivor_positions(keep)
    return pos


@functools.partial(jax.jit, static_argnames=("n_out_pad",))
def _gather_staged_output(cols, perm, pos_all, mk, start, end,
                          n_out_pad: int):
    """Gather survivors [start, end) of the merged order into a padded
    StagedCols matrix — entirely on device.

    This is the write-through path for the HBM slab cache: compaction
    outputs become the next compaction's inputs WITHOUT ever leaving HBM
    (no re-upload of the packed output columns per job).

    start/end are traced scalars (no recompile per file split); n_out_pad
    is the static power-of-two bucket. Padding columns are rewritten with
    the pad template so future merges sort them to the tail.
    """
    from yugabyte_tpu.ops.slabs import FLAG_TOMBSTONE
    n_pad = cols.shape[1]
    idx = start + jnp.arange(n_out_pad, dtype=jnp.int32)
    valid = idx < end
    pos = pos_all[jnp.clip(idx, 0, n_pad - 1)]
    src = perm[pos]
    sub = cols[:, src]
    # TTL-expired survivors are rewritten as tombstones by the byte shell;
    # mirror the flag bit the shell sets (native/compaction_engine.cc
    # write_output: fl |= 1) so the staged entry matches the file
    fl = sub[_ROW_FLAGS] | jnp.where(mk[pos] & valid,
                                     jnp.uint32(FLAG_TOMBSTONE),
                                     jnp.uint32(0))
    sub = sub.at[_ROW_FLAGS].set(fl)
    pad_col = jnp.asarray(pad_template(cols.shape[0]))
    return jnp.where(valid[None, :], sub, pad_col[:, None])


def gather_staged_output_span(handle: MergeGCHandle, pos_all,
                              start: int, end: int) -> StagedCols:
    """Stage ONE output file's [start, end) survivor span directly from
    HBM — the per-span half of write-through: called as each
    _StreamingNativeWriter span completes, so the cache entry installs
    under the output file id the moment its SST exists on disk.

    pos_all: the survivor-position scan from survivor_positions(handle),
    computed once per job. Column stats are conservatively absent (every
    column treated as non-constant) to avoid any device->host fetch."""
    from yugabyte_tpu.ops.merge_gc import (bucket_size as _bucket,
                                           build_sort_schedule)
    staged = handle._staged
    r = _ROW_WORDS + staged.w
    n_out = end - start
    n_out_pad = _bucket(n_out)
    sort_rows, n_sort = build_sort_schedule(staged.w, np.zeros(r, dtype=bool))
    cols_out = _gather_staged_output(
        staged.cols_dev, handle._perm_dev, pos_all,
        handle._mk_dev, jnp.int32(start), jnp.int32(end), n_out_pad)
    return StagedCols(cols_out, sort_rows, n_sort, n_out,
                      n_out_pad, staged.w, None, None)


def gather_staged_outputs(handle: MergeGCHandle,
                          ranges: Sequence[Tuple[int, int]]
                          ) -> List[StagedCols]:
    """Stage the output files of a finished merge directly from HBM.

    ranges: per-output-file [start, end) positions in survivor order —
    exactly the spans the byte shell wrote (returned by
    storage/compaction.py _write_native_outputs). Returns one StagedCols
    per file, device-resident, suitable for DeviceSlabCache.put. The
    survivor-position scan (which consumes — donates — the keep mask on
    capable backends) runs once for all files.
    """
    if getattr(handle, "_perm_dev", None) is None \
            and hasattr(handle, "to_parent_products"):
        handle.to_parent_products()   # chunked: rebuild parent-domain arrays
    pos_all = survivor_positions(handle)
    return [gather_staged_output_span(handle, pos_all, start, end)
            for start, end in ranges]


# --------------------------------------------------------------------------
# Chunked subcompactions: bound the compiled shape of arbitrarily large jobs
# (ref: GenSubcompactionBoundaries, rocksdb/db/compaction_job.cc:330 — the
# reference splits one big compaction into key-range subcompactions; here
# each chunk reuses the SAME bucketed executable, so a 4M-row job rides the
# already-compiled 1M-row program instead of paying a fresh multi-minute
# XLA/Mosaic compile that scales with n).
#
# Chunk boundaries are doc-key ROUTE prefixes (first _W_ROUTE_CHUNK words
# masked to doc_key_len — the same order-preserving, doc-atomic routing
# dist_compact.py uses across mesh shards): every entry/version of one
# document shares its route, and encoded doc keys are prefix-free, so the
# route is monotone within each sorted run and a binary search per run
# yields slice bounds that never split a document — the GC segment logic
# never straddles chunks, and chunk concatenation preserves global order.

_W_ROUTE_CHUNK = 4


def _chunk_target_rows() -> int:
    """YBTPU_MERGE_CHUNK_ROWS: target padded rows per chunk launch.
    Values below 1024 (including 0 and negatives) disable chunking — a
    tiny target would explode into one chunk per handful of rows.

    Unset, chunking is on for TPU only. It exists to bound the compiled
    shape (the multi-minute Mosaic/XLA compile scales with n there) and
    to stream decision downloads per chunk; on the CPU fallback the
    lexsort impl compiles in seconds at ANY shape, while the chunk
    machinery costs real work — splitter sampling is a synchronous
    device round-trip inside launch and every carve copies the matrix —
    so chunking LOWERED CPU steady throughput ~15% when measured."""
    env = os.environ.get("YBTPU_MERGE_CHUNK_ROWS")
    if env is None:
        return (1 << 20) if jax.default_backend() == "tpu" else 0
    try:
        t = int(env)
    except ValueError:  # yblint: contained(malformed env override falls back to the platform default target)
        return (1 << 20) if jax.default_backend() == "tpu" else 0
    return t if t >= 1024 else 0


def _mask_route_host(words: np.ndarray, dkl: np.ndarray) -> np.ndarray:
    """words [w_route, s] u32, dkl [s] int32 -> doc-key-masked route
    (host wrapper over the shared merge_gc.route_word_mask)."""
    msk = np.asarray(route_word_mask(jnp.asarray(dkl, jnp.int32),
                                     words.shape[0]))
    return words & msk


@functools.partial(jax.jit, static_argnames=("k_pad", "m", "w_route",
                                             "n_iters"))
def _chunk_split_search(cols, run_ns, splitters, k_pad: int, m: int,
                        w_route: int, n_iters: int):
    """First index >= splitter per (run, splitter): [k_pad, n_split].

    Runs are sorted and routes are monotone within a run (see module
    comment), so a vectorized binary search with leading-axis gathers
    suffices; only real lanes (mid < run_n) are ever compared."""
    dkl = cols[_ROW_DKL].astype(jnp.int32)
    n_split = splitters.shape[0]
    runs = jnp.arange(k_pad, dtype=jnp.int32)[:, None]
    lo = jnp.zeros((k_pad, n_split), jnp.int32)
    hi = jnp.broadcast_to(run_ns[:, None], (k_pad, n_split))
    base = runs * m
    wt = cols[_ROW_WORDS:_ROW_WORDS + w_route].T          # [n, w_route]

    def body(_, lh):
        lo, hi = lh
        live = lo < hi
        mid = (lo + hi) >> 1
        idx = base + mid                                   # [k, n_split]
        kw = wt[idx]                                       # [k, ns, w]
        kd = dkl[idx]
        kr = kw & route_word_mask(kd, w_route, leading=False)
        sp = splitters[None, :, :]
        lt = jnp.zeros(kr.shape[:-1], bool)
        eq = jnp.ones(kr.shape[:-1], bool)
        for i in range(w_route):
            lt = lt | (eq & (kr[..., i] < sp[..., i]))
            eq = eq & (kr[..., i] == sp[..., i])
        ge = ~lt
        hi = jnp.where(live & ge, mid, hi)
        lo = jnp.where(live & ~ge, mid + 1, lo)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
    return lo


@functools.partial(jax.jit, static_argnames=("m", "m_c", "k_pad"))
def _carve_chunk(cols, starts, lens, m: int, m_c: int, k_pad: int):
    """Slice each run's [starts[i], starts[i]+lens[i]) rows into a fresh
    run-major [r, k_pad*m_c] matrix, padding the tails.

    A window may poke into the NEXT run's region (harmless: lens masking
    covers it, since starts[i]+lens[i] <= m).  Only the LAST slot can poke
    past the matrix end, where dynamic_slice would clamp and silently
    misalign lane j from starts[i]+j — that slot selects from a small
    [r, 2*m_c] tail extension instead of copying the whole parent."""
    r = cols.shape[0]
    n_pad = k_pad * m
    pad_col = jnp.asarray(pad_template(r))[:, None]
    lane = jnp.arange(m_c, dtype=jnp.int32)[None, :]
    parts = []
    for i in range(k_pad):
        st = i * m + starts[i]
        if i < k_pad - 1:
            seg = jax.lax.dynamic_slice(cols, (0, st), (r, m_c))
        else:
            seg_a = jax.lax.dynamic_slice(
                cols, (0, jnp.minimum(st, n_pad - m_c)), (r, m_c))
            tail_ext = jnp.concatenate(
                [jax.lax.dynamic_slice(cols, (0, n_pad - m_c), (r, m_c)),
                 jnp.tile(pad_col, (1, m_c))], axis=1)
            delta = jnp.maximum(st - (n_pad - m_c), 0)
            seg_b = jax.lax.dynamic_slice(tail_ext, (0, delta), (r, m_c))
            seg = jnp.where(st > n_pad - m_c, seg_b, seg_a)
        parts.append(jnp.where(lane < lens[i], seg, pad_col))
    return jnp.concatenate(parts, axis=1)


class _ChunkedMergeGCHandle:
    """Concatenation of per-chunk merge+GC results in global merged order.

    Chunks are range-partitioned by route, so chunk-order concatenation IS
    the global merged order; per-chunk perms (which index the chunk's own
    live-run concatenation) remap through the slice offsets.

    HBM write-through staging (gather_staged_outputs) works through
    `to_parent_products()`, which uploads the decoded decisions back as
    parent-domain device arrays: ~24 MB at 4M rows, far cheaper than the
    ~130 MB output-column re-upload that skipping write-through would
    cost every subsequent compaction."""

    def __init__(self, handles, metas, staged: StagedRuns,
                 params=None, snapshot: bool = False, carve=None):
        self._handles = handles          # one per chunk, dispatch order
        self._metas = metas              # (starts[k_live], lens[k_live])
        self._staged = staged
        self._result = None
        self._perm_dev = None
        self._keep_dev = None
        self._mk_dev = None
        # re-carve info for per-chunk device-fault retry: the chunk
        # buffers themselves are donated (their HBM is gone after the
        # launch), but the PARENT matrix is intact, so a failed chunk is
        # re-carved from it and re-dispatched once
        self._params = params
        self._snapshot = snapshot
        self._carve = carve              # (starts_full, lens_full, m_c)

    def _result_with_retry(self, i: int):
        """Chunk i's (perm, keep, mk) with ONE device-fault retry: re-carve
        the chunk from the intact parent matrix and re-dispatch. A second
        failure raises DeviceFaultError so the compaction layer can
        quarantine the shape bucket and fall back to the native merge."""
        from yugabyte_tpu.ops import device_faults
        h = self._handles[i]
        try:
            return h.result()
        except Exception as e:  # noqa: BLE001 — device-fault containment
            if self._carve is None or not device_faults.is_device_fault(e):
                raise
            _chunk_retry_counter().increment()
            from yugabyte_tpu.utils.trace import TRACE
            TRACE("run_merge: chunk %d device fault (%r) — re-carving "
                  "and retrying once", i, e)
            staged = self._staged
            starts, lens, m_c = self._carve[i]
            k_live = len(staged.run_ns)
            try:
                carved = _carve_chunk(
                    staged.cols_dev, jnp.asarray(starts),
                    jnp.asarray(lens), staged.m, m_c, staged.k_pad)
                sub = StagedRuns(carved, m_c, staged.k_pad, staged.w,
                                 [int(x) for x in lens[:k_live]],
                                 staged.cmp_rows, staged.n_cmp)
                h2 = launch_merge_gc(sub, self._params,
                                     snapshot=self._snapshot,
                                     host_async=False, donate=True)
                out = h2.result()
            except Exception as e2:  # noqa: BLE001 — retry exhausted
                raise DeviceFaultError(
                    (staged.k_pad, staged.m), e2) from e2
            self._handles[i] = h2   # memoized passes reuse the good run
            return out

    def _chunk_results(self):
        """Per-chunk (perm, keep, mk) host tuples — via ONE fused device
        concat + host transfer of every chunk's packed decisions (each
        separate np.asarray pays a full device round trip). Any failure
        degrades to the per-chunk path, which preserves the pallas ->
        network fallback semantics."""
        hs = self._handles
        from yugabyte_tpu.ops import device_faults
        if os.environ.get("YBTPU_FUSED_DOWNLOAD", "1") == "0" \
                or device_faults.armed_count():
            # armed fault injection takes the per-chunk path, where the
            # injection sites and the re-carve retry live — the fused
            # concat would bypass both
            return [self._result_with_retry(i) for i in range(len(hs))]
        try:
            from yugabyte_tpu.utils.metrics import pipeline_span
            devs = [h._packed_dev for h in hs]
            if len({d.shape[1] for d in devs}) == 1:
                rows = [d.shape[0] for d in devs]
                with pipeline_span("device"):
                    cat = np.asarray(jnp.concatenate(devs, axis=0))
                with pipeline_span("decision_unpack", inclusive="host"):
                    out, off = [], 0
                    for h, r in zip(hs, rows):
                        out.append(_decode_packed(cat[off:off + r],
                                                  h._staged))
                        off += r
                return out
        except Exception as e:  # noqa: BLE001 — degrade, never fail here
            import sys as _sys
            print(f"[run_merge] fused chunk download failed — using the "
                  f"per-chunk path: {e!r}", file=_sys.stderr, flush=True)
        return [self._result_with_retry(i) for i in range(len(hs))]

    def _remap_perm(self, p: np.ndarray, starts: np.ndarray,
                    lens: np.ndarray) -> np.ndarray:
        """Chunk-local perm (over the chunk's slot concatenation) ->
        global input-row indices, through the slice offsets and — when the
        slots were greedily packed — the per-slot run_maps."""
        staged = self._staged
        k_live = len(staged.run_ns)
        lb = np.concatenate(([0], np.cumsum(lens)))
        run_of = np.searchsorted(lb[1:], p, side="right")
        slot_pos = p - lb[run_of] + starts[run_of]
        if staged.run_maps is None:
            grb = np.concatenate(([0], np.cumsum(staged.run_ns)))
            return grb[:k_live][run_of] + slot_pos
        out = np.empty(len(p), dtype=np.int64)
        for r_i in range(k_live):
            selr = run_of == r_i
            if selr.any():
                out[selr] = staged.run_maps[r_i][slot_pos[selr]]
        return out

    def result(self):
        if self._result is not None:
            return self._result
        from yugabyte_tpu.utils.metrics import pipeline_span
        perms, keeps, mks = [], [], []
        chunks = self._chunk_results()
        with pipeline_span("decision_remap"):
            for (p, keep, mk), (starts, lens) in zip(chunks, self._metas):
                perms.append(self._remap_perm(p, starts, lens))
                keeps.append(keep)
                mks.append(mk)
            self._result = (np.concatenate(perms), np.concatenate(keeps),
                            np.concatenate(mks))
        return self._result

    def result_iter(self):
        """Stream per-chunk (perm, keep, make_tombstone) — the stage-C
        hand-off of the compaction pipeline. Chunks are range-partitioned
        by route, so chunk-order concatenation IS the global merged order:
        the consumer (storage/compaction.py's streaming SST writer) can
        write chunk i's survivors while chunks i+1.. still compute or
        ride the link. All pending packed buffers start their async D2H
        up front; the full result is memoized so a later result() call
        pays nothing extra."""
        if self._result is not None:
            yield self._result
            return
        for h in self._handles:
            pd = getattr(h, "_packed_dev", None)
            if pd is not None:
                try:
                    pd.copy_to_host_async()
                except (AttributeError, NotImplementedError):
                    pass
        from yugabyte_tpu.utils.metrics import pipeline_span
        perms, keeps, mks = [], [], []
        for i, (starts, lens) in enumerate(self._metas):
            p, keep, mk = self._result_with_retry(i)
            # (no span is held open across the yield)
            with pipeline_span("decision_remap"):
                perm_g = self._remap_perm(p, starts, lens)
            perms.append(perm_g)
            keeps.append(keep)
            mks.append(mk)
            yield perm_g, keep, mk
        with pipeline_span("decision_remap"):
            self._result = (np.concatenate(perms), np.concatenate(keeps),
                            np.concatenate(mks))

    def to_parent_products(self) -> None:
        """Build the parent-domain device arrays gather_staged_outputs
        needs (perm over the PADDED run-major layout, keep/mk padded to
        n_pad) from the decoded host results."""
        if self._perm_dev is not None:
            return
        staged = self._staged
        perm, keep, mk = self.result()
        grb = np.concatenate(([0], np.cumsum(staged.run_ns)))
        run_of = np.searchsorted(grb[1:], perm, side="right")
        perm_pad = (run_of.astype(np.int64) * staged.m
                    + (perm - grb[run_of]))
        n_pad = staged.n_pad
        pp = np.zeros(n_pad, dtype=np.int32)
        pp[:len(perm_pad)] = perm_pad
        kp = np.zeros(n_pad, dtype=bool)
        kp[:len(keep)] = keep
        mp = np.zeros(n_pad, dtype=bool)
        mp[:len(mk)] = mk
        dev = getattr(staged.cols_dev, "device", None)
        put = (lambda a: jax.device_put(a, dev)) if dev is not None \
            else jnp.asarray
        self._perm_dev = put(pp)
        self._keep_dev = put(kp)
        self._mk_dev = put(mp)


def _launch_chunked(staged: StagedRuns, params: GCParams, snapshot: bool,
                    target: int):
    """Split one staged job into route-partitioned chunk launches.

    Returns a handle, or None when chunking cannot help (chunk bucket
    would not shrink below the parent's m) — the caller then launches the
    single big program as before."""
    k_live = len(staged.run_ns)
    if k_live < 1 or staged.n == 0:
        return None
    m, k_pad, w = staged.m, staged.k_pad, staged.w
    w_route = min(_W_ROUTE_CHUNK, w)
    nc = max(2, -(-staged.n // max(1, target // 2)))
    n_split = nc - 1
    run_ns_arr = np.zeros(k_pad, dtype=np.int32)
    run_ns_arr[:k_live] = staged.run_ns

    # --- splitters from host-side strided samples (tiny download) -------
    s_per = 256
    idx = []
    for i, rn in enumerate(staged.run_ns):
        if rn > 0:
            idx.append(i * m + (np.arange(s_per, dtype=np.int64) * rn)
                       // s_per)
    idx = np.concatenate(idx)
    words = np.asarray(staged.cols_dev[
        _ROW_WORDS:_ROW_WORDS + w_route][:, idx])
    dkl = np.asarray(staged.cols_dev[_ROW_DKL][idx]).astype(np.int32)
    routes = _mask_route_host(words, dkl).T          # [s, w_route]
    order = np.lexsort(tuple(routes[:, i]
                             for i in range(w_route - 1, -1, -1)))
    routes = routes[order]
    q = (np.arange(1, nc, dtype=np.int64) * len(routes)) // nc
    splitters = routes[q]                            # [n_split, w_route]

    bounds = np.asarray(_chunk_split_search(
        staged.cols_dev, jnp.asarray(run_ns_arr), jnp.asarray(splitters),
        k_pad, m, w_route, int(m).bit_length() + 1))
    bounds = np.concatenate(
        [np.zeros((k_pad, 1), np.int32), bounds,
         run_ns_arr[:, None]], axis=1)               # [k_pad, nc+1]
    bounds = np.maximum.accumulate(bounds, axis=1)

    lens_all = np.diff(bounds, axis=1)               # [k_pad, nc]
    m_c = run_bucket(int(lens_all.max()))
    if m_c >= m:
        return None                                  # no shape win: skew
    handles, metas, carve = [], [], []
    for c in range(nc):
        starts = bounds[:, c].astype(np.int32)
        lens = lens_all[:, c].astype(np.int32)
        if int(lens.sum()) == 0:
            continue                                 # duplicate splitter
        carved = _carve_chunk(staged.cols_dev, jnp.asarray(starts),
                              jnp.asarray(lens), m, m_c, k_pad)
        sub = StagedRuns(carved, m_c, k_pad, w,
                         [int(x) for x in lens[:k_live]],
                         staged.cmp_rows, staged.n_cmp)
        # host_async=False: the parent handle fuses all chunks' packed
        # buffers into one concat + download; per-chunk async D2H would
        # move the same bytes twice. donate=True: the
        # carved matrix is transient (only this launch reads it), so XLA
        # reuses its HBM in place instead of holding chunk input + merge
        # working set live together
        handles.append(launch_merge_gc(sub, params, snapshot=snapshot,
                                       host_async=False, donate=True))
        metas.append((starts[:k_live].astype(np.int64),
                      lens[:k_live].astype(np.int64)))
        carve.append((starts, lens, m_c))
    if not handles:
        return None
    return _ChunkedMergeGCHandle(handles, metas, staged,
                                 params=params, snapshot=snapshot,
                                 carve=carve)


def _pick_impl(staged: StagedRuns) -> str:
    """Merge strategy: YBTPU_MERGE_IMPL = auto|pallas|network.

    auto on TPU: the pallas merge-path tournament (ops/pallas_merge.py)
    where `pallas_merge.supported` holds — it replaces ~log^2 full-array
    compare-exchange stages + a giant lane gather with log2(K) streaming
    level passes — and the jnp network otherwise.  The jnp network on
    every other backend (pallas interpret mode is far too slow for the
    production CPU fallback path).
    """
    impl = os.environ.get("YBTPU_MERGE_IMPL", "auto")
    if impl == "network" or staged.k_pad < 2:
        return "network"
    from yugabyte_tpu.ops import pallas_merge
    if not pallas_merge.supported(staged):
        if impl == "pallas":
            import sys as _sys
            print(f"[run_merge] YBTPU_MERGE_IMPL=pallas requested but "
                  f"preconditions fail (k_pad={staged.k_pad} m={staged.m} "
                  f"w={staged.w}) — using the jnp network instead",
                  file=_sys.stderr, flush=True)
        return "network"
    if impl == "pallas":
        return "pallas"
    return "pallas" if jax.default_backend() == "tpu" else "network"


# Deliberately unannotated latch bool: False->True exactly once, torn
# reads impossible for a bool, and a racy read only costs one extra
# pallas attempt that fails the same way.
_pallas_broken = False  # set on the first Mosaic lowering/runtime failure


def _fallback_counter(name: str, help: str):
    from yugabyte_tpu.utils.metrics import kernel_metrics
    return kernel_metrics().counter(name, help)


class _PallasFallbackHandle:
    """Wraps a pallas launch so a lazy compile/runtime failure (surfacing
    at .result()) degrades to the jnp network instead of killing the
    caller — the first real-TPU run of the kernel must never take the
    whole bench/compaction down with it."""

    def __init__(self, inner, staged, params, snapshot):
        self._inner = inner
        self._args = (staged, params, snapshot)
        self._effective = None   # set by result(): the handle that ran

    def result(self):
        global _pallas_broken
        try:
            out = self._inner.result()
            self._effective = self._inner
            return out
        except Exception as e:  # noqa: BLE001 — lowering/launch failure
            import sys as _sys
            _pallas_broken = True
            _fallback_counter(
                "kernel_pallas_fallback_total",
                "pallas merge failures degraded to the jnp "
                "network").increment()
            print(f"[run_merge] pallas kernel failed at result() — "
                  f"falling back to the jnp network for this process: "
                  f"{e!r}", file=_sys.stderr, flush=True)
            staged, params, snapshot = self._args
            self._effective = launch_merge_gc(staged, params,
                                              snapshot=snapshot)
            return self._effective.result()

    def result_iter(self):
        """Explicit (not via __getattr__): the inner handle's iterator
        would bypass the fallback try/except around .result()."""
        yield self.result()

    def __getattr__(self, name):
        # delegate device-resident merge products (_staged, _perm_dev,
        # _keep_dev, _mk_dev) to whichever handle actually produced the
        # result, so HBM write-through staging (gather_staged_outputs)
        # works through the fallback wrapper
        return getattr(self._effective if self._effective is not None
                       else self._inner, name)


def launch_merge_gc(staged: StagedRuns, params: GCParams,
                    snapshot: bool = False,
                    host_async: bool = True,
                    donate: bool = False) -> MergeGCHandle:
    """donate: the caller promises staged.cols_dev is TRANSIENT (a carved
    subcompaction chunk or a per-chunk pipeline upload that nothing reads
    after this launch) — the fused program then donates it so XLA reuses
    its HBM for the merge scratch. Never set for slab-cache entries or a
    chunked parent matrix (write-through staging gathers from those)."""
    global _pallas_broken
    from yugabyte_tpu.utils.metrics import (kernel_metrics,
                                            record_kernel_dispatch)
    record_kernel_dispatch("kernel_run_merge", staged.n, staged.n_pad)
    target = _chunk_target_rows()
    if (target and staged.k_pad >= 2 and staged.n_pad > target
            and staged.m >= 512):
        # bound the compiled shape: subcompaction chunks reuse the
        # already-compiled bucket executable (see _launch_chunked)
        h = _launch_chunked(staged, params, snapshot, target)
        if h is not None:
            kernel_metrics().counter(
                "kernel_chunked_launch_total",
                "merge jobs split into route-partitioned chunk "
                "launches").increment()
            return h
    # device-fault injection site "dispatch" (ops/device_faults.py): a
    # real XLA compile failure surfaces here, synchronously, per leaf
    # launch (each chunk of a chunked job passes through this point);
    # the bucket lets a "slow" nemesis throttle one shape bucket only
    from yugabyte_tpu.ops import device_faults
    device_faults.maybe_fault("dispatch", bucket=(staged.k_pad, staged.m))
    explicit = os.environ.get("YBTPU_MERGE_IMPL", "auto") == "pallas"
    if (not _pallas_broken or explicit) and _pick_impl(staged) == "pallas":
        from yugabyte_tpu.ops import pallas_merge
        try:
            h = pallas_merge.launch_merge_gc_pallas(staged, params,
                                                    snapshot=snapshot,
                                                    host_async=host_async)
        except Exception as e:  # noqa: BLE001 — trace/compile failure
            if explicit:
                raise
            import sys as _sys
            _pallas_broken = True
            _fallback_counter(
                "kernel_pallas_fallback_total",
                "pallas merge failures degraded to the jnp "
                "network").increment()
            print(f"[run_merge] pallas kernel failed to launch — using "
                  f"the jnp network for this process: {e!r}",
                  file=_sys.stderr, flush=True)
        else:
            kernel_metrics().counter(
                "kernel_pallas_merge_total",
                "merges launched on the pallas kernel").increment()
            _record_bucket(("pallas", staged.k_pad, staged.m, staged.w,
                            staged.n_cmp, params.is_major_compaction,
                            params.retain_deletes, snapshot))
            return h if explicit else _PallasFallbackHandle(
                h, staged, params, snapshot)
    kernel_metrics().counter(
        "kernel_network_merge_total",
        "merges launched on the jnp bitonic network").increment()
    cutoff = params.history_cutoff_ht
    cutoff_phys = cutoff >> 12
    lexsort = _use_lexsort()
    use_donate = donate and _donation_supported()
    fn = _merge_gc_runs_fused_donated if use_donate else _merge_gc_runs_fused
    _record_bucket(("lexsort" if lexsort else "network", staged.k_pad,
                    staged.m, staged.w, staged.n_cmp,
                    params.is_major_compaction, params.retain_deletes,
                    snapshot, use_donate))
    # runtime iota operand: see merge_network's pos docstring (compile-
    # time constant folding of per-stage parity masks)
    def _dispatch():
        pos = jnp.arange(staged.n_pad, dtype=jnp.int32)
        return fn(
            staged.cols_dev, jnp.asarray(staged.cmp_rows), pos,
            jnp.uint32(cutoff >> 32), jnp.uint32(cutoff & 0xFFFFFFFF),
            jnp.uint32(cutoff_phys >> 20),
            jnp.uint32(cutoff_phys & 0xFFFFF),
            k_pad=staged.k_pad, m=staged.m, w=staged.w,
            n_cmp=staged.n_cmp,
            is_major=params.is_major_compaction,
            retain_deletes=params.retain_deletes, snapshot=snapshot,
            lexsort=lexsort)

    packed, perm, keep, mk = _dispatch()
    if use_donate:
        # the dispatch above consumed cols_dev (XLA reuses its HBM);
        # poison it in the handle's staged copy so a later read — e.g.
        # gather_staged_outputs write-through on a handle that was
        # wrongly launched donated — fails loudly instead of staging
        # garbage into the slab cache. Decode only needs the metadata.
        import dataclasses as _dc
        staged = _dc.replace(
            staged, cols_dev=_DonatedBuffer("_merge_gc_runs_fused_donated"))
    # non-donated launches keep a relaunch closure: the input buffer is
    # intact, so a device fault at download time gets one re-dispatch
    # before the caller's native fallback (chunked jobs instead re-carve
    # from the parent in _ChunkedMergeGCHandle._result_with_retry)
    return MergeGCHandle(packed, staged, perm, keep, mk,
                         host_async=host_async,
                         relaunch=None if use_donate else _dispatch)


def merge_and_gc_runs(slabs: Sequence[KVSlab], params: GCParams, device=None,
                      staged: Optional[StagedRuns] = None,
                      snapshot: bool = False
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Blocking wrapper: stage (if needed), run, decode.

    Drop-in for ops/merge_gc.merge_and_gc_device when the caller knows the
    run structure — which every real caller (compaction over SSTs, scans
    over memtable+SSTs) does. Guards: empty input returns empty arrays; a
    heavily skewed run-size mix (where padding every run to the largest
    bucket would inflate device work/memory beyond 2x the radix path's
    single bucket) falls back to the radix kernel.
    """
    import os as _os
    from yugabyte_tpu.utils.metrics import kernel_metrics
    if staged is None:
        live = [s for s in slabs if s.n]
        if not live:
            z = np.zeros(0, dtype=np.int64)
            zb = np.zeros(0, dtype=bool)
            return z, zb, zb
        if (run_layout_inflation([s.n for s in live]) > 2.0
                or _os.environ.get("YBTPU_FORCE_RADIX", "").lower()
                not in ("", "0", "false")):
            from yugabyte_tpu.ops.merge_gc import merge_and_gc_device
            from yugabyte_tpu.ops.slabs import concat_slabs
            kernel_metrics().counter(
                "kernel_radix_fallback_total",
                "run-merges routed to the radix re-sort (skewed run "
                "layout or forced)").increment()
            merged = concat_slabs(live)
            perm, keep, mk = merge_and_gc_device(merged, params,
                                                 device=device)
            real = perm < merged.n
            return perm[real].astype(np.int64), keep[real], mk[real]
        staged = stage_runs_from_slabs(live, device)
    return launch_merge_gc(staged, params, snapshot=snapshot).result()


def run_layout_inflation(run_ns: Sequence[int]) -> float:
    """Padded-slot inflation of the run-major layout vs one radix bucket.

    k_pad * max(run_bucket) over bucket_size(sum): >1 means the bitonic
    path touches that many more slots than the radix re-sort would. Skewed
    picks (one huge base run + tiny L0s) can inflate ~K x; callers fall
    back to the radix kernel past 2x.
    """
    from yugabyte_tpu.ops.merge_gc import bucket_size
    k = len(run_ns)
    k_pad = 1 << max(0, (k - 1).bit_length()) if k > 1 else 1
    m = max(run_bucket(n) for n in run_ns)
    return (k_pad * m) / bucket_size(int(sum(run_ns)))
