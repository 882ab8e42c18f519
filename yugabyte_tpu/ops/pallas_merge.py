"""Pallas merge-path compaction kernel: the round-4 flagship.

Round 3's bitonic merge network (ops/run_merge.py merge_network) runs
~log2(2L) compare-exchange stages PER LEVEL over the full [C, n] comparator
matrix, then pays one giant lane-axis gather (`cols[:, perm]`, ~180 MB/s on
TPU) to materialize the merged matrix for GC.  At 4M rows that is ~44 full-
array HBM passes + a >1 s gather: measured ~50x off the HBM roofline
(VERDICT r3).

This module replaces it with the classic *merge path* decomposition
(Green/McColl/Bader-style diagonal partitioning), reshaped for the TPU
memory hierarchy:

  level pass (log2(K) of them, pairwise tournament over the pre-sorted runs):
    1. split search (jnp): for every output tile boundary d = t*TILE, a
       vectorized binary search over the pair's diagonal finds how many
       elements come from run A vs run B.  O(n/TILE * log L) work with
       leading-axis gathers of a few KB - negligible.
    2. tile merge (pallas): each grid step loads the two aligned TILE-blocks
       covering its A-window and B-window into VMEM (scalar-prefetched block
       indices), aligns them with log-decomposed static rolls, masks
       out-of-window lanes to +inf sentinels, and bitonically merges
       2*TILE lanes IN VMEM (log2(2*TILE) VPU stages).  All payload rows
       ride along, so the merged matrix streams straight back to HBM -
       no global gather, ever.

HBM traffic per level: read n + write n of the [Rp, n] payload (plus the
tiny split-search reads).  Total: 2 * n * Rp * 4 B * log2(K) - tens of ms
at 4M rows on a v5e, vs >1 s for the network+gather formulation.

Ordering is the identical composite comparator the network uses (pruned
cmp rows, descending rows complemented, global index as final tiebreak), so
perm/keep/make-tombstone are byte-identical to ops/run_merge.py and the
native C++ baseline (differential-tested in tests/test_pallas_merge.py).

ref (what this replaces, architecture only): rocksdb/table/merger.cc:51
(MergingIterator min-heap), rocksdb/db/compaction_job.cc:442.
"""

from __future__ import annotations

import functools
import os
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from yugabyte_tpu.ops.merge_gc import (
    _ROW_HT_HI, _ROW_KEY_LEN, _ROW_WID, _ROW_WORDS, GCParams, PAD_SENTINEL,
    gc_over_sorted, pack_bits_u32 as _pack_group_bits)
from yugabyte_tpu.utils import jax_setup  # noqa: F401  (compilation cache)

_U32_MAX = np.uint32(0xFFFFFFFF)   # numpy scalar: inlines as a literal


def _inv_word(row: int) -> int:
    """Complement mask for descending comparator rows (ht_hi/ht_lo/wid)."""
    return 0xFFFFFFFF if _ROW_HT_HI <= row <= _ROW_WID else 0


def _lex_gt_rows(a, b, n_rows: int):
    """Strict lexicographic > over the leading axis (row-major keys).

    Operates on [1, n] row slices, never 1-D vectors: Mosaic cannot lower
    wide 1-D i1 vectors (arith.trunci vector<Nxi8> -> vector<Nxi1>), so
    every mask stays 2-D.  Returns [1, n] bool."""
    gt = jnp.zeros((1,) + a.shape[1:], dtype=bool)
    eq = jnp.ones((1,) + a.shape[1:], dtype=bool)
    for i in range(n_rows):
        gt = gt | (eq & (a[i:i + 1] > b[i:i + 1]))
        eq = eq & (a[i:i + 1] == b[i:i + 1])
    return gt


def _lex_gt_last(a, b, c: int):
    """Strict lexicographic > over the LAST axis (gathered key tuples)."""
    gt = jnp.zeros(a.shape[:-1], dtype=bool)
    eq = jnp.ones(a.shape[:-1], dtype=bool)
    for i in range(c):
        gt = gt | (eq & (a[..., i] > b[..., i]))
        eq = eq & (a[..., i] == b[..., i])
    return gt


def _compute_splits(s_t, L: int, tile: int, n_pairs: int, tpp: int, c: int):
    """Merge-path diagonal splits for one tournament level.

    s_t: [n, c] complemented comparator keys, transposed so the binary
    search gathers along the LEADING axis (the fast gather direction).
    Returns int32 [n_pairs * (tpp + 1)]: for pair p, boundary t, the number
    of A-run elements among the first t*tile merged elements.  Ties take A
    first (global index order - A's indices all precede B's), which the
    strict `keyA > keyB` predicate encodes exactly.
    """
    zeros = jnp.zeros((n_pairs, 1), jnp.int32)
    full = jnp.full((n_pairs, 1), L, jnp.int32)
    if tpp <= 1:
        return jnp.concatenate([zeros, full], axis=1).reshape(-1)
    d = (jnp.arange(1, tpp, dtype=jnp.int32) * tile)[None, :]
    pair = jnp.arange(n_pairs, dtype=jnp.int32)[:, None]
    base_a = pair * (2 * L)
    base_b = base_a + L
    d2 = jnp.broadcast_to(d, (n_pairs, tpp - 1))
    lo = jnp.maximum(0, d2 - L)
    hi = jnp.minimum(d2, L)

    def body(_, lh):
        lo, hi = lh
        live = lo < hi
        mid = (lo + hi) >> 1
        ka = s_t[base_a + mid]              # [n_pairs, tpp-1, c]
        kb = s_t[base_b + (d2 - mid - 1)]
        gt = _lex_gt_last(ka, kb, c)        # keyA[mid] > keyB[d-mid-1]
        lo = jnp.where(live & ~gt, mid + 1, lo)
        hi = jnp.where(live & gt, mid, hi)
        return lo, hi

    iters = max(1, int(L).bit_length() + 1)
    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return jnp.concatenate([zeros, lo, full], axis=1).reshape(-1)


def _rev_window_start(p, t, a0, L: int, tile: int, n: int):
    """Start of the B window in REVERSED-matrix coordinates.

    Shared by the kernel body (to derive the in-tile shift) and the
    BlockSpec index maps (to prefetch the covering blocks) — the two MUST
    agree exactly or the kernel shifts against the wrong blocks.  May be
    negative near the array end (wrapped lanes are always masked).
    """
    return n - tile - p * 2 * L - L - t * tile + a0


def _rev_block_lo(p, t, a0, L: int, tile: int, n: int):
    """Block index of the low prefetched block for the reversed B window."""
    rb0 = _rev_window_start(p, t, a0, L, tile, n)
    return jnp.clip(rb0 // tile, 0, n // tile - 1)


def _shift_left(buf, amt, max_shift: int):
    """buf[:, i] <- buf[:, i + amt] for dynamic amt in [0, max_shift):
    log-decomposed static rolls (guaranteed Mosaic lowering; a dynamic
    lane-axis slice is not)."""
    k = 1
    while k < max_shift:
        buf = jnp.where((amt & k) != 0, jnp.roll(buf, -k, axis=1), buf)
        k *= 2
    return buf


def _make_tile_kernel(L: int, tile: int, tpp: int, rp: int, n: int,
                      cmp_rows: Tuple[int, ...], idx_row: int):
    """Kernel body for one tournament level (closure over static config).

    The B window is loaded from a globally lane-REVERSED copy of the
    payload matrix (flipped outside the kernel — Mosaic has no lowering
    for the `rev` primitive, so `wb[:, ::-1]` inside the kernel fails on
    real TPU).  In reversed coordinates the window is a contiguous
    ascending slice whose keys run descending, which is exactly the
    bitonic layout the halving network needs.
    """
    c = len(cmp_rows)
    nblk = L // tile
    inv_consts = [_inv_word(r) for r in cmp_rows]

    def kernel(sa_ref, a_lo, a_hi, br_lo, br_hi, out_ref):
        p = pl.program_id(0)
        t = pl.program_id(1)
        base = p * (tpp + 1)
        a0 = sa_ref[base + t]
        a1 = sa_ref[base + t + 1]
        la = a1 - a0
        da = a0 - jnp.minimum(a0 // tile, nblk - 1) * tile
        rb0 = _rev_window_start(p, t, a0, L, tile, n)
        blk_lo = _rev_block_lo(p, t, a0, L, tile, n)
        dr = (rb0 - blk_lo * tile) & (2 * tile - 1)

        def window(lo_ref, hi_ref, shift, max_shift, valid_mask):
            # valid_mask is [1, tile]; all mask math stays 2-D for Mosaic
            buf = jnp.concatenate([lo_ref[:], hi_ref[:]], axis=1)
            buf = _shift_left(buf, shift, max_shift)[:, :tile]
            keys = [jnp.where(valid_mask, buf[r:r + 1] ^ jnp.uint32(iv),
                              _U32_MAX)
                    for r, iv in zip(cmp_rows, inv_consts)]
            keys.append(jnp.where(valid_mask, buf[idx_row:idx_row + 1],
                                  _U32_MAX))
            return jnp.concatenate(keys + [buf], axis=0)  # [c+1+rp, tile]

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        wa = window(a_lo, a_hi, da, tile, lane < la)
        # valid B lanes are the LAST tile-la: reversed window keys descend
        wb = window(br_lo, br_hi, dr, 2 * tile, lane >= la)
        z = jnp.concatenate([wa, wb], axis=1)             # bitonic [., 2t]
        lane2 = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * tile), 1)
        s = tile
        while s >= 1:
            hi_half = (lane2 & s) != 0                    # [1, 2t]
            partner = jnp.where(hi_half, jnp.roll(z, s, axis=1),
                                jnp.roll(z, -s, axis=1))
            gt = _lex_gt_rows(z[:c + 1], partner[:c + 1], c + 1)
            # hi_half XOR gt == where(hi_half, ~gt, gt) but stays an i1
            # predicate: a select with BOOL OPERANDS materializes i8 bools
            # and Mosaic cannot truncate i8 vectors back to i1
            take = hi_half ^ gt                           # [1, 2t]
            z = jnp.where(take, partner, z)
            s //= 2
        out_ref[:] = z[c + 1:, :tile]

    return kernel


def _merge_level(p_mat, L: int, tile: int, cmp_rows: Tuple[int, ...],
                 idx_row: int, interpret: bool):
    """One tournament level: merge run pairs of length L into length 2L."""
    rp, n = p_mat.shape
    n_pairs = n // (2 * L)
    tpp = (2 * L) // tile
    nblk = L // tile
    c = len(cmp_rows)

    inv_vec = jnp.asarray([_inv_word(r) for r in cmp_rows], jnp.uint32)
    s_t = (p_mat[jnp.asarray(cmp_rows, jnp.int32), :]
           ^ inv_vec[:, None]).T                     # [n, c]
    sa = _compute_splits(s_t, L, tile, n_pairs, tpp, c)
    # Mosaic cannot lower `rev`, so the B windows load from a lane-flipped
    # copy produced here in XLA (one extra HBM pass per level)
    p_rev = jnp.flip(p_mat, axis=1)
    nb_total = n // tile

    def ima_lo(p, t, sa_ref):
        a0 = sa_ref[p * (tpp + 1) + t]
        return (0, p * 2 * nblk + jnp.minimum(a0 // tile, nblk - 1))

    def ima_hi(p, t, sa_ref):
        a0 = sa_ref[p * (tpp + 1) + t]
        return (0, p * 2 * nblk + jnp.minimum(a0 // tile + 1, nblk - 1))

    def imbr_lo(p, t, sa_ref):
        a0 = sa_ref[p * (tpp + 1) + t]
        return (0, _rev_block_lo(p, t, a0, L, tile, n))

    def imbr_hi(p, t, sa_ref):
        a0 = sa_ref[p * (tpp + 1) + t]
        return (0, jnp.minimum(_rev_block_lo(p, t, a0, L, tile, n) + 1,
                               nb_total - 1))

    def imo(p, t, sa_ref):
        return (0, p * 2 * nblk + t)

    kernel = _make_tile_kernel(L, tile, tpp, rp, n, cmp_rows, idx_row)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pairs, tpp),
        in_specs=[pl.BlockSpec((rp, tile), ima_lo),
                  pl.BlockSpec((rp, tile), ima_hi),
                  pl.BlockSpec((rp, tile), imbr_lo),
                  pl.BlockSpec((rp, tile), imbr_hi)],
        out_specs=pl.BlockSpec((rp, tile), imo),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, n), jnp.uint32),
        interpret=interpret,
    )(sa, p_mat, p_mat, p_rev, p_rev)


@functools.partial(jax.jit, static_argnames=(
    "k_pad", "m", "w", "cmp_rows_t", "tile", "is_major", "retain_deletes",
    "snapshot", "interpret"))
def _pallas_merge_gc_fused(cols, pos,
                           cutoff_hi, cutoff_lo, cutoff_phys_hi,
                           cutoff_phys_lo,
                           k_pad: int, m: int, w: int,
                           cmp_rows_t: Tuple[int, ...], tile: int,
                           is_major: bool, retain_deletes: bool,
                           snapshot: bool, interpret: bool):
    """Fused tournament merge + GC + packed decision buffer.

    Same contract as run_merge._merge_gc_runs_fused: returns
    (packed_groups [n//32, 2+b], perm, keep, make_tombstone), with perm =
    run-major input index of each merged position, so MergeGCHandle and the
    write-through staging path work unchanged.
    """
    r = cols.shape[0]
    n = k_pad * m
    idx_row = r
    rp = ((r + 1 + 7) // 8) * 8
    p_mat = jnp.concatenate(
        [cols, pos.astype(jnp.uint32)[None, :],
         jnp.zeros((rp - r - 1, n), jnp.uint32)], axis=0)

    L = m
    while L < n:
        p_mat = _merge_level(p_mat, L, tile, cmp_rows_t, idx_row, interpret)
        L *= 2

    s = p_mat[:r]
    perm = p_mat[idx_row].astype(jnp.int32)
    keep, make_tomb = gc_over_sorted(
        s, w, cutoff_hi, cutoff_lo, cutoff_phys_hi, cutoff_phys_lo,
        is_major=is_major, retain_deletes=retain_deletes, snapshot=snapshot)
    keep = keep & (s[_ROW_KEY_LEN] != jnp.uint32(PAD_SENTINEL))

    groups = [_pack_group_bits(keep, n), _pack_group_bits(make_tomb, n)]
    b = max(1, (k_pad - 1).bit_length())
    src = (perm >> int(m).bit_length() - 1).astype(jnp.uint32)
    for t in range(b):
        groups.append(_pack_group_bits((src >> t) & 1, n))
    return jnp.stack(groups, axis=1), perm, keep, make_tomb


def default_tile(rp_rows: int) -> int:
    """VMEM-budgeted tile: 4 in-blocks + out + ~3x work values, 2x buffered."""
    t = int(os.environ.get("YBTPU_PALLAS_TILE", 0))
    if t:
        return t
    return 4096 if rp_rows <= 24 else 2048


def supported(staged) -> bool:
    """Pallas path preconditions: >=2 runs, tile-divisible power-of-two m."""
    if staged.k_pad < 2:
        return False
    rp = ((_ROW_WORDS + staged.w + 1 + 7) // 8) * 8
    tile = min(default_tile(rp), staged.m)
    if tile < 128 and not _interpret_mode():
        return False
    return staged.m % tile == 0


def _interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def launch_merge_gc_pallas(staged, params: GCParams, snapshot: bool = False,
                           host_async: bool = True):
    """Drop-in for run_merge.launch_merge_gc using the pallas tournament."""
    from yugabyte_tpu.ops.run_merge import MergeGCHandle
    cutoff = params.history_cutoff_ht
    cutoff_phys = cutoff >> 12
    pos = jnp.arange(staged.n_pad, dtype=jnp.int32)
    rp = ((_ROW_WORDS + staged.w + 1 + 7) // 8) * 8
    tile = min(default_tile(rp), staged.m)
    packed, perm, keep, mk = _pallas_merge_gc_fused(
        staged.cols_dev, pos,
        jnp.uint32(cutoff >> 32), jnp.uint32(cutoff & 0xFFFFFFFF),
        jnp.uint32(cutoff_phys >> 20), jnp.uint32(cutoff_phys & 0xFFFFF),
        k_pad=staged.k_pad, m=staged.m, w=staged.w,
        cmp_rows_t=tuple(int(x) for x in staged.cmp_rows), tile=tile,
        is_major=params.is_major_compaction,
        retain_deletes=params.retain_deletes, snapshot=snapshot,
        interpret=_interpret_mode())
    return MergeGCHandle(packed, staged, perm, keep, mk,
                         host_async=host_async)
