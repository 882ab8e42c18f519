"""Distributed compaction: range-repartition + per-shard merge/GC over a mesh.

The multi-chip form of the north-star kernel, in two shapes:

1. `distributed_compact` — ONE large job, key-range-sharded: each key range
   is one DEVICE of a `jax.sharding.Mesh`, and the data movement that the
   reference does with per-thread file iterators (ref:
   rocksdb/db/compaction_job.cc:330 GenSubcompactionBoundaries, :456-468)
   happens as XLA collectives over ICI:

     1. each shard samples its local route keys
     2. all_gather the samples -> identical global splitters on every shard
     3. bucket rows by destination shard; all_to_all exchanges the buckets
        (fixed per-destination capacity with all-0xFF padding rows, which
        sort to the tail and are dropped by the GC keep-mask like padding)
     4. per-shard fused radix merge + MVCC GC (ops/merge_gc.sort_and_gc)

   The input cols upload ONCE as a device-resident sharded buffer
   (explicit `NamedSharding` over the shard axis); the overflow retry
   (splitter skew blew a bucket past capacity) re-launches at doubled
   capacity FROM that resident buffer — no host re-pack, no re-upload.
   Attempts that provably cannot retry (capacity already covers every
   row, or the 64x ceiling) donate the buffer so XLA reuses its HBM for
   the exchange scratch.

2. `pooled_merge_gc` — MANY small jobs, one job per device: the
   compaction-pool wave kernel (tserver/compaction_pool.py). Concurrent
   tablets' merge+GC jobs of one shape bucket stack along the mesh axis
   and run as ONE shard_map dispatch; each slot runs the same fused
   program as the single-device path (ops/run_merge._merge_gc_runs_impl),
   so per-slot decisions are bit-identical to a sequential job — the
   multi-tablet aggregate-throughput service is a scheduling win, never a
   semantics change. Per-slot merge products stay device-resident for the
   write-through survivor-span gather, so the resident L0->L1->L2 chain
   survives sharding (the slot's device IS the tablet's cache partition).

Routing is by the first `_W_ROUTE` 32-bit words of the DOC KEY portion of
each key (words masked to doc_key_len, zero beyond it), compared
lexicographically. Every entry of one document has identical doc-key bytes
and doc_key_len, hence an identical route key — so a document's root + column
entries and all versions of a key always land on one shard and the GC segment
logic never straddles shards. Because routing is an order-preserving prefix
of the key, shards remain globally range-partitioned: shard s's keys all
sort <= shard s+1's.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from yugabyte_tpu.ops import merge_gc
from yugabyte_tpu.utils.jax_setup import Prewarm
from yugabyte_tpu.ops.merge_gc import (
    _ROW_DKL, _ROW_FLAGS, _ROW_KEY_LEN, _ROW_WORDS, GCParams, PAD_SENTINEL,
    StagedCols, bucket_size, build_sort_schedule, column_stats, pack_cols,
    pad_template, sort_and_gc)

# Route on up to this many leading doc-key words (16 bytes). Documents whose
# doc keys share all 16 bytes route to the same bucket; the overflow retry
# absorbs the resulting skew, so this is a perf knob, not correctness.
_W_ROUTE = 4

_SAMPLES_PER_SHARD = 64

# Capacity lattice floor + retry ceiling: capacity quantizes to powers of
# two >= _CAPACITY_MIN (the manifest's declared compile-key lattice), and
# the overflow retry doubles capacity_factor up to _MAX_CAPACITY_FACTOR
# before declaring the splitters hopeless.
_CAPACITY_MIN = 64
_MAX_CAPACITY_FACTOR = 64


def _overflow_retry_counter():
    from yugabyte_tpu.utils.metrics import kernel_metrics
    return kernel_metrics().counter(
        "dist_compact_overflow_retry_total",
        "distributed-compaction attempts re-launched at doubled "
        "per-destination capacity after a bucket overflow (splitter "
        "skew); retries re-shard from the device-resident cols")


@functools.lru_cache(maxsize=64)
def dist_compact_fn(mesh: Mesh, capacity: int, is_major: bool,
                    retain_deletes: bool = False, axis: str = "shard",
                    donate: bool = False):
    """Build (and cache) the jitted distributed compaction step for a mesh.

    Cached per (mesh, capacity, is_major, retain_deletes, axis, donate):
    rebuilding the shard_map closure per call would defeat the jit trace
    cache and re-lower the whole multi-collective program every compaction.

    Input cols: [R, n_total] sharded along dim 1; n_total = n_shards * n_local.
    Output: (cols_out [R, n_shards*capacity] sharded, keep, make_tombstone,
             overflow flag per shard, source-row index per merged position).

    donate: the caller promises the cols buffer is dead after this launch
    (an attempt that cannot be retried) — XLA then reuses its HBM for the
    exchange scratch instead of holding input + working set live together.
    """
    n_shards = mesh.devices.size

    def per_shard(cols_local, cutoff_hi, cutoff_lo, cph, cpl):
        r, n_local = cols_local.shape
        w_route = min(_W_ROUTE, r - _ROW_WORDS)
        u32max = jnp.uint32(0xFFFFFFFF)
        is_pad_in = cols_local[_ROW_KEY_LEN] == jnp.uint32(PAD_SENTINEL)
        # -- route key: doc-key words masked to doc_key_len ----------------
        # (identical across every entry/version of one document; padding
        # rows get all-0xFF route words so they route to the last shard)
        dkl = cols_local[_ROW_DKL].astype(jnp.int32)      # pad rows: -1
        words = cols_local[_ROW_WORDS:_ROW_WORDS + w_route]
        mask = merge_gc.route_word_mask(dkl, w_route)     # shared defn
        route = jnp.where(is_pad_in[None, :], u32max, words & mask)
        # -- 1/2: sample + all_gather + splitters --------------------------
        step = max(1, n_local // _SAMPLES_PER_SHARD)
        samples = route[:, ::step][:, :_SAMPLES_PER_SHARD]  # [w_route, s_loc]
        samp_pad = is_pad_in[::step][:_SAMPLES_PER_SHARD]
        g_samp = jax.lax.all_gather(samples, axis)          # [shards, w, s_loc]
        g_samp = jnp.moveaxis(g_samp, 1, 0).reshape(w_route, -1)
        g_pad = jax.lax.all_gather(samp_pad, axis).reshape(-1)
        # lex sort on the route words with the pad flag as final tiebreak,
        # so padding samples sort strictly after real ones even on 0xFF ties
        sorted_ops = jax.lax.sort(
            [g_samp[i] for i in range(w_route)] + [g_pad.astype(jnp.uint32)],
            num_keys=w_route + 1)
        # exact real-sample count (no row-count arithmetic -> no overflow)
        n_real_samples = jnp.maximum(
            g_pad.shape[0] - jnp.sum(g_pad.astype(jnp.int32)), 1)
        qs = (jnp.arange(1, n_shards) * n_real_samples) // n_shards
        splitters = [sorted_ops[i][qs] for i in range(w_route)]  # each [S-1]
        # -- 3: bucket + exchange ------------------------------------------
        # dest = number of splitters lexicographically <= route key
        lt = jnp.zeros((n_local, n_shards - 1), bool)
        eq = jnp.ones((n_local, n_shards - 1), bool)
        for i in range(w_route):
            rw, sw = route[i][:, None], splitters[i][None, :]
            lt = lt | (eq & (rw < sw))
            eq = eq & (rw == sw)
        dest = jnp.sum(~lt, axis=1)                          # [n_local]
        order = jnp.argsort(dest)                            # stable
        # input padding rows route to the LAST shard but are excluded from
        # counts so they can't trigger a spurious overflow
        real_dest = jnp.where(is_pad_in, n_shards, dest)     # bin n_shards: pad
        counts = jnp.bincount(real_dest, length=n_shards + 1)[:n_shards]
        all_counts = jnp.bincount(dest, length=n_shards)
        offsets = jnp.concatenate(
            [jnp.zeros(1, all_counts.dtype), jnp.cumsum(all_counts)[:-1]])
        overflow = jnp.any(counts > capacity)
        pos_in_group = jnp.arange(n_local) - offsets[dest[order]]
        valid = pos_in_group < capacity
        # rows past capacity go to a dump column that is sliced off before
        # the exchange — they can never clobber a real slot
        slot = jnp.where(valid, dest[order] * capacity + pos_in_group,
                         n_shards * capacity)
        # the global input index rides the exchange as one extra u32 row so
        # the host can map every surviving (shuffled, merged) row back to
        # its source slab row — output VALUES are gathered host-side from
        # exactly these indices (values never cross the mesh)
        idx_local = (jax.lax.axis_index(axis).astype(jnp.uint32)
                     * jnp.uint32(n_local)
                     + jnp.arange(n_local, dtype=jnp.uint32))
        ship = jnp.concatenate([cols_local, idx_local[None, :]], axis=0)
        pad_col = jnp.concatenate(
            [jnp.asarray(pad_template(r)), jnp.full(1, 0xFFFFFFFF,
                                                    jnp.uint32)])
        send = jnp.tile(pad_col[:, None], (1, n_shards * capacity + 1))
        send = send.at[:, slot].set(ship[:, order])
        send3 = send[:, :-1].reshape(r + 1, n_shards, capacity)
        recv = jax.lax.all_to_all(send3, axis, split_axis=1, concat_axis=1,
                                  tiled=False)
        recv = recv.reshape(r + 1, n_shards * capacity)
        cols_shard, idx_shard = recv[:r], recv[r]
        # -- 4: local fused merge + GC -------------------------------------
        perm, keep, mk = sort_and_gc(cols_shard, cutoff_hi, cutoff_lo, cph, cpl,
                                     w=r - _ROW_WORDS, is_major=is_major,
                                     retain_deletes=retain_deletes)
        out = cols_shard[:, perm]
        # padding rows are identified explicitly by the key_len sentinel
        is_pad = out[_ROW_KEY_LEN] == jnp.uint32(PAD_SENTINEL)
        keep = keep & ~is_pad
        return out, keep, mk, overflow[None], idx_shard[perm]

    spec = P(None, axis)
    fn = shard_map(
        per_shard, mesh=mesh,
        in_specs=(spec, P(), P(), P(), P()),
        out_specs=(spec, P(axis), P(axis), P(axis), P(axis)))
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def _quantized_capacity(n_local: int, n_shards: int, factor: float) -> int:
    """Per-destination exchange capacity on the power-of-two lattice.

    Raw rows-per-destination varies per job and would mint a fresh
    shard_map executable per size; quantized, a tablet's whole compaction
    lifetime (including doubling retries) stays on a handful of compile
    keys — the manifest's declared dist_compact lattice."""
    cap_raw = max(_CAPACITY_MIN, int(n_local / n_shards * factor))
    return 1 << (cap_raw - 1).bit_length()


@dataclass
class DistOutputs:
    """Device-resident products of one distributed compaction step: the
    merged output cols (still sharded over the mesh) plus keep/tombstone
    masks, for zero-reupload survivor-span staging (the write-through
    path of the dist-native job — storage/compaction.py installs each
    output file's span into the HBM slab cache from HERE, never from a
    host round trip)."""
    cols_dev: object           # [r, S*capacity] sharded, merged order
    keep_dev: object           # [S*capacity] sharded
    mk_dev: object             # [S*capacity] sharded
    w: int                     # key words (r - _ROW_WORDS)
    capacity: int
    n_shards: int
    _pos_all: object = field(default=None, repr=False)

    def bucket_key(self) -> Tuple[int, int]:
        """Quarantine vocabulary for the dist family: (n_shards,
        capacity) — the dominant compile-key pair of dist_compact_fn."""
        return (self.n_shards, self.capacity)

    def gather_span(self, start: int, end: int) -> StagedCols:
        """Stage ONE output file's [start, end) survivor span directly
        from the sharded device outputs — the dist twin of
        ops/run_merge.gather_staged_output_span. The gather crosses shard
        boundaries as XLA collectives; the result is committed to the
        first mesh device so later merges see a single-device input."""
        from yugabyte_tpu.ops.run_merge import _survivor_positions
        if self._pos_all is None:
            self._pos_all = _survivor_positions(self.keep_dev)
        n_out = end - start
        n_out_pad = bucket_size(n_out)
        out = _dist_gather_span(self.cols_dev, self._pos_all, self.mk_dev,
                                jnp.int32(start), jnp.int32(end),
                                n_out_pad)
        r = _ROW_WORDS + self.w
        sort_rows, n_sort = build_sort_schedule(self.w,
                                               np.zeros(r, dtype=bool))
        return StagedCols(out, sort_rows, n_sort, n_out, n_out_pad,
                          self.w, None, None)


@functools.partial(jax.jit, static_argnames=("n_out_pad",))
def _dist_gather_span(cols, pos_all, mk, start, end, n_out_pad: int):
    """Gather survivors [start, end) of the sharded merged order into a
    padded StagedCols matrix (single logical result; the cross-shard
    gather lowers to collectives). Mirrors _gather_staged_output's
    tombstone-flag rewrite so the staged entry matches the SST bytes the
    shell writes for the same span."""
    from yugabyte_tpu.ops.slabs import FLAG_TOMBSTONE
    n_pad = cols.shape[1]
    idx = start + jnp.arange(n_out_pad, dtype=jnp.int32)
    valid = idx < end
    pos = pos_all[jnp.clip(idx, 0, n_pad - 1)]
    sub = cols[:, pos]
    fl = sub[_ROW_FLAGS] | jnp.where(mk[pos] & valid,
                                     jnp.uint32(FLAG_TOMBSTONE),
                                     jnp.uint32(0))
    sub = sub.at[_ROW_FLAGS].set(fl)
    pad_col = jnp.asarray(pad_template(cols.shape[0]))
    return jnp.where(valid[None, :], sub, pad_col[:, None])


def stage_sharded_cols(slab, mesh: Mesh, axis: str = "shard"):
    """Pack a slab's key columns ONCE and upload them ONCE as a
    device-resident buffer sharded over the mesh. Returns (cols_dev,
    n_local). Overflow retries re-shard from this buffer instead of
    re-packing and re-uploading the whole slab from host."""
    n_shards = mesh.devices.size
    cols = pack_cols(slab)[0]
    # pad the column count to a multiple of shards (pack_cols gives powers
    # of two; mesh sizes are powers of two on TPU pods)
    if cols.shape[1] % n_shards:
        extra = n_shards - (cols.shape[1] % n_shards)
        pad_block = np.tile(pad_template(cols.shape[0])[:, None], (1, extra))
        cols = np.concatenate([cols, pad_block], axis=1)
    cols_dev = jax.device_put(cols, NamedSharding(mesh, P(None, axis)))
    return cols_dev, cols.shape[1] // n_shards


def distributed_compact(slab, params: GCParams, mesh: Mesh, axis: str = "shard",
                        capacity_factor: float = 2.0):
    """Host wrapper: pack a slab, shard it over the mesh, run the step.

    Returns (cols_out, keep, make_tombstone, src_idx) as host arrays;
    cols_out rows follow ops/merge_gc layout, in globally range-partitioned
    sorted order (shard s holds keys <= shard s+1's); src_idx[i] is the
    input slab row that produced merged position i (valid where keep/mk
    apply — padding positions carry sentinel indices and keep=False)."""
    (out, keep, mk, src_idx), _outputs = _distributed_compact_impl(
        slab, params, mesh, axis, capacity_factor, want_outputs=False)
    return np.asarray(out), keep, mk, src_idx


def distributed_compact_with_outputs(slab, params: GCParams, mesh: Mesh,
                                     axis: str = "shard",
                                     capacity_factor: float = 2.0
                                     ) -> Tuple[np.ndarray, np.ndarray,
                                                np.ndarray, DistOutputs]:
    """The dist-native form: decisions as host arrays (keep, mk, src_idx)
    plus the DEVICE-RESIDENT merged outputs for write-through span
    staging — the full output cols never cross back to the host."""
    (_out, keep, mk, src_idx), outputs = _distributed_compact_impl(
        slab, params, mesh, axis, capacity_factor, want_outputs=True)
    return keep, mk, src_idx, outputs


def _distributed_compact_impl(slab, params: GCParams, mesh: Mesh,
                              axis: str, capacity_factor: float,
                              want_outputs: bool):
    from yugabyte_tpu.ops import device_faults
    from yugabyte_tpu.ops.run_merge import _donation_supported
    from yugabyte_tpu.utils.metrics import (pipeline_span,
                                            record_kernel_dispatch)
    n_shards = mesh.devices.size
    # ONE host stage per job: pack + upload happen once, regardless of
    # how many capacity-doubling retries follow (the old recursive form
    # re-packed per attempt and double-counted this stage)
    with pipeline_span("merge_stage", inclusive="host"):
        cols_dev, n_local = stage_sharded_cols(slab, mesh, axis)
        cutoff = params.history_cutoff_ht
        cutoff_phys = cutoff >> 12
        cut_args = (jnp.uint32(cutoff >> 32),
                    jnp.uint32(cutoff & 0xFFFFFFFF),
                    jnp.uint32(cutoff_phys >> 20),
                    jnp.uint32(cutoff_phys & 0xFFFFF))
    factor = capacity_factor
    while True:
        capacity = _quantized_capacity(n_local, n_shards, factor)
        # an attempt that provably cannot overflow (capacity covers every
        # real row) or that has exhausted the retry ladder will never
        # need the input again: donate it so XLA reuses its HBM for the
        # exchange scratch (no-op on backends that ignore donation)
        no_retry = (capacity >= slab.n or factor >= _MAX_CAPACITY_FACTOR)
        donate = no_retry and _donation_supported()
        fn = dist_compact_fn(mesh, capacity, params.is_major_compaction,
                             params.retain_deletes, axis, donate)
        # the device stage is recorded per ATTEMPT — a failed (overflowed)
        # attempt burns real device wall and must show in the profile
        with pipeline_span("device"):
            # fault-injection site: a real XLA compile/dispatch failure of
            # the sharded program surfaces here (containment in
            # storage/compaction)
            device_faults.maybe_fault("dispatch")
            out, keep, mk, overflow, src_idx = fn(cols_dev, *cut_args)
            if donate:
                cols_dev = None   # consumed by the launch
            # kick every shard output's D2H in one async wave (the overflow
            # word decides retry first, so the big buffers ride the link
            # while the host inspects the small one)
            for a in ((keep, mk, src_idx) if want_outputs
                      else (out, keep, mk, src_idx)):
                try:
                    a.copy_to_host_async()
                except (AttributeError, NotImplementedError):  # yblint: contained(backend lacks async D2H; the sync download below covers it)
                    pass
            device_faults.maybe_fault("result")
            ovf = bool(np.any(np.asarray(overflow)))
        if not ovf:
            break
        if factor >= _MAX_CAPACITY_FACTOR:
            raise RuntimeError(
                f"distributed compaction bucket overflow at "
                f"{_MAX_CAPACITY_FACTOR}x")
        _overflow_retry_counter().increment()
        factor *= 2
    with pipeline_span("decision_unpack", inclusive="host"):
        keep_h = np.asarray(keep)
        mk_h = np.asarray(mk)
        src_h = np.asarray(src_idx).astype(np.int64)
        outputs = None
        if want_outputs:
            outputs = DistOutputs(out, keep, mk,
                                  w=int(out.shape[0]) - _ROW_WORDS,
                                  capacity=capacity, n_shards=n_shards)
    record_kernel_dispatch("kernel_dist_compact", slab.n,
                           n_shards * n_local)
    return (out, keep_h, mk_h, src_h), outputs


# ---------------------------------------------------------------------------
# Pooled multi-job waves: one tablet job per mesh device.
#
# The compaction pool (tserver/compaction_pool.py) packs queued jobs of one
# shape bucket into the slots of a single shard_map dispatch: slot i's
# device runs job i's complete fused merge+GC (the SAME program as the
# single-device path, so decisions are bit-identical), and only the packed
# decision words come back. On a real mesh this is J-way device
# parallelism; on any backend it amortizes the per-job dispatch + transfer
# overhead across the wave.

@functools.lru_cache(maxsize=64)
def pool_wave_fn(mesh: Mesh, k_pad: int, m: int, w: int, n_cmp: int,
                 is_major: bool, retain_deletes: bool, lexsort: bool,
                 axis: str = "shard"):
    """One compaction-pool wave: mesh-size independent merge+GC jobs of
    one (k_pad, m, w, n_cmp) bucket, one job per device.

    Inputs (global shapes; leading axis = slot): cols [S, r, n],
    cmp_rows [S, n_cmp], pos [n] (replicated), cut [S, 4] (the per-job
    cutoff words). Output: packed decisions [S, n//32, 2+b] plus the
    per-slot device-resident merge products (perm/keep/mk) for
    write-through survivor staging."""
    from yugabyte_tpu.ops import run_merge

    def per_slot(cols, cmp_rows, pos, cut):
        packed, perm, keep, mk = run_merge._merge_gc_runs_impl(
            cols[0], cmp_rows[0], pos, cut[0, 0], cut[0, 1], cut[0, 2],
            cut[0, 3], k_pad=k_pad, m=m, w=w, n_cmp=n_cmp,
            is_major=is_major, retain_deletes=retain_deletes,
            snapshot=False, lexsort=lexsort)
        return packed[None], perm[None], keep[None], mk[None]

    spec3 = P(axis, None, None)
    spec2 = P(axis, None)
    fn = shard_map(per_slot, mesh=mesh,
                   in_specs=(spec3, spec2, P(), spec2),
                   out_specs=(spec3, spec2, spec2, spec2))
    return jax.jit(fn)


def pool_slot_bucket(slabs: Sequence) -> Tuple[int, int, int]:
    """(k_pad, m, w) shape bucket a job's runs stage into — computed the
    same way stage_pool_slot lays the matrix out (greedy run packing
    included) WITHOUT packing anything, so the pool's wave grouping and
    the actual staging agree on the bucket."""
    from yugabyte_tpu.ops.run_merge import (packed_run_ns, quantize_width,
                                            run_bucket)
    live = [s for s in slabs if s.n]
    ns = packed_run_ns([s.n for s in live])
    k = len(ns)
    k_pad = 1 << max(0, (k - 1).bit_length()) if k > 1 else 1
    m = max(run_bucket(n) for n in ns)
    w = quantize_width(max(int(s.width_words) for s in live))
    return (k_pad, m, w)


def stage_pool_slot(slabs: Sequence, k_pad: int, m: int, w: int):
    """Pack one job's runs into a HOST [r, k_pad*m] run-major matrix (the
    wave stacks these and uploads once). Returns a StagedRuns whose
    cols_dev is the host ndarray — pooled_merge_gc moves it to the slot's
    device; everything else (run_ns/run_maps/cmp schedule) is exactly
    what stage_runs_from_slabs would record for the same job."""
    from yugabyte_tpu.ops.run_merge import (StagedRuns, _cmp_schedule,
                                            _merge_const_stats,
                                            pack_runs_greedy)
    live, run_maps = pack_runs_greedy([s for s in slabs if s.n])
    r = _ROW_WORDS + w
    cols = np.empty((r, k_pad * m), dtype=np.uint32)
    cols[:] = pad_template(r)[:, None]
    stats = []
    for i, s in enumerate(live):
        sub, n_s, _, _ = pack_cols(s, n_pad_override=s.n, w_pad_override=w)
        cols[:, i * m: i * m + n_s] = sub
        stats.append(column_stats(sub, n_s))
    cmp_rows, n_cmp = _cmp_schedule(w, _merge_const_stats(stats, r))
    return StagedRuns(cols, m, k_pad, w, [s.n for s in live],
                      cmp_rows, n_cmp, run_maps=run_maps)


class PoolWaveHandle:
    """Result of one pooled wave: per-job host decisions plus per-slot
    device-resident merge products for write-through survivor staging."""

    def __init__(self, decisions, metas, cols_dev, perm_dev, keep_dev,
                 mk_dev, w: int, n_pad: int):
        self.decisions = decisions     # [(perm, keep, mk)] per job
        self._metas = metas
        self._cols_dev = cols_dev
        self._perm_dev = perm_dev
        self._keep_dev = keep_dev
        self._mk_dev = mk_dev
        self._w = w
        self._n_pad = n_pad
        self._pos_all: dict = {}

    def _slot_piece(self, arr, slot: int):
        """The [1, ...] per-device piece of a wave output for one slot
        (looked up by shard index, not list position — addressable-shard
        order is a backend detail)."""
        for sh in arr.addressable_shards:
            idx = sh.index[0]
            if idx.start == slot:
                return sh.data
        raise KeyError(f"slot {slot} not addressable")

    def gather_span(self, slot: int, start: int, end: int) -> StagedCols:
        """Stage job `slot`'s [start, end) survivor span directly from
        that slot's device — the pooled twin of
        ops/run_merge.gather_staged_output_span: the tablet's output
        cache entry is gathered on ITS shard of the mesh, so the
        resident chain survives sharding."""
        from yugabyte_tpu.ops.run_merge import (_gather_staged_output,
                                                _survivor_positions)
        cols = self._slot_piece(self._cols_dev, slot)[0]
        perm = self._slot_piece(self._perm_dev, slot)[0]
        mk = self._slot_piece(self._mk_dev, slot)[0]
        pos_all = self._pos_all.get(slot)
        if pos_all is None:
            keep = self._slot_piece(self._keep_dev, slot)[0]
            pos_all = self._pos_all[slot] = _survivor_positions(keep)
        n_out = end - start
        n_out_pad = bucket_size(n_out)
        out = _gather_staged_output(cols, perm, pos_all, mk,
                                    jnp.int32(start), jnp.int32(end),
                                    n_out_pad)
        r = _ROW_WORDS + self._w
        sort_rows, n_sort = build_sort_schedule(self._w,
                                               np.zeros(r, dtype=bool))
        return StagedCols(out, sort_rows, n_sort, n_out, n_out_pad,
                          self._w, None, None)


def pooled_merge_gc(mesh: Mesh, jobs: Sequence[Tuple[object, GCParams]],
                    axis: str = "shard") -> PoolWaveHandle:
    """Run up to mesh-size merge+GC jobs as ONE wave dispatch.

    jobs: [(staged, params)] where staged is a StagedRuns from
    stage_pool_slot (host cols) or stage_runs_from_staged (device cols on
    the slot's cache partition — the resident hit path). All jobs must
    share one (k_pad, m, w) bucket and one (is_major, retain_deletes)
    pair — the pool's wave builder groups by exactly this key. Unfilled
    slots carry all-pad matrices (they sort trivially and keep nothing).

    Decisions per job are bit-identical to a single-device
    launch_merge_gc of the same staged runs: each slot runs the same
    fused program with the same comparator, schedule quantization and
    packed-decision encoding."""
    from yugabyte_tpu.ops import device_faults, run_merge
    from yugabyte_tpu.utils.metrics import record_kernel_dispatch

    n_slots = mesh.devices.size
    assert 0 < len(jobs) <= n_slots, (len(jobs), n_slots)
    k_pad, m, w = (jobs[0][0].k_pad, jobs[0][0].m, jobs[0][0].w)
    p0 = jobs[0][1]
    for st, p in jobs:
        assert (st.k_pad, st.m, st.w) == (k_pad, m, w), \
            "wave jobs must share one shape bucket"
        assert (p.is_major_compaction, p.retain_deletes) == \
            (p0.is_major_compaction, p0.retain_deletes), \
            "wave jobs must share GC statics"
    r = _ROW_WORDS + w
    n = k_pad * m
    # one wave-wide n_cmp (the max of the jobs' lattice points): padding a
    # job's schedule by repeating its last row is a comparator no-op, so
    # only the shared static changes
    n_cmp = max(st.n_cmp for st, _p in jobs)
    cmp_all = np.empty((n_slots, n_cmp), dtype=np.int32)
    cut_all = np.zeros((n_slots, 4), dtype=np.uint32)
    devices = list(mesh.devices.flat)
    pieces: List[object] = []
    any_device_staged = any(not isinstance(st.cols_dev, np.ndarray)
                            for st, _p in jobs)
    host_stack = (None if any_device_staged
                  else np.empty((n_slots, r, n), dtype=np.uint32))
    pad_mat = None
    for i in range(n_slots):
        if i < len(jobs):
            st, p = jobs[i]
            rows = np.asarray(st.cmp_rows, dtype=np.int32)
            if len(rows) < n_cmp:
                rows = np.concatenate(
                    [rows, np.full(n_cmp - len(rows), rows[-1], np.int32)])
            cmp_all[i] = rows[:n_cmp]
            cutoff = int(p.history_cutoff_ht)
            cph = cutoff >> 12
            cut_all[i] = ((cutoff >> 32) & 0xFFFFFFFF,
                          cutoff & 0xFFFFFFFF,
                          (cph >> 20) & 0xFFFFFFFF, cph & 0xFFFFF)
            if host_stack is not None:
                host_stack[i] = st.cols_dev
            else:
                cd = st.cols_dev
                if isinstance(cd, np.ndarray):
                    pieces.append(jax.device_put(cd[None], devices[i]))
                else:
                    # resident hit: the job restaged from its shard's
                    # cache partition; move only if it sits elsewhere
                    # (a device-to-device copy, never through the host)
                    piece = jnp.expand_dims(cd, 0)
                    pieces.append(jax.device_put(piece, devices[i]))
        else:
            cmp_all[i] = np.int32(_ROW_KEY_LEN)
            if host_stack is not None:
                if pad_mat is None:
                    pad_mat = np.broadcast_to(pad_template(r)[:, None],
                                              (r, n))
                host_stack[i] = pad_mat
            else:
                if pad_mat is None:
                    pad_mat = np.broadcast_to(pad_template(r)[:, None],
                                              (r, n)).copy()
                pieces.append(jax.device_put(pad_mat[None], devices[i]))
    sharding3 = NamedSharding(mesh, P(axis, None, None))
    if host_stack is not None:
        cols_dev = jax.device_put(host_stack, sharding3)
    else:
        cols_dev = jax.make_array_from_single_device_arrays(
            (n_slots, r, n), sharding3, pieces)
    sharding2 = NamedSharding(mesh, P(axis, None))
    cmp_dev = jax.device_put(cmp_all, sharding2)
    cut_dev = jax.device_put(cut_all, sharding2)
    pos = np.arange(n, dtype=np.int32)
    lexsort = run_merge._use_lexsort()
    fn = pool_wave_fn(mesh, k_pad, m, w, n_cmp, p0.is_major_compaction,
                      p0.retain_deletes, lexsort, axis)
    run_merge._record_bucket(("pool_wave", n_slots, k_pad, m, w, n_cmp,
                              p0.is_major_compaction, p0.retain_deletes,
                              lexsort))
    # fault-injection sites: the wave's containment (the pool demotes the
    # bucket on the health board and completes every wave job natively)
    # hooks here; the bucket lets a "slow" nemesis throttle one (k, m)
    device_faults.maybe_fault("dispatch", bucket=(k_pad, m))
    packed, perm, keep, mk = fn(cols_dev, cmp_dev, pos, cut_dev)
    try:
        packed.copy_to_host_async()
    except (AttributeError, NotImplementedError):  # yblint: contained(backend lacks async D2H; the sync download below covers it)
        pass
    device_faults.maybe_fault("result")
    packed_h = np.asarray(packed)
    decisions = [run_merge._decode_packed(packed_h[i], st)
                 for i, (st, _p) in enumerate(jobs)]
    record_kernel_dispatch("kernel_pool_wave",
                           sum(st.n for st, _p in jobs), n_slots * n)
    return PoolWaveHandle(decisions, [st for st, _p in jobs], cols_dev,
                          perm, keep, mk, w, n)


# ---------------------------------------------------------------------------
# Prewarm: the dist/pool families land inside the PR-7 manifest/budget/
# prewarm discipline like every other kernel family.

# The declared compile-key lattice (mirrored by the kernel manifest's
# dist_compact entries): per-destination capacities universal compaction
# actually produces for flush-sized through once-compacted runs, times
# both is_major variants, on whatever mesh the server resolved.
_PREWARM_CAPACITIES = (1 << 13, 1 << 14)
_PREWARM_POOL_SHAPES = ((2, 1 << 16, 4, 8), (4, 1 << 16, 4, 8))


def prewarm_dist_compact(mesh: Mesh,
                         capacities: Optional[Sequence[int]] = None,
                         pool_shapes: Optional[Sequence[Tuple[int, int,
                                                              int, int]]]
                         = None) -> Prewarm:
    """Ahead-of-traffic compile of the mesh families: the key-range
    sharded dist_compact step per (capacity, is_major) and the pool wave
    program per (bucket, is_major). Run by PrewarmKernelsOp when the
    server resolved a >1-device mesh; returns what compiled and what
    the compiler refused."""
    from yugabyte_tpu.ops import run_merge
    caps = tuple(capacities) if capacities is not None \
        else _PREWARM_CAPACITIES
    shapes = tuple(pool_shapes) if pool_shapes is not None \
        else _PREWARM_POOL_SHAPES
    n_shards = mesh.devices.size
    lexsort = run_merge._use_lexsort()
    pw = Prewarm("dist_compact")

    u32 = jax.ShapeDtypeStruct((), jnp.uint32)
    for capacity in caps:
        r = _ROW_WORDS + 4
        n_total = n_shards * max(capacity, _CAPACITY_MIN)
        cols = jax.ShapeDtypeStruct((r, n_total), jnp.uint32)
        for is_major in (True, False):
            pw.warm(
                f"dist_compact (n_shards={n_shards} capacity={capacity} "
                f"is_major={is_major})",
                lambda: dist_compact_fn(mesh, capacity, is_major)
                .lower(cols, u32, u32, u32, u32).compile())
    for (k_pad, m, w, n_cmp) in shapes:
        r = _ROW_WORDS + w
        n = k_pad * m
        args = (jax.ShapeDtypeStruct((n_shards, r, n), jnp.uint32),
                jax.ShapeDtypeStruct((n_shards, n_cmp), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.int32),
                jax.ShapeDtypeStruct((n_shards, 4), jnp.uint32))
        for is_major in (True, False):
            got = pw.warm(
                f"pool_wave (slots={n_shards} k_pad={k_pad} m={m} w={w} "
                f"is_major={is_major})",
                lambda: pool_wave_fn(mesh, k_pad, m, w, n_cmp, is_major,
                                     False, lexsort)
                .lower(*args).compile())
            if got:
                run_merge._record_bucket(
                    ("pool_wave", n_shards, k_pad, m, w, n_cmp, is_major,
                     False, lexsort))
    return pw
