"""Device-resident slab cache: SST key columns pinned in TPU HBM.

The TPU-native analog of the reference's block cache (ref:
rocksdb/util/lru_cache.cc) — but where the reference caches decoded blocks in
host RAM to avoid disk reads, this caches *staged key-column matrices* in
device HBM to avoid host->device transfers, which dominate compaction cost on
a transfer-limited interconnect. Flush and compaction write-through: every
new SST's key columns are staged once, so steady-state compaction finds all
inputs already resident and only ships back the (bit-packed) keep masks.

Residency is a real multi-level set, not a flat LRU: entries carry the LSM
level of the file they stage (flush outputs are level 0; a compaction output
is one above its deepest input), and capacity eviction prefers the SHALLOW
levels — an L0 slab is small, short-lived (the next pick consumes and drops
it) and cheap to re-stage, while an L2 base run is the expensive thing the
chained L0->L1->L2 path exists to keep in HBM. Entries referenced by an
in-flight compaction are PINNED so eviction can never race a running merge.

Values stay host-side: merge+GC only permutes and drops entries, so value
bytes never need to cross to the device at all (the original sidecar
insight, SURVEY.md section 2.7).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from yugabyte_tpu.ops.merge_gc import (
    _ROW_WORDS, StagedCols, bucket_size, build_sort_schedule,
    pad_template, stage_slab)
from yugabyte_tpu.ops.slabs import KVSlab
from yugabyte_tpu.utils import flags

flags.define_flag("device_cache_capacity_bytes", 4 << 30,
                  "HBM budget for the device-resident slab cache "
                  "(staged SST key columns); eviction prefers shallow "
                  "levels and never touches pinned entries")

CacheKey = Tuple[str, int]  # (namespace, file_id) — file ids are per-DB


@dataclass
class _Resident:
    """One cache entry: the staged columns plus residency metadata."""
    staged: StagedCols
    level: int = 0      # LSM level of the staged file (0 = flush output)
    pins: int = 0       # in-flight compactions reading this entry
    bytes: int = 0      # nbytes RECORDED in _used (vals staging grows
    #                     an entry in place; eviction must subtract what
    #                     was added, not what is there now)


class DeviceSlabCache:
    """Server-wide cache; keys are namespaced per DB because VersionSet file
    ids are only unique within one DB (like the reference's per-DB file
    numbers under a shared block cache)."""

    def __init__(self, device=None, capacity_bytes: Optional[int] = None):
        from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
        from yugabyte_tpu.utils import lock_rank
        self.device = device
        self.capacity = (capacity_bytes if capacity_bytes is not None
                         else flags.get_flag("device_cache_capacity_bytes"))
        self._lock = lock_rank.tracked(threading.Lock(),
                                       "device_cache.slab_lock")
        self._map: "OrderedDict[CacheKey, _Resident]" = \
            OrderedDict()                  # guarded-by: _lock
        self._used = 0                     # guarded-by: _lock
        # per-instance ints (tests diff fresh caches) + process-wide
        # registry counters so the hit ratio is scrapeable
        self.hits = 0                      # guarded-by: _lock
        self.misses = 0                    # guarded-by: _lock
        self.evictions = 0                 # guarded-by: _lock
        e = ROOT_REGISTRY.entity("server", "device_cache")
        self._c_hits = e.counter("device_cache_hits_total",
                                 "HBM slab cache hits")
        self._c_misses = e.counter("device_cache_misses_total",
                                   "HBM slab cache misses")
        self._c_evict = e.counter("device_cache_evictions_total",
                                  "entries evicted under HBM pressure")
        self._c_read_stage = e.counter(
            "device_cache_read_stage_total",
            "entries staged by the SERVE path (batched point reads / "
            "scans) on a residency miss — write-through from flush and "
            "compaction should keep this near zero in steady state")
        self._g_used = e.gauge("device_cache_used_bytes",
                               "HBM bytes resident in the slab cache")
        self._g_pinned = e.gauge("device_cache_pinned_count",
                                 "entries pinned by in-flight compactions")

    def get(self, key: CacheKey) -> Optional[StagedCols]:
        with self._lock:
            ent = self._map.get(key)
            if ent is None:
                self.misses += 1
                self._c_misses.increment()
                return None
            self._map.move_to_end(key)
            self.hits += 1
            self._c_hits.increment()
            return ent.staged

    def contains(self, key: CacheKey) -> bool:
        """Metrics-neutral probe (offload policy peeks without counting)."""
        with self._lock:
            return key in self._map

    def peek(self, key: CacheKey) -> Optional[StagedCols]:
        """Metrics-neutral `get`: no hit or miss counted, no LRU touch."""
        with self._lock:
            ent = self._map.get(key)
            return None if ent is None else ent.staged

    def level_of(self, key: CacheKey) -> Optional[int]:
        """Resident entry's LSM level, or None when absent (metrics-neutral:
        compaction derives its output level from the input levels)."""
        with self._lock:
            ent = self._map.get(key)
            return None if ent is None else ent.level

    # ------------------------------------------------------------- pinning
    def pin(self, key: CacheKey) -> bool:
        """Pin an entry for an in-flight job: capacity eviction skips it.
        Returns False when the key is not resident (nothing to pin)."""
        with self._lock:
            ent = self._map.get(key)
            if ent is None:
                return False
            ent.pins += 1
            self._g_pinned.set(self._pinned_unlocked())
            return True

    def unpin(self, key: CacheKey) -> None:
        with self._lock:
            ent = self._map.get(key)
            if ent is not None and ent.pins > 0:
                ent.pins -= 1
            self._g_pinned.set(self._pinned_unlocked())

    def pinned_count(self) -> int:
        """Entries with at least one pin — the chaos/fault tests assert
        this drains to zero after every job, including faulted ones."""
        with self._lock:
            return self._pinned_unlocked()

    def _pinned_unlocked(self) -> int:
        return sum(1 for e in self._map.values() if e.pins > 0)

    # ----------------------------------------------------------- mutation
    def put(self, key: CacheKey, staged: StagedCols, level: int = 0) -> None:
        with self._lock:
            prior = self._map.pop(key, None)
            pins = 0
            if prior is not None:
                # replace, not refuse: a stale entry under a reused id must
                # never shadow fresh data (correctness, not just freshness)
                self._used -= prior.bytes
                pins = prior.pins
            self._map[key] = _Resident(staged, level=level, pins=pins,
                                       bytes=staged.nbytes)
            self._used += staged.nbytes
            self._evict_unlocked(protect=key)
            self._g_used.set(self._used)

    def attach_vals(self, key: CacheKey, vals_dev) -> None:
        """Attach staged value words to a resident entry (pushdown-scan
        write-through): the entry grows in place and the growth is
        accounted so eviction stays balanced."""
        with self._lock:
            ent = self._map.get(key)
            if ent is None:
                return
            ent.staged.vals_dev = vals_dev
            delta = ent.staged.nbytes - ent.bytes
            ent.bytes += delta
            self._used += delta
            self._evict_unlocked(protect=key)
            self._g_used.set(self._used)

    def _evict_unlocked(self, protect: Optional[CacheKey] = None) -> None:
        """Capacity eviction, shallow levels first (L0 slabs are cheap to
        re-stage and about to be consumed anyway), LRU within a level.
        Pinned entries — inputs of a running merge — are never touched;
        if only pinned entries remain over budget, residency temporarily
        exceeds capacity rather than racing the job."""
        while self._used > self.capacity:
            victim = None
            best = None
            for age, (k, ent) in enumerate(self._map.items()):
                if ent.pins > 0 or k == protect:
                    continue
                rank = (ent.level, age)
                if best is None or rank < best:
                    best = rank
                    victim = k
            if victim is None:
                break
            self._used -= self._map.pop(victim).bytes
            self.evictions += 1
            self._c_evict.increment()

    def drop(self, key: CacheKey) -> None:
        with self._lock:
            ent = self._map.pop(key, None)
            if ent is not None:
                self._used -= ent.bytes
                self._g_used.set(self._used)
                self._g_pinned.set(self._pinned_unlocked())

    def drop_namespace(self, namespace: str) -> None:
        """Evict everything a closed DB staged, freeing its HBM residency."""
        with self._lock:
            dead = [k for k in self._map if k[0] == namespace]
            for k in dead:
                self._used -= self._map.pop(k).bytes
            if dead:
                self._g_used.set(self._used)
                self._g_pinned.set(self._pinned_unlocked())

    def stage_from_raw(self, key: CacheKey, rfb,
                       level: int = 0) -> StagedCols:
        """Raw-block staging (the device codec's cache miss path): decode
        one parsed file's raw block regions ON DEVICE
        (ops/block_codec.decode_file_to_staged) and install the resulting
        cols — no host decode_block runs, so sst_block_decode_total stays
        flat even when the chain starts cold."""
        from yugabyte_tpu.ops.block_codec import decode_file_to_staged
        staged = decode_file_to_staged(rfb, self.device)
        self.put(key, staged, level=level)
        return staged

    def stage(self, key: CacheKey, slab: KVSlab,
              level: int = 0, for_read: bool = False,
              include_vals: bool = False, device=None) -> StagedCols:
        staged = stage_slab(slab, device if device is not None
                            else self.device)
        if include_vals:
            # pushdown-scan write-through: the value words ride along so
            # the NEXT filtered/aggregating scan is fully resident
            import jax
            import jax.numpy as jnp
            from yugabyte_tpu.ops.scan import pack_vals, pushdown_metrics
            packed = pack_vals(slab, staged.n_pad)
            staged.vals_dev = (jax.device_put(packed, self.device)
                               if self.device is not None
                               else jnp.asarray(packed))
            pushdown_metrics()["vals_staged"].increment()
        self.put(key, staged, level=level)
        if for_read:
            # a read had to decode+upload what write-through was
            # supposed to have left resident — the residency-health
            # signal for the batched point-read path
            self._c_read_stage.increment()
        return staged

    def snapshot(self) -> dict:
        """Residency block for /compactionz: totals plus the per-level
        breakdown the multi-level eviction policy acts on."""
        with self._lock:
            levels: Dict[int, dict] = {}
            for ent in self._map.values():
                lv = levels.setdefault(ent.level,
                                       {"entries": 0, "bytes": 0,
                                        "pinned": 0})
                lv["entries"] += 1
                lv["bytes"] += ent.staged.nbytes
                if ent.pins > 0:
                    lv["pinned"] += 1
            shards: Dict[str, dict] = {}
            for key, ent in self._map.items():
                # direct-keyed caches (tests) use bare ids, not
                # (namespace, file_id) tuples — they have no shard view
                ns = key[0] if isinstance(key, tuple) and key else None
                if not isinstance(ns, str) or "/shard" not in ns:
                    continue
                sh = shards.setdefault(
                    "shard" + ns.rsplit("/shard", 1)[1],
                    {"entries": 0, "bytes": 0, "pinned": 0})
                sh["entries"] += 1
                sh["bytes"] += ent.staged.nbytes
                if ent.pins > 0:
                    sh["pinned"] += 1
            out = {
                "capacity_bytes": self.capacity,
                "used_bytes": self._used,
                "entries": len(self._map),
                "pinned": self._pinned_unlocked(),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "levels": {f"L{k}": v for k, v in sorted(levels.items())},
            }
            if shards:
                # per-mesh-shard residency (the compaction pool's
                # partitioned namespaces — storage survives sharding)
                out["shards"] = dict(sorted(shards.items()))
            return out


class NamespacedSlabCache:
    """Per-DB view over a shared DeviceSlabCache: callers use bare file ids."""

    def __init__(self, shared: DeviceSlabCache, namespace: str):
        self._shared = shared
        self.namespace = namespace

    @property
    def device(self):
        return self._shared.device

    @property
    def hits(self):
        return self._shared.hits

    @property
    def misses(self):
        return self._shared.misses

    def get(self, file_id: int):
        return self._shared.get((self.namespace, file_id))

    def contains(self, file_id: int) -> bool:
        return self._shared.contains((self.namespace, file_id))

    def peek(self, file_id: int):
        return self._shared.peek((self.namespace, file_id))

    def level_of(self, file_id: int) -> Optional[int]:
        return self._shared.level_of((self.namespace, file_id))

    def pin(self, file_id: int) -> bool:
        return self._shared.pin((self.namespace, file_id))

    def unpin(self, file_id: int) -> None:
        self._shared.unpin((self.namespace, file_id))

    def pinned_count(self) -> int:
        return self._shared.pinned_count()

    def put(self, file_id: int, staged: StagedCols, level: int = 0) -> None:
        self._shared.put((self.namespace, file_id), staged, level=level)

    def attach_vals(self, file_id: int, vals_dev) -> None:
        self._shared.attach_vals((self.namespace, file_id), vals_dev)

    def drop(self, file_id: int) -> None:
        self._shared.drop((self.namespace, file_id))

    def drop_all(self) -> None:
        self._shared.drop_namespace(self.namespace)

    def stage(self, file_id: int, slab: KVSlab,
              level: int = 0, for_read: bool = False,
              include_vals: bool = False) -> StagedCols:
        return self._shared.stage((self.namespace, file_id), slab,
                                  level=level, for_read=for_read,
                                  include_vals=include_vals)

    def stage_from_raw(self, file_id: int, rfb, level: int = 0
                       ) -> StagedCols:
        return self._shared.stage_from_raw((self.namespace, file_id), rfb,
                                           level=level)


class ShardPartition(NamespacedSlabCache):
    """Per-mesh-shard partition of the shared cache: keys carry the shard
    in the namespace (``<ns>/shard<i>``) and staging commits to that
    shard's DEVICE — so a pooled tablet's resident L0->L1->L2 chain lives
    in the HBM of the mesh slot that compacts it (the compaction pool
    gives each tablet a sticky home shard for exactly this affinity).
    Pins, eviction, levels and metrics are the shared cache's; only key
    spelling and device placement change."""

    def __init__(self, shared: DeviceSlabCache, namespace: str,
                 shard: int, device=None):
        super().__init__(shared, f"{namespace}/shard{shard}")
        self.shard = shard
        self._device = device

    @property
    def device(self):
        return self._device if self._device is not None \
            else self._shared.device

    def stage(self, file_id: int, slab: KVSlab,
              level: int = 0, for_read: bool = False,
              include_vals: bool = False) -> StagedCols:
        return self._shared.stage((self.namespace, file_id), slab,
                                  level=level, for_read=for_read,
                                  include_vals=include_vals,
                                  device=self._device)


class HostStagingPool:
    """Reusable host-side staging arrays for stage A of the compaction
    pipeline (ops/run_merge.stage_runs_from_slabs packs column matrices
    into these before the H2D upload).

    Shape buckets make reuse effective: every chunk of a pipelined job
    (and most jobs of a tablet's lifetime) stages the same [r, k_pad*m]
    matrix shape, so after warmup the host never allocates — the pinned
    pages stay hot and the allocator never fragments under a double-
    buffered producer that holds two staging arrays in flight.

    Callers must only release() an array once the upload has COPIED it
    (true on tpu/gpu backends; the CPU backend may alias host memory, so
    its callers skip release and the array is simply garbage-collected).
    """

    def __init__(self, max_per_shape: int = 2, max_bytes: int = 1 << 30):
        from yugabyte_tpu.utils import lock_rank
        self._free: dict = {}              # guarded-by: _lock
        self._bytes = 0                    # guarded-by: _lock
        # ids of arrays acquired and not yet released/forgotten — the
        # chaos harness's leak detector: after every job (including a
        # cancelled or device-faulted one) this must drain back to 0
        self._leases: set = set()          # guarded-by: _lock
        self._max_per_shape = max_per_shape
        self._max_bytes = max_bytes
        self._lock = lock_rank.tracked(threading.Lock(),
                                       "device_cache.staging_pool_lock")
        from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
        e = ROOT_REGISTRY.entity("server", "device_cache")
        self._c_reuse = e.counter(
            "staging_pool_reuse_total",
            "stage-A packings served from a pooled host array")
        self._c_alloc = e.counter(
            "staging_pool_alloc_total",
            "stage-A packings that allocated a fresh host array")
        self._g_leases = e.gauge(
            "staging_pool_outstanding_lease_count",
            "staging arrays acquired and not yet released")

    def acquire(self, shape: Tuple[int, int], dtype=np.uint32) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            bucket = self._free.get(key)
            if bucket:
                arr = bucket.pop()
                self._bytes -= arr.nbytes
                self._leases.add(id(arr))
                self._g_leases.set(len(self._leases))
                self._c_reuse.increment()
                return arr
        arr = np.empty(shape, dtype=dtype)
        with self._lock:
            self._leases.add(id(arr))
            self._g_leases.set(len(self._leases))
        self._c_alloc.increment()
        return arr

    def release(self, arr: np.ndarray) -> None:
        key = (arr.shape, arr.dtype.str)
        with self._lock:
            self._leases.discard(id(arr))
            self._g_leases.set(len(self._leases))
            bucket = self._free.setdefault(key, [])
            if (len(bucket) < self._max_per_shape
                    and self._bytes + arr.nbytes <= self._max_bytes):
                bucket.append(arr)
                self._bytes += arr.nbytes

    def forget(self, arr: np.ndarray) -> None:
        """End a lease WITHOUT recycling the pages: the CPU backend may
        alias the array's memory into the device buffer, so the caller
        hands the array off for garbage collection instead of release().
        Not a leak — the lease is accounted done."""
        with self._lock:
            self._leases.discard(id(arr))
            self._g_leases.set(len(self._leases))

    def outstanding(self) -> int:
        """Leases neither released nor forgotten — the chaos soak asserts
        this returns to zero after fault windows heal."""
        with self._lock:
            return len(self._leases)


_staging_pool: Optional[HostStagingPool] = None  # guarded-by: _staging_pool_lock
_staging_pool_lock = threading.Lock()


def host_staging_pool() -> HostStagingPool:
    """Process-wide staging pool (one per process, like the slab cache)."""
    global _staging_pool
    with _staging_pool_lock:
        if _staging_pool is None:
            _staging_pool = HostStagingPool()
        return _staging_pool


def merged_column_stats(staged_list: Sequence[StagedCols], w: int
                        ) -> np.ndarray:
    """Cross-input is_const vector over staged inputs, vectorized: a row
    prunes from the sort/compare schedule only when it is constant WITH
    THE SAME VALUE across every input (constant-per-input with differing
    values still orders the merge). Inputs narrower than w expose their
    extra word rows as constant zero; inputs without column stats (device
    write-through gathers skip the host fetch) poison every row they
    cover as non-constant."""
    r_total = _ROW_WORDS + w
    k = len(staged_list)
    consts = np.zeros((k, r_total), dtype=bool)
    firsts = np.zeros((k, r_total), dtype=np.uint32)
    for i, s in enumerate(staged_list):
        rs = min(_ROW_WORDS + s.w, r_total)
        consts[i, rs:] = True              # implicit zero-pad word rows
        if s.col_const is not None:
            consts[i, :rs] = s.col_const[:rs]
            firsts[i, :rs] = s.col_first[:rs]
    return consts.all(axis=0) & (firsts == firsts[0:1]).all(axis=0)


def concat_staged(staged_list: Sequence[StagedCols]) -> StagedCols:
    """Concatenate staged inputs ON DEVICE into one padded cols matrix.

    All transfers avoided: ONE cached jitted program (_concat_staged_fused,
    ops/run_merge.py — part of the restage_concat kernel family in the
    compile-surface manifest) pads each input's width to the max, lays the
    real rows out contiguously and pads the tail to the bucket size, all
    in HBM. The merged sort schedule prunes rows via the vectorized
    cross-input column stats (merged_column_stats).
    """
    import jax.numpy as jnp
    from yugabyte_tpu.ops.run_merge import _concat_staged_fused

    w = max(s.w for s in staged_list)
    n = sum(s.n for s in staged_list)
    n_pad = bucket_size(n)
    parts = tuple(s.cols_dev for s in staged_list)
    ns = jnp.asarray([s.n for s in staged_list], dtype=jnp.int32)
    cat = _concat_staged_fused(parts, ns, w=w, n_pad=n_pad)
    is_const = merged_column_stats(staged_list, w)
    sort_rows, n_sort = build_sort_schedule(w, is_const)
    return StagedCols(cat, sort_rows, n_sort, n, n_pad, w)
