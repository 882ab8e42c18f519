"""ServerExecutionContext: the server-wide TPU dispatch seam.

Capability parity with the reference's shared background-work machinery:
every tablet's compactions run as tasks on ONE server-wide priority pool
(ref: rocksdb/db/db_impl.cc:201-440 CompactionTask/FlushTask on
yb::PriorityThreadPool; util/priority_thread_pool.h:61; pool sizing flag
`priority_thread_pool_size`, docdb/docdb_rocksdb_util.cc:137), and all
tablets share one block cache (ref: db/table_cache.cc).

The TPU-native context additionally owns the shared JAX device handle and
the HBM-resident DeviceSlabCache, so every tablet's compaction rides one
device queue and one staged-slab working set. `tserver_device=none` asks
for the native C++ merge+GC baseline instead ("native" device sentinel,
storage/compaction.py); a JAX backend that fails to initialise raises.
"""

from __future__ import annotations

from typing import Optional

from yugabyte_tpu.storage.device_cache import DeviceSlabCache
from yugabyte_tpu.storage.sst import BlockCache
from yugabyte_tpu.tablet.tablet import TabletOptions
from yugabyte_tpu.utils import flags
from yugabyte_tpu.utils.metrics import MetricRegistry
from yugabyte_tpu.utils.threadpool import PriorityThreadPool
from yugabyte_tpu.utils.trace import TRACE

flags.define_flag("tserver_compaction_pool_size", 2,
                  "worker threads in the shared server-wide compaction pool "
                  "(ref priority_thread_pool_size, "
                  "docdb_rocksdb_util.cc:137)")
flags.define_flag("tserver_device", "auto",
                  "JAX device for the compaction/scan kernels: 'auto' "
                  "(first visible device), 'none' (native C++ merge+GC "
                  "only)")
flags.define_flag("block_cache_bytes", 256 << 20,
                  "host RAM budget for the shared decoded-block cache "
                  "(ref block cache sizing, docdb_rocksdb_util.cc)")
flags.define_flag("tserver_mesh_compaction_pool", 1,
                  "schedule device-routed compactions through the "
                  "mesh-sharded multi-tablet pool "
                  "(tserver/compaction_pool.py) when a >1-device mesh "
                  "is visible; 0 = inline per-tablet device dispatch")


def resolve_device(mode: str):
    """Resolve (shared JAX device, mesh-or-None), or ('native', None)
    for mode 'none'.

    With more than one visible device, a 1-D Mesh over all of them is
    returned too: large compactions fan subcompactions across it
    (parallel/dist_compact.py)."""
    if mode == "none":
        return "native", None
    import jax
    devices = jax.devices()
    mesh = None
    mesh_n = 1
    if len(devices) > 1:
        import numpy as _np
        from jax.sharding import Mesh
        # power-of-two shard count: run-padding and the all_to_all
        # capacity math assume it (and TPU slices come that way)
        mesh_n = 1 << (len(devices).bit_length() - 1)
        mesh = Mesh(_np.asarray(devices[:mesh_n]), ("shard",))
    TRACE("server device: %s (mesh devices: %d)", devices[0], mesh_n)
    return devices[0], mesh


class ServerExecutionContext:  # yblint: disable=ybsan-coverage (set-once-in-__init__ config holder, read-only after construction; the pools/caches it owns carry their own guarded-by annotations)
    """One per TabletServer process; every hosted tablet's TabletOptions
    come from here so compaction pool, device, HBM slab cache and block
    cache are shared server-wide."""

    def __init__(self, metrics: Optional[MetricRegistry] = None,
                 device=None, mesh=None):
        """device (with, optionally, a mesh that holds it): the caller's
        choice in place of the `tserver_device` flag's."""
        if device is not None:
            self.device, self.mesh = device, mesh
        else:
            self.device, self.mesh = resolve_device(
                flags.get_flag("tserver_device"))
        self.device_cache = None
        if self.device != "native":
            # capacity rides --device_cache_capacity_bytes (defined by
            # storage/device_cache.py, the flag's single owner)
            self.device_cache = DeviceSlabCache(self.device)
        # mesh-sharded multi-tablet compaction pool (ROADMAP item 3):
        # device-routed compactions from every hosted tablet share the
        # mesh through batch-slot waves / whole-mesh dist jobs
        self.compaction_pool = None
        n_threads = flags.get_flag("tserver_compaction_pool_size")
        pooled = self.mesh is not None \
            and flags.get_flag("tserver_mesh_compaction_pool")
        if pooled:
            # A compaction thread blocks in `pool_wait` for the whole of
            # the job it submits, so the threads bound what the mesh pool
            # can ever see queued: with the flag's 2 a four-slot wave is
            # never more than half full. Two waves' worth, so that the
            # next wave queues up while the slots of this one finish
            # (upstream sizes priority_thread_pool_size by the machine;
            # here the mesh is the machine). No mesh: the flag, as ever.
            n_threads = max(n_threads, 2 * int(self.mesh.devices.size))
        self.pool = PriorityThreadPool(max_threads=n_threads,
                                       name="compact")
        if pooled:
            from yugabyte_tpu.tserver.compaction_pool import CompactionPool
            self.compaction_pool = CompactionPool(self.mesh,
                                                  device=self.device)
        self.block_cache = BlockCache(flags.get_flag("block_cache_bytes"))
        # the live device-vs-native routing authority (PR 16): one
        # process-wide health record per (kernel family, shape bucket),
        # replacing the old static calibration-file loader
        from yugabyte_tpu.storage.bucket_health import health_board
        self.health_board = health_board()
        self.offload_policy = self.health_board
        self._entity = None
        if metrics is not None:
            e = metrics.entity("server", "execution")
            self._g_queue = e.gauge("compaction_pool_queue_depth",
                                    "queued background compactions")
            self._g_active = e.gauge("compaction_pool_active_count",
                                     "running background compactions")
            # cache hit/miss counters live on the caches themselves now
            # (ROOT_REGISTRY, storage/device_cache.py) — real counters,
            # not refresh-time gauge mirrors
            self._entity = e

    def prewarm_op(self):
        """The one-shot maintenance op that compiles the common
        compaction-kernel shape buckets at startup (flag-gated; see
        tserver/maintenance_manager.PrewarmKernelsOp). None when this
        server has no JAX device — the native path compiles nothing."""
        if self.device == "native":
            return None
        from yugabyte_tpu.tserver.maintenance_manager import (
            PrewarmKernelsOp)
        return PrewarmKernelsOp(mesh=self.mesh)

    def device_info(self) -> dict:
        """Resolved platform, device kind and device count (the
        /compactionz `device` block): 'native' for the C++ path."""
        if self.device == "native":
            return {"platform": "native", "kind": "", "count": 0}
        return {"platform": self.device.platform,
                "kind": self.device.device_kind,
                "count": 1 if self.mesh is None
                else int(self.mesh.devices.size)}

    def tablet_options(self) -> TabletOptions:
        return TabletOptions(device=self.device,
                             mesh=self.mesh,
                             offload_policy=self.offload_policy,
                             device_cache=self.device_cache,
                             compaction_pool=self.pool,
                             mesh_pool=self.compaction_pool,
                             block_cache=self.block_cache)

    def refresh_metrics(self) -> None:
        if self._entity is None:
            return
        self._g_queue.set(self.pool.queue_depth())
        self._g_active.set(self.pool.active_count())

    def shutdown(self) -> None:
        if self.compaction_pool is not None:
            self.compaction_pool.shutdown()
        self.pool.shutdown(wait=False)
