"""DB: the LSM storage engine facade.

Capability parity with the reference's DBImpl as YB uses it (ref:
src/yb/rocksdb/db/db_impl.cc): WAL-less writes (the Raft log is the WAL and
the Raft index becomes the sequence/frontier — ref: tablet/tablet.cc:1247-1260),
memtable -> flush -> universal compaction, manifest recovery, checkpoints.
Reads merge memtable + SSTs (ref: MergingIterator table/merger.cc:51 — here a
heapq.merge over sorted sources, since point/short reads stay on CPU; large
scans go through the TPU scan kernel in ops/scan.py).
"""

from __future__ import annotations

import heapq
import os
import threading
from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional, Tuple

from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime
from yugabyte_tpu.docdb.doc_key import split_key_and_ht
from yugabyte_tpu.docdb.value_type import ValueType
from yugabyte_tpu.ops.slabs import pack_doc_ht
from yugabyte_tpu.storage import compaction as compaction_mod
from yugabyte_tpu.storage.memtable import (MemTable, make_internal_key,
                                           new_memtable)
from yugabyte_tpu.storage.sst import (
    BlockCache, Frontier, SSTReader, SSTWriter, data_file_name)
from yugabyte_tpu.storage.version_set import VersionSet
from yugabyte_tpu.utils import flags
from yugabyte_tpu.utils.status import StatusError
from yugabyte_tpu.utils.threadpool import PriorityThreadPool
from yugabyte_tpu.utils.trace import TRACE
from yugabyte_tpu.utils import lock_rank

flags.define_flag("memstore_size_bytes", 128 * 1024 * 1024,
                  "flush memtable at this size (ref docdb_rocksdb_util.cc:113)")
flags.define_flag("memtable_native", True,
                  "Use the C++ memtable arena (native/memtable_arena.cc) "
                  "when the toolchain is available")
flags.define_flag("read_native", True,
                  "serve point reads and scans through the native read "
                  "engine (native/read_engine.cc) when it builds; the "
                  "Python merge path remains the fallback (ref: "
                  "block_based_table_reader.cc:1144-1286)")
flags.define_flag("point_read_batched", True,
                  "resolve DB.multi_get through the batched device "
                  "kernels (ops/point_read.py) when a device + slab "
                  "cache are configured; the native per-key path is the "
                  "byte-identical fallback")
flags.define_flag("point_read_learned_index", True,
                  "seed the batched locate kernel with persisted "
                  "learned per-SST indexes (advisory; mispredictions "
                  "fall back to the exact seek)")


def _storage_metrics():
    """Process-wide read/scan tier histograms (ref: the reference's
    rocksdb_db_get_micros / db_iter latency metrics)."""
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    e = ROOT_REGISTRY.entity("server", "storage")
    return (e.histogram("db_get_duration_ms",
                        "point-read latency through DB.get"),
            e.histogram("db_scan_duration_ms",
                        "full device-scan latency through DB.scan_visible"),
            e.histogram("db_multi_get_duration_ms",
                        "batched point-read latency through DB.multi_get"))


def flush_slab_metrics():
    """Where the slab that a flush stages in the device cache came from,
    in flushes: the native encoder's columns, or `pack_kvs` entry by entry
    in Python (where the compaction engine did not build, or a Python
    MemTable on an encrypted env): beside a compiler on default flags the
    second stays 0."""
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    e = ROOT_REGISTRY.entity("server", "storage")
    return {
        "native": e.counter(
            "flush_slab_native_total",
            "flushes whose slab came from the native job's columns"),
        "python": e.counter(
            "flush_slab_python_total",
            "flushes whose slab came from pack_kvs, an entry at a time "
            "in Python"),
    }


class CompactionStats:
    """Per-DB compaction/flush accounting — the `/compactionz` analogue of
    RocksDB's GetProperty("rocksdb.stats") (ref: rocksdb/db/
    internal_stats.cc). Running write amplification is
    (flush bytes + compaction bytes written) / flush bytes: how many times
    each ingested byte is rewritten by the LSM."""

    def __init__(self):
        self._lock = threading.Lock()
        self.flushes = 0
        self.flush_bytes_written = 0
        self.flush_rows = 0
        self.compactions = 0
        self.compaction_bytes_read = 0
        self.compaction_bytes_written = 0
        self.compaction_files_in = 0
        self.compaction_files_out = 0
        self.compaction_rows_in = 0
        self.compaction_rows_out = 0
        self.versions_gcd = 0          # input entries dropped by MVCC GC
        self.tombstones_written = 0    # TTL expiries rewritten as tombstones

    def record_flush(self, nbytes: int, rows: int) -> None:
        with self._lock:
            self.flushes += 1
            self.flush_bytes_written += nbytes
            self.flush_rows += rows

    def record_compaction(self, bytes_read: int, bytes_written: int,
                          files_in: int, files_out: int,
                          rows_in: int, rows_out: int,
                          tombstones_written: int = 0) -> None:
        with self._lock:
            self.compactions += 1
            self.compaction_bytes_read += bytes_read
            self.compaction_bytes_written += bytes_written
            self.compaction_files_in += files_in
            self.compaction_files_out += files_out
            self.compaction_rows_in += rows_in
            self.compaction_rows_out += rows_out
            self.versions_gcd += max(0, rows_in - rows_out)
            self.tombstones_written += tombstones_written

    def to_dict(self) -> dict:
        with self._lock:
            ingested = self.flush_bytes_written
            write_amp = ((ingested + self.compaction_bytes_written)
                         / ingested if ingested else 0.0)
            return {
                "flushes": self.flushes,
                "flush_bytes_written": self.flush_bytes_written,
                "flush_rows": self.flush_rows,
                "compactions": self.compactions,
                "compaction_bytes_read": self.compaction_bytes_read,
                "compaction_bytes_written": self.compaction_bytes_written,
                "compaction_files_in": self.compaction_files_in,
                "compaction_files_out": self.compaction_files_out,
                "compaction_rows_in": self.compaction_rows_in,
                "compaction_rows_out": self.compaction_rows_out,
                "versions_gcd": self.versions_gcd,
                "tombstones_written": self.tombstones_written,
                "write_amplification": round(write_amp, 3),
            }


@dataclass
class DBOptions:
    block_entries: Optional[int] = None
    block_cache: Optional[BlockCache] = None
    compaction_pool: Optional[PriorityThreadPool] = None
    device: object = None  # JAX device for compaction kernels
    # jax.sharding.Mesh over >1 device: large compactions fan their
    # subcompactions across it (parallel/dist_compact.py); None = single
    # device (ref: subcompaction threads, compaction_job.cc:456-468)
    mesh: object = None
    # tserver/compaction_pool.CompactionPool: when set, device-routed
    # compactions are scheduled through the mesh-sharded multi-tablet
    # pool (batch-slot waves / whole-mesh dist jobs) instead of running
    # the device stage inline on this DB's compaction thread
    mesh_pool: object = None
    # measured device-vs-native router (storage/offload_policy.py)
    offload_policy: object = None
    # HBM-resident slab cache (storage/device_cache.py); shared across
    # tablets like the reference's server-wide block cache
    device_cache: object = None
    # returns current history cutoff HT value (ref: tablet_retention_policy.h:29)
    retention_policy: Callable[[], int] = lambda: 0
    memstore_size_bytes: Optional[int] = None
    auto_compact: bool = True


_OVERLAY_TOO_BIG = object()  # sentinel: memtable too large to repack


class DB:
    def __init__(self, db_dir: str, options: Optional[DBOptions] = None):
        self.db_dir = db_dir
        self.opts = options or DBOptions()
        # RocksDB-style background-error slot (ref: db_impl.cc
        # error_handler_): a failed flush/compaction parks the DB in
        # degraded read-only mode — writes reject retryably, reads keep
        # serving the installed state — until retry_background_work()
        # clears it. The hook tells the owner (TabletPeer) to transition
        # the tablet to FAILED.
        self._bg_error: Optional["Status"] = None
        self.on_background_error: Optional[Callable[[object], None]] = None
        self._writing: set = set()  # SST paths mid-write (orphan-sweep guard)
        self._device_cache = None
        if self.opts.device_cache is not None:
            from yugabyte_tpu.storage.device_cache import (
                DeviceSlabCache, NamespacedSlabCache)
            # namespace file ids per DB under the shared server-wide cache
            # (kept off self.opts: DBOptions may be shared between DBs)
            self._device_cache = (
                NamespacedSlabCache(self.opts.device_cache, os.path.abspath(db_dir))
                if isinstance(self.opts.device_cache, DeviceSlabCache)
                else self.opts.device_cache)
        # host-side packed-run cache: flush/compaction outputs retained
        # decoded so steady-state compactions skip read+decode entirely
        # (storage/run_cache.py; None when disabled or no native engine).
        # Only the device+native combined compaction path consumes it
        # (compaction.py:196 needs device_cache + a device kernel), so a
        # native-only or deviceless DB must not pay the per-flush survivor
        # copy and pinned host RAM for a cache nothing ever reads.
        self._run_cache = None
        if self._device_cache is not None and \
                self.opts.device not in (None, "native"):
            from yugabyte_tpu.storage.run_cache import (NamespacedRunCache,
                                                        shared_run_cache)
            _rc = shared_run_cache()
            if _rc is not None:
                self._run_cache = NamespacedRunCache(
                    _rc, os.path.abspath(db_dir))
        os.makedirs(db_dir, exist_ok=True)
        self.compaction_stats = CompactionStats()
        self.versions = VersionSet(db_dir)
        self.versions.recover()
        self.mem = new_memtable()
        self._imm: Optional[MemTable] = None   # guarded-by: _lock; memtable being flushed
        self._readers: dict = {}
        self._lock = lock_rank.tracked(threading.RLock(), "db._lock")
        self._compacting = False  # guarded-by: _lock
        self._closed = False  # guarded-by: _lock
        # Cancellation seam for in-flight background work: close() and a
        # tablet-FAILED transition (cancel_background_work) flip it, and
        # the compaction pipeline checks it at every stage boundary — an
        # in-flight offloaded job aborts cleanly (partial outputs swept,
        # staging leases released, nothing installed) instead of racing
        # shutdown to the filesystem.
        from yugabyte_tpu.utils.cancellation import CancellationToken
        self._cancel = CancellationToken(f"compaction@{db_dir}")
        self._pins: dict = {}       # file_id -> active scan count
        self._obsolete: dict = {}   # file_id -> reader awaiting unpin+delete
        # Runs after the memtable swap, before this DB's SST installs. The
        # tablet points the intents DB's hook at regular_db.flush so the
        # intents flushed frontier never persists ahead of the regular DB
        # for ops spanning both (bootstrap replays from the min frontier;
        # an OP_UPDATE_TXN whose intent tombstones persisted but whose
        # regular-DB rows didn't would replay as a no-op and lose data).
        self.pre_flush_hook: Optional[Callable[[], None]] = None
        # native read engine state: per-SST native handles + a frozen
        # ReaderSet snapshot, both rebuilt when the live-file set changes
        self._native_readers: dict = {}
        self._rset = None
        self._rset_gen = 0  # bumped on every invalidation: a ReaderSet
        #                     built against gen G installs only if still G
        self._mem_run_cache: Optional[Tuple[int, int, object]] = None
        for fm in self.versions.live_files():
            self._readers[fm.file_id] = SSTReader(fm.path, self.opts.block_cache)

    def memstore_bytes(self) -> int:
        """Mutable + flushing memtable bytes (global-memstore arbitration)."""
        with self._lock:
            total = self.mem.approximate_bytes
            if self._imm is not None:
                total += self._imm.approximate_bytes
            return total

    def oldest_memstore_write_s(self) -> Optional[float]:
        with self._lock:
            times = [self.mem.oldest_write_s]
            if self._imm is not None:
                times.append(self._imm.oldest_write_s)
        times = [t for t in times if t is not None]
        return min(times) if times else None

    def approx_entry_count(self) -> int:
        """Cheap emptiness probe (used to skip the intent overlay on
        intent-free tablets). Zero means definitely empty."""
        with self._lock:
            if self.mem.approximate_bytes or self._imm is not None:
                return 1
            return len(self._readers)

    def approx_row_entries(self) -> int:
        """Rough live-entry count (SST props + a memtable byte-derived
        guess) — the pushdown size gate's input: a fused dispatch only
        beats the per-row host path once the scan is big enough to
        amortize dispatch + (first-time) compile cost."""
        with self._lock:
            n = sum(r.props.n_entries for r in self._readers.values())
            # ~32 bytes/entry is the right order for the gate's purpose
            n += self.mem.approximate_bytes // 32
            if self._imm is not None:
                n += self._imm.approximate_bytes // 32
            return n

    def has_deep_files(self) -> bool:
        """Any live SST holding documents deeper than row+column — the
        tablet's gate for the flat batched row-read fast path (deep rows
        cannot be reconstructed from enumerated column probes)."""
        with self._lock:
            return any(r.props.has_deep for r in self._readers.values())

    def mem_entries_range(self, lower: bytes, upper: bytes
                          ) -> List[Tuple[bytes, bytes]]:
        """Memtable(+imm) entries with lower <= internal_key < upper —
        the host-side row probe of the tablet's batched read (catches
        recent deep/unknown-subkey writes that exact-key probes of the
        enumerated schema columns would miss)."""
        with self._lock:
            mems = [self.mem] + ([self._imm] if self._imm is not None
                                 else [])
        out: List[Tuple[bytes, bytes]] = []
        for m in mems:
            out.extend(m.entries_range(lower, upper))
        return out

    # ------------------------------------------------------- background error
    @property
    def background_error(self):
        """The parked Status, or None when healthy."""
        return self._bg_error

    def _require_writable(self) -> None:
        err = self._bg_error
        if err is not None:
            from yugabyte_tpu.utils.status import Status, StatusError
            raise StatusError(Status.ServiceUnavailable(
                f"DB {self.db_dir} is read-only after a background error "
                f"({err}); retry later"))

    def _set_background_error(self, where: str, exc: BaseException,
                              corruption: bool = False) -> None:
        from yugabyte_tpu.utils.status import Code, Status
        if corruption:
            st = Status.Corruption(
                f"{where} detected corrupt data in {self.db_dir}: {exc}")
        else:
            st = Status.IoError(f"{where} failed in {self.db_dir}: {exc}")
        with self._lock:
            if self._bg_error is not None:
                # first error wins — except corruption, which UPGRADES a
                # retryable I/O park: lost bytes need a rebuild, and the
                # sticky corruption code is what blocks in-place retry
                if not corruption or \
                        self._bg_error.code == Code.CORRUPTION:
                    return
            self._bg_error = st
        TRACE("db %s: background error (%s): %s", self.db_dir, where, exc)
        cb = self.on_background_error
        if cb is not None:
            cb(st)

    def cancel_background_work(self, reason: str = "shutdown") -> None:
        """Abort in-flight background compactions at their next stage
        boundary (tablet-FAILED transition, shutdown). One-way until
        retry_background_work re-arms a fresh token."""
        self._cancel.cancel(reason)

    def retry_background_work(self) -> bool:
        """Clear the parked error and retry the failed work (the
        maintenance manager drives this with capped backoff, ref
        DBImpl::Resume). Returns True when the DB is healthy again; a
        failing retry re-parks it. A CORRUPTION error is STICKY: lost
        bytes cannot be retried back into existence — the replica must
        be rebuilt from a healthy peer (remote bootstrap)."""
        from yugabyte_tpu.utils.status import Code
        with self._lock:
            if self._bg_error is not None \
                    and self._bg_error.code == Code.CORRUPTION:
                return False
            if self._cancel.cancelled and not self._closed:
                # recovery re-arms the cancellation seam for the retried
                # background work (the old token is permanently tripped;
                # re-armed even without a parked error — a tablet-FAILED
                # cancel may have fired without this DB itself erroring)
                from yugabyte_tpu.utils.cancellation import (
                    CancellationToken)
                self._cancel = CancellationToken(
                    f"compaction@{self.db_dir}")
            if self._bg_error is None:
                return True
            self._bg_error = None
        from yugabyte_tpu.utils.status import StatusError
        try:
            self.flush()
        except (OSError, StatusError):
            return False  # flush's failure path re-set the background error
        if self.opts.auto_compact:
            self.maybe_schedule_compaction()
        return self._bg_error is None

    def _sweep_orphan_outputs_unlocked(self) -> None:
        """Remove SST files on disk that no version references and no
        in-flight writer owns — the partial outputs of a failed
        flush/compaction (ref: PurgeObsoleteFiles after a failed job)."""
        try:
            names = os.listdir(self.db_dir)
        except OSError as e:
            # sweep runs again next retry cycle, but a silent skip hid
            # e.g. a permissions regression — surface it
            TRACE("db %s: orphan sweep cannot list dir: %s",
                  self.db_dir, e)
            return
        live = set(self.versions.files)
        writing = {os.path.basename(p) for p in self._writing}
        for name in names:
            stem = name.split(".", 1)[0]
            if not (name.endswith(".sst") or name.endswith(".sblock.0")) \
                    or not stem.isdigit():
                continue
            base_name = stem + ".sst"
            if int(stem) in live or base_name in writing:
                continue
            try:
                os.remove(os.path.join(self.db_dir, name))
            except OSError as e:
                # an orphan that cannot be removed leaks disk until some
                # later sweep succeeds — keep trying, but say so
                TRACE("db %s: orphan sweep cannot remove %s: %s",
                      self.db_dir, name, e)

    # ------------------------------------------------------------------ write
    def _post_write_locked(self, op_id: Tuple[int, int]) -> bool:
        """Shared writer tail (lock held): op-id tracking + flush trigger."""
        self._last_op_id = max(getattr(self, "_last_op_id", (0, 0)), op_id)
        limit = self.opts.memstore_size_bytes or \
            flags.get_flag("memstore_size_bytes")
        return self.mem.approximate_bytes >= limit

    def write_batch(self, items: List[Tuple[bytes, DocHybridTime, bytes]],
                    op_id: Tuple[int, int] = (0, 0)) -> None:
        """Apply a batch (already carrying DocHybridTimes). WAL-less: durability
        comes from the Raft log above (ref: tablet.cc:1247 WriteToRocksDB)."""
        self._require_writable()
        with self._lock:
            mem = self.mem
            if len(items) > 8 or hasattr(mem, "add_columns"):
                # the native arena always takes the batch call (its add()
                # would pay a full ctypes round trip PER ROW)
                mem.add_batch(items)
            else:
                for key_prefix, dht, value in items:
                    mem.add(key_prefix, dht, value)
            need_flush = self._post_write_locked(op_id)
        # flush outside the lock: concurrent writers keep inserting into the
        # fresh memtable while the immutable one packs + writes its SST
        if need_flush:
            self.flush()

    def write_batch_columns(self, keys: List[bytes], ht, wid,
                            values: List[bytes],
                            op_id: Tuple[int, int] = (0, 0)) -> None:
        """Columnar bulk write (batched-RPC apply / bulk-load shape):
        parallel key/value lists + uint64 HT and uint32 write-id arrays —
        one native memtable call instead of per-row tuple assembly
        (ref: db/memtable.cc Add, write path hot loop)."""
        self._require_writable()
        with self._lock:
            mem = self.mem
            if hasattr(mem, "add_columns"):
                mem.add_columns(keys, ht, wid, values)
            else:
                mem.add_batch([
                    (k, DocHybridTime(HybridTime(int(h)), int(w)), v)
                    for k, h, w, v in zip(keys, ht, wid, values)])
            need_flush = self._post_write_locked(op_id)
        if need_flush:
            self.flush()

    # ---------------------------------------------------- native read engine
    def _native_rset(self):
        """Frozen native ReaderSet over the live SSTs, or None when the
        native read engine is disabled/unavailable. Snapshots outlive
        installs: in-flight scans keep the old set (and its pinned file
        bytes) alive by reference, so no file pinning is needed."""
        if not flags.get_flag("read_native"):
            return None
        rset = self._rset
        if rset is not None:  # lock-free hot path (GIL-atomic attr read;
            return rset       # stale snapshots are safe, see docstring)
        from yugabyte_tpu.storage import native_read
        if not native_read.available():
            return None
        with self._lock:
            if self._rset is not None:
                return self._rset
            gen = self._rset_gen
            readers = dict(self._readers)
            existing = dict(self._native_readers)
        built = {}
        for fid, r in readers.items():
            nr = existing.get(fid)
            built[fid] = nr if nr is not None else \
                native_read.NativeSSTReader(r)
        rset = native_read.ReaderSet(list(built.values()))
        with self._lock:
            if self._rset_gen != gen:
                # a flush/compaction installed while we built: our snapshot
                # is already stale — serve it for THIS call only (the file
                # set it holds was live and consistent), do not cache it
                return rset if self._rset is None else self._rset
            self._native_readers = built
            self._rset = rset
        return rset

    def _memtable_run(self):
        """Packed memtable(+imm) overlay for native scans, cached per
        memtable version (rebuilding per scan would re-pay per-entry
        packing on every read of a write-hot tablet)."""
        from yugabyte_tpu.docdb.value import decode_control_fields
        from yugabyte_tpu.docdb.value_type import ValueType as VT
        from yugabyte_tpu.storage.native_read import PackedRun
        with self._lock:
            mem, imm = self.mem, self._imm
        key = (id(mem), mem.version, id(imm),
               imm.version if imm is not None else -1)
        cached = self._mem_run_cache
        if cached is not None and cached[0] == key:
            return cached[1]
        if mem.empty and (imm is None or imm.empty):
            run = None
        elif (mem.n_entries
              + (imm.n_entries if imm is not None else 0)) > 200_000:
            # write-hot tablet near the flush threshold: repacking the
            # whole memtable per scan costs more than the Python merge it
            # replaces — signal the caller to take the fallback path
            return _OVERLAY_TOO_BIG
        else:
            sources = [mem.iter_from(b"")]
            if imm is not None:
                sources.append(imm.iter_from(b""))
            entries = []
            for ikey, value in heapq.merge(*sources):
                prefix, dht = split_key_and_ht(ikey)
                fl = 0
                ttl = 0
                try:
                    _, ttl_ms, off = decode_control_fields(value)
                    tag = value[off] if off < len(value) else 0
                    if tag == VT.kTombstone:
                        fl |= 1
                    elif tag == VT.kObject:
                        fl |= 2
                    if ttl_ms is not None:
                        fl |= 4
                        ttl = ttl_ms
                except (IndexError, ValueError):
                    pass
                entries.append((prefix, dht.ht.value, dht.write_id, fl, ttl,
                                value))
            run = PackedRun(entries)
        self._mem_run_cache = (key, run)
        return run

    def scan_native(self, lower: bytes = b"", upper: Optional[bytes] = None,
                    read_ht_value: Optional[int] = None,
                    visible: bool = False, batch_rows: int = 65536,
                    internal_keys: bool = False):
        """Native streaming scan (NativeScan) over SSTs + memtable overlay,
        or None when the native engine is unavailable. visible=True
        resolves MVCC visibility in C++ (DocRowwiseIterator's RESOLVE
        stage); internal_keys=True emits full internal keys (raw mode)."""
        from yugabyte_tpu.storage.native_read import NativeScan
        # overlay snapshot BEFORE the reader set (see get(): double
        # coverage is safe, a hidden row is not)
        overlay = self._memtable_run()
        if overlay is _OVERLAY_TOO_BIG:
            return None
        rset = self._native_rset()
        if rset is None:
            return None
        mode = 1 if visible else (2 if internal_keys else 0)
        return NativeScan(
            rset, lower, upper,
            read_ht_value if read_ht_value is not None else (2**64 - 1),
            overlay=overlay, batch_rows=batch_rows, mode=mode)

    def ingest_packed(self, keys_blob: bytes, key_offs, ht, wid,
                      vals_blob: bytes, val_offs,
                      op_id: Tuple[int, int] = (0, 0)) -> Optional[int]:
        """Bulk-load one packed run directly as an L0 SST, bypassing the
        memtable (the reference's bulk-load / external-file ingestion path,
        ref: src/yb/tools/yb_bulk_load.cc,
        rocksdb/db/external_sst_file_ingestion_job.cc). Rows need not be
        pre-sorted — the native encoder orders them. Returns the file id,
        or None for an empty run. Requires the native engine (callers fall
        back to write_batch + flush)."""
        from yugabyte_tpu.storage import native_engine
        from yugabyte_tpu.storage.sst import write_sst_from_packed
        from yugabyte_tpu.utils.env import get_env
        if not (native_engine.available() and not get_env().encrypted):
            raise RuntimeError("ingest_packed requires the native engine")
        self._require_writable()
        n = len(key_offs) - 1
        if n == 0:
            return None
        with self._lock:
            fid = self.versions.new_file_id()
            self._last_op_id = max(getattr(self, "_last_op_id", (0, 0)),
                                   op_id)
        path = os.path.join(self.db_dir, f"{fid:06d}.sst")
        frontier = Frontier(op_id_min=op_id, op_id_max=op_id,
                            history_cutoff=0)
        props = write_sst_from_packed(
            path, keys_blob, key_offs, ht, wid, vals_blob, val_offs,
            frontier=frontier, block_entries=self.opts.block_entries)
        with self._lock:
            self.versions.add_file(fid, path, props)
            self._readers[fid] = SSTReader(path, self.opts.block_cache)
            self._rset = None
            self._rset_gen += 1
        if self.opts.auto_compact:
            self.maybe_schedule_compaction()
        return fid

    # ------------------------------------------------------------------ read
    def get(self, key_prefix: bytes, read_ht: Optional[HybridTime] = None
            ) -> Optional[Tuple[DocHybridTime, bytes]]:
        """Latest version of key_prefix visible at read_ht (raw KV semantics;
        document semantics layer above in docdb)."""
        import time as _time
        t0 = _time.monotonic()
        try:
            return self._get_inner(key_prefix, read_ht)
        except StatusError as e:
            self._route_read_corruption(e)
            raise
        finally:
            _storage_metrics()[0].increment(
                (_time.monotonic() - t0) * 1e3)

    def _route_read_corruption(self, e: "StatusError") -> None:
        """A read that hit corrupt SST bytes (block CRC / footer
        mismatch) must not surface as a raw Corruption to the client:
        route it to the background-error slot — parking the DB and
        failing the tablet so the master rebuilds the replica — and
        re-raise RETRYABLY so the client walks to a healthy replica."""
        from yugabyte_tpu.utils.status import Code, Status
        if e.status.code != Code.CORRUPTION:
            return
        self._set_background_error("read", e, corruption=True)
        raise StatusError(Status.ServiceUnavailable(
            f"read hit corrupt SST data in {self.db_dir} "
            f"({e.status.message}); replica is being repaired — retry "
            f"another replica")) from e

    def _get_inner(self, key_prefix: bytes,
                   read_ht: Optional[HybridTime] = None
                   ) -> Optional[Tuple[DocHybridTime, bytes]]:
        read_ht = read_ht or HybridTime.kMax
        seek = make_internal_key(key_prefix, DocHybridTime(read_ht, 0xFFFFFFFF))
        boundary = key_prefix + bytes([ValueType.kHybridTime])
        # memtable snapshot BEFORE the reader set: a flush landing between
        # the two moves entries mem -> SST, and the old MemTable object
        # still holds them, so either ordering race at worst double-covers
        # a row (newest version wins) — never hides one
        with self._lock:
            mems = [self.mem] + ([self._imm] if self._imm is not None
                                 else [])
        rset = self._native_rset()
        if rset is not None:
            # native fast path: memtable probes in Python (bisect), SSTs in
            # one native call; newest visible version wins across sources
            best = None  # (ht_value, wid, value)
            for mem in mems:
                hit = mem.point_get(seek, boundary)
                if hit is not None:
                    _, dht = split_key_and_ht(hit[0])
                    cand = (dht.ht.value, dht.write_id, hit[1])
                    if best is None or cand[:2] > best[:2]:
                        best = cand
            if rset.n:
                hit = rset.multi_get(key_prefix, -1, read_ht.value)
                if hit is not None:
                    ht_v, wid, _fl, val = hit
                    if best is None or (ht_v, wid) > best[:2]:
                        best = (ht_v, wid, val)
            if best is None:
                return None
            return DocHybridTime(HybridTime(best[0]), best[1]), best[2]
        # Bloom filters hold DOC key prefixes (storage/bloom.py): probe with
        # the DocKey portion, not the full subdoc key.
        from yugabyte_tpu.ops.slabs import _doc_key_len
        try:
            bloom_key = key_prefix[: _doc_key_len(key_prefix)]
        except Exception:
            bloom_key = None
        for ikey, value in self.iter_from(seek, check_bloom_doc=bloom_key):
            if not ikey.startswith(boundary):
                return None
            prefix, dht = split_key_and_ht(ikey)
            if prefix == key_prefix and dht.ht.value <= read_ht.value:
                return dht, value
            return None
        return None

    # ------------------------------------------------------- batched read
    def multi_get(self, keys: List[bytes],
                  read_ht: Optional[HybridTime] = None,
                  doc_key_lens: Optional[List[int]] = None
                  ) -> List[Optional[Tuple[DocHybridTime, bytes]]]:
        """Batched point reads: BYTE-IDENTICAL to
        ``[self.get(k, read_ht) for k in keys]`` (per-key MVCC at the
        shared read_ht), but the SST layer resolves the whole batch in
        vectorized device kernels over the HBM-resident slab matrices
        (ops/point_read.py: bloom probe -> block locate -> survivor
        gather) while the memtable probes stay host-side. Falls back —
        byte-identically — to the native per-key path when no device is
        configured, the batch's shape bucket is quarantined after a
        device fault, or a kernel dispatch faults mid-batch.

        doc_key_lens: optional per-key DocKey prefix lengths (the bloom
        probe's filter keys); callers that built the keys (tablet
        multi_read) pass them to skip per-key host parsing."""
        import time as _time
        t0 = _time.monotonic()
        try:
            return self._multi_get_inner(list(keys), read_ht,
                                         doc_key_lens)
        except StatusError as e:
            self._route_read_corruption(e)
            raise
        finally:
            _storage_metrics()[2].increment(
                (_time.monotonic() - t0) * 1e3)

    def _multi_get_inner(self, keys, read_ht, doc_key_lens=None):
        from yugabyte_tpu.utils import latency as _latency
        read_ht = read_ht or HybridTime.kMax
        if not keys:
            return []
        if flags.get_flag("point_read_batched") \
                and self._device_cache is not None \
                and self.opts.device not in (None, "native"):
            # host wall around the whole device point-read path; its
            # sub-stages (utils/latency.py) say where inside
            with _latency.stage_span(_latency.STAGE_DEVICE_DISPATCH):
                res = self._multi_get_device(keys, read_ht, doc_key_lens)
            if res is not None:
                return res
        with _latency.stage_span(_latency.STAGE_HOST_FALLBACK):
            return self._multi_get_native(keys, read_ht)

    def _multi_get_native(self, keys, read_ht):
        """The CPU fallback: one native multi_get per key over a single
        reader-set snapshot (storage/native_read.py), memtable probes in
        Python — the loop body of _get_inner without the per-call
        snapshot/metric overhead. Byte-identical to sequential gets."""
        # memtable snapshot BEFORE the reader set (see get())
        with self._lock:
            mems = [self.mem] + ([self._imm] if self._imm is not None
                                 else [])
        rset = self._native_rset()
        if rset is None:
            return [self._get_inner(k, read_ht) for k in keys]
        mems = [m for m in mems if not m.empty]
        sst_hits = (rset.multi_get_many(keys, read_ht.value)
                    if rset.n else [None] * len(keys))
        mem_hits = self._mem_probe_many(mems, keys, read_ht)
        out = []
        for sh, best in zip(sst_hits, mem_hits):
            if sh is not None:
                ht_v, wid, _fl, val = sh
                if best is None or (ht_v, wid) > best[:2]:
                    best = (ht_v, wid, val)
            out.append(None if best is None else
                       (DocHybridTime(HybridTime(best[0]), best[1]),
                        best[2]))
        return out

    @staticmethod
    def _mem_probe_many(mems, keys, read_ht):
        """Newest memtable candidate per key as (ht_value, wid, value),
        via each memtable's BATCHED probe (one lock acquisition per
        memtable instead of one per key — the per-key locking dominated
        batched reads of memtable-resident rows)."""
        if not mems:
            return [None] * len(keys)
        probes = [(make_internal_key(k, DocHybridTime(read_ht, 0xFFFFFFFF)),
                   k + bytes([ValueType.kHybridTime])) for k in keys]
        best = [None] * len(keys)
        for mem in mems:
            for i, hit in enumerate(mem.point_get_many(probes)):
                if hit is None:
                    continue
                _, dht = split_key_and_ht(hit[0])
                cand = (dht.ht.value, dht.write_id, hit[1])
                if best[i] is None or cand[:2] > best[i][:2]:
                    best[i] = cand
        return best

    def _multi_get_device(self, keys, read_ht, doc_key_lens=None):
        """The batched device path, or None when this batch must take
        the native fallback (unstageable residency, quarantined shape
        bucket, or a mid-batch device fault — all byte-identical)."""
        from yugabyte_tpu.ops import device_faults, point_read
        from yugabyte_tpu.storage import offload_policy
        from yugabyte_tpu.utils.latency import sub_span
        # memtable snapshot BEFORE the reader set (see get())
        with sub_span("stage_lookup"):
            with self._lock:
                mems = [self.mem] + ([self._imm] if self._imm is not None
                                     else [])
                readers = list(self._readers.items())
                for fid, _ in readers:
                    self._pins[fid] = self._pins.get(fid, 0) + 1
        try:
            with sub_span("stage_lookup"):
                staged_by = self._staged_readers(readers)
                if staged_by is None:
                    return None  # stale residency: let native serve
                from yugabyte_tpu.storage.bucket_health import health_board
                board = health_board()
                if any(not board.allow_device(
                        "point_read_locate",
                        offload_policy.point_read_bucket_key(st.n_pad))
                       for _fid, _r, st in staged_by):
                    return None
            results: List = [None] * len(keys)
            cur = {"n_pad": staged_by[0][2].n_pad if staged_by else 0}
            import time as _time
            t0 = _time.monotonic()
            try:
                self._multi_get_device_batches(
                    keys, read_ht, mems, staged_by, results,
                    doc_key_lens, cur)
                if staged_by:
                    board.record_device(
                        "point_read_locate",
                        offload_policy.point_read_bucket_key(
                            cur["n_pad"]),
                        len(keys), _time.monotonic() - t0)
            except Exception as e:  # noqa: BLE001 — device-fault containment
                if not device_faults.is_device_fault(e):
                    raise
                # fault containment: park the shape bucket and serve this
                # batch (and the quarantine window) via the native path,
                # byte-identically — mirrors the compaction fallback
                board.record_fault(
                    "point_read_locate",
                    offload_policy.point_read_bucket_key(cur["n_pad"]),
                    reason=f"point-read {type(e).__name__}: {e}")
                point_read.point_read_metrics()[
                    "device_fallbacks"].increment()
                TRACE("multi_get: device fault mid-batch (%r) — shape "
                      "bucket (1, %d) quarantined; serving natively",
                      e, cur["n_pad"])
                return None
            return results
        finally:
            with sub_span("stage_lookup"), self._lock:
                for fid, _ in readers:
                    self._pins[fid] -= 1
                    if not self._pins[fid]:
                        del self._pins[fid]
                self._purge_obsolete_unlocked()

    def _staged_readers(self, readers):
        """[(fid, reader, staged cols)] of the non-empty SSTs, staging a
        slab-cache miss on the way; None when a resident entry is stale."""
        from yugabyte_tpu.utils.latency import sub_span
        staged_by = []
        for fid, r in readers:
            if r.props.n_entries == 0:
                continue
            st = self._device_cache.get(fid)
            if st is None:
                # write-through on miss, like scan_visible: the next
                # batch over this file finds it resident (a corrupt
                # block raises: multi_get routes + re-raises)
                with sub_span("stage_miss"):
                    st = self._device_cache.stage(fid, r.read_all(),
                                                  for_read=True)
            if st.n != r.props.n_entries:
                return None
            staged_by.append((fid, r, st))
        return staged_by

    def _multi_get_device_batches(self, keys, read_ht, mems, staged_by,
                                  results, doc_key_lens, cur):
        """Resolve `keys` chunk by chunk into `results` (which arrives
        holding None for every key). On the host nothing here runs once
        per key or once per operand that can run once per chunk, once
        per block or once per file: one query pack, numpy operands, the
        files' own operands resident, array compares, values by block."""
        import numpy as np
        from yugabyte_tpu.ops import point_read
        from yugabyte_tpu.ops.run_merge import quantize_width
        from yugabyte_tpu.ops.slabs import _doc_key_len
        from yugabyte_tpu.utils.latency import sub_span
        metrics = point_read.point_read_metrics()
        mems = [m for m in mems if not m.empty]
        use_model = flags.get_flag("point_read_learned_index")
        device = self._device_cache.device
        rhi = np.uint32(read_ht.value >> 32)
        rlo = np.uint32(read_ht.value & 0xFFFFFFFF)
        file_widths = {st.w for _fid, _r, st in staged_by}
        # the device calls below (hash_batch, probe_bloom, locate_batch)
        # carry their own sub-stage spans: `device_enqueue` around each
        # dispatch, `device_wait` around each blocking download
        for start in range(0, len(keys), 1024):
            chunk = keys[start: start + 1024]
            b = len(chunk)
            metrics["batches"].increment()
            metrics["keys"].increment(b)
            metrics["batch_rows"].increment(b)
            # bloom hashes over the DocKey prefixes — one device FNV
            # dispatch per chunk (storage/bloom.py is the CPU twin)
            with sub_span("query_pack"):
                if doc_key_lens is not None:
                    dkls = doc_key_lens[start: start + 1024]
                else:
                    dkls = [_doc_key_len(k) for k in chunk]
                w_hash = quantize_width(max(1, -(-max(dkls) // 4)))
                # ONE pack, at the widest width in play: the hash's and
                # a narrower file's operand are its first columns
                widths = file_widths | {w_hash}
                w_pack = max(widths)
                qw, ql = point_read.pack_query_batch(chunk, w_pack)
                qw_by_w = {w: (qw if w == w_pack
                               else np.ascontiguousarray(qw[:, :w]))
                           for w in widths}
                dk_pad = np.zeros(len(ql), dtype=np.int32)
                dk_pad[:b] = dkls
            h1, h2 = point_read.hash_batch(qw_by_w[w_hash], dk_pad)
            exact_fallback = set()
            best = None  # [ht u64, wid, row, file index, valid] arrays
            for fi, (fid, r, st) in enumerate(staged_by):
                cur["n_pad"] = st.n_pad
                maybe = point_read.probe_bloom(r, h1, h2, device=device)
                if maybe is not None and not maybe[:b].any():
                    metrics["bloom_skips"].increment()
                    continue
                with sub_span("query_pack"):
                    n_dev, model = point_read.locate_device_operands(r)
                    if not use_model:
                        model = None
                # the chunk's own rows of the padded results: what follows
                # costs by the keys asked, not by their bucket
                idx, hit, hhi, hlo, wid, miss = (
                    x[:b] for x in point_read.locate_batch(
                        st, qw_by_w[st.w], ql, rhi, rlo, n_dev, model))
                with sub_span("chunk_combine"):
                    if model is not None:
                        metrics["learned_hits"].increment()
                        missed = np.flatnonzero(miss)
                        if len(missed):
                            metrics["learned_fallbacks"].increment(
                                len(missed))
                            exact_fallback.update(missed.tolist())
                    ht = (hhi.astype(np.uint64) << np.uint64(32)) | hlo
                    row = idx.astype(np.int64)
                    if best is None:
                        # what a column holds where `valid` is False is
                        # never read
                        best = [ht, wid, row, np.full(b, fi, np.int64), hit]
                        continue
                    upd = hit & (~best[4] | (ht > best[0])
                                 | ((ht == best[0]) & (wid > best[1])))
                    best[0] = np.where(upd, ht, best[0])
                    best[1] = np.where(upd, wid, best[1])
                    best[2] = np.where(upd, row, best[2])
                    best[3] = np.where(upd, fi, best[3])
                    best[4] = best[4] | hit
            self._combine_device_chunk(chunk, start, read_ht, mems,
                                       staged_by, best, exact_fallback,
                                       results, metrics)

    def _combine_device_chunk(self, chunk, start, read_ht, mems,
                              staged_by, best, exact_fallback, results,
                              metrics):
        """Merge device SST winners with host memtable probes — newest
        (ht, wid) wins, a tie goes to the memtable: exactly get()'s
        compare, as array compares. Python runs per key only for the
        keys a memtable wins and the learned index's mispredictions;
        the SST winners' values are fetched block by block at the end
        (`value_fetch`)."""
        import numpy as np
        from yugabyte_tpu.utils.latency import sub_span
        b = len(chunk)
        with sub_span("chunk_combine"):
            sst_wins = (best[4].copy() if best is not None
                        else np.zeros(b, bool))
            mem_hits = (self._mem_probe_many(mems, chunk, read_ht)
                        if mems else [])
            held = [i for i, h in enumerate(mem_hits) if h is not None]
            mem_wins = np.zeros(b, bool)
            if held:
                mem_wins[held] = True
                if best is not None:
                    m_ht = np.zeros(b, np.uint64)
                    m_wid = np.zeros(b, np.uint32)
                    m_ht[held] = np.array([mem_hits[i][0] for i in held],
                                          dtype=np.uint64)
                    m_wid[held] = np.array([mem_hits[i][1] for i in held],
                                           dtype=np.uint32)
                    sst_wins &= ~mem_wins | (best[0] > m_ht) | (
                        (best[0] == m_ht) & (best[1] > m_wid))
                    mem_wins &= ~sst_wins
            if exact_fallback:
                # learned-index misprediction beyond its bound: the
                # binary-search invariant caught it — resolve these keys
                # exactly (correctness never rides the model)
                missed = list(exact_fallback)
                sst_wins[missed] = False
                mem_wins[missed] = False
                for i in missed:
                    results[start + i] = self._get_inner(chunk[i], read_ht)
            for i in np.flatnonzero(mem_wins).tolist():
                ht_v, wid_v, value = mem_hits[i]
                results[start + i] = (
                    DocHybridTime(HybridTime(ht_v), wid_v), value)
        with sub_span("value_fetch"):
            won = np.flatnonzero(sst_wins)
            if len(won):
                self._fetch_staged_values(
                    staged_by, best[3][won], best[2][won], best[0][won],
                    best[1][won], won + start, results, metrics)

    @staticmethod
    def _fetch_staged_values(staged_by, files, rows, hts, wids, slots,
                             results, metrics) -> None:
        """results[slot] = (doc hybrid time, value bytes) for each SST
        winner, given as parallel arrays: staged file index, row (sorted
        order), hybrid time, write id, result slot. The survivor-gather
        half of the batched read (values never live in HBM;
        ops/slabs.py), block by block: per file one searchsorted of its
        rows against the blocks' first rows, per distinct block one
        read_block and one index over its values."""
        import numpy as np
        from yugabyte_tpu.ops.slabs import ValueArray
        n_blocks = 0
        for fi in np.unique(files).tolist():
            r = staged_by[fi][1]
            offs = r.block_row_offsets()
            of_file = np.flatnonzero(files == fi)
            blks = np.searchsorted(offs, rows[of_file], side="right") - 1
            order = np.argsort(blks, kind="stable")
            of_file = of_file[order]
            blks = blks[order]
            in_blk = rows[of_file] - offs[blks]
            slot_l = slots[of_file].tolist()
            ht_l = hts[of_file].tolist()
            wid_l = wids[of_file].tolist()
            los = [0] + (np.flatnonzero(blks[1:] != blks[:-1]) + 1).tolist()
            n_blocks += len(los)
            for lo, hi, blk in zip(los, los[1:] + [len(blks)],
                                   blks[los].tolist()):
                slab = r.read_block(blk)
                va = ValueArray.from_list(slab.values)
                vi = slab.value_idx[in_blk[lo:hi]]
                data = memoryview(va.data)
                for slot, ht_v, wid_v, s, e in zip(
                        slot_l[lo:hi], ht_l[lo:hi], wid_l[lo:hi],
                        va.offsets[vi].tolist(),
                        va.offsets[vi + 1].tolist()):
                    results[slot] = (DocHybridTime(HybridTime(ht_v), wid_v),
                                     data[s:e].tobytes())
        metrics["value_fetch_rows"].increment(len(slots))
        metrics["value_fetch_blocks"].increment(n_blocks)

    def iter_from(self, seek_internal_key: bytes = b"",
                  check_bloom_doc: Optional[bytes] = None
                  ) -> Iterator[Tuple[bytes, bytes]]:
        """Merged (internal_key, value) stream in memcmp order (the
        MergingIterator equivalent). SSTs stream through the native read
        engine (C++ k-way merge over in-place block views) when available,
        merged lazily with the Python memtable iterators — the memtable
        never pays a repack; the full-Python heap merge remains the
        fallback and the oracle."""
        if check_bloom_doc is None and flags.get_flag("read_native"):
            from yugabyte_tpu.storage import native_read
            if native_read.available():
                # memtable snapshot BEFORE the reader set: a racing flush
                # at worst double-covers rows (deduped below), never hides
                with self._lock:
                    mems = [self.mem] + ([self._imm]
                                         if self._imm is not None else [])
                rset = self._native_rset()
                if rset is not None:
                    prefix_seek, _ = split_key_and_ht(seek_internal_key)
                    from yugabyte_tpu.storage.native_read import NativeScan
                    scan = NativeScan(rset, lower=prefix_seek, mode=2)
                    sources = [m.iter_from(seek_internal_key) for m in mems]
                    sources.append(
                        self._native_iter(scan, seek_internal_key))
                    return _dedup_ikeys(heapq.merge(*sources))
        with self._lock:
            sources = []
            sources.append(self.mem.iter_from(seek_internal_key))
            if self._imm is not None:
                sources.append(self._imm.iter_from(seek_internal_key))
            readers = list(self._readers.values())
        for r in readers:
            if check_bloom_doc is not None and not r.may_contain_doc(check_bloom_doc):
                continue
            sources.append(_sst_iter_from(r, seek_internal_key))
        return heapq.merge(*sources)

    @staticmethod
    def _native_iter(scan, seek_internal_key: bytes
                     ) -> Iterator[Tuple[bytes, bytes]]:
        """Adapt a mode-2 NativeScan to the iter_from contract. The native
        seek is by key PREFIX (any version); when the seek carried an HT
        suffix, drop the leading newer-version entries it excludes."""
        skipping = bool(seek_internal_key)
        for batch in scan.batches():
            koffs, voffs = batch.key_offs, batch.val_offs
            keys, vals = batch.keys, batch.vals
            for i in range(batch.n):
                ikey = keys[koffs[i]: koffs[i + 1]].tobytes()
                if skipping:
                    if ikey < seek_internal_key:
                        continue
                    skipping = False
                yield ikey, vals[voffs[i]: voffs[i + 1]].tobytes()

    def scan_visible(self, read_ht_value: int,
                     lower_key: Optional[bytes] = None,
                     upper_key: Optional[bytes] = None):
        """TPU scan path: yield (key_prefix, value_bytes, ht_value) of every
        entry visible at read_ht in [lower_key, upper_key), in key order.

        One fused device program resolves merge + MVCC visibility + range
        filter for the whole range (ops/scan.py), instead of the per-step
        Python heap merge of iter_from. SST key columns come from the HBM
        slab cache (write-through on miss) — a RESIDENT file is never
        block-decoded to stage the filter: the kernel runs over the
        cached matrix and only the blocks holding surviving entries are
        decoded for their keys/values (ops/scan.ResidentSource). Input
        SSTs are PINNED for the scan's lifetime so a concurrent
        compaction cannot delete them (the reference's Version
        refcounting, ref: db/version_set.cc).
        """
        from yugabyte_tpu.ops.scan import (ResidentSource, SlabSource,
                                           visible_entries_sources)
        import time as _time
        t0 = _time.monotonic()
        with self._lock:
            slabs = [self.mem.to_slab()]
            if self._imm is not None:
                slabs.append(self._imm.to_slab())
            readers = list(self._readers.items())
            for fid, _ in readers:
                self._pins[fid] = self._pins.get(fid, 0) + 1
        try:
            sources = [SlabSource(sl) for sl in slabs]
            for fid, r in readers:
                st = (self._device_cache.get(fid)
                      if self._device_cache is not None else None)
                if st is not None and not r.props.has_deep:
                    # resident fast path: zero host block decode to stage
                    sources.append(ResidentSource(r, st))
                    continue
                try:
                    sl = r.read_all()
                except StatusError as e:
                    # corrupt block under a scan: park + fail retryably
                    # (the client walks replicas), never a raw Corruption
                    self._route_read_corruption(e)
                    raise
                if self._device_cache is not None and not r.props.has_deep:
                    st = self._device_cache.stage(fid, sl, for_read=True)
                    sources.append(SlabSource(sl, st))
                else:
                    sources.append(SlabSource(sl))
            try:
                yield from visible_entries_sources(
                    sources, read_ht_value, lower_key, upper_key,
                    device=self.opts.device)
            except StatusError as e:
                # a resident source decodes survivor blocks lazily — a
                # corrupt block surfacing mid-stream takes the same
                # containment path as the eager decode above
                self._route_read_corruption(e)
                raise
        finally:
            _storage_metrics()[1].increment(
                (_time.monotonic() - t0) * 1e3)
            with self._lock:
                for fid, _ in readers:
                    self._pins[fid] -= 1
                    if not self._pins[fid]:
                        del self._pins[fid]
                self._purge_obsolete_unlocked()

    # ----------------------------------------------------- query pushdown
    def _pushdown_sources(self, spec):
        """Build the fused-scan source list with pins held + value words
        staged (ROADMAP item 5). Returns (sources, readers) — the caller
        owns unpinning via _release_scan_pins. Raises
        PushdownUnsupported("deep") on deep-document files (the kernels
        are depth-2 only) so callers fall back host-side, counted."""
        from yugabyte_tpu.docdb.scan_spec import PushdownUnsupported
        from yugabyte_tpu.ops.scan import (ResidentSource, SlabSource,
                                           pack_vals, pushdown_metrics)
        with self._lock:
            slabs = [self.mem.to_slab()]
            if self._imm is not None:
                slabs.append(self._imm.to_slab())
            readers = list(self._readers.items())
            for fid, _ in readers:
                self._pins[fid] = self._pins.get(fid, 0) + 1
        try:
            sources = [SlabSource(sl) for sl in slabs]
            for fid, r in readers:
                if r.props.has_deep:
                    raise PushdownUnsupported("deep")
                st = (self._device_cache.get(fid)
                      if self._device_cache is not None else None)
                if st is None:
                    sl = self._read_all_contained(r)
                    if self._device_cache is not None:
                        st = self._device_cache.stage(
                            fid, sl, for_read=True,
                            include_vals=spec.needs_vals)
                        sources.append(ResidentSource(r, st))
                    else:
                        sources.append(SlabSource(sl, sorted_source=True))
                    continue
                if spec.needs_vals and st.vals_dev is None:
                    # resident cols without value words: decode once,
                    # attach, and every later pushdown scan is resident
                    import jax
                    import jax.numpy as jnp
                    sl = self._read_all_contained(r)
                    packed = pack_vals(sl, st.n_pad)
                    dev = self._device_cache.device
                    vals_dev = (jax.device_put(packed, dev)
                                if dev is not None
                                else jnp.asarray(packed))
                    self._device_cache.attach_vals(fid, vals_dev)
                    pushdown_metrics()["vals_staged"].increment()
                sources.append(ResidentSource(r, st))
            return sources, readers
        except BaseException:
            self._release_scan_pins(readers)
            raise

    def _read_all_contained(self, r):
        try:
            return r.read_all()
        except StatusError as e:
            self._route_read_corruption(e)
            raise

    def _release_scan_pins(self, readers) -> None:
        with self._lock:
            for fid, _ in readers:
                self._pins[fid] -= 1
                if not self._pins[fid]:
                    del self._pins[fid]
            self._purge_obsolete_unlocked()

    def scan_filtered(self, read_ht_value: int, spec,
                      lower_key: Optional[bytes] = None,
                      upper_key: Optional[bytes] = None):
        """Fused filtered scan: yields the visible entries of exactly
        the rows satisfying spec.predicates, resolved in one device
        dispatch over the resident slab matrices. The dispatch runs
        EAGERLY — device faults surface here (as PushdownUnsupported,
        bucket quarantined) with zero rows emitted and zero pins leaked,
        so the caller can serve the same query through the host path."""
        from yugabyte_tpu.ops.scan import (ResidentSource,
                                           filtered_entries_sources,
                                           pushdown_metrics)
        sources, readers = self._pushdown_sources(spec)
        try:
            it = filtered_entries_sources(
                sources, read_ht_value, spec, lower_key, upper_key,
                device=self.opts.device)
        except BaseException:
            self._release_scan_pins(readers)
            raise

        def entries():
            try:
                yield from it
            except StatusError as e:
                # corrupt winner block mid-stream: same containment as
                # the plain scan path (park + retryable to the client)
                self._route_read_corruption(e)
                raise
            finally:
                blocks = sum(s.decoded_blocks for s in sources
                             if isinstance(s, ResidentSource))
                pushdown_metrics()["blocks"].increment(max(blocks, 0))
                self._release_scan_pins(readers)

        return entries()

    def scan_aggregate(self, read_ht_value: int, spec,
                       lower_key: Optional[bytes] = None,
                       upper_key: Optional[bytes] = None) -> dict:
        """Fused aggregating scan: one dispatch returns the aggregate
        partial for this DB's whole source set ({"rows", "cols"}), with
        exact MVCC visibility across memtables and SSTs. Scalars only —
        host memory is touched once per RESULT, not once per row."""
        from yugabyte_tpu.ops.scan import aggregate_sources
        sources, readers = self._pushdown_sources_spanned(spec)
        try:
            return aggregate_sources(sources, read_ht_value, spec,
                                     lower_key, upper_key,
                                     device=self.opts.device)
        finally:
            self._release_scan_pins(readers)

    # ----------------------------------------------------------------- flush
    def flush(self) -> Optional[int]:
        """Memtable -> L0 SST (ref: db/flush_job.cc).

        The lock is held only to swap the memtable and to install the result;
        slab packing + SST write + fsync run unlocked while reads serve from
        the immutable memtable (self._imm).
        """
        with self._lock:
            if self._imm is not None:
                return None  # a flush is already in progress
            if self._bg_error is not None:
                return None  # parked: retry_background_work re-drives
            if self.mem.empty:
                return None
            self._imm, self.mem = self.mem, new_memtable()
            imm = self._imm
            last_op = getattr(self, "_last_op_id", (0, 0))
        fid = path = None
        from yugabyte_tpu.utils.metrics import pipeline_span
        try:
            if self.pre_flush_hook is not None:
                self.pre_flush_hook()
            fid = self.versions.new_file_id()
            path = os.path.join(self.db_dir, f"{fid:06d}.sst")
            with self._lock:
                self._writing.add(path)
            slab = None
            from yugabyte_tpu.storage import native_engine
            from yugabyte_tpu.utils.env import get_env
            if native_engine.available() and not get_env().encrypted:
                # native flush encoder: block encode + bloom + doc-key
                # parsing in C++ (the write-path hot loop, ref:
                # db/flush_job.cc WriteLevel0Table), with run-cache
                # write-through so the first compaction over this output
                # skips read+decode, and the slab that device staging
                # (below) needs handed back from the same job's columns
                with pipeline_span("flush_pack"):
                    packed = imm.to_packed()
                frontier = Frontier(op_id_min=last_op, op_id_max=last_op,
                                    history_cutoff=0)
                from yugabyte_tpu.storage.sst import write_sst_from_packed
                def take_slab(job):
                    nonlocal slab
                    with pipeline_span("flush_slab_build"):
                        slab = job.export_slab()
                    flush_slab_metrics()["native"].increment()
                with pipeline_span("flush_sst_write"):
                    props = write_sst_from_packed(
                        path, *packed, frontier=frontier,
                        block_entries=self.opts.block_entries,
                        run_cache=self._run_cache, file_id=fid,
                        on_job=take_slab
                        if self._device_cache is not None else None)
                n_flushed = len(packed[1]) - 1
            else:
                with pipeline_span("flush_slab_build"):
                    slab = imm.to_slab()
                flush_slab_metrics()[imm.slab_source].increment()
                ht = slab.ht_hi.astype("u8") << 32 | slab.ht_lo
                frontier = Frontier(op_id_min=last_op, op_id_max=last_op,
                                    ht_min=int(ht.min()) if slab.n else 0,
                                    ht_max=int(ht.max()) if slab.n else 0,
                                    history_cutoff=0)
                with pipeline_span("flush_sst_write"):
                    props = SSTWriter(path, block_entries=self.opts.block_entries).write(slab, frontier)
                n_flushed = slab.n
            from yugabyte_tpu.utils import sync_point
            sync_point.hit("db.flush:before_manifest")
            if self._device_cache is not None and slab is not None:
                with pipeline_span("flush_device_stage"):
                    self._device_cache.stage(fid, slab)  # write-through to HBM
            with pipeline_span("flush_install"), self._lock:
                self.versions.add_file(fid, path, props)
                self.versions.set_flushed_frontier(frontier)
                self._readers[fid] = SSTReader(path, self.opts.block_cache)
                self._imm = None
                self._rset = None  # native snapshot is stale
                self._rset_gen += 1
                self._mem_run_cache = None
            self.compaction_stats.record_flush(
                props.data_size + props.base_size, n_flushed)
            TRACE("flushed %d entries to %s", n_flushed, path)
        except BaseException as e:
            with self._lock:
                # restore un-flushed entries into the live memtable
                for k, v in imm.iter_from():
                    prefix, dht = split_key_and_ht(k)
                    self.mem.add(prefix, dht, v)
                self._imm = None
                # partial outputs of the aborted flush — but never a file
                # the version set already adopted (an error between the
                # manifest add and the frontier edit leaves it live)
                installed = fid is not None and fid in self.versions.files
            if path is not None and not installed:
                _delete_sst_files(path)
                if self._device_cache is not None and fid is not None:
                    self._device_cache.drop(fid)
            from yugabyte_tpu.utils.status import StatusError
            if isinstance(e, (OSError, StatusError)):
                # Contained: version set untouched (or still consistent),
                # no rows lost (memtable restored). Park read-only; the
                # maintenance manager retries with capped backoff.
                self._set_background_error("flush", e)
                return None
            raise
        finally:
            if path is not None:
                with self._lock:
                    self._writing.discard(path)
        if self.opts.auto_compact:
            self.maybe_schedule_compaction()
        return fid

    # ------------------------------------------------------------ compaction
    def maybe_schedule_compaction(self) -> bool:
        """(ref: DBImpl::MaybeScheduleFlushOrCompaction db_impl.cc:2127)."""
        with self._lock:
            if self._compacting or self._closed or \
                    self._bg_error is not None:
                return False
            pick = compaction_mod.pick_universal(self.versions.live_files())
            if pick is None:
                return False
            self._compacting = True
            for fm in pick.inputs:
                fm.being_compacted = True
        if self.opts.compaction_pool is not None:
            self.opts.compaction_pool.submit(lambda: self._run_compaction(pick),
                                             priority=0)
        else:
            self._run_compaction(pick)
        return True

    def _run_compaction(self, pick) -> None:
        try:
            self._run_compaction_inner(pick)
        except BaseException as e:
            from yugabyte_tpu.utils.cancellation import OperationCancelled
            from yugabyte_tpu.utils.status import StatusError
            if isinstance(e, OperationCancelled):
                # CLEAN abort (shutdown / tablet-FAILED): nothing was
                # installed and the job unwound its own partials; sweep
                # any stragglers but do NOT park the DB — this is not a
                # storage fault.
                with self._lock:
                    self._sweep_orphan_outputs_unlocked()
                TRACE("db %s: compaction aborted: %s", self.db_dir, e)
                return
            if not isinstance(e, (OSError, StatusError)):
                raise
            # Contained like a failed flush: the version set still points
            # at the inputs (nothing installed), partial outputs are swept,
            # and the DB parks read-only for the backoff retry. A
            # CORRUPTION status (corrupt input block tripped the decode —
            # Python or native shell) parks STICKY instead: retrying into
            # the same bad bytes can never succeed; the replica must be
            # rebuilt from a healthy peer.
            from yugabyte_tpu.utils.status import Code
            with self._lock:
                self._sweep_orphan_outputs_unlocked()
            self._set_background_error(
                "compaction", e,
                corruption=isinstance(e, StatusError)
                and e.status.code == Code.CORRUPTION)

    def _run_compaction_inner(self, pick) -> None:
        from yugabyte_tpu.utils.metrics import pipeline_span
        try:
            # the job's root span: `job` is its wall, `job_other` what no
            # stage span under it names (utils/metrics._PIPELINE_STAGES)
            with pipeline_span("job", inclusive="job", stage="job_other"):
                self._compact_and_install(pick)
        finally:
            with self._lock:
                self._compacting = False
                # On failure the inputs stay live: make them pickable again.
                for fm in pick.inputs:
                    fm.being_compacted = False
                # Reap deferred readers whose pinning scans finished while
                # this compaction ran (scans also purge on exit; this covers
                # the case where no further scan ever happens).
                self._purge_obsolete_unlocked()
        # cascade if still over trigger
        if self.opts.auto_compact:
            self.maybe_schedule_compaction()

    def _compact_and_install(self, pick) -> None:
        """The job body under the root span: dispatch, then install the
        outputs into the version set, open their readers and delete the
        inputs."""
        from yugabyte_tpu.utils.metrics import pipeline_span
        inputs = [self._readers[fm.file_id] for fm in pick.inputs]
        cutoff = self.opts.retention_policy()
        result = self._dispatch_compaction(pick, inputs, cutoff)
        from yugabyte_tpu.utils import sync_point
        sync_point.hit("db.compaction:before_install")
        with self._lock:
            removed = [fm.file_id for fm in pick.inputs]
            with pipeline_span("version_install"):
                self.versions.install_compaction(
                    removed,
                    [(fid, p, props) for fid, p, props in result.outputs])
            self._rset = None  # native snapshot is stale; removed
            self._rset_gen += 1
            # native readers are dropped from the dict below and freed
            # by refcount once in-flight scans release their snapshot
            with pipeline_span("reader_open"):
                for fid, path, props in result.outputs:
                    self._readers[fid] = SSTReader(path,
                                                   self.opts.block_cache)
            with pipeline_span("input_delete"):
                for fid in removed:
                    self._native_readers.pop(fid, None)
                    r = self._readers.pop(fid, None)
                    if r:
                        if self._pins.get(fid):
                            # an active scan still reads this SST: defer
                            # the close+delete until its pin drops
                            self._obsolete[fid] = r
                        else:
                            r.close()
                            _delete_sst_files(r.base_path)
                    if self._device_cache is not None:
                        self._device_cache.drop(fid)
                    if self._run_cache is not None:
                        self._run_cache.drop(fid)
        self.compaction_stats.record_compaction(
            bytes_read=sum(fm.total_size for fm in pick.inputs),
            bytes_written=sum(p.data_size + p.base_size
                              for _fid, _path, p in result.outputs),
            files_in=len(pick.inputs), files_out=len(result.outputs),
            rows_in=result.rows_in, rows_out=result.rows_out,
            tombstones_written=result.tombstones_written)
        TRACE("compaction: %d files -> %d rows (%d in)",
              len(pick.inputs), result.rows_out, result.rows_in)

    def _dispatch_compaction(self, pick, inputs, cutoff):
        """Route one picked compaction: through the mesh-sharded
        multi-tablet pool when this server has one AND the job would take
        the device path anyway (the same measured offload decision the
        inline path makes — the pool is a scheduling win, never a routing
        override), else the inline run_compaction_job."""
        pool = self.opts.mesh_pool
        if pool is not None and self.opts.device not in (None, "native"):
            est = sum(r.props.n_entries for r in inputs)
            has_deep = any(r.props.has_deep for r in inputs)
            board = self.opts.offload_policy
            cached = bool(self._device_cache is not None and all(
                self._device_cache.contains(fm.file_id)
                for fm in pick.inputs))
            use = True
            if board is not None:
                from yugabyte_tpu.ops.run_merge import packed_run_ns
                from yugabyte_tpu.storage.offload_policy import bucket_key
                qkey = bucket_key(packed_run_ns(
                    [r.props.n_entries for r in inputs
                     if r.props.n_entries]))
                # probe=False: this thread only SUBMITS — the pool
                # worker that dispatches claims any probe slot itself
                use = board.use_device("run_merge_fused", qkey,
                                       est_rows=est, cached=cached,
                                       probe=False)
            if not has_deep and use:
                handle = pool.submit_compaction(
                    self.db_dir, inputs=inputs, out_dir=self.db_dir,
                    new_file_id=self.versions.new_file_id,
                    history_cutoff_ht=cutoff, is_major=pick.is_major,
                    block_entries=self.opts.block_entries,
                    input_ids=[fm.file_id for fm in pick.inputs],
                    device_cache=self._device_cache, est_rows=est,
                    cancel=self._cancel)
                from yugabyte_tpu.utils.metrics import pipeline_span
                with pipeline_span("pool_wait"):
                    # the pool's worker runs the job on its own thread
                    return handle.result()
        return compaction_mod.run_compaction_job(
            inputs, self.db_dir, self.versions.new_file_id, cutoff,
            pick.is_major, device=self.opts.device,
            block_entries=self.opts.block_entries,
            device_cache=self._device_cache,
            input_ids=[fm.file_id for fm in pick.inputs],
            mesh=self.opts.mesh,
            offload_policy=self.opts.offload_policy,
            run_cache=self._run_cache,
            cancel=self._cancel)

    def compact_all(self) -> None:
        """Force a full (major) compaction of all live files."""
        with self._lock:
            files = [f for f in self.versions.live_files() if not f.being_compacted]
            if len(files) < 2:
                return
            for fm in files:
                fm.being_compacted = True
            pick = compaction_mod.CompactionPick(files, is_major=True)
            self._compacting = True
        self._run_compaction(pick)

    def _purge_obsolete_unlocked(self) -> None:
        for fid in [f for f in self._obsolete if not self._pins.get(f)]:
            r = self._obsolete.pop(fid)
            r.close()
            _delete_sst_files(r.base_path)

    # ------------------------------------------------------------------ scrub
    def scrub(self, limiter=None, cancel=None) -> dict:
        """At-rest integrity scrub: deep-verify every live SST (block
        CRCs, footer, index/bloom consistency — storage/integrity.py) at
        a throttled byte rate. Files are PINNED while verified so a
        concurrent compaction cannot delete them mid-read. A corrupt
        file is quarantined (renamed ``*.corrupt``) and the DB parks
        with a STICKY Corruption background error — the owner tablet
        goes FAILED (``failed_corrupt``) and must be rebuilt from a
        healthy peer; in-place retry is refused."""
        from yugabyte_tpu.storage import integrity
        from yugabyte_tpu.utils.status import Status
        with self._lock:
            targets = [(fid, r.base_path)
                       for fid, r in self._readers.items()]
            for fid, _ in targets:
                self._pins[fid] = self._pins.get(fid, 0) + 1
        report = {"files": 0, "blocks": 0, "entries": 0, "bytes": 0,
                  "corrupt": []}
        try:
            for fid, base_path in targets:
                if cancel is not None:
                    cancel.check()
                rep = integrity.verify_sst(base_path, limiter=limiter,
                                           cancel=cancel)
                report["files"] += 1
                report["blocks"] += rep.n_blocks
                report["entries"] += rep.n_entries
                report["bytes"] += rep.bytes_verified
                if rep.errors:
                    report["corrupt"].append(
                        {"path": base_path, "errors": rep.errors[:4]})
                    integrity.quarantine_sst(base_path,
                                             reason=rep.errors[0])
                    self._set_background_error(
                        "scrub",
                        StatusError(Status.Corruption(
                            f"{base_path}: {rep.errors[0]}")),
                        corruption=True)
        finally:
            with self._lock:
                for fid, _ in targets:
                    self._pins[fid] -= 1
                    if not self._pins[fid]:
                        del self._pins[fid]
                self._purge_obsolete_unlocked()
        integrity.record_scrub(report["files"], report["blocks"],
                               report["bytes"], len(report["corrupt"]))
        return report

    # ------------------------------------------------------------ checkpoint
    def checkpoint(self, out_dir: str) -> None:
        """Hard-link snapshot (ref: utilities/checkpoint/checkpoint.cc:56)."""
        os.makedirs(out_dir, exist_ok=True)
        with self._lock:
            for fm in self.versions.live_files():
                for p in (fm.path, data_file_name(fm.path)):
                    os.link(p, os.path.join(out_dir, os.path.basename(p)))
            import shutil
            if os.path.exists(self.versions.manifest_path):
                shutil.copy(self.versions.manifest_path,
                            os.path.join(out_dir, "MANIFEST"))

    def close(self) -> None:
        # trip the cancellation seam FIRST: an in-flight pipelined
        # compaction aborts at its next stage boundary instead of writing
        # into a directory whose readers we are about to close
        self._cancel.cancel("db closed")
        with self._lock:
            self._closed = True
            # native handles free via refcount (in-flight scans may still
            # hold the snapshot)
            self._native_readers = {}
            self._rset = None
            self._rset_gen += 1
            self._mem_run_cache = None
            self._purge_obsolete_unlocked()
            for r in self._obsolete.values():
                r.close()  # still pinned: close the handle, leave the files
            self._obsolete.clear()
            for r in self._readers.values():
                r.close()
            self._readers.clear()
            if self._device_cache is not None and \
                    hasattr(self._device_cache, "drop_all"):
                self._device_cache.drop_all()  # free this DB's HBM residency
            if self._run_cache is not None:
                self._run_cache.drop_all()

    @property
    def n_live_files(self) -> int:
        return len(self.versions.files)

    # (appended at the class's end: a line that moves above the compaction
    # methods re-keys the Pallas merge's compile cache, PERF.md section 7)
    def _pushdown_sources_spanned(self, spec):
        """`_pushdown_sources` under the serve path's `stage_lookup` span,
        with a slab or value words staged inside the request counted
        (the grouped aggregate's stage-miss counter)."""
        from yugabyte_tpu.docdb.scan_spec import GroupAggSpec
        from yugabyte_tpu.utils import latency
        with latency.sub_span("stage_lookup"):
            if isinstance(spec, GroupAggSpec) \
                    and self._device_cache is not None:
                with self._lock:
                    fids = list(self._readers)
                missing = 0
                for fid in fids:
                    st = self._device_cache.peek(fid)
                    missing += st is None or st.vals_dev is None
                if missing:
                    from yugabyte_tpu.ops.scan_group import group_metrics
                    group_metrics()["stage_miss"].increment(missing)
            return self._pushdown_sources(spec)



def _dedup_ikeys(stream: Iterator[Tuple[bytes, bytes]]
                 ) -> Iterator[Tuple[bytes, bytes]]:
    """Suppress adjacent duplicate internal keys: a flush racing the
    memtable snapshot can surface one row from both the memtable and the
    fresh SST; legitimate data never repeats a full internal key."""
    prev = None
    for kv in stream:
        if kv[0] == prev:
            continue
        prev = kv[0]
        yield kv


def _sst_iter_from(reader: SSTReader, seek: bytes) -> Iterator[Tuple[bytes, bytes]]:
    """Merged-stream source over one SST from `seek` (internal-key order).

    The first block is entered by BINARY SEARCH on the reconstructed
    internal keys — the old linear skip from the block start cost ~half a
    block (~2K entry decodes) per point read and dominated YCSB-C wall
    time (ref: the reference's block restart-point binary seek,
    rocksdb/table/block.cc Seek)."""
    prefix_seek, _ = split_key_and_ht(seek)
    b = reader.seek_block(prefix_seek if prefix_seek else seek)
    # Search phase: binary-search each block until one holds an entry
    # >= seek. The block index is on key PREFIXES while seek carries the
    # HT suffix, so a version chain spilling across blocks can leave the
    # first (or several) candidate blocks entirely below seek — stopping
    # the search after one block would emit too-new versions unfiltered.
    while b < reader.n_blocks:
        slab = reader.read_block(b)
        raw = slab.key_words.astype(">u4").tobytes()
        stride = slab.width_words * 4

        def ikey(i: int) -> bytes:
            kp = raw[i * stride: i * stride + int(slab.key_len[i])]
            return make_internal_key(kp, slab.doc_ht(i))

        lo, hi = 0, slab.n
        while lo < hi:
            mid = (lo + hi) // 2
            if ikey(mid) < seek:
                lo = mid + 1
            else:
                hi = mid
        b += 1
        if lo < slab.n:
            for i in range(lo, slab.n):
                yield ikey(i), slab.values[int(slab.value_idx[i])]
            break
        # whole block < seek: search the next one
    # Stream phase: every later block is entirely >= seek — reuse the
    # reader's own decode loop rather than duplicating it here.
    for kp, dht, value, _fl in reader.iter_entries(b):
        yield make_internal_key(kp, dht), value


def _delete_sst_files(base_path: str) -> None:
    for p in (base_path, data_file_name(base_path)):
        try:
            os.remove(p)
        except FileNotFoundError:  # yblint: contained(idempotent delete — both halves may already be gone)
            pass
