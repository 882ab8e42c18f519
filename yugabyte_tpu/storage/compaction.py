"""Compaction: universal picker + the TPU-offloaded compaction job.

Picker parity with the reference's universal compaction (ref:
src/yb/rocksdb/db/compaction_picker.cc UniversalCompactionPicker; YB default
for DocDB, docdb/docdb_rocksdb_util.cc:637-658): sorted runs newest-first,
merge adjacent runs chosen by size-ratio / run-count triggers; a full
compaction (all runs) is "major" and may drop tombstones.

Job parity with CompactionJob::Run (ref: rocksdb/db/compaction_job.cc:442):
but the three hot loops (merge / dedup+filter / encode) become:
    read blocks -> concat slabs -> ops.merge_and_gc_device -> write SSTs
The merge+GC runs on TPU (or any JAX backend) and the keep/perm decisions are
byte-identical across backends, so the CPU fallback produces identical SSTs.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from yugabyte_tpu.ops.merge_gc import GCParams, merge_and_gc_device
from yugabyte_tpu.ops.slabs import KVSlab, concat_slabs
from yugabyte_tpu.storage.sst import (Frontier, SSTProps, SSTReader,
                                      SSTWriter, sst_compression_enabled)
from yugabyte_tpu.storage.version_set import FileMeta
from yugabyte_tpu.docdb.value import Value
from yugabyte_tpu.utils import flags

flags.define_flag("universal_compaction_max_merge_width", 16,
                  "cap on runs merged in one universal pick "
                  "(ref max_merge_width, docdb_rocksdb_util.cc)")
flags.define_flag("universal_compaction_always_include_size_bytes",
                  64 << 10,
                  "runs at or below this size join a pick regardless of "
                  "the size-ratio rule (ref "
                  "universal_compaction_always_include_size_threshold)")
flags.define_flag("universal_compaction_min_merge_width", 4,
                  "min sorted runs to trigger a compaction")
flags.define_flag("universal_compaction_size_ratio_pct", 20,
                  "merge run into candidate set while its size <= (1+ratio) * accumulated")
flags.define_flag("compaction_max_output_entries_per_sst", 2_000_000,
                  "split compaction output files at this row count")
flags.define_flag("compaction_rate_bytes_per_sec", 0,
                  "token-bucket cap on compaction output bytes/sec; "
                  "0 = unlimited (ref rocksdb/util/rate_limiter.cc)")
flags.define_flag("distributed_compaction_min_rows", 1 << 20,
                  "jobs at or above this many input rows fan their "
                  "subcompactions across the device mesh when one is "
                  "available (ref: subcompaction sizing, "
                  "compaction_job.cc:330 GenSubcompactionBoundaries)")

_rate_limiter = None       # guarded-by: _rate_limiter_lock
_rate_limiter_rate = 0     # guarded-by: _rate_limiter_lock
_rate_limiter_lock = __import__("threading").Lock()


def compaction_rate_limiter():
    """Process-wide limiter paced by the flag (rebuilt when it changes);
    one shared bucket across all compaction threads."""
    global _rate_limiter, _rate_limiter_rate
    rate = flags.get_flag("compaction_rate_bytes_per_sec")
    if rate <= 0:
        return None
    with _rate_limiter_lock:
        if _rate_limiter is None or _rate_limiter_rate != rate:
            from yugabyte_tpu.utils.rate_limiter import RateLimiter
            _rate_limiter = RateLimiter(rate)
            _rate_limiter_rate = rate
        return _rate_limiter


def _wants_distributed(mesh, n_rows: int) -> bool:
    """The single authority for the distributed-compaction gate: a >1-
    device mesh and a job at or above the size threshold. Written once so
    the offload-policy gate, the combined-path gate and the dispatch gate
    cannot drift apart."""
    return (mesh is not None
            and getattr(mesh, "devices", np.empty(0)).size > 1
            and n_rows >= flags.get_flag("distributed_compaction_min_rows"))


def filter_expired_inputs(inputs: Sequence[SSTReader],
                          history_cutoff_ht: int, is_major: bool,
                          retain_deletes: bool):
    """Whole-file TTL drop (ref: docdb/compaction_file_filter.h:60
    ExpirationFilter): an input SST whose every entry carries a TTL that
    expired before the history cutoff contributes nothing to the output —
    skip reading it entirely. Only at major compactions without
    retain-deletes, where expired values are eligible to vanish (same
    gate as the per-entry filter's drop path).

    A fully expired file is droppable only if its KEY RANGE is disjoint
    from every other input's: an expired entry still shadows older
    versions of its key in other files, and the per-entry filter drops
    both — dropping just the file would resurrect the shadowed version
    (the reference gates file expiration on TTL-uniform tables for the
    same reason).

    Returns (kept_inputs, dropped_inputs)."""
    if not is_major or retain_deletes:
        return list(inputs), []
    cutoff_phys_us = history_cutoff_ht >> 12
    inputs = list(inputs)
    kept, dropped = [], []
    for i, r in enumerate(inputs):
        exp = getattr(r.props, "max_expire_us", 0)
        if not exp or exp > cutoff_phys_us:
            kept.append(r)
            continue
        overlaps = any(
            o is not r and o.props.n_entries
            and not (r.props.last_key < o.props.first_key
                     or o.props.last_key < r.props.first_key)
            for o in inputs)
        if overlaps:
            kept.append(r)   # shadowing possible: take the per-entry path
        else:
            dropped.append(r)
    return kept, dropped


@dataclass
class CompactionPick:
    inputs: List[FileMeta]
    is_major: bool


def pick_universal(files: List[FileMeta]) -> Optional[CompactionPick]:
    """files must be newest-first. Returns runs to merge, or None."""
    min_width = flags.get_flag("universal_compaction_min_merge_width")
    max_width = flags.get_flag("universal_compaction_max_merge_width")
    ratio = flags.get_flag("universal_compaction_size_ratio_pct")
    always_sz = flags.get_flag(
        "universal_compaction_always_include_size_bytes")
    candidates = [f for f in files if not f.being_compacted]
    if len(candidates) < min_width:
        return None
    # Accumulate newest-first while sizes stay within ratio (universal rule:
    # stop at the first run that dwarfs the accumulated candidates — never
    # force-include it, or every few flushes rewrites the whole base run).
    # Files under the always-include threshold join regardless of ratio
    # (ref always_include_size_threshold, docdb_rocksdb_util.cc).
    acc = candidates[0].total_size
    picked = [candidates[0]]
    for f in candidates[1:]:
        if len(picked) >= max_width:
            break
        if (f.total_size <= always_sz
                or f.total_size * 100 <= (100 + ratio) * acc):
            picked.append(f)
            acc += f.total_size
        else:
            break
    if len(picked) < min_width:
        return None
    is_major = len(picked) == len(files)  # all live runs -> bottommost
    return CompactionPick(picked, is_major)


@dataclass
class CompactionResult:
    outputs: List[Tuple[int, str, SSTProps]]  # (file_id, base_path, props)
    rows_in: int
    rows_out: int
    # survivors rewritten as tombstones (TTL expiry at a non-major
    # compaction); 0 where the path cannot cheaply count them (pure
    # native shell) — /compactionz reports it as a lower bound
    tombstones_written: int = 0


def _route_job(all_inputs, input_ids, device, device_cache, mesh,
               offload_policy, _no_combined: bool):
    """run_compaction_job's routing prelude: (device, board_key,
    combined) — `device` turned "native" where the health board says so,
    `board_key` = (board, family, qkey) when the board gated native,
    `combined` = "dist" / "device_native" when a device+native job takes
    this one."""
    board_key = None
    if (offload_policy is not None and device is not None
            and device != "native" and not _no_combined):
        # Measured device-vs-native routing (VERDICT r3 #2): auto-offload
        # only where the live bucket-health board says the device path
        # wins — `offload_policy` IS the BucketHealthBoard
        # (storage/bucket_health.py). Distributed (mesh) jobs are gated
        # on their own (n_shards, capacity) key below.
        est_rows = sum(r.props.n_entries for r in all_inputs)
        cached = bool(device_cache is not None and input_ids is not None
                      and all(device_cache.contains(fid)
                              for fid in input_ids))
        if not _wants_distributed(mesh, est_rows):
            from yugabyte_tpu.ops import run_merge
            from yugabyte_tpu.storage import offload_policy as _pol
            qkey = _pol.bucket_key(run_merge.packed_run_ns(
                [r.props.n_entries for r in all_inputs if
                 r.props.n_entries]))
            # probe=False: this is a routing DECISION — the probe slot
            # for a DEGRADED bucket is claimed at the device-native
            # path's own allow_device(), immediately before dispatch,
            # so a fall-through (deep inputs, radix override) can never
            # wedge a claimed probe with no recorder behind it
            if not offload_policy.use_device("run_merge_fused", qkey,
                                             est_rows=est_rows,
                                             cached=cached, probe=False):
                device = "native"
                # time the native completion so the board's native EWMA
                # is live measurement, not a calibration-file fossil
                board_key = (offload_policy, "run_merge_fused", qkey)
    if device is not None and device != "native" and not _no_combined:
        # The flagship production path: device merge+GC decisions + the
        # C++ byte shell + device-side write-through (the configuration
        # bench.py measures). Gated BEFORE the expiry filtering below —
        # the combined path re-runs identical filtering itself. Taken
        # when the native shell can run the bytes (unencrypted), every
        # input is depth-2 (the SST props record deep-ness so no decode
        # is needed to decide), and the radix debug override is off; the
        # combined path falls back here for skewed run layouts —
        # _no_combined breaks that recursion.
        from yugabyte_tpu.storage import native_engine
        from yugabyte_tpu.utils.env import get_env
        force_radix = os.environ.get("YBTPU_FORCE_RADIX", "").lower() \
            not in ("", "0", "false")
        wants_dist = _wants_distributed(
            mesh, sum(r.props.n_entries for r in all_inputs))
        if (native_engine.available() and not get_env().encrypted
                and not force_radix
                and not any(r.props.has_deep for r in all_inputs)):
            return device, board_key, "dist" if wants_dist \
                else "device_native"
    return device, board_key, None


def run_compaction_job(inputs: Sequence[SSTReader], out_dir: str,
                       new_file_id, history_cutoff_ht: int, is_major: bool,
                       retain_deletes: bool = False, device=None,
                       block_entries: Optional[int] = None, device_cache=None,
                       input_ids: Optional[Sequence[int]] = None,
                       mesh=None, offload_policy=None, run_cache=None,
                       _no_combined: bool = False,
                       cancel=None) -> CompactionResult:
    """The compaction job (ref: CompactionJob::Run, compaction_job.cc:442).

    new_file_id: callable returning the next file id (VersionSet.new_file_id).
    device_cache + input_ids: when set, input key columns come from (or are
    written through to) the HBM-resident slab cache — host->device upload is
    skipped for cache hits; values always stream from disk on the host side.
    mesh: a jax.sharding.Mesh over >1 device — jobs at or above
    distributed_compaction_min_rows fan their subcompactions across it
    (parallel/dist_compact.py), the mesh analog of the reference's
    subcompaction threads (compaction_job.cc:456-468).
    cancel: a utils/cancellation.CancellationToken — DB shutdown or a
    tablet-FAILED transition aborts the job at the next stage boundary
    (OperationCancelled; partial outputs are cleaned up, nothing is
    installed).
    """
    from yugabyte_tpu.utils.metrics import pipeline_span
    if cancel is not None:
        cancel.check()
    all_inputs = list(inputs)
    orig_input_ids = list(input_ids) if input_ids is not None else None
    with pipeline_span("routing"):
        device, board_key, combined = _route_job(
            all_inputs, input_ids, device, device_cache, mesh,
            offload_policy, _no_combined)
    if combined is not None:
        if combined == "dist":
            # mesh-sized job: distributed decisions + the SAME native
            # byte shell / streaming writer as the single-device path,
            # so sharded outputs stay byte-identical
            return run_compaction_job_dist_native(
                all_inputs, out_dir, new_file_id, history_cutoff_ht,
                is_major, retain_deletes, device=device,
                block_entries=block_entries, device_cache=device_cache,
                input_ids=orig_input_ids, mesh=mesh, cancel=cancel)
        return run_compaction_job_device_native(
            all_inputs, out_dir, new_file_id, history_cutoff_ht,
            is_major, retain_deletes, device=device,
            block_entries=block_entries, device_cache=device_cache,
            input_ids=orig_input_ids, run_cache=run_cache,
            cancel=cancel)
    inputs, dropped = filter_expired_inputs(
        inputs, history_cutoff_ht, is_major, retain_deletes)
    dropped_rows = sum(r.props.n_entries for r in dropped)
    if input_ids is not None:
        # keep the cache-id pairing aligned with the FILTERED input list —
        # a whole-file drop earlier in the list must not shift every
        # later reader onto its neighbor's staged columns
        id_of = {id(r): fid for r, fid in zip(all_inputs, input_ids)}
        input_ids = [id_of[id(r)] for r in inputs]
    if not inputs:
        return CompactionResult([], dropped_rows, 0)
    if device == "native":
        from yugabyte_tpu.storage import native_engine
        from yugabyte_tpu.utils.env import get_env
        if native_engine.available() and not get_env().encrypted:
            # the C++ shell reads/writes raw files; under encryption at
            # rest the Python shell (which goes through the Env) runs
            import time as _time
            t0 = _time.monotonic()
            result = _run_native_job(inputs, out_dir, new_file_id,
                                     history_cutoff_ht, is_major,
                                     retain_deletes, block_entries,
                                     frontier_inputs=all_inputs,
                                     cancel=cancel)
            result.rows_in += dropped_rows
            if board_key is not None:
                board, family, qkey = board_key
                board.record_native(family, qkey, result.rows_in,
                                    _time.monotonic() - t0)
            return result
    slabs = [r.read_all() for r in inputs]
    keep_idx = [i for i, s in enumerate(slabs) if s.n]
    slabs = [slabs[i] for i in keep_idx]
    if not slabs:
        return CompactionResult([], 0, 0)
    merged = concat_slabs(slabs)
    params = GCParams(history_cutoff_ht, is_major, retain_deletes)
    from yugabyte_tpu.ops.slabs import FLAG_DEEP
    if device != "native" and bool((merged.flags & FLAG_DEEP).any()):
        # Documents deeper than row+column: the fused kernel implements
        # only depth-2 overwrite truncation, so route to the native path,
        # which carries the full per-component overwrite STACK (ref:
        # docdb_compaction_filter.cc:104-123).
        device = "native"
    surv = tomb_flags = None
    if device != "native" and _wants_distributed(mesh, merged.n):
        # Large job + multi-device mesh: fan the subcompactions across the
        # devices (parallel/dist_compact.py) — the mesh analog of the
        # reference's per-thread subcompactions. Decisions are identical
        # to the single-device kernel (differential-tested); outputs come
        # back globally range-partitioned, so survivor order matches.
        from yugabyte_tpu.parallel.dist_compact import distributed_compact
        _cols, keep_d, mk_d, src_idx = distributed_compact(
            merged, params, mesh)
        surv = src_idx[keep_d]
        tomb_flags = mk_d[keep_d]
    elif device == "native":
        # No JAX device available (e.g. TPU init failed at server start):
        # the native C++ baseline implements identical merge+GC semantics
        # (differential-tested vs the kernel) on the host.
        from yugabyte_tpu.storage.cpu_baseline import compact_cpu_baseline
        offsets = np.concatenate(
            ([0], np.cumsum([s.n for s in slabs]))).tolist()
        perm, keep, make_tomb = compact_cpu_baseline(
            merged, offsets, history_cutoff_ht, is_major, retain_deletes)
    else:
        # Run-aware device path (ops/run_merge.py): the inputs are sorted
        # runs, so the kernel merges them with a bitonic network instead of
        # re-sorting, and ships back packed decisions instead of a full perm.
        from yugabyte_tpu.ops import run_merge
        skewed = (run_merge.run_layout_inflation([s.n for s in slabs]) > 2.0
                  or os.environ.get("YBTPU_FORCE_RADIX", "").lower()
                  not in ("", "0", "false"))
        if device_cache is not None and input_ids is not None:
            ids = [input_ids[i] for i in keep_idx]
            staged_list = []
            for fid, slab in zip(ids, slabs):
                st = device_cache.get(fid)
                if st is None:
                    st = device_cache.stage(fid, slab)
                staged_list.append(st)
            if skewed:
                # one huge run + tiny ones: padding every run to the largest
                # bucket would inflate HBM/work ~K x; the radix re-sort over
                # a single bucket is cheaper there
                from yugabyte_tpu.storage.device_cache import concat_staged
                perm, keep, make_tomb = merge_and_gc_device(
                    merged, params, device=device,
                    staged=concat_staged(staged_list))
            else:
                staged_runs = run_merge.stage_runs_from_staged(staged_list)
                perm, keep, make_tomb = run_merge.merge_and_gc_runs(
                    slabs, params, device=device, staged=staged_runs)
        else:
            # merge_and_gc_runs falls back to the radix kernel itself when
            # the run layout would inflate
            perm, keep, make_tomb = run_merge.merge_and_gc_runs(
                slabs, params, device=device)
    if surv is None:
        surv = perm[keep]                  # input indices, merged order
        tomb_flags = make_tomb[keep]
    rows_out = int(surv.shape[0])

    # Frontier for outputs: union of input frontiers + this cutoff
    # (ref: compaction_job.cc:683-692, 929-931) — INCLUDING whole-file-
    # dropped inputs, whose op-id progress must not regress.
    fr = _merge_frontiers([r.props.frontier for r in all_inputs],
                          history_cutoff_ht)

    limiter = compaction_rate_limiter()
    outputs: List[Tuple[int, str, SSTProps]] = []
    max_rows = flags.get_flag("compaction_max_output_entries_per_sst")
    tombstone_value = Value.tombstone().encode()
    out_level = 0
    if device_cache is not None:
        in_levels = [device_cache.level_of(fid)
                     for fid in (input_ids or []) if fid is not None]
        out_level = 1 + max([lv for lv in in_levels if lv is not None],
                            default=0)
    for start in range(0, rows_out, max_rows):
        if cancel is not None:
            cancel.check()
        end = min(start + max_rows, rows_out)
        sel = surv[start:end]
        out_slab = _gather_slab(merged, sel, tomb_flags[start:end], tombstone_value)
        fid = new_file_id()
        base_path = os.path.join(out_dir, f"{fid:06d}.sst")
        # fit_lindex=False: python compaction outputs stay byte-identical
        # to the native writer's (which cannot fit); compaction-output
        # models come from the device-native span hook, where the sorted
        # keys are in HBM for free
        props = SSTWriter(base_path, block_entries=block_entries,
                          fit_lindex=False).write(out_slab, fr)
        outputs.append((fid, base_path, props))
        if limiter is not None and end < rows_out:
            # pace between files; no debt-sleep after the last one (it
            # would only delay install while writing nothing)
            limiter.acquire(props.data_size + props.base_size)
        if device_cache is not None:
            # write-through for the next pick, one level below the
            # deepest input (multi-level eviction priority)
            device_cache.stage(fid, out_slab, level=out_level)
    return CompactionResult(outputs, merged.n + dropped_rows, rows_out,
                            tombstones_written=int(
                                np.count_nonzero(tomb_flags)))


class _StreamingNativeWriter:
    """Stage C of the compaction pipeline: write output SSTs from survivor
    spans AS THE SPANS FILL, instead of after the whole decision download.

    feed(n_available) is called each time a pipeline chunk's survivors
    land in the shell (NativeCompactionJob.append_survivors) — it writes
    every output file whose full [start, start+max_rows) span is already
    covered, so the native block encode + file I/O of file i overlaps the
    device compute / D2H of chunks i+1... finish() writes the tail.

    File splits, pacing, tombstone and base-assembly rules are EXACTLY
    those of _write_native_outputs (which delegates here), so pipelined
    and sequential jobs produce byte-identical files over identical
    ranges. A full span is only written from feed() while strictly more
    survivors are known to exist — the final span (full or partial) goes
    through finish(), which never pace-sleeps after the last file."""

    def __init__(self, job, out_dir: str, new_file_id, fr,
                 block_entries: Optional[int], has_deep: bool = False,
                 cancel=None, on_span=None, lindex_for_span=None):
        self._job = job
        self._out_dir = out_dir
        self._new_file_id = new_file_id
        self._fr = fr
        self._has_deep = has_deep
        self._cancel = cancel
        # called as (fid, base_path, start, end) after each span's SST
        # exists on disk — the device write-through installer hooks here
        # so cache entries land under the output ids AS the spans
        # complete, not after the whole job
        self._on_span = on_span
        # optional (start, end) -> Optional[lindex dict] hook, called
        # BEFORE the span's base file is assembled: the device-native
        # path fits the learned per-SST index over the survivor span's
        # staged columns while they are still in HBM (for free — the
        # sorted keys are already there; storage/learned_index.py)
        self._lindex_for_span = lindex_for_span
        self._block_entries = (block_entries if block_entries is not None
                               else flags.get_flag("sst_block_entries"))
        self._max_rows = flags.get_flag(
            "compaction_max_output_entries_per_sst")
        self._limiter = compaction_rate_limiter()
        self._tombstone_value = Value.tombstone().encode()
        self._next_start = 0
        self.outputs: List[Tuple[int, str, SSTProps]] = []
        self.ranges: List[Tuple[int, int]] = []

    def _write_span(self, start: int, end: int, more_coming: bool) -> None:
        from yugabyte_tpu.storage.sst import data_file_name, write_base_file
        from yugabyte_tpu.utils.metrics import pipeline_span
        if self._cancel is not None:
            # file-split boundary: the clean abort point of stage C —
            # already-written files are swept by the caller's unwind
            self._cancel.check()
        with pipeline_span("write"):
            fid = self._new_file_id()
            base_path = os.path.join(self._out_dir, f"{fid:06d}.sst")
            size, index, hashes, fk, lk = self._job.write_output(
                start, end, data_file_name(base_path), self._block_entries,
                compress=sst_compression_enabled(),
                tombstone_value=self._tombstone_value)
            # (the learned-index gather and fit are spans of their own
            # inside: `write` is the shell encode + file I/O around them)
            lindex = (self._lindex_for_span(start, end)
                      if self._lindex_for_span is not None else None)
            props = write_base_file(base_path, index, end - start, hashes,
                                    fk, lk, self._fr, size,
                                    has_deep=self._has_deep, lindex=lindex)
            self.outputs.append((fid, base_path, props))
            self.ranges.append((start, end))
        if self._on_span is not None:
            with pipeline_span("cache_install"):
                self._on_span(fid, base_path, start, end)
        if self._limiter is not None and more_coming:
            # pace between files; no debt-sleep after the last one (it
            # would only delay install while writing nothing)
            with pipeline_span("pace"):
                self._limiter.acquire(props.data_size + props.base_size)

    def feed(self, n_available: int) -> None:
        # strictly >: an exactly-full final span must come from finish()
        # (we cannot know here whether more survivors follow, and the
        # sequential path never paces after the last file)
        while n_available - self._next_start > self._max_rows:
            self._write_span(self._next_start,
                             self._next_start + self._max_rows,
                             more_coming=True)
            self._next_start += self._max_rows

    def finish(self, rows_out: int
               ) -> Tuple[List[Tuple[int, str, SSTProps]],
                          List[Tuple[int, int]]]:
        start = self._next_start
        while start < rows_out:
            end = min(start + self._max_rows, rows_out)
            self._write_span(start, end, more_coming=end < rows_out)
            start = end
        self._next_start = start
        return self.outputs, self.ranges


def _write_native_outputs(job, out_dir: str, new_file_id, fr,
                          block_entries: Optional[int],
                          has_deep: bool = False, cancel=None
                          ) -> Tuple[List[Tuple[int, str, SSTProps]],
                                     List[Tuple[int, int]]]:
    """Write the native job's survivors as (possibly split) output SSTs,
    pacing between files (shared by the pure-native and device+native
    paths — the pacing/tombstone/base-assembly rules live once in
    _StreamingNativeWriter; this is its everything-already-available
    form).

    Returns (outputs, ranges): ranges[i] is the [start, end) survivor span
    written to outputs[i] — the single authority for file splits (the
    device write-through gathers exactly these spans; re-deriving them
    from the flag would silently desync if the flag changes mid-job)."""
    writer = _StreamingNativeWriter(job, out_dir, new_file_id, fr,
                                    block_entries, has_deep=has_deep,
                                    cancel=cancel)
    return writer.finish(job.n_survivors)


def _run_native_job(inputs: Sequence[SSTReader], out_dir: str, new_file_id,
                    history_cutoff_ht: int, is_major: bool,
                    retain_deletes: bool, block_entries: Optional[int],
                    frontier_inputs: Optional[Sequence[SSTReader]] = None,
                    cancel=None) -> CompactionResult:
    """Full-native compaction: the byte path (decode/merge/encode) runs in
    C++ (native/compaction_engine.cc); Python assembles base files and
    frontiers. Same outputs as the Python shell, ~10x less wall."""
    from yugabyte_tpu.storage import native_engine
    from yugabyte_tpu.utils.metrics import pipeline_span

    with native_engine.NativeCompactionJob() as job:
        with pipeline_span("native_ingest"):
            for r in inputs:
                if cancel is not None:
                    cancel.check()
                with open(r.data_path, "rb") as f:
                    job.add_input(f.read(), r.block_handles)
            rows_in = job.prepare()
        with pipeline_span("native_merge"):
            rows_out = job.merge(history_cutoff_ht, is_major,
                                 retain_deletes)
        fr = _merge_frontiers(
            [r.props.frontier for r in (frontier_inputs or inputs)],
            history_cutoff_ht)
        outputs, _ranges = _write_native_outputs(
            job, out_dir, new_file_id, fr, block_entries,
            has_deep=any(r.props.has_deep for r in inputs),
            cancel=cancel)
    return CompactionResult(outputs, rows_in, rows_out)


def _route_device_native(all_inputs, orig_input_ids,
                         history_cutoff_ht: int, is_major: bool,
                         retain_deletes: bool):
    """run_compaction_job_device_native's routing prelude: (verdict,
    inputs, input_ids, dropped_rows, qkey, board). `verdict` is "device"
    (go on: the filtered inputs with their re-aligned cache ids), "empty"
    (nothing left after expiry filtering), "encrypted" / "skewed" (another
    job form takes it) or "parked" (the health board holds the bucket)."""
    from yugabyte_tpu.ops import run_merge
    from yugabyte_tpu.utils.env import get_env
    if get_env().encrypted:
        return "encrypted", all_inputs, orig_input_ids, 0, None, None
    id_of = ({id(r): fid for r, fid in zip(all_inputs, orig_input_ids)}
             if orig_input_ids is not None else None)
    inputs, dropped = filter_expired_inputs(
        all_inputs, history_cutoff_ht, is_major, retain_deletes)
    dropped_rows = sum(r.props.n_entries for r in dropped)
    inputs = [r for r in inputs if r.props.n_entries]
    if not inputs:
        return "empty", inputs, None, dropped_rows, None, None
    # cache ids re-aligned to the filtered list (see run_compaction_job)
    input_ids = ([id_of[id(r)] for r in inputs]
                 if id_of is not None else None)
    if run_merge.run_layout_inflation(
            [r.props.n_entries for r in inputs]) > 2.0:
        return "skewed", inputs, input_ids, dropped_rows, None, None
    from yugabyte_tpu.storage import offload_policy as offload_policy_mod
    from yugabyte_tpu.utils.trace import TRACE
    qkey = offload_policy_mod.bucket_key(
        run_merge.packed_run_ns([r.props.n_entries for r in inputs]))
    surface = offload_policy_mod.declared_surface_keys()
    if surface and qkey not in surface:
        # reachable shape the committed manifest never declared: count it
        # (the compile-surface budget reviews growth; this is the live
        # signal that the lattice and reality have diverged)
        from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
        ROOT_REGISTRY.entity("server", "offload_policy").counter(
            "compaction_offsurface_bucket_total",
            "device-native compactions whose shape bucket is outside "
            "the declared kernel compile surface").increment()
        TRACE("compaction: bucket k_pad=%d m=%d is outside the declared "
              "compile surface", *qkey)
    from yugabyte_tpu.storage.bucket_health import health_board
    board = health_board()
    verdict = ("device" if board.allow_device("run_merge_fused", qkey)
               else "parked")
    return verdict, inputs, input_ids, dropped_rows, qkey, board


def run_compaction_job_device_native(
        inputs: Sequence[SSTReader], out_dir: str, new_file_id,
        history_cutoff_ht: int, is_major: bool,
        retain_deletes: bool = False, device=None,
        block_entries: Optional[int] = None, device_cache=None,
        input_ids: Optional[Sequence[int]] = None,
        run_cache=None, cancel=None) -> CompactionResult:
    """The production hot path: TPU decisions + native byte shell.

    The device kernel (ops/run_merge.py) computes merge+GC decisions from
    HBM-cached key columns — launched FIRST so its compute and the packed
    decision download overlap the C++ shell's block decode of the same
    inputs (native/compaction_engine.cc); the shell then materializes the
    output SSTs from the injected survivors. Steady state does zero
    host->device upload (flush/compaction write-through staged the
    inputs) and ~0.5 byte/row download.

    Caller contract: inputs must not contain deep documents (FLAG_DEEP —
    depth > row+column); run_compaction_job routes those to the native
    merge, which carries the full overwrite stack."""
    from yugabyte_tpu.utils.metrics import pipeline_span
    from yugabyte_tpu.utils.trace import TRACE

    all_inputs = list(inputs)
    orig_input_ids = list(input_ids) if input_ids is not None else None
    with pipeline_span("routing"):
        verdict, inputs, input_ids, dropped_rows, qkey, board = \
            _route_device_native(all_inputs, orig_input_ids,
                                 history_cutoff_ht, is_major,
                                 retain_deletes)
    if verdict == "empty":
        return CompactionResult([], dropped_rows, 0)
    if verdict in ("encrypted", "skewed"):
        # encrypted: the C++ shell bypasses the Env, so take the Env-aware
        # device path; skewed run sizes would pad every run to the largest
        # bucket on device, so take the radix-kernel job (same outputs).
        # Either way: the original input list with its ORIGINAL id pairing
        return run_compaction_job(all_inputs, out_dir, new_file_id,
                                  history_cutoff_ht, is_major,
                                  retain_deletes, device=device,
                                  block_entries=block_entries,
                                  device_cache=device_cache,
                                  input_ids=orig_input_ids,
                                  _no_combined=True, cancel=cancel)
    if verdict == "parked":
        # QUARANTINED (recent fault / sticky mismatch) or DEGRADED with
        # no probe slot: native-only until the board re-opens the bucket
        # (surfaced on /healthz and /compactionz)
        TRACE("compaction: shape bucket k_pad=%d m=%d is parked by the "
              "health board — routing native", *qkey)
        import time as _time
        t0 = _time.monotonic()
        result = run_compaction_job(all_inputs, out_dir, new_file_id,
                                    history_cutoff_ht, is_major,
                                    retain_deletes, device="native",
                                    block_entries=block_entries,
                                    input_ids=orig_input_ids,
                                    _no_combined=True, cancel=cancel)
        # the parked completion is live native measurement too — it is
        # what the probe's device rate has to beat to re-promote
        board.record_native("run_merge_fused", qkey, result.rows_in,
                            _time.monotonic() - t0)
        return result

    from yugabyte_tpu.ops import block_codec as block_codec_mod
    # The device codec rides the COLD byte path: when every input is
    # already in the native run cache the shell ingests with zero decode
    # anyway (and its export keeps the chain warm), so the shell keeps
    # those jobs; everything else decodes and encodes on device.
    all_run_cached = bool(
        run_cache is not None and input_ids is not None
        and all(run_cache.contains(fid) for fid in input_ids))
    import time as _time
    t0 = _time.monotonic()
    try:
        if block_codec_mod.codec_enabled() and not all_run_cached:
            try:
                result = _device_codec_attempt(
                    inputs, all_inputs, input_ids, dropped_rows, out_dir,
                    new_file_id, history_cutoff_ht, is_major,
                    retain_deletes, device, block_entries, device_cache,
                    cancel)
                board.record_device("run_merge_fused", qkey,
                                    result.rows_in,
                                    _time.monotonic() - t0)
                return result
            except block_codec_mod.BlockCodecUnsupported as e:
                block_codec_mod.codec_metrics()[
                    "encode_fallbacks"].increment()
                TRACE("compaction: device codec unsupported for this "
                      "job (%s) — taking the native byte shell", e)
        else:
            block_codec_mod.codec_metrics()["encode_fallbacks"].increment()
        result = _device_native_attempt(
            inputs, all_inputs, input_ids, dropped_rows, out_dir,
            new_file_id, history_cutoff_ht, is_major, retain_deletes,
            device, block_entries, device_cache, run_cache, cancel)
        board.record_device("run_merge_fused", qkey, result.rows_in,
                            _time.monotonic() - t0)
        return result
    except Exception as e:  # noqa: BLE001 — device-fault containment
        from yugabyte_tpu.ops import device_faults
        from yugabyte_tpu.ops.run_merge import DeviceFaultError
        from yugabyte_tpu.storage.integrity import (ShadowMismatch,
                                                    shadow_mismatch_counter)
        shadow_mm = isinstance(e, ShadowMismatch)
        if not (shadow_mm or isinstance(e, DeviceFaultError)
                or device_faults.is_device_fault(e)):
            # host-side failures (disk faults, cancellation) take their
            # own containment paths — only KERNEL-path faults may fall
            # back to the native merge
            raise
        cause = e.cause if isinstance(e, DeviceFaultError) else e
        if shadow_mm:
            # STICKY: wrong bytes out-rank any fault — only an operator
            # clear (board.clear_mismatch) re-opens the bucket
            board.record_mismatch(
                "run_merge_fused", qkey,
                reason=f"{type(cause).__name__}: {cause}")
        else:
            board.record_fault(
                "run_merge_fused", qkey,
                reason=f"{type(cause).__name__}: {cause}")
        _storage_fallback_counter().increment()
        # the native re-run below writes through the shell encode
        block_codec_mod.codec_metrics()["encode_fallbacks"].increment()
        if shadow_mm:
            # the alarm: device decisions diverged from the native
            # oracle — a SILENT-corruption event (bit flip / donation
            # bug / miscompile), never an expected fault
            shadow_mismatch_counter().increment()
            TRACE("compaction: SHADOW VERIFY MISMATCH (%s) — partial "
                  "outputs deleted, shape bucket k_pad=%d m=%d "
                  "quarantined; re-running the job natively", cause,
                  *qkey)
        else:
            TRACE("compaction: device fault mid-job (%r) — shape bucket "
                  "k_pad=%d m=%d quarantined; completing via the native "
                  "merge", cause, *qkey)
        # Byte-identical completion: the attempt unwound cleanly (its
        # partial outputs deleted, staging leases released), so the
        # whole job re-runs on the native path over the SAME filtered
        # inputs — the differential-tested twin of the kernel path.
        t1 = _time.monotonic()
        result = _run_native_job(inputs, out_dir, new_file_id,
                                 history_cutoff_ht, is_major,
                                 retain_deletes, block_entries,
                                 frontier_inputs=all_inputs,
                                 cancel=cancel)
        result.rows_in += dropped_rows
        board.record_native("run_merge_fused", qkey, result.rows_in,
                            _time.monotonic() - t1)
        return result

def _storage_fallback_counter():
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    return ROOT_REGISTRY.entity("server", "offload_policy").counter(
        "compaction_device_fallback_total",
        "compactions completed via the native merge after a mid-job "
        "device fault")


def _ingest_decode_counter():
    """The warm resident chain's honesty meter: zero increments across a
    chained L0->L1->L2 sequence proves the shell ingested every input
    from the packed-run cache without re-reading or re-decoding SST
    bytes (the acceptance criterion's flat decode counter)."""
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    return ROOT_REGISTRY.entity("server", "storage").counter(
        "compaction_ingest_decode_total",
        "compaction inputs the native shell read and decoded from SST "
        "files (run-cache hits ingest without touching the bytes)")


class _ResidentSpanInstaller:
    """Write-through installer for the device-resident chain: as each
    _StreamingNativeWriter span completes, the matching survivor span is
    gathered ON DEVICE from the input staged columns (ops/run_merge.
    gather_staged_output_span — key columns never leave HBM) and
    installed into the slab cache under the OUTPUT file id, so the cache
    entry provably corresponds to the SST that just hit disk. A sampled
    digest check (storage/integrity.py) re-derives the entry from the
    decoded bytes; a divergent entry is dropped, never installed.

    Chunked handles cannot expose parent-domain device arrays mid-stream
    (the decisions are still riding the link), so their spans buffer and
    install together in finish() — the same point the pre-span-install
    code staged everything."""

    def __init__(self, device_cache, level: int):
        self.device_cache = device_cache
        self.level = level
        self.handle = None          # set once the merge is launched
        self.installed: List[int] = []
        self._pending: List[Tuple[int, str, int, int]] = []
        self._pos_all = None
        self._span_cache: dict = {}   # (start, end) -> StagedCols

    def _ready(self) -> bool:
        """True once the handle exposes parent-domain device arrays
        (rebuilding them from a fully-drained chunked stream if needed)."""
        h = self.handle
        if h is None:
            return False
        if getattr(h, "_perm_dev", None) is not None:
            return True
        if hasattr(h, "to_parent_products") \
                and getattr(h, "_result", None) is not None:
            h.to_parent_products()  # chunked stream fully drained
            return getattr(h, "_perm_dev", None) is not None
        return False

    def _gather_span(self, start: int, end: int):
        from yugabyte_tpu.ops import run_merge
        from yugabyte_tpu.utils.metrics import pipeline_span
        st = self._span_cache.pop((start, end), None)
        if st is not None:
            return st
        if self._pos_all is None:
            # one survivor-position scan per job; consumes (donates) the
            # keep mask on backends that honor donation
            with pipeline_span("survivor_positions"):
                self._pos_all = run_merge.survivor_positions(self.handle)
        with pipeline_span("span_gather"):
            return run_merge.gather_staged_output_span(
                self.handle, self._pos_all, start, end)

    def lindex_for_span(self, start: int, end: int):
        """Learned-index fit over the survivor span's staged columns —
        run while the sorted keys are still in HBM (the 'for free' half
        of the pragmatic-learned-index recipe); the gathered span is
        cached so the install that follows never re-gathers. None when
        the handle is mid-stream (chunked spans write before their
        decisions finish riding the link) — those files simply carry no
        model (it is advisory)."""
        from yugabyte_tpu.ops import point_read
        from yugabyte_tpu.utils import flags as _flags
        from yugabyte_tpu.utils.metrics import pipeline_span
        if not _flags.get_flag("sst_learned_index") or not self._ready():
            return None
        st = self._gather_span(start, end)
        self._span_cache[(start, end)] = st
        with pipeline_span("lindex_fit"):
            return point_read.fit_learned_index_device(st)

    def on_span(self, fid: int, base_path: str, start: int, end: int
                ) -> None:
        if self.handle is None:
            return
        if not self._ready():
            self._pending.append((fid, base_path, start, end))
            return
        self._install(fid, base_path, start, end)

    def _install(self, fid: int, base_path: str, start: int, end: int
                 ) -> None:
        from yugabyte_tpu.storage import integrity
        st = self._gather_span(start, end)
        if not integrity.maybe_verify_resident_entry(st, base_path):
            return  # digest mismatch: the next reader re-stages from bytes
        self.device_cache.put(fid, st, level=self.level)
        self.installed.append(fid)

    def finish(self) -> None:
        """Install the spans a chunked stream had to defer."""
        h = self.handle
        if h is None or not self._pending:
            return
        if getattr(h, "_perm_dev", None) is None \
                and hasattr(h, "to_parent_products"):
            h.to_parent_products()
        if getattr(h, "_perm_dev", None) is None:
            return
        pending, self._pending = self._pending, []
        for fid, base_path, start, end in pending:
            self._install(fid, base_path, start, end)

    def unwind(self) -> None:
        """Fault/cancellation unwind: every entry this attempt installed
        describes a file the unwind just deleted — drop them so the
        cache never outlives its SSTs."""
        for fid in self.installed:
            self.device_cache.drop(fid)
        self.installed = []


def _device_native_attempt(
        inputs, all_inputs, input_ids, dropped_rows: int, out_dir: str,
        new_file_id, history_cutoff_ht: int, is_major: bool,
        retain_deletes: bool, device, block_entries, device_cache,
        run_cache, cancel) -> CompactionResult:
    """One attempt of the pipelined device+native job (the body of
    run_compaction_job_device_native). UNWINDS CLEANLY on any failure or
    cancellation: every output file it wrote is deleted before the
    exception propagates, so the caller can fall back to the native
    merge (device fault) or abort (shutdown) without leaking partial
    SSTs into the version set's directory."""
    pipeline = os.environ.get("YBTPU_PIPELINE", "1").lower() \
        not in ("0", "false", "off")

    # cached-run ids, in INPUT ORDER (the device survivor indexes are
    # run-major over exactly this order) — all-or-nothing: a partial hit
    # still pays the file path for every input. contains() first so a
    # partial-hit job neither inflates hit metrics nor promotes entries
    # it never consumes; get() only once every input is present. Probed
    # BEFORE the ingest thread starts (the probes are cheap and the
    # thread must not race the run-cache's LRU bookkeeping).
    cached_ids = None
    if run_cache is not None and input_ids is not None \
            and all(run_cache.contains(fid) for fid in input_ids):
        ids = [run_cache.get(fid) for fid in input_ids]
        if all(i is not None for i in ids):
            cached_ids = ids

    tombstone_value = Value.tombstone().encode()
    state = {"writer": None, "installer": None, "pins": []}
    try:
        return _device_native_body(
            inputs, all_inputs, input_ids, dropped_rows, out_dir,
            new_file_id, history_cutoff_ht, is_major, retain_deletes,
            device, block_entries, device_cache, run_cache, cancel,
            pipeline, cached_ids, tombstone_value, state)
    except BaseException:
        # clean unwind: delete every output file this attempt wrote, so
        # a device-fault fallback or a cancellation leaves no partial
        # SSTs behind (staging-pool leases were already released by
        # stage_runs_from_slabs' own unwind)
        w = state["writer"]
        if w is not None:
            from yugabyte_tpu.storage.sst import data_file_name
            for _fid, base_path, _props in w.outputs:
                for p in (base_path, data_file_name(base_path)):
                    try:
                        os.remove(p)
                    except OSError:  # yblint: contained(unwind cleanup of partial outputs; the file may not exist yet)
                        pass
        inst = state["installer"]
        if inst is not None:
            # cache coherence under the unwind: the deleted partial
            # outputs must not stay resident
            inst.unwind()
        raise
    finally:
        if device_cache is not None:
            # zero leaked pins, fault or no fault: the inputs this job
            # pinned against eviction are released on EVERY exit path
            for fid in state["pins"]:
                device_cache.unpin(fid)


def _device_native_body(
        inputs, all_inputs, input_ids, dropped_rows: int, out_dir: str,
        new_file_id, history_cutoff_ht: int, is_major: bool,
        retain_deletes: bool, device, block_entries, device_cache,
        run_cache, cancel, pipeline: bool, cached_ids,
        tombstone_value: bytes, state: dict) -> CompactionResult:
    from yugabyte_tpu.ops import device_faults, run_merge
    from yugabyte_tpu.ops.merge_gc import stage_slab
    from yugabyte_tpu.storage import integrity, native_engine

    import threading
    from yugabyte_tpu.utils.metrics import pipeline_span

    # Online shadow verification (sampled): the native heap-merge oracle
    # re-derives this job's survivor decisions on its own thread
    # (overlapping the device work below); every decision chunk is
    # compared before its bytes can install. A mismatch unwinds the
    # attempt, quarantines the bucket and re-runs the job natively.
    with pipeline_span("shadow_setup"):
        shadow = integrity.maybe_shadow_verifier(
            inputs, history_cutoff_ht, is_major, retain_deletes)

    with native_engine.NativeCompactionJob() as job:
        # -- stage A (host): the native shell ingests the input bytes on
        # its own thread — file reads, block decode and CRC all release
        # the GIL, so this overlaps the device staging + kernel dispatch
        # below. Steady state takes the zero-decode run-cache path (the
        # bytes were retained when these SSTs were produced).
        ingest = {"rows_in": None, "err": None}

        def _ingest_inputs():
            # a root of its own thread, under the legacy `host` only: its
            # wall overlaps the job thread's stages (which name the wait
            # for it `ingest_join`)
            with pipeline_span("shell_ingest", inclusive="host",
                               stage=None, parent=None):
                _ingest_inputs_inner()

        def _ingest_inputs_inner():
            try:
                pinned = False
                if cached_ids is not None:
                    try:
                        # add_cached pins each run (C++ shared_ptr) — an
                        # entry evicted between the probe above and here
                        # raises, and the job falls back to the file path
                        # (stray pinned runs are ignored by prepare() and
                        # freed at job close)
                        for rid in cached_ids:
                            job.add_cached(rid)
                        pinned = True
                    except KeyError:  # yblint: contained(run-cache entry evicted since the probe — job falls back to the file path)
                        pinned = False
                if pinned:
                    ingest["rows_in"] = job.prepare_cached()
                else:
                    for r in inputs:
                        if cancel is not None:
                            # input boundary: shutdown aborts the ingest
                            # before paying for the next file read
                            cancel.check()
                        with open(r.data_path, "rb") as f:
                            job.add_input(f.read(), r.block_handles)
                        _ingest_decode_counter().increment()
                    ingest["rows_in"] = job.prepare()
            except BaseException as e:  # noqa: BLE001  # yblint: contained(parked in ingest['err'], re-raised on the join path)
                ingest["err"] = e

        ingest_thread = None
        if pipeline:
            ingest_thread = threading.Thread(
                target=_ingest_inputs, name="compaction-ingest",
                daemon=True)
            ingest_thread.start()

        try:
            # -- stage B: stage the key columns (HBM slab-cache hits skip
            # the upload; misses decode on host threads) and dispatch the
            # fused merge+GC — asynchronously, chunked and double-buffered
            # inside launch_merge_gc, with the carved chunk buffers
            # donated so XLA reuses their HBM in place.
            with pipeline_span("ingest", inclusive="host"):
                misses = [i for i, (r, fid) in enumerate(
                    zip(inputs, input_ids or [None] * len(inputs)))
                    if not (device_cache is not None and fid is not None
                            and device_cache.contains(fid))]
                slabs_by_idx = {}
                if pipeline and len(misses) > 1:
                    # cold inputs: decode SST blocks in parallel host threads
                    # (read_all is numpy + file I/O, GIL-light); uploads stay
                    # serial below — device_put ordering is the staging order
                    def _read(i):
                        try:
                            slabs_by_idx[i] = inputs[i].read_all()
                        except Exception as e:  # noqa: BLE001  # yblint: contained(decode retried serially below; a persistent fault raises there)
                            # a dead reader thread must not take the whole
                            # job down with a bare stderr traceback — the
                            # serial fallback re-reads this input and is the
                            # path that surfaces a real disk fault
                            from yugabyte_tpu.utils.trace import TRACE
                            TRACE("compaction: cold-miss decode of %s failed "
                                  "on the reader thread (%s); serial path "
                                  "will retry", inputs[i].data_path, e)
                    readers = [threading.Thread(target=_read, args=(i,),
                                                daemon=True) for i in misses]
                    for t in readers:
                        t.start()
                    for t in readers:
                        t.join()
                staged_list = []
                for i, (r, fid) in enumerate(
                        zip(inputs, input_ids or [None] * len(inputs))):
                    if cancel is not None:
                        cancel.check()  # before each per-input device upload
                    with pipeline_span("stage_input"):
                        st = device_cache.get(fid) if (
                            device_cache is not None
                            and fid is not None) else None
                        if st is None:
                            slab = slabs_by_idx.get(i)
                            if slab is None:
                                slab = r.read_all()
                            st = (device_cache.stage(fid, slab)
                                  if device_cache is not None
                                  and fid is not None
                                  else stage_slab(slab, device))
                        if device_cache is not None and fid is not None \
                                and device_cache.pin(fid):
                            # pinned for the whole attempt (released in the
                            # attempt's finally): capacity eviction can never
                            # race this running merge off its inputs
                            state["pins"].append(fid)
                    staged_list.append(st)
                with pipeline_span("merge_stage"):
                    staged_runs = run_merge.stage_runs_from_staged(staged_list)
                params = GCParams(history_cutoff_ht, is_major, retain_deletes)
                with pipeline_span("merge_launch"):
                    handle = run_merge.launch_merge_gc(staged_runs, params)
        finally:
            # the thread calls into the C++ job; it MUST finish before any
            # unwind can free the job (use-after-free otherwise)
            if ingest_thread is not None:
                with pipeline_span("ingest_join"):
                    ingest_thread.join()
        if ingest_thread is None:
            _ingest_inputs()
        if ingest["err"] is not None:
            raise ingest["err"]
        rows_in = ingest["rows_in"]

        # -- stage C: stream each chunk's decisions into the shell as its
        # download lands, writing every output file whose survivor span
        # is already complete — device compute, D2H transfer and native
        # encode/file I/O overlap instead of serializing.
        fr = _merge_frontiers([r.props.frontier for r in all_inputs],
                              history_cutoff_ht)
        has_deep = any(r.props.has_deep for r in inputs)
        tombstones_written = 0
        installer = None
        if device_cache is not None:
            # output residency level: one below the deepest input — the
            # chained L0->L1->L2 eviction policy keeps deep (expensive to
            # re-stage) outputs resident over shallow short-lived ones
            in_levels = [device_cache.level_of(fid)
                         for fid in (input_ids or []) if fid is not None]
            out_level = 1 + max([lv for lv in in_levels if lv is not None],
                                default=0)
            installer = _ResidentSpanInstaller(device_cache, out_level)
            installer.handle = handle
            state["installer"] = installer
        writer = _StreamingNativeWriter(
            job, out_dir, new_file_id, fr, block_entries,
            has_deep=has_deep, cancel=cancel,
            on_span=installer.on_span if installer is not None else None,
            lindex_for_span=(installer.lindex_for_span
                             if installer is not None else None))
        state["writer"] = writer   # the attempt's unwind sweeps .outputs
        if pipeline:
            for perm_c, keep_c, mk_c in handle.result_iter():
                if cancel is not None:
                    cancel.check()  # chunk boundary: abort in-flight job
                with pipeline_span("survivor_select"):
                    surv = perm_c[keep_c]
                    mk_surv = mk_c[keep_c]
                    # silent-corruption injection point (tests): a flipped
                    # decision lands in the SST unless shadow verify is on
                    device_faults.maybe_flip_survivors(surv, mk_surv)
                if shadow is not None:
                    shadow.check_chunk(surv, mk_surv)
                tombstones_written += int(np.count_nonzero(mk_surv))
                with pipeline_span("shell_feed"):
                    job.append_survivors(surv, mk_surv)
                    writer.feed(job.n_survivors)
            rows_out = job.n_survivors
            if shadow is not None:
                shadow.finish(rows_out)  # before the tail files write
            outputs, ranges = writer.finish(rows_out)
        else:
            perm, keep, mk = handle.result()
            with pipeline_span("survivor_select"):
                surv = perm[keep]
                mk_surv = mk[keep]
                device_faults.maybe_flip_survivors(surv, mk_surv)
            if shadow is not None:
                shadow.check_chunk(surv, mk_surv)
            tombstones_written = int(np.count_nonzero(mk_surv))
            with pipeline_span("shell_feed"):
                job.set_survivors(surv, mk_surv)
            rows_out = job.n_survivors
            if shadow is not None:
                shadow.finish(rows_out)
            outputs, ranges = writer.finish(job.n_survivors)
        if run_cache is not None:
            # run-cache write-through: exported survivors are
            # byte-equivalent to re-decoding the files just written, so
            # the NEXT compaction over these outputs starts all-cached
            with pipeline_span("run_export"):
                for (fid, _base, _props), (start, end) in zip(outputs,
                                                              ranges):
                    rid = job.export_run(start, end, tombstone_value)
                    run_cache.put(fid, rid,
                                  native_engine.runcache_entry_bytes(rid))
    if installer is not None:
        # spans a chunked stream deferred (parent-domain device arrays
        # only exist once every chunk's decisions landed) install here;
        # non-chunked jobs already installed per span as each SST hit
        # disk. Either way the entries were gathered ON DEVICE — zero
        # host->device transfer — and `ranges` are the spans the shell
        # actually wrote.
        with pipeline_span("installer_finish"):
            installer.finish()
    return CompactionResult(outputs, rows_in + dropped_rows, rows_out,
                            tombstones_written=tombstones_written)


class _DeviceCodecWriter:
    """Stage C of the device-codec job: write output SSTs whose block
    bytes were assembled by `block_encode_fused` (ops/block_codec.py) —
    the shell-free twin of _StreamingNativeWriter.

    File splits, pacing, tombstone and base-assembly rules are exactly
    those of _StreamingNativeWriter, so codec and shell jobs produce
    byte-identical files over identical survivor ranges.  Each span's
    cols are gathered ON DEVICE once and shared three ways: the encode
    dispatch, the learned-index fit and the write-through install."""

    def __init__(self, handle, values, w_out: int, out_dir: str,
                 new_file_id, fr, block_entries: Optional[int],
                 has_deep: bool = False, cancel=None, installer=None):
        self._handle = handle
        self._values = values          # global ValueArray, input order
        self._w_out = w_out
        self._out_dir = out_dir
        self._new_file_id = new_file_id
        self._fr = fr
        self._has_deep = has_deep
        self._cancel = cancel
        self._installer = installer
        self._block_entries = (block_entries if block_entries is not None
                               else flags.get_flag("sst_block_entries"))
        self._max_rows = flags.get_flag(
            "compaction_max_output_entries_per_sst")
        self._limiter = compaction_rate_limiter()
        self._tombstone_value = Value.tombstone().encode()
        self._pos_all = None
        self.outputs: List[Tuple[int, str, SSTProps]] = []
        self.ranges: List[Tuple[int, int]] = []

    def _gather_span(self, start: int, end: int):
        from yugabyte_tpu.ops import run_merge
        from yugabyte_tpu.utils.metrics import pipeline_span
        h = self._handle
        if getattr(h, "_perm_dev", None) is None \
                and hasattr(h, "to_parent_products"):
            # chunked stream: decisions fully drained before stage C, so
            # the parent-domain device arrays can rebuild here
            with pipeline_span("parent_products"):
                h.to_parent_products()
        inst = self._installer
        if inst is not None:
            st = inst._gather_span(start, end)
            # prefill the installer's span cache: the lindex fit and the
            # post-write install reuse this gather instead of repeating it
            inst._span_cache[(start, end)] = st
            return st, inst.lindex_for_span(start, end)
        if self._pos_all is None:
            with pipeline_span("survivor_positions"):
                self._pos_all = run_merge.survivor_positions(h)
        with pipeline_span("span_gather"):
            return run_merge.gather_staged_output_span(
                h, self._pos_all, start, end), None

    def _write_span(self, surv: np.ndarray, mk: np.ndarray,
                    start: int, end: int, more_coming: bool) -> None:
        from yugabyte_tpu.ops import block_codec
        from yugabyte_tpu.storage.sst import (data_file_name, write_base_file,
                                              sst_compression_enabled)
        from yugabyte_tpu.utils.env import get_env
        from yugabyte_tpu.utils.metrics import pipeline_span
        if self._cancel is not None:
            self._cancel.check()   # file-split boundary: clean abort point
        st, lindex = self._gather_span(start, end)
        with pipeline_span("value_gather"):
            vals = self._values.gather(surv[start:end],
                                       replace_mask=mk[start:end],
                                       replacement=self._tombstone_value)
        blocks, index, hashes, fk, lk = block_codec.encode_span(
            st, end - start, self._w_out, vals, self._block_entries,
            compress=sst_compression_enabled())
        with pipeline_span("write"):
            fid = self._new_file_id()
            base_path = os.path.join(self._out_dir, f"{fid:06d}.sst")
            data_path = data_file_name(base_path)
            if os.path.exists(data_path):
                os.remove(data_path)   # never append to a stale data file
            df = get_env().open_append(data_path)
            try:
                size = 0
                for blk in blocks:
                    df.append(blk)
                    size += len(blk)
                df.flush(fsync=True)
            finally:
                df.close()
            props = write_base_file(base_path, index, end - start, hashes,
                                    fk, lk, self._fr, size,
                                    has_deep=self._has_deep, lindex=lindex)
            self.outputs.append((fid, base_path, props))
            self.ranges.append((start, end))
        if self._installer is not None:
            with pipeline_span("cache_install"):
                self._installer.on_span(fid, base_path, start, end)
        if self._limiter is not None and more_coming:
            with pipeline_span("pace"):
                self._limiter.acquire(props.data_size + props.base_size)

    def write_all(self, surv: np.ndarray, mk: np.ndarray, rows_out: int
                  ) -> Tuple[List[Tuple[int, str, SSTProps]],
                             List[Tuple[int, int]]]:
        start = 0
        while start < rows_out:
            end = min(start + self._max_rows, rows_out)
            self._write_span(surv, mk, start, end,
                             more_coming=end < rows_out)
            start = end
        return self.outputs, self.ranges


def _device_codec_attempt(
        inputs, all_inputs, input_ids, dropped_rows: int, out_dir: str,
        new_file_id, history_cutoff_ht: int, is_major: bool,
        retain_deletes: bool, device, block_entries, device_cache,
        cancel) -> CompactionResult:
    """One attempt of the shell-free device-codec job (decode, merge and
    encode all on device; the host only CRC-checks raw bytes, splices
    values and writes files).  Unwinds exactly like
    _device_native_attempt: partial outputs deleted, installed cache
    entries dropped, zero leaked pins — so the caller's containment can
    quarantine + re-run natively after any device fault."""
    state = {"writer": None, "installer": None, "pins": []}
    try:
        return _device_codec_body(
            inputs, all_inputs, input_ids, dropped_rows, out_dir,
            new_file_id, history_cutoff_ht, is_major, retain_deletes,
            device, block_entries, device_cache, cancel, state)
    except BaseException:
        w = state["writer"]
        if w is not None:
            from yugabyte_tpu.storage.sst import data_file_name
            for _fid, base_path, _props in w.outputs:
                for p in (base_path, data_file_name(base_path)):
                    try:
                        os.remove(p)
                    except OSError:  # yblint: contained(unwind cleanup of partial outputs; the file may not exist yet)
                        pass
        inst = state["installer"]
        if inst is not None:
            inst.unwind()
        raise
    finally:
        if device_cache is not None:
            for fid in state["pins"]:
                device_cache.unpin(fid)


def _device_codec_body(
        inputs, all_inputs, input_ids, dropped_rows: int, out_dir: str,
        new_file_id, history_cutoff_ht: int, is_major: bool,
        retain_deletes: bool, device, block_entries, device_cache,
        cancel, state: dict) -> CompactionResult:
    from yugabyte_tpu.ops import block_codec, device_faults, run_merge
    from yugabyte_tpu.ops.slabs import ValueArray
    from yugabyte_tpu.storage import integrity
    from yugabyte_tpu.utils.metrics import pipeline_span

    with pipeline_span("shadow_setup"):
        shadow = integrity.maybe_shadow_verifier(
            inputs, history_cutoff_ht, is_major, retain_deletes)

    # -- stage A: raw-byte ingest. One file read + per-block CRC check +
    # zero-copy value slicing per input (block_format.split_raw_block);
    # key columns decode ON DEVICE (block_decode_fused) unless the slab
    # cache already holds them — either way no host decode_block runs, so
    # sst_block_decode_total and compaction_ingest_decode_total stay flat
    # even on a COLD chain.
    with pipeline_span("ingest", inclusive="host"):
        staged_list = []
        values_parts = []
        rows_in = 0
        w_out = 1
        for r, fid in zip(inputs, input_ids or [None] * len(inputs)):
            if cancel is not None:
                cancel.check()   # input boundary, like the shell ingest
            with pipeline_span("raw_read"):
                raw = r.read_raw()
            with pipeline_span("raw_parse"):
                rfb = block_codec.parse_raw_file(raw, r.block_handles)
            values_parts.extend(rfb.value_parts)
            rows_in += rfb.n
            w_out = max(w_out, rfb.w)
            # a slab-cache hit, or the decode dispatch (`decode` inside)
            with pipeline_span("stage_input"):
                st = device_cache.get(fid) if (
                    device_cache is not None and fid is not None) else None
                if st is None:
                    st = (device_cache.stage_from_raw(fid, rfb)
                          if device_cache is not None and fid is not None
                          else block_codec.decode_file_to_staged(rfb,
                                                                 device))
                if device_cache is not None and fid is not None \
                        and device_cache.pin(fid):
                    state["pins"].append(fid)
            staged_list.append(st)
        with pipeline_span("value_concat"):
            values = ValueArray.concat(values_parts)

    # -- stage B: the same fused merge+GC launch as the shell path
    with pipeline_span("merge_stage", inclusive="host"):
        staged_runs = run_merge.stage_runs_from_staged(staged_list)
    params = GCParams(history_cutoff_ht, is_major, retain_deletes)
    with pipeline_span("merge_launch", inclusive="host"):
        handle = run_merge.launch_merge_gc(staged_runs, params)

    # decisions drain fully before stage C: the survivor indices drive
    # the host value gather, and span gathers need the parent-domain
    # device arrays (chunked streams only expose them post-drain). The
    # handle's own spans name the wait for the device (`device`) and the
    # unpack of what came back (`decision_unpack`, `decision_remap`).
    surv_parts, mk_parts = [], []
    for perm_c, keep_c, mk_c in handle.result_iter():
        if cancel is not None:
            cancel.check()
        with pipeline_span("survivor_select"):
            surv_c = perm_c[keep_c]
            mk_surv = mk_c[keep_c]
            device_faults.maybe_flip_survivors(surv_c, mk_surv)
        if shadow is not None:
            shadow.check_chunk(surv_c, mk_surv)
        surv_parts.append(surv_c)
        mk_parts.append(mk_surv)
    with pipeline_span("survivor_concat"):
        surv = (np.concatenate(surv_parts) if surv_parts
                else np.zeros(0, dtype=np.int64))
        mk = (np.concatenate(mk_parts) if mk_parts
              else np.zeros(0, dtype=bool))
    rows_out = int(surv.shape[0])
    if shadow is not None:
        shadow.finish(rows_out)

    # -- stage C: device block encode + host value splice per span
    fr = _merge_frontiers([r.props.frontier for r in all_inputs],
                          history_cutoff_ht)
    has_deep = any(r.props.has_deep for r in inputs)
    installer = None
    if device_cache is not None:
        in_levels = [device_cache.level_of(fid)
                     for fid in (input_ids or []) if fid is not None]
        out_level = 1 + max([lv for lv in in_levels if lv is not None],
                            default=0)
        installer = _ResidentSpanInstaller(device_cache, out_level)
        installer.handle = handle
        state["installer"] = installer
    writer = _DeviceCodecWriter(
        handle, values, w_out, out_dir, new_file_id, fr, block_entries,
        has_deep=has_deep, cancel=cancel, installer=installer)
    state["writer"] = writer
    outputs, _ranges = writer.write_all(surv, mk, rows_out)
    if installer is not None:
        with pipeline_span("installer_finish"):
            installer.finish()
    return CompactionResult(outputs, rows_in + dropped_rows, rows_out,
                            tombstones_written=int(np.count_nonzero(mk)))


class _DistResidentInstaller:
    """Write-through installer for the dist-native path: as each output
    span's SST hits disk, the matching survivor span is gathered from the
    SHARDED device outputs (parallel/dist_compact.DistOutputs.gather_span
    — the merged cols never return to the host) and installed under the
    output file id, digest-sampled like the single-device installer."""

    def __init__(self, device_cache, level: int, outputs_dev):
        self.device_cache = device_cache
        self.level = level
        self._outputs = outputs_dev
        self.installed: List[int] = []

    def on_span(self, fid: int, base_path: str, start: int, end: int
                ) -> None:
        from yugabyte_tpu.storage import integrity
        st = self._outputs.gather_span(start, end)
        if not integrity.maybe_verify_resident_entry(st, base_path):
            return  # digest mismatch: the next reader re-stages from bytes
        self.device_cache.put(fid, st, level=self.level)
        self.installed.append(fid)

    def unwind(self) -> None:
        for fid in self.installed:
            self.device_cache.drop(fid)
        self.installed = []


def run_compaction_job_dist_native(
        inputs: Sequence[SSTReader], out_dir: str, new_file_id,
        history_cutoff_ht: int, is_major: bool,
        retain_deletes: bool = False, device=None,
        block_entries: Optional[int] = None, device_cache=None,
        input_ids: Optional[Sequence[int]] = None, mesh=None,
        cancel=None) -> CompactionResult:
    """The mesh production path: key-range-sharded merge+GC decisions
    (parallel/dist_compact.py) + the native byte shell + device-resident
    span write-through.

    Stage A ingests the input bytes into the C++ shell on its own thread
    (overlapping the pack/upload/exchange below, exactly like the
    single-device device-native job); the distributed step returns only
    the decision-sized arrays (keep/mk/src_idx) while the merged output
    cols stay SHARDED on the mesh, where the resident-span installer
    gathers each output file's survivors for the HBM cache. Outputs are
    byte-identical to the sequential native path (same survivors, same
    _StreamingNativeWriter split/pacing/tombstone rules).

    Fault containment mirrors run_compaction_job_device_native: any
    kernel-path fault (or shadow mismatch) unwinds cleanly — partial
    outputs deleted, installed entries dropped — quarantines the
    (n_shards, capacity) bucket and completes the job via the native
    merge, byte-identically."""
    import threading
    import time as _time
    from yugabyte_tpu.ops import device_faults
    from yugabyte_tpu.ops.merge_gc import bucket_size
    from yugabyte_tpu.parallel.dist_compact import (
        _quantized_capacity, distributed_compact_with_outputs)
    from yugabyte_tpu.storage import integrity, native_engine
    from yugabyte_tpu.utils.metrics import pipeline_span

    all_inputs = list(inputs)
    id_of = ({id(r): fid for r, fid in zip(all_inputs, input_ids)}
             if input_ids is not None else None)
    inputs, dropped = filter_expired_inputs(
        inputs, history_cutoff_ht, is_major, retain_deletes)
    dropped_rows = sum(r.props.n_entries for r in dropped)
    inputs = [r for r in inputs if r.props.n_entries]
    if not inputs:
        return CompactionResult([], dropped_rows, 0)
    input_ids = ([id_of[id(r)] for r in inputs]
                 if id_of is not None else None)

    n_shards = mesh.devices.size
    est_rows = sum(r.props.n_entries for r in inputs)
    bucket = (n_shards, _quantized_capacity(
        bucket_size(est_rows) // n_shards, n_shards, 2.0))
    from yugabyte_tpu.storage.bucket_health import health_board
    board = health_board()
    if not board.allow_device("dist_compact", bucket):
        # the (n_shards, capacity) bucket is parked (fault quarantine /
        # sticky mismatch / degraded without a probe slot): complete via
        # the sequential native merge, byte-identically
        from yugabyte_tpu.utils.trace import TRACE
        TRACE("compaction: dist bucket n_shards=%d capacity=%d is "
              "parked by the health board — routing native", *bucket)
        t0 = _time.monotonic()
        result = _run_native_job(inputs, out_dir, new_file_id,
                                 history_cutoff_ht, is_major,
                                 retain_deletes, block_entries,
                                 frontier_inputs=all_inputs,
                                 cancel=cancel)
        result.rows_in += dropped_rows
        board.record_native("dist_compact", bucket, result.rows_in,
                            _time.monotonic() - t0)
        return result
    t_job = _time.monotonic()
    shadow = integrity.maybe_shadow_verifier(
        inputs, history_cutoff_ht, is_major, retain_deletes)
    params = GCParams(history_cutoff_ht, is_major, retain_deletes)
    state = {"writer": None, "installer": None}
    try:
        with native_engine.NativeCompactionJob() as job:
            ingest = {"rows_in": None, "err": None}

            def _ingest_inputs():
                # its own thread's root, legacy `host` only (see
                # _device_native_body)
                with pipeline_span("shell_ingest", inclusive="host",
                                   stage=None, parent=None):
                    try:
                        for r in inputs:
                            if cancel is not None:
                                cancel.check()
                            with open(r.data_path, "rb") as f:
                                job.add_input(f.read(), r.block_handles)
                            _ingest_decode_counter().increment()
                        ingest["rows_in"] = job.prepare()
                    except BaseException as e:  # noqa: BLE001  # yblint: contained(parked in ingest['err'], re-raised on the join path)
                        ingest["err"] = e

            ingest_thread = threading.Thread(
                target=_ingest_inputs, name="dist-compaction-ingest",
                daemon=True)
            ingest_thread.start()
            try:
                slabs = [r.read_all() for r in inputs]
                merged = concat_slabs([s for s in slabs if s.n])
                bucket = (n_shards, _quantized_capacity(
                    bucket_size(merged.n) // n_shards, n_shards, 2.0))
                keep, mk, src_idx, outputs_dev = \
                    distributed_compact_with_outputs(merged, params, mesh)
                bucket = outputs_dev.bucket_key()
            finally:
                # the thread calls into the C++ job; it MUST finish
                # before any unwind can free the job
                with pipeline_span("ingest_join"):
                    ingest_thread.join()
            if ingest["err"] is not None:
                raise ingest["err"]
            rows_in = ingest["rows_in"]
            surv = src_idx[keep]
            mk_surv = mk[keep]
            device_faults.maybe_flip_survivors(surv, mk_surv)
            if shadow is not None:
                shadow.check_chunk(surv, mk_surv)
            rows_out = int(surv.shape[0])
            if shadow is not None:
                shadow.finish(rows_out)
            fr = _merge_frontiers([r.props.frontier for r in all_inputs],
                                  history_cutoff_ht)
            installer = None
            if device_cache is not None:
                in_levels = [device_cache.level_of(fid)
                             for fid in (input_ids or [])
                             if fid is not None]
                out_level = 1 + max([lv for lv in in_levels
                                     if lv is not None], default=0)
                installer = _DistResidentInstaller(device_cache, out_level,
                                                   outputs_dev)
                state["installer"] = installer
            writer = _StreamingNativeWriter(
                job, out_dir, new_file_id, fr, block_entries,
                has_deep=False, cancel=cancel,
                on_span=installer.on_span if installer is not None
                else None)
            state["writer"] = writer
            if cancel is not None:
                cancel.check()
            job.set_survivors(surv, mk_surv)
            outputs, _ranges = writer.finish(job.n_survivors)
        board.record_device("dist_compact", bucket, rows_in + dropped_rows,
                            _time.monotonic() - t_job)
        return CompactionResult(outputs, rows_in + dropped_rows, rows_out,
                                tombstones_written=int(
                                    np.count_nonzero(mk_surv)))
    except Exception as e:  # noqa: BLE001 — device-fault containment
        from yugabyte_tpu.ops.run_merge import DeviceFaultError
        from yugabyte_tpu.storage.integrity import (ShadowMismatch,
                                                    shadow_mismatch_counter)
        from yugabyte_tpu.storage.sst import data_file_name
        from yugabyte_tpu.utils.trace import TRACE
        w = state["writer"]
        if w is not None:
            for _fid, base_path, _props in w.outputs:
                for p in (base_path, data_file_name(base_path)):
                    try:
                        os.remove(p)
                    except OSError:  # yblint: contained(unwind cleanup of partial outputs; the file may not exist yet)
                        pass
        inst = state["installer"]
        if inst is not None:
            inst.unwind()
        shadow_mm = isinstance(e, ShadowMismatch)
        if not (shadow_mm or isinstance(e, DeviceFaultError)
                or device_faults.is_device_fault(e)):
            raise
        if shadow_mm:
            board.record_mismatch("dist_compact", bucket,
                                  reason=f"{type(e).__name__}: {e}")
        else:
            board.record_fault("dist_compact", bucket,
                               reason=f"{type(e).__name__}: {e}")
        _storage_fallback_counter().increment()
        if shadow_mm:
            shadow_mismatch_counter().increment()
        TRACE("compaction: dist-native job failed (%r) — bucket "
              "n_shards=%d capacity=%d quarantined; completing via the "
              "native merge", e, *bucket)
        t1 = _time.monotonic()
        result = _run_native_job(inputs, out_dir, new_file_id,
                                 history_cutoff_ht, is_major,
                                 retain_deletes, block_entries,
                                 frontier_inputs=all_inputs,
                                 cancel=cancel)
        result.rows_in += dropped_rows
        board.record_native("dist_compact", bucket, result.rows_in,
                            _time.monotonic() - t1)
        return result


def run_compaction_job_with_decisions(
        inputs: Sequence[SSTReader], slabs: Sequence[KVSlab], out_dir: str,
        new_file_id, history_cutoff_ht: int, is_major: bool,
        retain_deletes: bool, block_entries: Optional[int],
        surv: np.ndarray, mk_surv: np.ndarray, rows_in: int,
        frontier_inputs: Optional[Sequence[SSTReader]] = None,
        cancel=None, on_span=None) -> CompactionResult:
    """Write a compaction job's outputs from externally computed survivor
    decisions — the compaction pool's wave path (the device stage ran as
    one slot of a pooled mesh dispatch; this is stage C).

    The byte path is EXACTLY the sequential writer's: the native shell +
    _StreamingNativeWriter where the shell can run the bytes, else the
    python gather+SSTWriter loop — so pooled outputs are byte-identical
    to a sequential job over the same inputs.

    inputs: the FILTERED reader list (whole-file-expired inputs already
    dropped by the caller); slabs: their read_all() slabs (reused by the
    python fallback so bytes are not read twice); surv indexes the
    concatenation of the live slabs in input order, in merged order."""
    from yugabyte_tpu.storage import native_engine
    from yugabyte_tpu.utils.env import get_env
    from yugabyte_tpu.storage.sst import data_file_name

    fr = _merge_frontiers(
        [r.props.frontier for r in (frontier_inputs or inputs)],
        history_cutoff_ht)
    has_deep = any(r.props.has_deep for r in inputs)
    rows_out = int(surv.shape[0])
    tombstones = int(np.count_nonzero(mk_surv))
    if native_engine.available() and not get_env().encrypted \
            and not has_deep:
        with native_engine.NativeCompactionJob() as job:
            for r in inputs:
                if cancel is not None:
                    cancel.check()
                with open(r.data_path, "rb") as f:
                    job.add_input(f.read(), r.block_handles)
                _ingest_decode_counter().increment()
            job.prepare()
            job.set_survivors(surv, mk_surv)
            writer = _StreamingNativeWriter(
                job, out_dir, new_file_id, fr, block_entries,
                has_deep=has_deep, cancel=cancel, on_span=on_span)
            try:
                outputs, _ranges = writer.finish(job.n_survivors)
            except BaseException:
                for _fid, base_path, _props in writer.outputs:
                    for p in (base_path, data_file_name(base_path)):
                        try:
                            os.remove(p)
                        except OSError:  # yblint: contained(unwind cleanup of partial outputs; the file may not exist yet)
                            pass
                raise
        return CompactionResult(outputs, rows_in, rows_out,
                                tombstones_written=tombstones)
    # python writer (byte-identical to run_compaction_job's python path
    # over the same decisions; the Env-aware route under encryption)
    merged = concat_slabs([s for s in slabs if s.n])
    limiter = compaction_rate_limiter()
    outputs: List[Tuple[int, str, SSTProps]] = []
    max_rows = flags.get_flag("compaction_max_output_entries_per_sst")
    tombstone_value = Value.tombstone().encode()
    try:
        for start in range(0, rows_out, max_rows):
            if cancel is not None:
                cancel.check()
            end = min(start + max_rows, rows_out)
            sel = surv[start:end]
            out_slab = _gather_slab(merged, sel, mk_surv[start:end],
                                    tombstone_value)
            fid = new_file_id()
            base_path = os.path.join(out_dir, f"{fid:06d}.sst")
            props = SSTWriter(base_path, block_entries=block_entries,
                              fit_lindex=False).write(out_slab, fr)
            outputs.append((fid, base_path, props))
            if on_span is not None:
                on_span(fid, base_path, start, end)
            if limiter is not None and end < rows_out:
                limiter.acquire(props.data_size + props.base_size)
    except BaseException:
        for _fid, base_path, _props in outputs:
            for p in (base_path, data_file_name(base_path)):
                try:
                    os.remove(p)
                except OSError:  # yblint: contained(unwind cleanup of partial outputs; the file may not exist yet)
                    pass
        raise
    return CompactionResult(outputs, rows_in, rows_out,
                            tombstones_written=tombstones)


def _gather_slab(slab: KVSlab, sel: np.ndarray, make_tomb: np.ndarray,
                 tombstone_value: bytes) -> KVSlab:
    """Materialize the surviving rows (vectorized; no per-row Python —
    values move as one offset-arithmetic gather, ref hot loop ③
    compaction_job.cc:958-1024)."""
    from yugabyte_tpu.ops.slabs import FLAG_TOMBSTONE, ValueArray
    va = ValueArray.from_list(slab.values)
    values = va.gather(slab.value_idx[sel], replace_mask=make_tomb,
                       replacement=tombstone_value)
    flags_out = slab.flags[sel].copy()
    flags_out[make_tomb] |= FLAG_TOMBSTONE
    return KVSlab(
        key_words=slab.key_words[sel], key_len=slab.key_len[sel],
        doc_key_len=slab.doc_key_len[sel], ht_hi=slab.ht_hi[sel],
        ht_lo=slab.ht_lo[sel], write_id=slab.write_id[sel],
        flags=flags_out, ttl_ms=slab.ttl_ms[sel],
        value_idx=np.arange(len(sel), dtype=np.int32), values=values)


def _merge_frontiers(frontiers: Sequence[Frontier], history_cutoff: int) -> Frontier:
    live = [f for f in frontiers if f is not None]
    if not live:
        return Frontier(history_cutoff=history_cutoff)
    return Frontier(
        op_id_min=min(f.op_id_min for f in live),
        op_id_max=max(f.op_id_max for f in live),
        ht_min=min(f.ht_min for f in live),
        ht_max=max(f.ht_max for f in live),
        history_cutoff=max(history_cutoff, max(f.history_cutoff for f in live)),
    )
