"""Driver for cells whose traffic is the flush-fed chain of one tablet's
regular DB: what every tablet of a write-heavy table does between a
tserver's restarts. A CHAIN opens a fresh `DB` (default flags, the health
board, and the tserver's ONE `DeviceSlabCache` and ONE `BlockCache` shared by
all chains), writes `l0_runs` runs through `write_batch_columns` + `flush()`
(each flush writes its slab through to the slab cache), runs ONE
`compact_all()` over the four inputs it just flushed, all resident, and
closes. One job a chain: the base-plus-L0 job that would follow is the
"skewed" routing refusal, another cell's.

The runs are `compact-major.kv64`'s (same generator, same seed use), turned
into the key and value lists `write_batch_columns` takes once in set-up.
`compaction_rows_per_s` is the jobs' input rows over ALL the window's
seconds, flushes included: rows compacted over the time it took to get them
there.

`failed` counts events: a native routing decision, a `ZERO_COUNTERS`
movement, or a job during which a decode meter moved (an input missing from
the slab cache, a raw read, parse or decode stage entered, or a block
decoded). Two samplers of the program decode blocks that are NOT the job's
own reading:
- the shadow verifier (`shadow_verify_sample`, 2% of device jobs on default
  flags) re-reads a sampled job's inputs for its native oracle, on a thread
  of its own: a job it sampled is judged by the other meters alone;
- the write-through digest check (`resident_digest_sample`, 2% of cache
  installs) re-reads an output file the job just wrote, every data block of
  it: a job it checked is let off that many blocks, the data blocks of its
  largest outputs, one file a check, and nothing else. A decode beyond them,
  and every other meter, counts as before. A digest MISMATCH fails the job:
  the program found a write-through entry that diverged from its file.
The tally says how many jobs each sampled, and how many blocks were let off.
"""

import os
import time

import numpy as np

from benchmarks import datagen, reference, roofline
from benchmarks.drivers.compaction import (_data_files, _decode_outputs,
                                           _props_but_lindex)
from benchmarks.program import ZERO_COUNTERS

CONTROLS = ("history_cutoff_zero",)
# stage counters that stay flat while every input is resident (the `ingest`
# and `stage_input` spans are opened for a resident input too, and close at
# once)
DECODE_STAGES = ("raw_read", "raw_parse", "decode")


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.sizes = ctx.sizes
        self.traffic = ctx.traffic
        self.chains = 0
        self.native_db = None

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        from yugabyte_tpu.common.hybrid_time import HybridTime
        from yugabyte_tpu.storage import DB, DBOptions, SSTReader
        from yugabyte_tpu.storage import native_engine
        from yugabyte_tpu.storage.compaction import run_compaction_job
        from yugabyte_tpu.storage.device_cache import DeviceSlabCache
        from yugabyte_tpu.storage.sst import BlockCache
        from yugabyte_tpu.storage import offload_policy  # noqa: F401 (defines the flag)
        from yugabyte_tpu.utils import flags

        ctx = self.ctx
        ctx.require(flags.get_flag("device_offload_mode") == "auto",
                    "device_offload_mode is not at its default")
        ctx.require(native_engine.available(),
                    "native engine unavailable (g++ failed?)")
        self.n_runs = int(self.sizes["l0_runs"])
        rows = int(self.sizes["rows_per_run"])
        key_space = int(self.n_runs * rows
                        * float(self.sizes["key_space_share"]))
        gen = datagen.Kv64Runs(ctx.seed, ctx.config["shape"]["shares"])
        cutoff = HybridTime.from_micros(
            int(ctx.config["shape"]["history_cutoff_us"])).value
        self.cutoff = 0 if ctx.control == "history_cutoff_zero" else cutoff
        runs = [gen.run(rows, key_space, 1_000_000 * (g + 1))
                for g in range(self.n_runs)]
        self.expect = reference.major_compaction_survivors(gen, runs)
        self.key_bytes = int(self.expect["keys"].shape[1])
        self.value_bytes_in = int(sum(len(r["vals_blob"]) for r in runs))
        self.value_bytes_out = int(self.expect["val_len"].sum())
        # what write_batch_columns takes, made once, outside every window
        self.batches = [_as_batch(r) for r in runs]
        del runs

        # the native C++ job over the same four FLUSHED files, on a DB of
        # its own: the configuration's guarantee is byte identity with it
        self.native_db = DB(os.path.join(ctx.workdir, "native_inputs"),
                            DBOptions(auto_compact=False))
        self._write_runs(self.native_db)
        inputs = [fm.path for fm in self.native_db.versions.live_files()]
        ctx.require(len(inputs) == self.n_runs,
                    f"expected {self.n_runs} flushed L0 files")
        readers = [SSTReader(p) for p in inputs]
        native_dir = os.path.join(ctx.workdir, "native_out")
        os.makedirs(native_dir)
        ids = iter(range(1000, 1 << 20))
        t0 = time.monotonic()
        native = run_compaction_job(readers, native_dir, lambda: next(ids),
                                    cutoff, True, device="native")
        native_s = time.monotonic() - t0
        for r in readers:
            r.close()
        self.native_outputs = native.outputs
        self.native_files = _data_files(native.outputs)
        self.native_props = str(_props_but_lindex(native.outputs))
        ctx.log({"native_job": {"rows_in": native.rows_in,
                                "rows_out": native.rows_out,
                                "seconds": native_s,
                                "rows_per_s": native.rows_in / native_s}})
        ctx.require(native.rows_in == self.expect["rows_in"],
                    "native job read another row count than was written")
        self.device_cache = DeviceSlabCache(ctx.devices[0])
        self.db_options = dict(
            device=ctx.devices[0], device_cache=self.device_cache,
            block_cache=BlockCache(256 << 20),       # a tserver's default
            retention_policy=lambda: self.cutoff, auto_compact=False)

    def _write_runs(self, db) -> float:
        """The chain's write half; returns the seconds its flushes took."""
        flush_s = 0.0
        for g, (keys, ht, wid, values) in enumerate(self.batches):
            db.write_batch_columns(keys, ht, wid, values, op_id=(1, g + 1))
            t0 = time.monotonic()
            db.flush()
            flush_s += time.monotonic() - t0
        return flush_s

    # ------------------------------------------------------------ the loop
    def run(self, seconds: float, tracer) -> dict:
        """Chains one after another until `seconds` have passed; the
        window closes when the chain then in flight has finished."""
        from yugabyte_tpu.storage import DB, DBOptions
        from yugabyte_tpu.storage.bucket_health import health_board
        ctx = self.ctx
        jobs = []
        t0 = time.monotonic()
        while True:
            tracer.boundary(len(jobs))
            chain_dir = os.path.join(ctx.workdir, f"chain{self.chains:05d}")
            self.chains += 1
            t_chain = time.monotonic()
            with ctx.span("chain_open"):
                db = DB(chain_dir, DBOptions(offload_policy=health_board(),
                                             **self.db_options))
            with ctx.span("chain_write"):
                flush_s = self._write_runs(db)
            inputs = list(db.versions.live_files())
            ctx.require(len(inputs) == self.n_runs,
                        f"a chain flushed {len(inputs)} files")
            resident = sum(db._device_cache.contains(fm.file_id)
                           for fm in inputs)
            before = self._decode_meters()
            t_job = time.monotonic()
            with ctx.span("job_body"):
                db.compact_all()
            job_s = time.monotonic() - t_job
            moved = {k: v - before[k]
                     for k, v in self._decode_meters().items()
                     if v != before[k]}
            sampled = bool(moved.pop("shadow_verifier_sampled", 0))
            if sampled:
                moved.pop("sst_block_decode_total", None)
            ctx.require(db.background_error is None,
                        f"compaction parked the DB: {db.background_error}")
            outs = [(fm.file_id, fm.path, None)
                    for fm in db.versions.live_files()]
            # read after the meters above, so that reading cannot move them
            digest_checked = moved.pop("resident_digest_checked_total", 0)
            exempted = _digest_exemption(moved, digest_checked, outs)
            with ctx.span("chain_close"):
                db.close()
            now = time.monotonic()
            jobs.append({"outputs": outs, "seconds": job_s,
                         "flush_s": flush_s, "chain_s": now - t_chain,
                         "inputs_resident": resident,
                         "shadow_sampled": sampled,
                         "digest_checked": digest_checked,
                         "digest_blocks_exempted": exempted,
                         "decode_meters_moved": moved})
            tracer.note(bench_rows_in=self.expect["rows_in"], bench_jobs=1,
                        bench_jobs_wall_ms=job_s * 1e3,
                        bench_flush_wall_ms=flush_s * 1e3,
                        bench_chains_wall_ms=(now - t_chain) * 1e3,
                        bench_min_device_bytes=self._job_bytes())
            if now - t0 >= seconds:
                break
        tracer.boundary(len(jobs))
        return {"jobs": jobs, "seconds": time.monotonic() - t0}

    def _decode_meters(self) -> dict:
        """What moves when a job reads an input it should have found
        resident: slab-cache misses, the pipeline's raw-read, parse and
        decode stages, SST blocks decoded; whether the shadow verifier
        sampled the job, and the digest check's checks and mismatches
        (module docstring)."""
        from yugabyte_tpu.storage import integrity
        from yugabyte_tpu.storage.sst import _block_decode_counter
        from yugabyte_tpu.utils.metrics import pipeline_stage_totals
        stages = pipeline_stage_totals()
        integ = integrity.integrity_metrics()
        out = {"sst_block_decode_total": _block_decode_counter().value(),
               "slab_cache_misses": self.device_cache.misses,
               "shadow_verifier_sampled":
                   integ.counter("shadow_verify_jobs_total", "").value()
                   + integ.counter("shadow_verify_skipped_total",
                                   "").value(),
               "resident_digest_checked_total":
                   integ.counter("resident_digest_checked_total",
                                 "").value(),
               "resident_digest_mismatch_total":
                   integrity.resident_digest_mismatch_counter().value()}
        for s in DECODE_STAGES:
            out[f"stage_{s}_ms"] = stages.get(s, 0.0)
        return out

    def _job_bytes(self) -> int:
        return roofline.compaction_job_bytes(
            self.expect["rows_in"], self.expect["rows_out"], self.key_bytes,
            self.value_bytes_in, self.value_bytes_out)

    # ------------------------------------------------------------- results
    def metrics(self, window: dict, counters: dict) -> dict:
        rows = self.expect["rows_in"] * len(window["jobs"])
        return {"compaction_rows_per_s": rows / window["seconds"],
                "job_rows_per_s": rows / sum(j["seconds"]
                                             for j in window["jobs"])}

    def tally(self, window: dict, counters: dict) -> dict:
        jobs = window["jobs"]
        n = len(jobs)
        off_device = counters["offload_decisions_native_total"] + sum(
            counters[name] for name in ZERO_COUNTERS)
        left_resident = sum(1 for j in jobs if j["decode_meters_moved"]
                            or j["inputs_resident"] != self.n_runs)
        self.ctx.log({
            "window_jobs": n, "window_s": window["seconds"],
            "job_seconds": [round(j["seconds"], 4) for j in jobs],
            "flush_seconds": [round(j["flush_s"], 4) for j in jobs],
            "chain_seconds": [round(j["chain_s"], 4) for j in jobs],
            "jobs_off_device": off_device,
            "jobs_that_left_the_resident_path": left_resident,
            "jobs_the_shadow_verifier_sampled": sum(
                j["shadow_sampled"] for j in jobs),
            "jobs_the_digest_check_sampled": sum(
                1 for j in jobs if j["digest_checked"]),
            "digest_blocks_exempted": sum(
                j["digest_blocks_exempted"] for j in jobs),
            "decode_meters_moved": [j["decode_meters_moved"] for j in jobs
                                    if j["decode_meters_moved"]][:4],
            "pallas_merges": counters["kernel_pallas_merge_total"],
            "device_decisions": counters["offload_decisions_device_total"],
            "stage_ms_per_job": {
                k[len("compaction_pipeline_stage_"):-len("_total_ms")]:
                round(v / n, 1) for k, v in counters.items()
                if k.startswith("compaction_pipeline_stage_") and v}})
        return {"attempted": n,
                "failed": int(min(n, off_device + left_resident))}

    def verify(self, window: dict) -> dict:
        """Every job's SSTs against the native C++ job's bytes over the
        same four flushed files, and one job drawn from the seed decoded
        and held against the plain reference."""
        jobs = window["jobs"]
        differing = 0
        for job in jobs:
            same = _data_files(job["outputs"]) == self.native_files \
                and str(_props_but_lindex(job["outputs"])) == self.native_props
            differing += not same
        pick = int(datagen.rng_for(self.ctx.seed, 9).integers(0, len(jobs)))
        wrong_rows = reference.count_row_mismatches(
            self.expect, _decode_outputs(jobs[pick]["outputs"]))
        native_wrong = reference.count_row_mismatches(
            self.expect, _decode_outputs(self.native_outputs))
        return {"jobs_differing_from_native": (differing, 0),
                "rows_differing_from_reference": (wrong_rows, 0),
                "native_rows_differing_from_reference": (native_wrong, 0)}

    def close(self) -> None:
        if self.native_db is not None:
            self.native_db.close()


def _digest_exemption(moved: dict, checked: int, outs: list) -> int:
    """Takes the blocks that `checked` digest checks decoded off `moved`'s
    `sst_block_decode_total`: at most the data blocks of the job's
    `checked` largest output files (each check reads one file whole, with
    no block cache). Returns the blocks taken off."""
    from yugabyte_tpu.storage import SSTReader
    decoded = moved.get("sst_block_decode_total", 0)
    if not checked or not decoded:
        return 0
    blocks = []
    for _fid, path, _ in outs:
        reader = SSTReader(path)
        blocks.append(reader.n_blocks)
        reader.close()
    exempted = min(decoded, sum(sorted(blocks, reverse=True)[:checked]))
    if decoded == exempted:
        del moved["sst_block_decode_total"]
    else:
        moved["sst_block_decode_total"] = decoded - exempted
    return exempted


def _as_batch(run: dict):
    """One generated run as `write_batch_columns` takes it: parallel key
    and value lists with the hybrid-time and write-id arrays."""
    keys_blob, vals_blob = run["keys_blob"], run["vals_blob"]
    ko, vo = run["key_offs"].tolist(), run["val_offs"].tolist()
    keys = [keys_blob[a:b] for a, b in zip(ko, ko[1:])]
    values = [vals_blob[a:b] for a, b in zip(vo, vo[1:])]
    return keys, np.asarray(run["ht"], dtype=np.uint64), \
        np.asarray(run["wid"], dtype=np.uint32), values


