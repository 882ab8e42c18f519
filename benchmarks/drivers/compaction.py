"""Driver for cells whose traffic is compaction jobs of one tablet's regular
DB. The body is `chip_smoke.storage_phase`'s compaction part (PR 22), turned
into a loop: set-up writes the L0 runs once from the seed, runs the native
C++ job over the same files once, and prepares one hard-linked checkpoint of
the inputs per job; the window opens a DB on a checkpoint, drives
`DB.compact_all()` on default flags with a cold device cache, closes it, and
goes on to the next. Nothing of the generator runs inside the window.
"""

import os
import time

import numpy as np

from benchmarks import datagen, reference, roofline
from benchmarks.program import ZERO_COUNTERS

CONTROLS = ("history_cutoff_zero",)


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.sizes = ctx.sizes
        self.traffic = ctx.traffic
        self.job_dirs = []
        self.next_job = 0
        self.expect = None
        self.template = None

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
        n_runs = int(self.sizes["l0_runs"])
        rows = int(self.sizes["rows_per_run"])
        key_space = int(n_runs * rows * float(self.sizes["key_space_share"]))
        gen = datagen.Kv64Runs(ctx.seed, ctx.config["shape"]["shares"])
        cutoff = HybridTime.from_micros(
            int(ctx.config["shape"]["history_cutoff_us"])).value
        self.cutoff = 0 if ctx.control == "history_cutoff_zero" else cutoff
        runs = [gen.run(rows, key_space, 1_000_000 * (g + 1))
                for g in range(n_runs)]
        self.expect = reference.major_compaction_survivors(gen, runs)
        self.key_bytes = int(self.expect["keys"].shape[1])
        self.value_bytes_in = int(sum(len(r["vals_blob"]) for r in runs))
        self.value_bytes_out = int(self.expect["val_len"].sum())

        template = DB(os.path.join(ctx.workdir, "template"),
                      DBOptions(auto_compact=False))
        for g, run in enumerate(runs):
            template.ingest_packed(run["keys_blob"], run["key_offs"],
                                   run["ht"], run["wid"], run["vals_blob"],
                                   run["val_offs"], op_id=(1, g + 1))
        del runs
        inputs = [fm.path for fm in template.versions.live_files()]
        ctx.require(len(inputs) == n_runs, f"expected {n_runs} L0 files")
        self.template = template

        # the plain C++ job over the same files: the configuration's stated
        # guarantee is byte identity with it
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

        self.db_options = dict(
            device=ctx.devices[0],
            device_cache=DeviceSlabCache(ctx.devices[0]),
            block_cache=BlockCache(256 << 20),       # a tserver's default
            retention_policy=lambda: self.cutoff, auto_compact=False)
        self._prepare(int(self.traffic["prepared_jobs"]))

    def _prepare(self, n: int) -> None:
        for _ in range(n):
            d = os.path.join(self.ctx.workdir, f"job{len(self.job_dirs):05d}")
            self.template.checkpoint(d)
            self.job_dirs.append(d)

    # ------------------------------------------------------------ the loop
    def run(self, seconds: float, tracer) -> dict:
        """Jobs one after another until `seconds` have passed; the window
        closes when the job then in flight has finished, so every job that
        was started is counted with all of its time."""
        from yugabyte_tpu.storage import DB, DBOptions
        from yugabyte_tpu.storage.bucket_health import health_board
        ctx = self.ctx
        jobs = []
        t0 = time.monotonic()
        while True:
            tracer.boundary(len(jobs))
            if self.next_job == len(self.job_dirs):
                with ctx.span("job_prepare"):
                    self._prepare(8)
            job_dir = self.job_dirs[self.next_job]
            self.next_job += 1
            t_job = time.monotonic()
            with ctx.span("job_open"):
                db = DB(job_dir, DBOptions(offload_policy=health_board(),
                                           **self.db_options))
            with ctx.span("job_body"):
                db.compact_all()
            ctx.require(db.background_error is None,
                        f"compaction parked the DB: {db.background_error}")
            outs = [(fm.file_id, fm.path, None)
                    for fm in db.versions.live_files()]
            with ctx.span("job_close"):
                db.close()
            now = time.monotonic()
            jobs.append({"dir": job_dir, "outputs": outs,
                         "seconds": now - t_job})
            tracer.note(bench_rows_in=self.expect["rows_in"],
                        bench_jobs=1, bench_jobs_wall_ms=(now - t_job) * 1e3,
                        bench_min_device_bytes=self._job_bytes())
            if now - t0 >= seconds:
                break
        tracer.boundary(len(jobs))
        return {"jobs": jobs, "seconds": time.monotonic() - t0}

    def _job_bytes(self) -> int:
        return roofline.compaction_job_bytes(
            self.expect["rows_in"], self.expect["rows_out"], self.key_bytes,
            self.value_bytes_in, self.value_bytes_out)

    # ------------------------------------------------------------- results
    def metrics(self, window: dict, counters: dict) -> dict:
        rows = self.expect["rows_in"] * len(window["jobs"])
        return {"compaction_rows_per_s": rows / window["seconds"]}

    def tally(self, window: dict, counters: dict) -> dict:
        """A job the device path refused and the native path completed gave
        its user an SST, and the cell no measurement: it counts as failed."""
        off_device = counters["offload_decisions_native_total"] + sum(
            counters[name] for name in ZERO_COUNTERS)
        n = len(window["jobs"])
        self.ctx.log({"window_jobs": n, "window_s": window["seconds"],
                      "job_seconds": [round(j["seconds"], 4)
                                      for j in window["jobs"]],
                      "jobs_off_device": off_device,
                      "pallas_merges": counters["kernel_pallas_merge_total"],
                      "device_decisions":
                          counters["offload_decisions_device_total"],
                      "encode_fallbacks":
                          counters["compaction_block_encode_fallback_total"],
                      "stage_ms_per_job": {
                          k[len("compaction_pipeline_stage_"):-len("_total_ms")]:
                          round(v / n, 1) for k, v in counters.items()
                          if k.startswith("compaction_pipeline_stage_")}})
        return {"attempted": n, "failed": int(min(off_device, n))}

    def verify(self, window: dict) -> dict:
        """Every job's SSTs against the native C++ job's bytes, and one job
        drawn from the seed decoded and held against the plain reference."""
        jobs = window["jobs"]
        differing = 0
        for job in jobs:
            same = _data_files(job["outputs"]) == self.native_files \
                and str(_props_but_lindex(job["outputs"])) == self.native_props
            differing += not same
        pick = int(datagen.rng_for(self.ctx.seed, 9).integers(0, len(jobs)))
        got = _decode_outputs(jobs[pick]["outputs"])
        wrong_rows = reference.count_row_mismatches(self.expect, got)
        native_wrong = reference.count_row_mismatches(
            self.expect, _decode_outputs(self.native_outputs))
        return {"jobs_differing_from_native": (differing, 0),
                "rows_differing_from_reference": (wrong_rows, 0),
                "native_rows_differing_from_reference": (native_wrong, 0)}

    def close(self) -> None:
        if self.template is not None:
            self.template.close()


def _data_files(outputs) -> list:
    from yugabyte_tpu.storage.sst import data_file_name
    out = []
    for _fid, base_path, _props in outputs:
        with open(data_file_name(base_path), "rb") as f:
            out.append(f.read())
    return out


def _props_but_lindex(outputs) -> list:
    """Base-file contents as the reader sees them, less the learned index:
    the device path fits one at write-through and the native job never
    does, so it alone may differ between the two jobs' base files."""
    from yugabyte_tpu.storage import SSTReader
    out = []
    for _fid, base_path, _props in outputs:
        r = SSTReader(base_path)
        d = dict(vars(r.props))
        d.pop("lindex", None)
        out.append((d, r.block_handles))
        r.close()
    return out


def _decode_outputs(outputs) -> dict:
    """The rows of a job's output SSTs, as arrays, read back through the
    program's reader."""
    from yugabyte_tpu.storage import SSTReader
    keys, key_len, ht, val_len, val_data = [], [], [], [], []
    for _fid, base_path, _props in outputs:
        r = SSTReader(base_path)
        slab = r.read_all()
        r.close()
        n = slab.n
        keys.append(slab.key_words.astype(">u4").view(np.uint8).reshape(
            n, slab.width_words * 4))
        key_len.append(np.asarray(slab.key_len, dtype=np.int64))
        ht.append((np.asarray(slab.ht_hi, dtype=np.uint64) << np.uint64(32))
                  | np.asarray(slab.ht_lo, dtype=np.uint64))
        offs = np.asarray(slab.values.offsets, dtype=np.int64)
        idx = np.asarray(slab.value_idx, dtype=np.int64)
        lens = offs[idx + 1] - offs[idx]
        src = np.repeat(offs[idx], lens) + (
            np.arange(int(lens.sum())) - np.repeat(np.cumsum(lens) - lens,
                                                   lens))
        val_len.append(lens)
        val_data.append(np.asarray(slab.values.data, dtype=np.uint8)[src])
    width = max(k.shape[1] for k in keys)
    keys = np.concatenate([np.pad(k, ((0, 0), (0, width - k.shape[1])))
                           for k in keys])
    val_len = np.concatenate(val_len)
    val_data = np.concatenate(val_data)
    return {"n": int(len(keys)), "keys": keys,
            "key_len": np.concatenate(key_len), "ht": np.concatenate(ht),
            "val_len": val_len, "val_data": val_data,
            "val_offs": np.concatenate([[0], np.cumsum(val_len)])}
