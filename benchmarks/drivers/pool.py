"""Driver for cells whose traffic is rounds of compaction jobs handed, all at
the same instant, to one tablet server's compaction threads.

Set-up builds a `ServerExecutionContext` on default flags (its mesh over
every chip JAX shows, its `CompactionPool`, its shared device slab cache and
block cache, its `PriorityThreadPool`), writes two templates from the seed
(the wave job's and the mesh job's L0 runs), runs the native C++ job over
each once, and prepares hard-linked checkpoints of them for the rounds. A
round moves one checkpoint into each of its tablets' directories, opens the
tablets' regular DBs with the server's own options, hands every
`DB.compact_all` to the server's thread pool at once and waits until all
have installed: `DB._dispatch_compaction -> pool.submit_compaction ->
pool_wait` is the path timed, and nothing here submits to the pool, sets a
flag or touches the health board. (`--rehearse` alone lowers
`distributed_compaction_min_rows`, through a key that only the
configuration's `rehearse` sizes carry, so that its small mesh job still
takes the whole mesh.)
"""

import os
import shutil
import threading
import time

from benchmarks import datagen, reference, roofline
from benchmarks.drivers.compaction import (_data_files, _decode_outputs,
                                           _props_but_lindex)
from benchmarks.program import ZERO_COUNTERS

CONTROLS = ("history_cutoff_zero",)
POOL_COUNTERS = ("waves", "wave_jobs", "native_completions", "wave_faults")


class _Template:
    """One job shape: its L0 runs in a template DB, what the plain reference
    says must survive, and the native C++ job's output over the same files."""

    def __init__(self, ctx, name, gen, size, key_space_share, cutoff):
        from yugabyte_tpu.storage import DB, DBOptions, SSTReader
        from yugabyte_tpu.storage.compaction import run_compaction_job
        self.name = name
        n_runs, rows = int(size["l0_runs"]), int(size["rows_per_run"])
        key_space = int(n_runs * rows * float(key_space_share))
        runs = [gen.run(rows, key_space, 1_000_000 * (g + 1))
                for g in range(n_runs)]
        self.expect = reference.major_compaction_survivors(gen, runs)
        self.rows_in = self.expect["rows_in"]
        self.device_bytes = roofline.compaction_job_bytes(
            self.rows_in, self.expect["rows_out"],
            int(self.expect["keys"].shape[1]),
            int(sum(len(r["vals_blob"]) for r in runs)),
            int(self.expect["val_len"].sum()))
        self.db = DB(os.path.join(ctx.workdir, "template-" + name),
                     DBOptions(auto_compact=False))
        for g, run in enumerate(runs):
            self.db.ingest_packed(run["keys_blob"], run["key_offs"],
                                  run["ht"], run["wid"], run["vals_blob"],
                                  run["val_offs"], op_id=(1, g + 1))
        inputs = [fm.path for fm in self.db.versions.live_files()]
        ctx.require(len(inputs) == n_runs, f"expected {n_runs} L0 files")
        readers = [SSTReader(p) for p in inputs]
        native_dir = os.path.join(ctx.workdir, "native-" + name)
        os.makedirs(native_dir)
        ids = iter(range(1000, 1 << 20))
        t0 = time.monotonic()
        native = run_compaction_job(readers, native_dir, lambda: next(ids),
                                    cutoff, True, device="native")
        native_s = time.monotonic() - t0
        for r in readers:
            r.close()
        ctx.require(native.rows_in == self.rows_in,
                    "native job read another row count than was written")
        self.native_outputs = native.outputs
        self.native_files = _data_files(native.outputs)
        self.native_props = str(_props_but_lindex(native.outputs))
        ctx.log({"native_job": {"template": name, "rows_in": native.rows_in,
                                "rows_out": native.rows_out,
                                "seconds": native_s,
                                "rows_per_s": native.rows_in / native_s}})

    def same_as_native(self, outputs) -> bool:
        return _data_files(outputs) == self.native_files \
            and str(_props_but_lindex(outputs)) == self.native_props


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.sizes = ctx.sizes
        self.traffic = ctx.traffic
        self.server = None
        self.templates = {}
        self.prepared = []          # per round: [(tablet, template, dir)]
        self.next_round = 0
        self.done_dirs = []

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        from yugabyte_tpu.common.hybrid_time import HybridTime
        from yugabyte_tpu.storage import native_engine
        from yugabyte_tpu.storage import offload_policy  # noqa: F401 (defines the flag)
        from yugabyte_tpu.tserver.server_context import (
            ServerExecutionContext)
        from yugabyte_tpu.utils import flags

        ctx = self.ctx
        if "distributed_compaction_min_rows" in self.sizes:  # rehearse only
            flags.set_flag("distributed_compaction_min_rows",
                           int(self.sizes["distributed_compaction_min_rows"]))
        for flag, default in (("device_offload_mode", "auto"),
                              ("tserver_device", "auto"),
                              ("tserver_mesh_compaction_pool", 1)):
            ctx.require(flags.get_flag(flag) == default,
                        f"{flag} is not at its default")
        ctx.require(native_engine.available(),
                    "native engine unavailable (g++ failed?)")
        self.server = ServerExecutionContext()
        ctx.require(self.server.compaction_pool is not None
                    and self.server.mesh.devices.size == len(ctx.devices),
                    f"the server built no compaction pool over "
                    f"{len(ctx.devices)} devices")
        self.n_slots = self.server.compaction_pool.n_slots
        # what a tablet of this server opens its regular DB with; the
        # driver compacts by hand, one job a DB
        options = self.server.tablet_options()
        options.auto_compact = False
        self.regular_db_options = options.regular_db_options
        cutoff = HybridTime.from_micros(
            int(ctx.config["shape"]["history_cutoff_us"])).value
        self.cutoff = 0 if ctx.control == "history_cutoff_zero" else cutoff
        gen = datagen.Kv64Runs(ctx.seed, ctx.config["shape"]["shares"])
        for name in ("wave", "mesh"):
            self.templates[name] = _Template(
                ctx, name, gen, self.sizes[name + "_job"],
                self.sizes["key_space_share"], cutoff)
        dist_min = flags.get_flag("distributed_compaction_min_rows")
        ctx.require(self.templates["wave"].rows_in < dist_min
                    <= self.templates["mesh"].rows_in,
                    "the wave job must sit under "
                    "distributed_compaction_min_rows and the mesh job at "
                    "or over it")

        self.n_tablets = int(self.sizes["tablets"])
        rnd = self.traffic["round"]
        self.round_jobs = ["mesh"] * int(rnd["mesh_jobs"]) \
            + ["wave"] * int(rnd["wave_jobs"])
        ctx.require(len(self.round_jobs) <= self.n_tablets,
                    "a round has more jobs than the server has tablets")
        self.round_rows = sum(self.templates[t].rows_in
                              for t in self.round_jobs)
        # least bytes a round's jobs move, per device of the mesh: the
        # trace reduction's busy seconds are a per-device mean
        self.round_device_bytes = sum(
            self.templates[t].device_bytes
            for t in self.round_jobs) // len(ctx.devices)
        self.tablet_dirs = [os.path.join(ctx.workdir, "tablets",
                                         f"tablet-{t:02d}")
                            for t in range(self.n_tablets)]
        for d in self.tablet_dirs + [os.path.join(ctx.workdir, "done")]:
            os.makedirs(d)
        self._prepare(int(self.traffic["prepared_rounds"]))

    def _prepare(self, n: int) -> None:
        """Round r takes tablets 9r .. 9r+8 mod 24, the mesh job on the
        first; every job gets a hard-linked checkpoint of its template."""
        for _ in range(n):
            r = len(self.prepared)
            first = r * len(self.round_jobs)
            jobs = []
            for j, template in enumerate(self.round_jobs):
                d = os.path.join(self.ctx.workdir, "prepared",
                                 f"r{r:05d}-j{j}")
                self.templates[template].db.checkpoint(d)
                jobs.append(((first + j) % self.n_tablets, template, d))
            self.prepared.append(jobs)

    def _open(self, db_dir: str):
        """The tablet's regular DB, as `tablet/tablet.py` opens it."""
        from yugabyte_tpu.storage import DB
        return DB(db_dir, self.regular_db_options(lambda: self.cutoff))

    # ------------------------------------------------------------ the loop
    def _round(self, jobs: list) -> list:
        """One round: every job's DB opened on a checkpoint moved into its
        tablet's directory, every `compact_all` handed to the server's
        compaction threads at once, all waited for."""
        ctx = self.ctx
        tasks = []
        with ctx.span("round_open"):
            for tablet, template, src in jobs:
                db_dir = os.path.join(self.tablet_dirs[tablet], "regular")
                os.rename(src, db_dir)
                tasks.append({"tablet": tablet, "template": template,
                              "dir": db_dir, "db": self._open(db_dir),
                              "done": threading.Event(), "error": None})

        def body(task):
            task["t0"] = time.monotonic()
            try:
                task["db"].compact_all()
            except BaseException as e:  # noqa: BLE001 — told to the driver
                task["error"] = e
            finally:
                task["seconds"] = time.monotonic() - task["t0"]
                task["done"].set()

        with ctx.span("round_body"):
            for task in tasks:
                self.server.pool.submit(lambda task=task: body(task))
            for task in tasks:
                task["done"].wait()
        out = []
        with ctx.span("round_close"):
            for task in tasks:
                db = task["db"]
                ctx.require(task["error"] is None
                            and db.background_error is None,
                            f"compaction failed: {task['error']!r} "
                            f"{db.background_error}")
                names = [os.path.basename(fm.path)
                         for fm in db.versions.live_files()]
                db.close()
                done = os.path.join(ctx.workdir, "done",
                                    os.path.basename(os.path.dirname(
                                        task["dir"]))
                                    + f"-{len(self.done_dirs):06d}")
                os.rename(task["dir"], done)
                self.done_dirs.append(done)
                out.append({"tablet": task["tablet"],
                            "template": task["template"],
                            "seconds": task["seconds"],
                            "outputs": [(None, os.path.join(done, n), None)
                                        for n in names]})
        return out

    def run(self, seconds: float, tracer) -> dict:
        """Rounds one after another until `seconds` have passed; the window
        closes when the round then in flight has installed all its jobs."""
        from yugabyte_tpu.utils.metrics import kernel_metrics
        ctx = self.ctx
        for d in self.done_dirs:        # an earlier (warm-up) call's outputs
            shutil.rmtree(d, ignore_errors=True)
        self.done_dirs = []
        dist_steps = kernel_metrics().counter(
            "kernel_dist_compact_dispatch_total", "")
        pool = self.server.compaction_pool
        rounds = []
        t0 = time.monotonic()
        while True:
            tracer.boundary(len(rounds))
            if self.next_round == len(self.prepared):
                with ctx.span("round_prepare"):
                    self._prepare(4)
            jobs = self.prepared[self.next_round]
            self.next_round += 1
            snap0, dist0 = pool.snapshot(), dist_steps.value()
            t_round = time.monotonic()
            done = self._round(jobs)
            now = time.monotonic()
            snap = pool.snapshot()
            facts = {k: snap[k] - snap0[k] for k in POOL_COUNTERS}
            facts["dist_steps"] = dist_steps.value() - dist0
            rounds.append({"jobs": done, "seconds": now - t_round,
                           "pool": facts})
            tracer.note(bench_rows_in=self.round_rows, bench_rounds=1,
                        bench_rounds_wall_ms=(now - t_round) * 1e3,
                        bench_min_device_bytes=self.round_device_bytes,
                        bench_pool_wave_jobs=facts["wave_jobs"],
                        bench_pool_wave_slots=facts["waves"] * self.n_slots,
                        bench_pool_native_completions=facts[
                            "native_completions"],
                        bench_pool_wave_faults=facts["wave_faults"])
            if now - t0 >= seconds:
                break
        tracer.boundary(len(rounds))
        return {"rounds": rounds, "seconds": time.monotonic() - t0}

    # ------------------------------------------------------------- results
    def metrics(self, window: dict, counters: dict) -> dict:
        rows = self.round_rows * len(window["rounds"])
        return {"compaction_rows_per_s": rows / window["seconds"]}

    def tally(self, window: dict, counters: dict) -> dict:
        """A job the native path completed (bucket demoted or quarantined,
        wave fault), and a mesh job that did not take the whole mesh, gave
        its user an SST and the cell no measurement: they count as failed."""
        rounds = window["rounds"]
        pool = {k: sum(r["pool"][k] for r in rounds) for k in POOL_COUNTERS}
        n_mesh = self.round_jobs.count("mesh") * len(rounds)
        n_wave = self.round_jobs.count("wave") * len(rounds)
        mesh_off_dist = n_mesh - sum(r["pool"]["dist_steps"] for r in rounds)
        off_device = (pool["native_completions"] + pool["wave_faults"]
                      + max(0, mesh_off_dist)
                      + counters["offload_decisions_native_total"]
                      + sum(counters[name] for name in ZERO_COUNTERS))
        n = n_mesh + n_wave

        def seconds_of(template):
            return sorted(round(j["seconds"], 4) for r in rounds
                          for j in r["jobs"] if j["template"] == template)
        wave_s = seconds_of("wave")
        self.ctx.log({
            "window_rounds": len(rounds), "window_s": window["seconds"],
            "round_seconds": [round(r["seconds"], 4) for r in rounds],
            "mesh_job_seconds": seconds_of("mesh"),
            "wave_job_seconds": {"min": wave_s[0],
                                 "median": wave_s[len(wave_s) // 2],
                                 "max": wave_s[-1]},
            "pool": pool, "mesh_slots": self.n_slots,
            "wave_fill": pool["wave_jobs"] / max(
                1, pool["waves"] * self.n_slots),
            "mesh_jobs_off_dist_path": mesh_off_dist,
            "jobs_off_device": off_device,
            "pallas_merges": counters["kernel_pallas_merge_total"],
            "device_decisions": counters["offload_decisions_device_total"],
            "stage_ms_per_round": {
                k[len("compaction_pipeline_stage_"):-len("_total_ms")]:
                round(v / len(rounds), 1) for k, v in counters.items()
                if k.startswith("compaction_pipeline_stage_") and v}})
        return {"attempted": n, "failed": int(min(off_device, n))}

    def verify(self, window: dict) -> dict:
        """Every job's SSTs against the native C++ job's bytes over the same
        template, and one wave job and one mesh job drawn from the seed
        decoded and held against the plain reference."""
        jobs = [j for r in window["rounds"] for j in r["jobs"]]
        differing = sum(
            not self.templates[j["template"]].same_as_native(j["outputs"])
            for j in jobs)
        rng = datagen.rng_for(self.ctx.seed, 9)
        wrong_rows = native_wrong = 0
        for name, template in self.templates.items():
            mine = [j for j in jobs if j["template"] == name]
            pick = mine[int(rng.integers(0, len(mine)))]
            wrong_rows += reference.count_row_mismatches(
                template.expect, _decode_outputs(pick["outputs"]))
            native_wrong += reference.count_row_mismatches(
                template.expect, _decode_outputs(template.native_outputs))
        return {"jobs_differing_from_native": (differing, 0),
                "rows_differing_from_reference": (wrong_rows, 0),
                "native_rows_differing_from_reference": (native_wrong, 0)}

    def close(self) -> None:
        if self.server is not None:
            self.server.shutdown()
        for template in self.templates.values():
            template.db.close()
