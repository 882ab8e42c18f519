#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

  python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which alone touches JAX. It loads, warms up, measures for
`--seconds`, checks what the timed path produced against the plain reference
and prints one JSON object as its last line. It exits non-zero, and prints no
result, when JAX's first device is not a TPU or there are fewer chips than the
cell asks for, and when a stage raises. `--rehearse` is the only way to run it
on the CPU: the configuration's `rehearse` sizes, answers checked, counts
printed, never a rate.

The runner holds no cell's name. A cell is a `workloads` entry of
BENCHMARK.json; its configuration file (sizes, shapes, guarantees) and its
traffic file (`benchmarks/traffic/<traffic>.json`, which names the driver
under `benchmarks/drivers/`) are found by name, and so is the file of every
per-layer metric (`benchmarks/layer_metrics/<name>.json`, which names its
reader under `benchmarks/readers/`).
"""

import argparse
import contextlib
import importlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class BenchFailure(Exception):
    """A stage of the run did not hold; there is no result to print."""


def process_age_s() -> float:
    """Seconds since this process was created (10 ms resolution)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Context:
    """What a driver gets: the cell's files, the seed, the devices, a work
    directory, and the three calls it makes back (span, log, require)."""

    def __init__(self, config, traffic, sizes, seed, control, workdir,
                 devices):
        self.config, self.traffic, self.sizes = config, traffic, sizes
        self.seed, self.control = seed, control
        self.workdir, self.devices = workdir, devices
        self.tracing = False

    def span(self, name: str):
        """A host span on the profiler's clock while a trace is on."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax.profiler
        return jax.profiler.TraceAnnotation("bench/" + name)

    @staticmethod
    def log(facts: dict) -> None:
        print(json.dumps(facts), flush=True)

    @staticmethod
    def require(cond, what: str) -> None:
        if not cond:
            raise BenchFailure(what)


class NoTrace:
    """The tracer of warm-up loops and of `--trace 0` runs."""

    def boundary(self, units_done: int) -> None:
        pass

    def note(self, **facts) -> None:
        pass

    def timed_start(self) -> None:
        pass

    def timed_stop(self) -> None:
        pass


class Tracer(NoTrace):
    """Wraps a short part of the window in a `jax.profiler` trace. The
    traffic file's `trace` says which: `{"after_units": a, "units": n}` (the
    driver reports unit boundaries, a compaction job each) or
    `{"after_s": a, "seconds": n}` (a timer). Counters are read at both ends,
    and the driver's notes of the work done between them are summed."""

    def __init__(self, ctx: Context, spec: dict, trace_dir: str):
        self.ctx, self.spec, self.dir = ctx, spec, trace_dir
        self.notes = {}
        self.before = None
        self.deltas = None
        self.reduction = None
        self._window = None
        self._timer = None
        self._halt = threading.Event()

    def _start(self) -> None:
        import jax.profiler
        from benchmarks import program
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # the program is Python: too heavy
        opts.host_tracer_level = 2
        self.before = program.counters()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.ctx.tracing = True
        self._window = jax.profiler.TraceAnnotation("bench/trace_window")
        self._window.__enter__()

    def _stop(self) -> None:
        import jax.profiler
        from benchmarks import program
        self._window.__exit__(None, None, None)
        self.ctx.tracing = False
        jax.profiler.stop_trace()
        self.deltas = program.delta(self.before, program.counters())
        self.deltas.update(self.notes)

    def boundary(self, units_done: int) -> None:
        if "units" not in self.spec:
            return
        first = int(self.spec["after_units"])
        if units_done == first and self.before is None:
            self._start()
        elif self.ctx.tracing and units_done >= first + int(
                self.spec["units"]):
            self._stop()

    def note(self, **facts) -> None:
        if self.ctx.tracing:
            for k, v in facts.items():
                self.notes[k] = self.notes.get(k, 0) + v

    def timed_start(self) -> None:
        if "seconds" not in self.spec:
            return

        def body():
            # one thread opens and closes the window span
            if self._halt.wait(float(self.spec["after_s"])):
                return
            self._start()
            self._halt.wait(float(self.spec["seconds"]))
            self._stop()
        self._timer = threading.Thread(target=body, name="bench-tracer")
        self._timer.start()

    def timed_stop(self) -> None:
        if self._timer is not None:
            self._halt.set()
            self._timer.join()

    def reduce(self, keep_dir=None) -> None:
        from benchmarks import trace_reduce
        if self.deltas is None:
            raise BenchFailure("the window ended before the trace did: "
                               "raise --seconds or shorten `trace`")
        self.reduction = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(self.dir)))
        if keep_dir:
            shutil.copytree(self.dir, keep_dir, dirs_exist_ok=True)


def find_cell(bench: dict, name: str):
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, cfg


def metrics_for(bench: dict, kind: str, cell_name: str) -> list:
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def layer_metrics(bench, cell_name, observed) -> dict:
    out = {}
    for m in metrics_for(bench, "per_layer", cell_name):
        spec = load_json(os.path.join(HERE, "layer_metrics",
                                      m["name"] + ".json"))
        reader = importlib.import_module("benchmarks.readers."
                                         + spec["reader"])
        value = reader.read(spec, observed)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args) -> dict:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg = find_cell(bench, args.workload)
    config = load_json(os.path.join(ROOT, cfg["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from benchmarks import program
    devices = jax.devices()
    device = program.device_facts(devices)
    if args.rehearse:
        program.steer_rehearsal()
    else:
        Context.require(device["platform"] == "tpu",
                        f"no TPU: jax.devices()[0].platform is "
                        f"{device['platform']!r}")
        Context.require(len(devices) >= cell["chips"],
                        f"the cell needs {cell['chips']} chips, JAX sees "
                        f"{len(devices)}")
    clock = program.CompileClock()
    work_root = os.path.join(ROOT, ".bench_work")
    os.makedirs(work_root, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix=cell["name"] + "-", dir=work_root)
    sizes = config["rehearse" if args.rehearse else "sizes"]
    ctx = Context(config, traffic, sizes, args.seed, args.control, workdir,
                  devices[:cell["chips"]])
    driver_mod = importlib.import_module("benchmarks.drivers."
                                         + traffic["driver"])
    Context.require(args.control is None
                    or args.control in driver_mod.CONTROLS,
                    f"driver {traffic['driver']!r} has no control "
                    f"{args.control!r}")
    driver = driver_mod.Driver(ctx)
    try:
        driver.setup()
        warm = traffic["warmup"]
        warm_s = float(warm["seconds"]) * (
            min(1.0, args.seconds / 10.0) if args.rehearse else 1.0)
        rounds = []
        for i in range(int(warm["max_rounds"])):
            c0 = clock.count
            driver.run(warm_s, NoTrace())
            rounds.append(clock.count - c0)
            if rounds[-1] == 0 and i + 1 >= int(warm["min_rounds"]):
                break
        tracer = NoTrace()
        if args.trace:
            tracer = Tracer(ctx, traffic["trace"],
                            os.path.join(workdir, "trace"))
        before = program.counters()
        c0, s0 = clock.count, clock.seconds
        setup_s = process_age_s()
        window = driver.run(float(args.seconds), tracer)
        counters = program.delta(before, program.counters())
        ctx.log({"counters_before_window": {
            k: v for k, v in before.items()
            if v and not k.startswith("serve_path_")}})
        ctx.log({"warmup_compiles_per_round": rounds,
                 "compiles_in_window": clock.count - c0,
                 "compile_s_in_window": clock.seconds - s0,
                 "compiles_total": clock.count,
                 "compile_s_total": clock.seconds})
        device["memory_peak_bytes"] = program.memory_peak_bytes(devices)
        tally = driver.tally(window, counters)
        values = driver.metrics(window, counters)
        values["setup_s"] = setup_s
        line = {"correct": None, **tally, "metrics": {}, "device": device}
        if args.trace:
            tracer.reduce(args.keep_trace)
            red = tracer.reduction
            device["busy_s"], device["window_s"] = \
                red["busy_s"], red["window_s"]
            if not args.rehearse:
                line["metrics"] = layer_metrics(bench, cell["name"], {
                    "deltas": tracer.deltas, "trace": red,
                    "window": values, "device_kind": device["kind"]})
                line["breakdown"] = {"device_ops": red["device_ops"],
                                     "idle_gaps": red["idle_gaps"]}
            ctx.log({"traced": {k: red[k] for k in (
                "window_s", "busy_s", "devices", "longest_gap_s",
                "device_modules", "idle_gaps")},
                "traced_deltas": {k: v for k, v in tracer.deltas.items()
                                  if v}})
        elif not args.rehearse:
            for m in metrics_for(bench, "end_to_end", cell["name"]):
                if m["name"] in values:
                    line["metrics"][m["name"]] = {"value": values[m["name"]],
                                                  "unit": m["unit"]}
        if args.rehearse:
            line["rehearsal"] = True    # counts and answers, never a rate
        t0 = time.monotonic()
        compared = driver.verify(window)
        ctx.log({"verify_s": time.monotonic() - t0})
    finally:
        try:
            driver.close()
        except Exception as e:  # a shutdown that trips takes no result away
            print(f"close: {type(e).__name__}: {e}", file=sys.stderr,
                  flush=True)
        shutil.rmtree(workdir, ignore_errors=True)
    line["correct"] = all(v <= limit for v, limit in compared.values())
    line["compared"] = {k: {"value": v, "limit": limit}
                        for k, (v, limit) in compared.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU run at the configuration's `rehearse` sizes: "
                         "answers and counts, never a rate")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the profiler's files there (to look at one "
                         "trace by hand)")
    ap.add_argument("--control", default=None,
                    help="run with one guarantee of the configuration "
                         "broken (the builder's proof that `correct` can "
                         "fail); never part of a benchmark run")
    args = ap.parse_args(argv)
    line = run(args)
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
