"""The cells of PR 27, rehearsed whole on the CPU backend as
`test_controls.py` rehearses the first two: a sound run is `correct`, and
`correct` can fail.

  * `pool.kv64-v5e4` runs in a process of its own: `run.py --rehearse`
    takes `jax.devices()[:4]` and the server builds its mesh over every
    device JAX shows, so four CPU devices have to exist before JAX starts.
    Its control is `history_cutoff_zero`.
  * `ycsb-c.rf3` sends no update, so the driver's one control
    (`acked_write_dropped`: an update acknowledged and never sent) has
    nothing to drop and a run under it is `correct`; that `correct` can fail
    in this cell is shown by the fault of `test_controls.py`, an answer
    altered where it is produced.
"""

import json
import os
import subprocess
import sys

from benchmarks import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
POOL, YCSB_C = "pool.kv64-v5e4", "ycsb-c.rf3"


def rehearse_pool(seed, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", POOL, "--seed", str(seed), "--seconds", "1",
         "--rehearse", *extra],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    lines = [json.loads(l) for l in p.stdout.splitlines()
             if l.startswith("{")]
    line = lines[-1]
    assert list(line)[-1] == "compared" and line["rehearsal"]
    assert not line["metrics"]          # a CPU run never prints a rate
    assert p.returncode == (0 if line["correct"] else 1)
    return line, lines


def rehearse(capsys, workload, seed, seconds, *extra):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--rehearse", *extra])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["rehearsal"] and not line["metrics"]
    assert rc == (0 if line["correct"] else 1)
    return line, [json.loads(l) for l in out.out.splitlines()
                  if l.startswith("{")]


def test_the_pool_cell_is_correct_and_its_waves_are_full():
    line, lines = rehearse_pool(2**31 + 17, "--trace", "1")
    assert line["correct"] and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert all(c["value"] == 0 for c in line["compared"].values())
    tally = next(l for l in lines if "window_rounds" in l)
    assert tally["window_rounds"] >= 2 and tally["wave_fill"] == 1.0
    assert tally["mesh_jobs_off_dist_path"] == 0
    assert tally["pool"]["native_completions"] == 0
    assert tally["pool"]["wave_faults"] == 0
    assert line["attempted"] == 9 * tally["window_rounds"]
    deltas = next(l for l in lines if "traced_deltas" in l)["traced_deltas"]
    assert deltas["bench_rounds"] == 2
    assert deltas["bench_pool_wave_jobs"] == 16 \
        and deltas["bench_pool_wave_slots"] == 16
    for stage in ("pool_stage", "pool_wave", "pool_finish",
                  "pool_exclusive"):
        ms = deltas[f"compaction_pipeline_stage_{stage}_total_ms"]
        assert 0 < ms < deltas["bench_rounds_wall_ms"]


def test_the_pool_cells_control_is_not_correct():
    line, _ = rehearse_pool(41, "--control", "history_cutoff_zero")
    assert not line["correct"]
    assert line["compared"]["jobs_differing_from_native"]["value"] \
        == line["attempted"]
    assert line["compared"]["rows_differing_from_reference"]["value"] > 0
    assert line["compared"]["native_rows_differing_from_reference"][
        "value"] == 0


def test_the_pool_cells_real_sizes_leave_the_flags_alone():
    """Only the rehearsal's sizes carry a flag's value."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kv64-pool-v5e4.json")) as f:
        cfg = json.load(f)
    assert "distributed_compaction_min_rows" not in cfg["sizes"]
    assert cfg["sizes"]["tablets"] == cfg["deployment"]["tablets"] == 24
    wave, mesh = cfg["sizes"]["wave_job"], cfg["sizes"]["mesh_job"]
    assert wave["l0_runs"] * wave["rows_per_run"] == 1 << 18
    assert mesh["l0_runs"] * mesh["rows_per_run"] == 1 << 20
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kv64-tablet.json")) as f:
        assert cfg["shape"] == json.load(f)["shape"]


def test_ycsb_c_is_correct_and_sends_no_update(capsys):
    line, lines = rehearse(capsys, YCSB_C, 2**31 + 17, 2)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert all(c["value"] == 0 for c in line["compared"].values())
    window = next(l for l in lines if "read_batches" in l)
    assert window["update_batches"] == 0 and window["updates"] == 0
    assert window["read_batches"] > 0


def test_ycsb_c_differs_from_ycsb_a_in_the_mix_alone():
    def traffic(name):
        with open(os.path.join(ROOT, "benchmarks", "traffic",
                               name + ".json")) as f:
            return json.load(f)
    a, c = traffic("ycsb-a"), traffic("ycsb-c")
    assert c["operations"] == {"read": 1.0}
    for key in a:
        if key not in ("operations", "what", "warmup_note"):
            assert a[key] == c[key], key


def test_ycsb_c_a_read_answer_altered_is_not_correct(capsys, monkeypatch):
    """Every tenth multi_read returns two of its rows swapped."""
    from yugabyte_tpu.client.client import YBClient
    real = YBClient.multi_read
    calls = []

    def altered(self, table, doc_keys, *a, **kw):
        rows = real(self, table, doc_keys, *a, **kw)
        calls.append(1)
        if len(calls) % 10 == 0:
            i = next(i for i in range(1, len(rows))
                     if doc_keys[i] != doc_keys[0])
            rows[0], rows[i] = rows[i], rows[0]
        return rows

    monkeypatch.setattr(YBClient, "multi_read", altered)
    line, _ = rehearse(capsys, YCSB_C, 47, 3)
    assert len(calls) > 40 and not line["correct"]
    assert line["compared"]["reads_not_admissible"]["value"] >= 2
