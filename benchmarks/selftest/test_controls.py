"""`correct` can fail. Each case drives a whole run of the harness at the
configuration's `rehearse` sizes on the CPU backend (only the look for a
chip is skipped):

  * the control: one guarantee of the configuration broken (`--control`);
  * a fault: the timed path broken underneath the harness, an answer altered
    where it is produced.

And the harness refuses to call a CPU run, or a bare directory, a result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
COMPACT, YCSB = "compact-major.kv64", "ycsb-a.rf3"


def rehearse(capsys, workload, seed, seconds, *extra):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--rehearse", *extra])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared" and line["rehearsal"]
    assert not line["metrics"]          # a CPU run never prints a rate
    for name, c in line["compared"].items():
        assert f"compared {name}: {c['value']} (limit {c['limit']})" \
            in out.err
    assert rc == (0 if line["correct"] else 1)
    return line


@pytest.mark.parametrize("workload,seconds", [(COMPACT, 1), (YCSB, 2)])
def test_a_sound_run_is_correct(capsys, workload, seconds):
    line = rehearse(capsys, workload, 2**31 + 17, seconds)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert all(c["value"] == 0 for c in line["compared"].values())


@pytest.mark.parametrize("workload,seconds,control,number", [
    (COMPACT, 1, "history_cutoff_zero", "rows_differing_from_reference"),
    (YCSB, 2, "acked_write_dropped", "replica_records_wrong")])
def test_the_control_is_not_correct(capsys, workload, seconds, control,
                                    number):
    line = rehearse(capsys, workload, 41, seconds, "--control", control)
    assert not line["correct"]
    assert line["compared"][number]["value"] > 0


def test_fault_a_compaction_output_altered(capsys, monkeypatch):
    """One job of the window keeps what the GC should drop: its SST is a
    valid file with other rows in it."""
    from yugabyte_tpu.storage.db import DB
    real = DB.compact_all
    calls = []

    def altered(self):
        calls.append(1)
        if len(calls) == 5:
            self.opts.retention_policy = lambda: 0
        return real(self)

    monkeypatch.setattr(DB, "compact_all", altered)
    line = rehearse(capsys, COMPACT, 43, 1)
    assert len(calls) > 5 and not line["correct"]
    assert line["compared"]["jobs_differing_from_native"]["value"] == 1


def test_fault_a_read_answer_altered(capsys, monkeypatch):
    """Every tenth multi_read returns two of its rows swapped (the window's
    are checked, the warm-up's are not)."""
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
    line = rehearse(capsys, YCSB, 47, 3)
    assert len(calls) > 40 and not line["correct"]
    assert line["compared"]["reads_not_admissible"]["value"] >= 2


def test_a_cpu_run_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", COMPACT, "--seed", "1", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert p.returncode != 0 and '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_a_bare_directory_is_refused(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", COMPACT,
         "--seed", "1", "--seconds", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=300, cwd=tmp_path)
    assert p.returncode != 0 and '"correct"' not in p.stdout
