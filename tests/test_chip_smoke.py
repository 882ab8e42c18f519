"""CPU rehearsal of chip_smoke.py: each phase's body at a tiny size on the
CPU backend, and the script's refusal to call a CPU run a chip run.

The phases check what the program does on a TPU, so the test steers what
the program observes about the platform — and nothing else, and never
through an option of the script: a COLD bucket's first job goes to the
device (bucket_health._on_tpu), and the merge takes the Pallas kernel
(interpret mode off-TPU, selected the way a developer selects it)."""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from yugabyte_tpu.storage import bucket_health

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.requires_native("compaction_engine")


@pytest.fixture(scope="module")
def as_on_tpu():
    mp = pytest.MonkeyPatch()
    mp.setattr(bucket_health, "_on_tpu", lambda: True)
    mp.setenv("YBTPU_MERGE_IMPL", "pallas")
    bucket_health.health_board().reset()
    yield
    mp.undo()
    bucket_health.health_board().reset()


@pytest.fixture(scope="module")
def clock():
    return chip_smoke.CompileClock()


@pytest.fixture(scope="module")
def storage_line(tmp_path_factory, as_on_tpu, clock):
    dev = jax.devices()[0]
    return chip_smoke.run_phase(
        "storage", clock, dev, chip_smoke.storage_phase, dev, 22, 2048,
        str(tmp_path_factory.mktemp("storage")))


def test_storage_phase_on_cpu(storage_line):
    line = storage_line
    assert line["ok"] and line["byte_identical_to_native"]
    assert line["rows"] == 4 * 2048 and line["reduced"]
    assert line["counters"]["offload_decisions_device_total"] >= 1
    assert line["counters"]["kernel_pallas_merge_total"] >= 1
    assert line["multi_get_keys"] == 1024 and line["multi_get_hits"] > 0


def test_cluster_phase_on_cpu(tmp_path, storage_line, clock):
    """After the storage phase, as in the script: the cluster's scan RPCs
    must find their executables compiled (a cold compile outlasts the RPC
    deadline), so the sizes here land its one tablet in the same n_pad
    bucket as the storage phase's result, as the real sizes do."""
    dev = jax.devices()[0]
    line = chip_smoke.run_phase(
        "cluster", clock, dev, chip_smoke.cluster_phase, dev.platform, 22,
        2400, str(tmp_path), 1)
    assert line["ok"] and line["acked_writes"] >= 2400
    assert line["replicas_checked"] == 3
    assert all(d["platform"] == "cpu" for d in line["tserver_devices"])


def test_dist_phase_on_four_virtual_devices(tmp_path, as_on_tpu, clock):
    devs = jax.devices()[:4]
    line = chip_smoke.run_phase(
        "dist", clock, devs[0], chip_smoke.dist_phase, devs, 22, 1024, 512,
        str(tmp_path))
    assert line["byte_identical_to_single_device"]
    assert line["all_devices_hold_shards"]
    assert line["pool_identical_to_sequential"]


def test_script_refuses_the_cpu_backend():
    """`JAX_PLATFORMS=cpu python chip_smoke.py` exits non-zero and says
    ok: false; it never prints a result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "cpu" in last["reason"]
    assert '"ok": true' not in p.stdout


def test_a_failing_phase_fails_the_script(monkeypatch, capsys):
    """An exception inside a phase is not caught: it propagates out of
    main() after `ok: false` is printed."""
    class _Dev:
        platform, device_kind = "tpu", "fake"

    def boom(*a, **kw):
        raise RuntimeError("injected")

    monkeypatch.setattr(jax, "devices", lambda: [_Dev()])
    monkeypatch.setattr(chip_smoke, "storage_phase", boom)
    monkeypatch.setattr(chip_smoke.Phase, "__init__",
                        lambda self, *a: None)
    with pytest.raises(RuntimeError, match="injected"):
        chip_smoke.main([])
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {"ok": False,
                                   "reason": "RuntimeError: injected"}
