"""utils/native_build.py: one owner for the native libraries.

The binary's name carries a digest of what decides its bytes, a name only
ever appears by os.replace of a finished compile, and a failed build says
why. The first test is the reproduction of the race that made the same
tree count 1,185 passing tests in one run and 1,104 in the next: six
processes starting together on a tree that has no native/build yet.
"""

import ctypes
import glob
import os
import shutil
import signal
import subprocess
import sys
import time
import types

import pytest

from yugabyte_tpu.utils import native_build as nb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEMS = sorted(nb.LIBS)

# one call into each library, on the raw handle
CALLS = {
    "compaction_engine": "lib.ce_runcache_bytes()",
    "read_engine": "lib.rs_doc_key_len((ctypes.c_uint8 * 3)(83, 97, 0), 3)",
    "memtable_arena": "lib.mt_n(ctypes.c_void_p(lib.mt_new()))",
    "compaction_baseline": ("lib.compact_baseline(0, (ctypes.c_int64 * 1)(0),"
                            " 0, 0, *([None] * 7), 0, 1, 0, None, None, None)"),
}
WANT = {"compaction_engine": 0, "read_engine": 3, "memtable_arena": 0,
        "compaction_baseline": 0}

_CHILD = """
import ctypes, importlib, os, sys, time
from yugabyte_tpu.utils import native_build as nb
build_dir, stem, go = sys.argv[1:4]
nb.BUILD_DIR = build_dir
importlib.import_module(nb.LIBS[stem].owner)   # so the race is on the build
print("ready", flush=True)
while not os.path.exists(go):
    time.sleep(0.001)
lib = nb.load(stem)
print("answer", {call}, flush=True)
"""


@pytest.fixture
def fresh(tmp_path, monkeypatch):
    """native_build over a copy of native/ with an empty build directory
    and nothing loaded or failed yet; `fresh.spawns` lists the g++ runs."""
    native = tmp_path / "native"
    native.mkdir()
    for f in os.listdir(nb.NATIVE_DIR):
        if f.endswith((".cc", ".h")):
            shutil.copy(os.path.join(nb.NATIVE_DIR, f), native / f)
    monkeypatch.setattr(nb, "NATIVE_DIR", str(native))
    monkeypatch.setattr(nb, "BUILD_DIR", str(native / "build"))
    monkeypatch.setattr(nb, "_loaded", {})
    monkeypatch.setattr(nb, "_failed", {})
    spawns = []
    real_run = subprocess.run

    def run(argv, **kw):
        spawns.append(argv)
        return real_run(argv, **kw)
    monkeypatch.setattr(nb.subprocess, "run", run)
    return types.SimpleNamespace(native=native, build=native / "build",
                                 spawns=spawns)


def _built(build_dir):
    return sorted(os.path.basename(p)
                  for p in glob.glob(os.path.join(str(build_dir), "*"))
                  if not p.endswith(".lock"))


@pytest.mark.parametrize("stem", STEMS)
def test_six_processes_on_an_empty_build_dir_all_load(stem, tmp_path):
    build_dir, go = str(tmp_path / "build"), str(tmp_path / "go")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD.format(call=CALLS[stem]), build_dir,
         stem, go], stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO) for _ in range(6)]
    try:
        for p in procs:
            assert p.stdout.readline().strip() == "ready", p.stderr.read()
        open(go, "w").close()
        outs = [p.communicate(timeout=300) for p in procs]
    finally:
        for p in procs:
            p.kill()
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err
        assert out.strip() == f"answer {WANT[stem]}", (out, err)
    assert _built(build_dir) == [os.path.basename(nb.lib_path(stem))]


def test_failing_compile_midway_leaves_no_final_name(fresh, monkeypatch):
    def half_written(argv, **kw):
        with open(argv[argv.index("-o") + 1], "wb") as f:
            f.write(b"\x7fELF half a library")
        raise subprocess.CalledProcessError(1, argv, stderr="disk full")
    monkeypatch.setattr(nb.subprocess, "run", half_written)
    with pytest.raises(subprocess.CalledProcessError):
        nb.build("memtable_arena")
    assert _built(fresh.build) == []


def test_killed_compile_leaves_no_final_name_and_the_next_build_sweeps(
        fresh, tmp_path):
    """A fake g++ writes half its output and hangs; the builder is killed
    there. No library name exists, and the next build removes the rest."""
    bindir = tmp_path / "bin"
    bindir.mkdir()
    fake = bindir / "g++"
    fake.write_text('#!/bin/sh\nwhile [ "$1" != "-o" ]; do shift; done\n'
                    'printf half > "$2"\ntouch "$2.started"\nsleep 600\n')
    fake.chmod(0o755)
    env = dict(os.environ, PYTHONPATH=REPO,
               PATH=f"{bindir}{os.pathsep}{os.environ['PATH']}")
    child = subprocess.Popen(
        [sys.executable, "-c",
         "import sys\nfrom yugabyte_tpu.utils import native_build as nb\n"
         "nb.NATIVE_DIR, nb.BUILD_DIR = sys.argv[1:3]\n"
         "nb.build('memtable_arena')\n", str(fresh.native), str(fresh.build)],
        env=env, cwd=REPO, start_new_session=True)
    try:
        deadline = time.monotonic() + 120
        while not glob.glob(str(fresh.build / "*.started")):
            assert child.poll() is None and time.monotonic() < deadline
            time.sleep(0.01)
    finally:
        os.killpg(child.pid, signal.SIGKILL)
        child.wait()
    left = _built(fresh.build)
    assert left and not [f for f in left if f.endswith(".so")], left
    os.unlink(glob.glob(str(fresh.build / "*.started"))[0])
    lib = nb.build("memtable_arena")
    assert _built(fresh.build) == [os.path.basename(lib)]


def test_a_dep_header_edit_is_a_new_binary_and_an_untouched_tree_is_none(fresh):
    first, arena = nb.build("read_engine"), nb.lib_path("memtable_arena")
    assert len(fresh.spawns) == 1
    assert nb.build("read_engine") == first and len(fresh.spawns) == 1
    with open(fresh.native / "merge_gc_core.h", "a") as f:
        f.write("\n// edited\n")
    second = nb.build("read_engine")
    assert second != first and len(fresh.spawns) == 2
    assert _built(fresh.build) == [os.path.basename(second)]
    # memtable_arena.cc does not include the header: same name as before
    assert nb.lib_path("memtable_arena") == arena


def test_another_cpu_is_another_name_and_its_binary_is_never_opened(
        fresh, monkeypatch):
    """A -march=native binary built for another CPU dies of SIGILL here:
    it has another name, so nobody opens it, and a build sweeps it."""
    here = nb.lib_path("memtable_arena")
    with monkeypatch.context() as mp:
        mp.setattr(nb, "_host_tag", lambda: "some-other-cpu")
        foreign = nb.lib_path("memtable_arena")
    assert foreign != here
    os.makedirs(fresh.build)
    with open(foreign, "wb") as f:
        f.write(b"built for some other cpu")
    opened = []
    real_cdll = ctypes.CDLL
    monkeypatch.setattr(nb.ctypes, "CDLL",
                        lambda path: opened.append(path) or real_cdll(path))
    lib = nb.load("memtable_arena")
    assert lib.mt_n(ctypes.c_void_p(lib.mt_new())) == 0
    assert opened == [here] and len(fresh.spawns) == 1
    assert _built(fresh.build) == [os.path.basename(here)]


def test_failed_build_keeps_its_reason_says_it_once_and_is_not_retried(
        fresh, capfd):
    with open(fresh.native / "memtable_arena.cc", "a") as f:
        f.write("\nthis is not C++;\n")
    assert nb.available("memtable_arena") is False
    assert nb.available("memtable_arena") is False
    with pytest.raises(nb.NativeUnavailable, match="g\\+\\+ exited 1"):
        nb.load("memtable_arena")
    assert len(fresh.spawns) == 1, "a doomed g++ was spawned again"
    reason = nb.unavailable()["memtable_arena"]
    assert reason.startswith("g++ exited 1") and "error" in reason
    assert capfd.readouterr().err.count("memtable_arena unavailable") == 1
    assert _built(fresh.build) == []
    # the owner's probe reads the same answer
    from yugabyte_tpu.storage.memtable import native_memtable_available
    assert native_memtable_available() is False


@pytest.mark.parametrize("compiler,library,outcome", [
    ("/usr/bin/g++", False, "failed"),
    (None, False, "skipped"),
    ("/usr/bin/g++", True, "ran"),
])
def test_requires_native_fails_beside_a_compiler_and_skips_only_without(
        request, monkeypatch, compiler, library, outcome):
    conftest = next(p for p in request.config.pluginmanager.get_plugins()
                    if getattr(p, "__file__", "").endswith(
                        os.path.join("tests", "conftest.py")))
    monkeypatch.setattr(conftest.shutil, "which", lambda name: compiler)
    monkeypatch.setattr(nb, "_loaded", {"read_engine": object()} if library
                        else {})
    monkeypatch.setattr(nb, "_failed", {} if library else
                        {"read_engine": "g++ exited 1: no such header"})
    item = types.SimpleNamespace(iter_markers=lambda name: [
        pytest.mark.requires_native("read_engine").mark])
    if outcome == "ran":
        assert conftest.pytest_runtest_setup(item) is None
    elif outcome == "skipped":
        with pytest.raises(pytest.skip.Exception, match="no g\\+\\+"):
            conftest.pytest_runtest_setup(item)
    else:
        with pytest.raises(pytest.fail.Exception,
                           match="read_engine: g\\+\\+ exited 1: no such"):
            conftest.pytest_runtest_setup(item)
