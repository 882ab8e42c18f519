"""The one owner of the native (C++) libraries: which binary, built how,
loaded once, absent why.

A library's file name carries a digest of everything that decides its
bytes — the source, its header deps, the compile arguments and what
`-march=native` means on this machine: `native/build/<stem>.<digest>.so`.
A file under that name is therefore the right binary, and the only way a
file gets that name is `os.replace` of a finished compile, so a
half-written library, or one built for another CPU (it dies of SIGILL),
is never opened by anybody. Processes that start together on a fresh tree
may each compile; a `flock` around the compile makes all but one wait, and
nothing breaks where it cannot be taken.

`load(stem)` returns the process's one `ctypes.CDLL` of a library or
raises `NativeUnavailable`; `available(stem)` is the cached probe. A
failed build is not retried in this process, and says why once on stderr
and for as long as the process lives through `unavailable()`.
"""

from __future__ import annotations

import contextlib
import ctypes
import fcntl
import glob
import hashlib
import importlib
import os
import platform
import subprocess
import sys
import tempfile
from typing import Dict, NamedTuple, Sequence

from yugabyte_tpu.utils.trace import TRACE

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
BUILD_DIR = os.path.join(NATIVE_DIR, "build")

_CXX = ("g++", "-O3", "-march=native", "-shared", "-fPIC")

class _Lib(NamedTuple):
    deps: Sequence[str]        # headers the source includes
    extra_args: Sequence[str]  # after the source on g++'s command line
    owner: str                 # the module whose `_bind(lib)` declares the types


# by stem; the source is native/<stem>.cc
LIBS: Dict[str, _Lib] = {
    "compaction_engine": _Lib(("merge_gc_core.h",), ("-lz", "-lpthread"),
                              "yugabyte_tpu.storage.native_engine"),
    "read_engine": _Lib(("merge_gc_core.h",), ("-lz",),
                        "yugabyte_tpu.storage.native_read"),
    "memtable_arena": _Lib((), (), "yugabyte_tpu.storage.memtable"),
    "compaction_baseline": _Lib(("merge_gc_core.h",), (),
                                "yugabyte_tpu.storage.cpu_baseline"),
}

_loaded: Dict[str, ctypes.CDLL] = {}
_failed: Dict[str, str] = {}


class NativeUnavailable(RuntimeError):
    """A native library could not be built or loaded here; str() is why."""


def _host_tag() -> str:
    """What `-march=native` means on this machine: a digest of the CPU's
    feature flags (the machine type where /proc/cpuinfo has none)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return hashlib.sha256(line.encode()).hexdigest()[:16]
    except OSError:  # yblint: contained(no /proc: fall back to the machine type)
        pass
    return platform.machine()


def lib_path(stem: str) -> str:
    """Where the binary of the tree as it stands, for this CPU, lives."""
    h = hashlib.sha256()
    for name in (stem + ".cc", *LIBS[stem].deps):
        with open(os.path.join(NATIVE_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    h.update("\0".join((*_CXX, *LIBS[stem].extra_args,
                         _host_tag())).encode())
    return os.path.join(BUILD_DIR, f"{stem}.{h.hexdigest()[:16]}.so")


def build(stem: str) -> str:
    """The library's path, compiled first if no file has that name yet.
    Raises CalledProcessError (stderr captured) where g++ fails."""
    lib = lib_path(stem)
    if os.path.exists(lib):
        return lib
    os.makedirs(BUILD_DIR, exist_ok=True)
    with open(os.path.join(BUILD_DIR, ".lock"), "w") as lock:
        try:
            fcntl.flock(lock, fcntl.LOCK_EX)
            alone = True
        except OSError:  # yblint: contained(a filesystem without flock: everybody compiles, os.replace keeps each result whole)
            alone = False
        if os.path.exists(lib):  # built while this process waited
            return lib
        fd, tmp = tempfile.mkstemp(dir=BUILD_DIR, prefix=stem + ".",
                                   suffix=".tmp")
        os.close(fd)
        try:
            subprocess.run([*_CXX, "-o", tmp,
                            os.path.join(NATIVE_DIR, stem + ".cc"),
                            *LIBS[stem].extra_args],
                           check=True, capture_output=True, text=True)
            os.chmod(tmp, 0o755)
            os.replace(tmp, lib)
        finally:
            with contextlib.suppress(FileNotFoundError):  # gone if replaced
                os.unlink(tmp)
        # binaries of other sources, arguments or CPUs are dead weight (an
        # unlinked library stays mapped where it is loaded); so is what a
        # killed compile left, which under the lock is nobody's in progress
        dead = glob.glob(os.path.join(BUILD_DIR, stem + ".*.so"))
        if alone:
            dead += glob.glob(os.path.join(BUILD_DIR, stem + ".*.tmp"))
        for path in dead:
            if path != lib:
                with contextlib.suppress(FileNotFoundError):
                    os.unlink(path)
    return lib


def _reason(e: Exception) -> str:
    if isinstance(e, subprocess.CalledProcessError):
        tail = (e.stderr or "").strip()[-2000:]
        return f"g++ exited {e.returncode}: {tail}"
    return f"{type(e).__name__}: {e}"


def load(stem: str) -> ctypes.CDLL:
    """The process's handle on a library, built and opened on first use,
    its functions' types declared by the owner's `_bind` before anybody
    sees it."""
    lib = _loaded.get(stem)
    if lib is not None:
        return lib
    if stem in _failed:
        raise NativeUnavailable(f"{stem}: {_failed[stem]}")
    try:
        lib = ctypes.CDLL(build(stem))
        importlib.import_module(LIBS[stem].owner)._bind(lib)
    except (OSError, subprocess.CalledProcessError, AttributeError) as e:
        reason = _reason(e)
        if _failed.setdefault(stem, reason) is reason:
            TRACE("native library %s unavailable: %s", stem, reason)
            print(f"[native_build] {stem} unavailable, its callers take "
                  f"their Python paths: {reason}", file=sys.stderr,
                  flush=True)
        raise NativeUnavailable(f"{stem}: {_failed[stem]}") from e
    return _loaded.setdefault(stem, lib)


def available(stem: str) -> bool:
    """Build-once probe: a failed build is cached, so the hot path does
    not spawn a doomed g++ per call (nor raise: two dict lookups).
    `unavailable()` keeps the reason."""
    if stem in _loaded:
        return True
    if stem in _failed:
        return False
    try:
        load(stem)
        return True
    except NativeUnavailable:
        return False


def unavailable() -> Dict[str, str]:
    """{stem: why} for every library this process tried and could not get."""
    return dict(_failed)
