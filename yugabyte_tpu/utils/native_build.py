"""Build-on-first-use for the native (C++) components.

One place owns the compile-if-stale rule so every .so rebuilds under the
same conditions: rebuild when missing, or when mtime <= the NEWEST of the
source and its header deps. `<=`, not `<`: a fresh checkout gives sources
and any stale binary the SAME mtime. A foreign-machine -march=native
binary must never run here (it dies of SIGILL, as the first copy of a
sandbox-built tree to the TPU host did), and mtimes cannot tell: every
binary carries a `<lib>.host` stamp of the CPU it was built for and is
rebuilt where the stamp differs.
"""

from __future__ import annotations

import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional, Sequence

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
BUILD_DIR = os.path.join(NATIVE_DIR, "build")

_lock = threading.Lock()


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


def _built_for(lib: str) -> str:
    try:
        with open(lib + ".host") as f:
            return f.read().strip()
    except OSError:  # yblint: contained(no stamp = built by an older tree or elsewhere: rebuild)
        return ""


def build_native_lib(src_name: str, lib_name: str,
                     deps: Sequence[str] = ("merge_gc_core.h",),
                     extra_args: Sequence[str] = ()) -> str:
    """Compile native/<src_name> into native/build/<lib_name> if stale.

    Returns the .so path; raises CalledProcessError on compile failure.
    """
    src = os.path.join(NATIVE_DIR, src_name)
    lib = os.path.join(BUILD_DIR, lib_name)
    with _lock:
        src_mtime = os.path.getmtime(src)
        for d in deps:
            p = os.path.join(NATIVE_DIR, d)
            if os.path.exists(p):
                src_mtime = max(src_mtime, os.path.getmtime(p))
        host = _host_tag()
        if (not os.path.exists(lib) or os.path.getmtime(lib) <= src_mtime
                or _built_for(lib) != host):
            os.makedirs(BUILD_DIR, exist_ok=True)
            subprocess.run(["g++", "-O3", "-march=native", "-shared",
                            "-fPIC", "-o", lib, src, *extra_args],
                           check=True)
            with open(lib + ".host", "w") as f:
                f.write(host)
    return lib
