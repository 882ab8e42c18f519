"""Process-wide JAX configuration: persistent compilation cache.

The storage engine's kernels use shape bucketing (ops/merge_gc.py) so a
small set of executables covers all workloads, and this persistent cache
makes them a one-time cost per checkout rather than per process.

Where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and this
module names no directory; otherwise the cache lives at one fixed path
inside the checkout (the path is part of the cache key, so it must not
move between runs).
"""

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.makedirs(REPO_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


class Prewarm:
    """Ahead-of-traffic compiles for one kernel module: counts what
    compiled and names what the compiler refused, so the caller marks
    only real executables warmed.  A refused compile is reported, not
    raised — the first real dispatch of that shape surfaces it where
    fault containment can act on it."""

    def __init__(self, tag: str):
        self.tag = tag
        self.compiled = 0
        self.failed = []

    def warm(self, what: str, compile_fn, key=None) -> bool:
        """key: what `.failed` records instead of the description (the
        shape bucket an executable belongs to)."""
        try:
            compile_fn()
        except Exception as e:  # noqa: BLE001  # yblint: contained(a refused prewarm compile is recorded in .failed and reported to the caller; server startup must not die on it)
            import sys
            print(f"[{self.tag}] prewarm of {what} failed: {e!r}",
                  file=sys.stderr, flush=True)
            self.failed.append(what if key is None else key)
            return False
        self.compiled += 1
        return True


def lowering_text(jitted, args, statics) -> str:
    """StableHLO text of a jitted callable lowered against abstract args
    (ShapeDtypeStructs) — no device execution, no compilation.  The
    kernel compile-surface manifest (tools/analysis/kernel_manifest.py)
    fingerprints this text per (kernel, bucket) pair; the default
    StableHLO printing carries no source positions, so pure line drift
    cannot move the fingerprint."""
    return jitted.lower(*args, **statics).as_text()
