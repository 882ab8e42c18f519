#!/usr/bin/env python
"""bench_compare: key-by-key diff of two bench round JSONs, with a
regression gate.

    python tools/bench_compare.py OLD_ROUND.json NEW_ROUND.json
    python tools/bench_compare.py old.json new.json --check
    python tools/bench_compare.py cpu.json tpu.json --force

Rounds are the flat JSON documents bench.py emits (BENCH_*.json /
MULTICHIP_*.json). The tool flattens nested blocks into dotted keys,
keeps numeric leaves, and prints a labeled table of every key present
in both rounds: old, new, delta, percent change, and a direction-aware
verdict. Keys present in only one round are listed separately (a
renamed metric silently dropping out of comparison is itself a bug).

Backend labels are honored: each round's identity comes from
`meta.backend` (the PR-17 round stamp) falling back to the legacy
top-level `platform` key. Two rounds with different backends are
DIFFERENT EXPERIMENTS — a CPU round "regressing" against a TPU round
is noise — so the tool refuses the comparison (exit 2) unless --force.

Direction is inferred from the key's unit suffix:

  higher-better : *_per_sec, *_ratio, *_hits, vs_* / *_vs_* (speedup
                  ratios), *_scaling_*
  lower-better  : *_ms, *_s, *_mismatches, *_failures, *_fallbacks,
                  *_retries, *_errors
  neutral       : everything else — reported, never gated

With --check, every gated key's regression beyond its tolerance
(tools/bench_tolerances.json: `default_pct` plus per-key overrides;
keys matching an `ignore` prefix are never gated) fails the run with
exit 1 — the check.sh wiring that turns a bench regression into a red
build instead of a quietly worse committed round.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

# Sub-documents that are identity/provenance, not measurements.
_SKIP_SUBTREES = ("meta", "timeseries", "knobs")

_HIGHER_SUFFIXES = ("_per_sec", "_ratio", "_hits", "_ok")
_LOWER_SUFFIXES = ("_ms", "_s", "_mismatches", "_failures", "_fallbacks",
                   "_retries", "_errors", "_leaked_pins", "_leaked_leases")


def flatten(doc: dict, prefix: str = "") -> Dict[str, float]:
    """Dotted-key numeric leaves of a round document; identity
    subtrees and non-numeric leaves are skipped."""
    out: Dict[str, float] = {}
    for k, v in doc.items():
        if not prefix and k in _SKIP_SUBTREES:
            continue
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, prefix=f"{key}."))
        elif isinstance(v, bool):
            continue
        elif isinstance(v, (int, float)):
            out[key] = float(v)
    return out


def backend_of(doc: dict) -> str:
    meta = doc.get("meta")
    if isinstance(meta, dict) and meta.get("backend"):
        return str(meta["backend"])
    return str(doc.get("platform") or "unknown")


def direction(key: str) -> int:
    """+1 higher-better, -1 lower-better, 0 neutral (never gated)."""
    leaf = key.rsplit(".", 1)[-1]
    if leaf.startswith("vs_") or "_vs_" in leaf or "_scaling_" in leaf:
        return +1
    if leaf.endswith(_HIGHER_SUFFIXES):
        return +1
    if leaf.endswith(_LOWER_SUFFIXES):
        return -1
    return 0


def regression_pct(old: float, new: float, sign: int) -> float:
    """How much WORSE new is than old, in percent of old (0 when equal
    or improved). sign is direction()'s verdict."""
    if sign == 0 or old == 0:
        return 0.0
    worse = (old - new) if sign > 0 else (new - old)
    return max(0.0, 100.0 * worse / abs(old))


def load_tolerances(path: str) -> dict:
    try:
        with open(path) as f:
            tol = json.load(f)
    except OSError:
        return {"default_pct": 25.0, "keys": {}, "ignore": []}
    tol.setdefault("default_pct", 25.0)
    tol.setdefault("keys", {})
    tol.setdefault("ignore", [])
    return tol


def tolerance_for(key: str, tol: dict) -> Optional[float]:
    """The key's regression tolerance in percent, or None when the key
    is ignored (never gated)."""
    for pre in tol["ignore"]:
        if key.startswith(pre):
            return None
    if key in tol["keys"]:
        return float(tol["keys"][key])
    leaf = key.rsplit(".", 1)[-1]
    if leaf in tol["keys"]:
        return float(tol["keys"][leaf])
    return float(tol["default_pct"])


def compare(old: Dict[str, float], new: Dict[str, float], tol: dict
            ) -> Tuple[List[dict], List[str], List[str]]:
    rows = []
    for key in sorted(set(old) & set(new)):
        o, n = old[key], new[key]
        sign = direction(key)
        reg = regression_pct(o, n, sign)
        limit = tolerance_for(key, tol) if sign != 0 else None
        rows.append({
            "key": key, "old": o, "new": n, "delta": n - o,
            "pct": (100.0 * (n - o) / abs(o)) if o else 0.0,
            "dir": {1: "higher", -1: "lower", 0: "-"}[sign],
            "regression_pct": reg,
            "tolerance_pct": limit,
            "fails": limit is not None and reg > limit,
        })
    only_old = sorted(set(old) - set(new))
    only_new = sorted(set(new) - set(old))
    return rows, only_old, only_new


def print_table(rows: List[dict], label_a: str, label_b: str) -> None:
    w = max([len(r["key"]) for r in rows] + [12])
    print(f"{'key':<{w}}  {'old':>14}  {'new':>14}  {'change':>9}  "
          f"{'better':>7}  verdict")
    for r in rows:
        if r["fails"]:
            verdict = (f"REGRESSED ({r['regression_pct']:.1f}% > "
                       f"{r['tolerance_pct']:.0f}% tol)")
        elif r["dir"] == "-" or r["tolerance_pct"] is None:
            verdict = "info"
        elif r["regression_pct"] > 0:
            verdict = f"worse ({r['regression_pct']:.1f}% within tol)"
        else:
            verdict = "ok"
        print(f"{r['key']:<{w}}  {r['old']:>14.4g}  {r['new']:>14.4g}  "
              f"{r['pct']:>+8.1f}%  {r['dir']:>7}  {verdict}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_compare",
        description="diff two bench round JSONs key-by-key; --check "
                    "gates regressions against the committed tolerances")
    ap.add_argument("old")
    ap.add_argument("new")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when any gated key regresses beyond "
                         "its tolerance")
    ap.add_argument("--force", action="store_true",
                    help="compare across different backend labels "
                         "(CPU-vs-TPU rounds are different experiments; "
                         "refused by default)")
    ap.add_argument("--tolerances", default=None,
                    help="tolerance JSON (default: tools/"
                         "bench_tolerances.json next to this script)")
    args = ap.parse_args(argv)

    import os
    tol_path = args.tolerances or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "bench_tolerances.json")

    with open(args.old) as f:
        doc_a = json.load(f)
    with open(args.new) as f:
        doc_b = json.load(f)

    ba, bb = backend_of(doc_a), backend_of(doc_b)
    print(f"old: {args.old}  [backend={ba}]")
    print(f"new: {args.new}  [backend={bb}]")
    if ba != bb:
        if not args.force:
            print(f"bench_compare: REFUSING {ba}-vs-{bb} comparison — "
                  f"different backends measure different experiments; "
                  f"pass --force to override", file=sys.stderr)
            return 2
        print(f"bench_compare: WARNING — comparing across backends "
              f"({ba} vs {bb}) under --force; regressions below are "
              f"backend deltas, not code regressions")

    rows, only_old, only_new = compare(
        flatten(doc_a), flatten(doc_b), load_tolerances(tol_path))
    if rows:
        print_table(rows, args.old, args.new)
    else:
        print("no common numeric keys")
    if only_old:
        print(f"\nonly in {args.old}: {', '.join(only_old)}")
    if only_new:
        print(f"\nonly in {args.new}: {', '.join(only_new)}")

    failures = [r for r in rows if r["fails"]]
    if failures:
        print(f"\n{len(failures)} regression(s) beyond tolerance:")
        for r in failures:
            print(f"  {r['key']}: {r['old']:.4g} -> {r['new']:.4g} "
                  f"({r['regression_pct']:.1f}% worse, tolerance "
                  f"{r['tolerance_pct']:.0f}%)")
    if args.check:
        if failures:
            return 1
        print("\nbench_compare: OK (no regression beyond tolerance)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
