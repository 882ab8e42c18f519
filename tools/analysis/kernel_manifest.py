"""Kernel compile-surface manifest: the statically-checked contract for
every jitted kernel family.

ROADMAP item 5 demands that "every new kernel family must land inside the
bucket/prewarm/cache discipline" — this module turns that discipline from
tribal knowledge into a committed artifact plus two checks:

- `generate()` (device-free; run under JAX_PLATFORMS=cpu) enumerates the
  declared bucket lattice of every kernel family — the shapes
  `prewarm_buckets` warms, the chunk buckets `_chunk_target_rows` re-lands
  big jobs on, the radix/scan/gather side families — and
  `jax.eval_shape`/`.lower()`s each (kernel, bucket) pair.  NO device
  execution, no compilation: only abstract evaluation and StableHLO
  emission.  The result — input/output avals, static-arg signature,
  donation aliasing, a lowering fingerprint, prewarm coverage and the
  offload-policy quarantine key — is committed as
  `tools/analysis/kernel_manifest.json`.

- `check_manifest()` (pure stdlib, no jax import, sub-second) recomputes
  per-family SOURCE fingerprints over the AST of the symbols that define
  each family's compile surface and compares them (plus the budgets and
  the lattice invariants) against the committed JSON.  Any kernel change
  that could move the compile surface therefore fails tier-1 until the
  manifest is regenerated — making surface growth a reviewed decision
  (the diff of kernel_manifest.json) instead of an accident.

The compile-surface BUDGET is the distinct-executable count per family
(entries x their boolean/impl variant axes).  Exceeding it fails both
regeneration and the committed-JSON check; raising a budget is a one-line
reviewed edit here.

CLI:  python -m tools.analysis.kernel_manifest --check   (fast, no jax)
                                               --verify  (regen+compare)
                                               --write   (regenerate)
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
MANIFEST_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "kernel_manifest.json")
MANIFEST_RELPATH = "tools/analysis/kernel_manifest.json"

_RUN_MERGE = "yugabyte_tpu/ops/run_merge.py"
_MERGE_GC = "yugabyte_tpu/ops/merge_gc.py"
_SCAN = "yugabyte_tpu/ops/scan.py"
_SCAN_GROUP = "yugabyte_tpu/ops/scan_group.py"
_PALLAS = "yugabyte_tpu/ops/pallas_merge.py"
_DIST = "yugabyte_tpu/parallel/dist_compact.py"
_POLICY = "yugabyte_tpu/storage/offload_policy.py"
_DEVICE_CACHE = "yugabyte_tpu/storage/device_cache.py"
_POINT_READ = "yugabyte_tpu/ops/point_read.py"
_BLOOM = "yugabyte_tpu/storage/bloom.py"
_LEARNED = "yugabyte_tpu/storage/learned_index.py"
_BLOCK_CODEC = "yugabyte_tpu/ops/block_codec.py"
_BLOCK_FORMAT = "yugabyte_tpu/storage/block_format.py"

# Per-family compile-surface definition: which source symbols shape the
# lowered program (fingerprinted for the fast drift gate), the budget
# (max distinct executables the declared lattice may mint), and where a
# drift finding anchors.  gc_over_sorted is shared by every merge family:
# editing the GC half re-fingerprints all of them, which is exactly right.
FAMILIES: Dict[str, dict] = {
    "run_merge_fused": {
        "budget": 36,
        "anchor": _RUN_MERGE,
        "symbols": {
            _RUN_MERGE: [
                "_merge_gc_runs_impl", "merge_network", "_lex_gt",
                "_FUSED_STATICS", "_merge_gc_runs_fused",
                "_merge_gc_runs_fused_donated", "quantize_width",
                "_quantize_cmp", "_CMP_LATTICE", "_cmp_schedule",
                "_PREWARM_SHAPES", "prewarm_buckets", "run_bucket",
                "_chunk_target_rows",
            ],
            _MERGE_GC: ["gc_over_sorted", "pack_bits_u32", "pad_template"],
        },
    },
    "merge_gc_fused": {
        "budget": 8,
        "anchor": _MERGE_GC,
        "symbols": {
            _MERGE_GC: [
                "_merge_gc_fused", "sort_and_gc", "gc_over_sorted",
                "bucket_size", "build_sort_schedule", "full_sort_sequence",
            ],
        },
    },
    "scan_fused": {
        "budget": 16,
        "anchor": _SCAN,
        "symbols": {
            _SCAN: ["_scan_fused", "_pack_bound"],
            _MERGE_GC: ["sort_and_gc", "gc_over_sorted", "bucket_size"],
        },
    },
    "scan_filtered": {
        # query pushdown (ROADMAP item 5): snapshot scan + row-level
        # predicate filter in one program. Predicates/bounds ride as
        # OPERAND DATA padded to the PRED_SLOTS lattice, so the compile
        # key is (n_pad, w, p_pad) x the presorted axis (a single SST
        # source skips the merge sort + gather — the CPU fast path).
        "budget": 16,
        "anchor": _SCAN,
        "symbols": {
            _SCAN: ["_scan_filtered_fused", "_pushdown_base", "_row_pass",
                    "_segment_any", "_seg_or_combine", "_doc_segments",
                    "_key_byte_at", "_cmp_words", "_pack_bound",
                    "_concat_vals_fused", "pack_vals", "VAL_WORDS",
                    "_VAL_ROWS", "PRED_SLOTS", "pred_slot_bucket",
                    "_PREWARM_NPADS", "_PREWARM_W"],
            _MERGE_GC: ["sort_and_gc", "gc_over_sorted", "bucket_size",
                        "pack_bits_u32"],
        },
    },
    "scan_agg": {
        # fused aggregating scan: COUNT/SUM/MIN/MAX via segment-reduce
        # over the filtered row set — one dispatch per (tablet, query),
        # scalars only cross back. Aggregate column selectors are data
        # (AGG_SLOTS lattice); has_vals covers the COUNT(*)-only shape;
        # the presorted axis mirrors scan_filtered.
        "budget": 32,
        "anchor": _SCAN,
        "symbols": {
            _SCAN: ["_scan_agg_fused", "_pushdown_base", "_row_pass",
                    "_segment_any", "_seg_or_combine", "_doc_segments",
                    "_key_byte_at", "_cmp_words", "_pack_bound",
                    "VAL_WORDS", "_VAL_ROWS", "PRED_SLOTS", "AGG_SLOTS",
                    "pred_slot_bucket", "agg_slot_bucket",
                    "_PREWARM_NPADS", "_PREWARM_W"],
            _MERGE_GC: ["sort_and_gc", "gc_over_sorted", "bucket_size"],
        },
    },
    "scan_group_agg": {
        # the typed, grouped aggregate (TPC-H Q1 / Q6): one dispatch a
        # tablet lifts the referenced columns to row level, evaluates the
        # predicates, finds the groups and reduces in integer limbs.
        # Column, predicate, group and term selectors are DATA; the static
        # axes are the two shape classes (column slots, term slots), the
        # min/max outputs and the presorted single-source form.
        "budget": 16,
        "anchor": _SCAN_GROUP,
        "symbols": {
            _SCAN_GROUP: ["_scan_group_agg_fused", "_segmented_sum",
                          "_mul64", "_add64", "_neg64", "_u32",
                          "GROUP_SLOTS", "PRED_PAD", "SHAPE_CLASSES",
                          "MAX_FACTORS", "KEY_WORDS", "_WIDE",
                          "_TAG_INT64", "shape_class"],
            _SCAN: ["_pushdown_base", "_doc_segments", "_key_byte_at",
                    "_cmp_words", "VAL_WORDS", "_VAL_ROWS",
                    "_PREWARM_NPADS", "_PREWARM_W"],
            _MERGE_GC: ["sort_and_gc", "gc_over_sorted", "bucket_size"],
        },
    },
    "gather_staged": {
        "budget": 12,
        "anchor": _RUN_MERGE,
        "symbols": {
            _RUN_MERGE: ["_survivor_positions_impl", "_survivor_positions",
                         "_survivor_positions_donated",
                         "survivor_positions", "_gather_staged_output",
                         "gather_staged_output_span",
                         "gather_staged_outputs"],
            _MERGE_GC: ["bucket_size", "pad_template"],
        },
    },
    "restage_concat": {
        # device-side re-staging of cache-resident per-SST cols into the
        # merge layouts (run-major for the bitonic/lexsort path, one
        # contiguous padded matrix for the radix path) — the chained
        # L0->L1->L2 hot path launches the run-major form before every
        # merge over resident inputs
        "budget": 8,
        "anchor": _RUN_MERGE,
        "symbols": {
            _RUN_MERGE: ["_restage_concat", "_concat_staged_fused",
                         "stage_runs_from_staged"],
            _DEVICE_CACHE: ["concat_staged", "merged_column_stats"],
            _MERGE_GC: ["bucket_size", "pad_template"],
        },
    },
    "pallas_merge": {
        "budget": 12,
        "anchor": _PALLAS,
        "symbols": {
            _PALLAS: [
                "_pallas_merge_gc_fused", "_merge_level",
                "_make_tile_kernel", "_compute_splits", "default_tile",
                "supported",
            ],
            _MERGE_GC: ["gc_over_sorted"],
        },
    },
    "chunk_carve": {
        "budget": 8,
        "anchor": _RUN_MERGE,
        "symbols": {
            _RUN_MERGE: ["_chunk_split_search", "_carve_chunk",
                         "_W_ROUTE_CHUNK", "_chunk_target_rows"],
            _MERGE_GC: ["route_word_mask", "pad_template"],
        },
    },
    "point_read_probe": {
        # batched serve-path bloom gate: the device FNV hash over the
        # doc-key prefixes (one dispatch per multi_get chunk) + the
        # per-SST bit probe. storage/bloom.py is the CPU twin — its
        # builder arithmetic DEFINES the bit positions, so it is part of
        # this family's compile surface.
        "budget": 8,
        "anchor": _POINT_READ,
        "symbols": {
            _POINT_READ: ["_fnv64_fused", "_mul64_by_prime",
                          "_bloom_probe_fused", "bloom_device_words",
                          "pack_query_batch", "batch_bucket",
                          "BATCH_BUCKETS", "_PREWARM_MWORDS",
                          "_PREWARM_WIDTHS", "_K_MAX",
                          "BLOOM_PROBE_MAX_BITS"],
            _BLOOM: ["fnv64_masked", "BloomFilterBuilder", "BloomFilter"],
        },
    },
    "point_read_locate": {
        # vectorized point locate + survivor gather over resident slab
        # matrices, optionally seeded by the learned per-SST index
        # (ROADMAP item 4's serve-path kernel)
        "budget": 16,
        "anchor": _POINT_READ,
        "symbols": {
            _POINT_READ: ["_locate_gather_fused", "_seek_pred",
                          "_predict_pos", "_x_words", "_sub64",
                          "_f64ish", "_ge64", "_LG_WINDOW",
                          "batch_bucket", "BATCH_BUCKETS",
                          "_PREWARM_NPADS", "_PREWARM_WIDTHS"],
            _MERGE_GC: ["bucket_size", "pad_template"],
            _LEARNED: ["LINDEX_SEGMENTS", "LINDEX_MAX_ERR",
                       "model_operands", "_anchor_positions"],
        },
    },
    "index_fit": {
        # learned-index fit over staged (sorted) cols — runs at
        # flush/compaction write-through while the keys are in HBM for
        # free; the numpy twin in storage/learned_index.py shares the
        # inference arithmetic and is fingerprinted with it
        "budget": 4,
        "anchor": _POINT_READ,
        "symbols": {
            _POINT_READ: ["_index_fit_fused", "_predict_pos", "_x_words",
                          "_sub64", "_f64ish", "_ge64",
                          "fit_learned_index_device"],
            _LEARNED: ["fit_from_sorted_words", "fit_from_packed_keys",
                       "fit_from_slab", "finish_model", "_predict_host",
                       "_anchor_positions", "LINDEX_SEGMENTS",
                       "LINDEX_MIN_ENTRIES"],
        },
    },
    "block_decode": {
        # device SST block decode (ROADMAP item 2): raw block bodies ->
        # staged cols without host decode_block. The on-disk layout
        # (block_format.py) IS this family's compile surface: editing
        # encode_block/decode_block re-fingerprints both codec families.
        "budget": 8,
        "anchor": _BLOCK_CODEC,
        "symbols": {
            _BLOCK_CODEC: ["_block_decode_impl", "_block_decode_fused",
                           "_block_decode_fused_donated", "_bswap32",
                           "_quantize_width", "_PREWARM_DECODE",
                           "decode_avals", "prewarm_block_codec"],
            _BLOCK_FORMAT: ["encode_block", "decode_block",
                            "split_raw_block", "fixed_region_bytes",
                            "META_BYTES_PER_ROW"],
            _MERGE_GC: ["bucket_size", "pad_template"],
        },
    },
    "block_encode": {
        # device SST block encode: gathered survivor-span cols -> the
        # exact on-disk column encodings (host splices values + CRC).
        # Jit-keyed on shapes only (no static args), so the lattice is
        # the (n_out_pad, w_pad) span-gather vocabulary.
        "budget": 4,
        "anchor": _BLOCK_CODEC,
        "symbols": {
            _BLOCK_CODEC: ["_block_encode_impl", "_block_encode_fused",
                           "_bswap32", "encode_span", "_PREWARM_DECODE",
                           "prewarm_block_codec"],
            _BLOCK_FORMAT: ["encode_block", "split_raw_block",
                            "fixed_region_bytes", "META_BYTES_PER_ROW"],
            # the in-kernel bloom hash shares the point-read FNV limb
            # arithmetic; the numpy twin in storage/bloom.py DEFINES the
            # bit positions, so both are part of this compile surface
            _POINT_READ: ["_mul64_by_prime", "_FNV_OFFSET_HI",
                          "_FNV_OFFSET_LO", "_FNV_PRIME_LOW"],
            _BLOOM: ["fnv64_masked"],
            _MERGE_GC: ["bucket_size", "pad_template"],
        },
    },
    "dist_compact": {
        # mesh families: the key-range-sharded dist step (capacity
        # quantized to powers of two, n_shards from the mesh, both
        # is_major variants, a donated no-retry twin) and the
        # multi-tablet pool wave program (one job per device; buckets
        # shared with run_merge's lattice). shard_map cannot be lowered
        # without a real mesh, so entries are declared against the
        # 8-device bench mesh with no lowering fingerprint (like
        # pallas_merge) — prewarm_dist_compact warms exactly this
        # lattice on whatever mesh the server resolves.
        "budget": 16,
        "anchor": _DIST,
        "symbols": {
            _DIST: ["dist_compact_fn", "distributed_compact",
                    "distributed_compact_with_outputs",
                    "_distributed_compact_impl", "stage_sharded_cols",
                    "_dist_gather_span", "_quantized_capacity",
                    "_CAPACITY_MIN", "_MAX_CAPACITY_FACTOR",
                    "pool_wave_fn", "pooled_merge_gc", "stage_pool_slot",
                    "pool_slot_bucket", "prewarm_dist_compact",
                    "_PREWARM_CAPACITIES", "_PREWARM_POOL_SHAPES",
                    "_W_ROUTE", "_SAMPLES_PER_SHARD"],
            _RUN_MERGE: ["_merge_gc_runs_impl", "_cmp_schedule",
                         "quantize_width", "run_bucket",
                         "packed_run_ns"],
            _MERGE_GC: ["sort_and_gc", "gc_over_sorted",
                        "route_word_mask"],
        },
    },
}

# the row layout constant (ops/merge_gc.py): 8 metadata rows + key words
_ROW_WORDS = 8
_CMP_LATTICE = (2, 4, 6, 8, 12, 16, 24, 32)


# ---------------------------------------------------------------------------
# Source fingerprints (pure stdlib — the fast tier-1 gate must not pay a
# jax import, let alone a trace)
# ---------------------------------------------------------------------------

def _strip_docstrings(node: ast.AST) -> ast.AST:
    """Remove docstring Exprs so comment-grade edits don't trip the gate
    (the fingerprint must move only when the lowered program could)."""
    for n in ast.walk(node):
        body = getattr(n, "body", None)
        if isinstance(body, list) and body \
                and isinstance(body[0], ast.Expr) \
                and isinstance(body[0].value, ast.Constant) \
                and isinstance(body[0].value.value, str):
            del body[0]
            if not body:
                body.append(ast.Pass())
    return node


def _module_symbols(source: str) -> Dict[str, ast.AST]:
    """Top-level name -> def/assign node of one module."""
    out: Dict[str, ast.AST] = {}
    tree = ast.parse(source)
    for stmt in tree.body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            out[stmt.name] = stmt
        elif isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = stmt
        elif isinstance(stmt, ast.AnnAssign) \
                and isinstance(stmt.target, ast.Name):
            out[stmt.target.id] = stmt
    return out


def source_fingerprint(family: str, root: str = REPO_ROOT,
                       source_overrides: Optional[Dict[str, str]] = None
                       ) -> str:
    """sha256 over the (docstring-stripped, position-free) AST dumps of
    the family's surface-defining symbols.  `source_overrides` maps a
    relpath to replacement source text (synthetic-drift tests)."""
    h = hashlib.sha256()
    spec = FAMILIES[family]["symbols"]
    for relpath in sorted(spec):
        if source_overrides and relpath in source_overrides:
            src = source_overrides[relpath]
        else:
            with open(os.path.join(root, relpath), encoding="utf-8") as fh:
                src = fh.read()
        symbols = _module_symbols(src)
        for name in sorted(spec[relpath]):
            node = symbols.get(name)
            dump = ("<missing>" if node is None else
                    ast.dump(_strip_docstrings(node),
                             include_attributes=False))
            h.update(f"{relpath}:{name}={dump}\n".encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Lattice invariants (pure): a declared/warmed bucket must sit ON the
# quantization lattice — a shape off it warms (or budgets) nothing real.
# ---------------------------------------------------------------------------

def _is_pow2(x: int) -> bool:
    return x > 0 and (x & (x - 1)) == 0


def bucket_lattice_errors(bucket: Dict[str, int]) -> List[str]:
    """Violations of the (k_pad, m, w, n_cmp) lattice for a run-merge
    shaped bucket; empty means the bucket is a valid lattice point."""
    errs: List[str] = []
    k_pad = bucket.get("k_pad")
    m = bucket.get("m")
    w = bucket.get("w")
    n_cmp = bucket.get("n_cmp")
    if k_pad is not None and not _is_pow2(int(k_pad)):
        errs.append(f"k_pad={k_pad} is not a power of two")
    if m is not None and (not _is_pow2(int(m)) or int(m) < 256):
        errs.append(f"m={m} is not a power-of-two run bucket >= 256")
    if w is not None and (not _is_pow2(int(w)) or int(w) < 4):
        errs.append(f"w={w} is not a quantize_width point (pow2 >= 4)")
    if n_cmp is not None and int(n_cmp) not in _CMP_LATTICE:
        errs.append(f"n_cmp={n_cmp} is not on the _CMP_LATTICE "
                    f"{_CMP_LATTICE}")
    n_shards = bucket.get("n_shards")
    slots = bucket.get("slots")
    capacity = bucket.get("capacity")
    if n_shards is not None and not _is_pow2(int(n_shards)):
        errs.append(f"n_shards={n_shards} is not a power of two")
    if slots is not None and not _is_pow2(int(slots)):
        errs.append(f"slots={slots} is not a power of two")
    if capacity is not None and (not _is_pow2(int(capacity))
                                 or int(capacity) < 64):
        errs.append(f"capacity={capacity} is not a quantized exchange "
                    "capacity (pow2 >= 64)")
    return errs


# ---------------------------------------------------------------------------
# The fast committed-JSON check (tier-1; < 5s because it never imports jax)
# ---------------------------------------------------------------------------

def load_manifest(path: str = MANIFEST_PATH) -> Optional[dict]:
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


_UNSET = object()


def check_manifest(manifest=_UNSET,
                   root: str = REPO_ROOT,
                   source_overrides: Optional[Dict[str, str]] = None
                   ) -> List[Tuple[str, str, str]]:
    """(family, code, message) problems with the committed manifest vs the
    current sources.  Codes: manifest-missing, manifest-drift,
    budget-exceeded, budget-drift, off-lattice-bucket, family-missing.
    Omit `manifest` to check the committed JSON; an explicit None means
    "the manifest file is missing"."""
    if manifest is _UNSET:
        manifest = load_manifest()
    problems: List[Tuple[str, str, str]] = []
    if manifest is None:
        return [("run_merge_fused", "manifest-missing",
                 f"{MANIFEST_RELPATH} is missing or unparseable — "
                 "regenerate with `python -m tools.analysis."
                 "kernel_manifest --write`")]
    fams = manifest.get("families", {})
    for name, spec in FAMILIES.items():
        rec = fams.get(name)
        if rec is None:
            problems.append((name, "family-missing",
                             f"kernel family {name!r} has no manifest "
                             "record — regenerate the manifest"))
            continue
        fp = source_fingerprint(name, root, source_overrides)
        if rec.get("source_fingerprint") != fp:
            problems.append((
                name, "manifest-drift",
                f"compile surface of {name!r} changed (source "
                "fingerprint mismatch) without regenerating "
                f"{MANIFEST_RELPATH} — run `python -m tools.analysis."
                "kernel_manifest --write`, review the surface diff, and "
                "commit it"))
        if rec.get("budget") != spec["budget"]:
            problems.append((
                name, "budget-drift",
                f"{name!r} budget in the manifest ({rec.get('budget')}) "
                f"disagrees with the declared budget ({spec['budget']}) "
                "— regenerate the manifest"))
        n_exec = rec.get("distinct_executables")
        if spec["budget"] is not None and n_exec is not None \
                and n_exec > spec["budget"]:
            problems.append((
                name, "budget-exceeded",
                f"{name!r} declares {n_exec} distinct executables, over "
                f"its compile-surface budget of {spec['budget']} — "
                "shrink the lattice or raise the budget (a reviewed "
                "decision) in tools/analysis/kernel_manifest.py"))
        for entry in rec.get("entries", ()):
            errs = bucket_lattice_errors(entry.get("bucket", {}))
            for e in errs:
                problems.append((name, "off-lattice-bucket",
                                 f"{name} bucket {entry.get('key')}: {e}"))
    return problems


def entry_key(bucket: Dict[str, int], impl: str = "") -> str:
    parts = [f"{k}={bucket[k]}" for k in sorted(bucket)]
    if impl:
        parts.append(f"impl={impl}")
    return " ".join(parts)


# ---------------------------------------------------------------------------
# Generation (device-free: eval_shape + lower only; run with
# JAX_PLATFORMS=cpu — the CLI below forces it before importing jax)
# ---------------------------------------------------------------------------

def _aval_str(x) -> str:
    shape = "x".join(str(d) for d in x.shape)
    return f"{x.dtype.name}[{shape}]" if shape else f"{x.dtype.name}[]"


def _lowering_sha256(lowered_text: str) -> str:
    return hashlib.sha256(lowered_text.encode()).hexdigest()


def _full_cmp_rows(w: int) -> List[int]:
    """The unpruned compare schedule for key width w, quantized onto the
    n_cmp lattice — the schedule prewarm and the manifest share."""
    import numpy as np
    from yugabyte_tpu.ops.run_merge import _cmp_schedule
    rows, _n_cmp = _cmp_schedule(w, np.zeros(_ROW_WORDS + w, dtype=bool))
    return [int(r) for r in rows]


def _gen_run_merge_fused() -> dict:
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import run_merge
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    for (k_pad, m, w, n_cmp) in sorted(run_merge._PREWARM_SHAPES):
        r = _ROW_WORDS + w
        n = k_pad * m
        u32 = jax.ShapeDtypeStruct((), jnp.uint32)
        args = (jax.ShapeDtypeStruct((r, n), jnp.uint32),
                jax.ShapeDtypeStruct((n_cmp,), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.int32),
                u32, u32, u32, u32)
        for impl in ("lexsort", "network"):
            statics = dict(k_pad=k_pad, m=m, w=w, n_cmp=n_cmp,
                           is_major=True, retain_deletes=False,
                           snapshot=False, lexsort=(impl == "lexsort"))
            out = jax.eval_shape(
                lambda *a: run_merge._merge_gc_runs_fused(*a, **statics),
                *args)
            text = lowering_text(run_merge._merge_gc_runs_fused, args,
                                 statics)
            bucket = {"k_pad": k_pad, "m": m, "w": w, "n_cmp": n_cmp}
            entries.append({
                "key": entry_key(bucket, impl),
                "bucket": bucket,
                "impl": impl,
                "static_args": statics,
                "in_avals": [_aval_str(a) for a in args],
                "out_avals": [_aval_str(o) for o in
                              jax.tree_util.tree_leaves(out)],
                # the donated twin aliases arg 0 (carved chunk buffers);
                # both variants exist per bucket, as does is_major
                "donation": {"donate_argnums": [0], "variants": 2},
                "variant_axes": {"is_major": 2, "donate": 2},
                "executables": 4,
                "prewarmed": True,
                "quarantine_key": [k_pad, m],
                "lowering_sha256": _lowering_sha256(text),
            })
    return {"entries": entries}


def _gen_merge_gc_fused() -> dict:
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import merge_gc
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    w = 4
    r = _ROW_WORDS + w
    for n_pad in (1 << 16, 1 << 20):
        u32 = jax.ShapeDtypeStruct((), jnp.uint32)
        args = (jax.ShapeDtypeStruct((r, n_pad), jnp.uint32),
                jax.ShapeDtypeStruct((4 + w,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
                u32, u32, u32, u32)
        statics = dict(w=w, is_major=True, retain_deletes=False)
        out = jax.eval_shape(
            lambda *a: merge_gc._merge_gc_fused(*a, **statics), *args)
        text = lowering_text(merge_gc._merge_gc_fused, args, statics)
        bucket = {"n_pad": n_pad, "w": w}
        entries.append({
            "key": entry_key(bucket),
            "bucket": bucket,
            "static_args": statics,
            "in_avals": [_aval_str(a) for a in args],
            "out_avals": [_aval_str(o) for o in
                          jax.tree_util.tree_leaves(out)],
            # the pruned radix schedule rides as OPERANDS (sort_rows,
            # n_sort), so one executable covers every pruning — the
            # compile key is the shape bucket alone
            "donation": None,
            "variant_axes": {"is_major": 2},
            "executables": 2,
            "prewarmed": False,
            "quarantine_key": [1, n_pad],
            "lowering_sha256": _lowering_sha256(text),
        })
    return {"entries": entries}


def _gen_scan_fused() -> dict:
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import scan as scan_mod
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    w = 4
    r = _ROW_WORDS + w
    for n_pad in (1 << 16, 1 << 20):
        u32 = jax.ShapeDtypeStruct((), jnp.uint32)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        args = (jax.ShapeDtypeStruct((r, n_pad), jnp.uint32),
                jax.ShapeDtypeStruct((4 + w,), jnp.int32), i32,
                u32, u32, u32, u32,
                jax.ShapeDtypeStruct((w,), jnp.uint32), i32,
                jax.ShapeDtypeStruct((w,), jnp.uint32), i32)
        statics = dict(w=w, has_lower=True, has_upper=True,
                       upper_truncated=False)
        out = jax.eval_shape(
            lambda *a: scan_mod._scan_fused(*a, **statics), *args)
        text = lowering_text(scan_mod._scan_fused, args, statics)
        bucket = {"n_pad": n_pad, "w": w}
        entries.append({
            "key": entry_key(bucket),
            "bucket": bucket,
            "static_args": statics,
            "in_avals": [_aval_str(a) for a in args],
            "out_avals": [_aval_str(o) for o in
                          jax.tree_util.tree_leaves(out)],
            "donation": None,
            # reachable bound combos: none/lower/upper/both x the
            # truncated-upper refinement (truncation only with an upper)
            "variant_axes": {"bounds": 6},
            "executables": 6,
            "prewarmed": False,
            "quarantine_key": [1, n_pad],
            "lowering_sha256": _lowering_sha256(text),
        })
    return {"entries": entries}


def _scan_pushdown_args(jax, jnp, n_pad: int, w: int, p_pad: int,
                        has_vals: bool):
    sdt = jax.ShapeDtypeStruct
    i32 = sdt((), jnp.int32)
    u32 = sdt((), jnp.uint32)
    b1 = sdt((), jnp.bool_)
    from yugabyte_tpu.ops.scan import _VAL_ROWS, VAL_WORDS
    return (sdt((_ROW_WORDS + w, n_pad), jnp.uint32),
            sdt((_VAL_ROWS, n_pad if has_vals else 1), jnp.uint32),
            sdt((4 + w,), jnp.int32), i32, u32, u32, u32, u32,
            sdt((w,), jnp.uint32), i32, sdt((w,), jnp.uint32), i32,
            b1, b1,
            sdt((p_pad,), jnp.uint32), sdt((p_pad,), jnp.int32),
            sdt((p_pad,), jnp.int32),
            sdt((p_pad,), jnp.uint32), sdt((p_pad,), jnp.uint32),
            sdt((p_pad, VAL_WORDS), jnp.uint32), sdt((p_pad,), jnp.int32))


def _gen_scan_filtered() -> dict:
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import scan as scan_mod
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    w = scan_mod._PREWARM_W
    for n_pad in scan_mod._PREWARM_NPADS:
        for p_pad in scan_mod.PRED_SLOTS:
          for presorted in (False, True):
            args = _scan_pushdown_args(jax, jnp, n_pad, w, p_pad, True)
            statics = dict(w=w, p_pad=p_pad, presorted=presorted)
            out = jax.eval_shape(
                lambda *a: scan_mod._scan_filtered_fused(*a, **statics),
                *args)
            text = lowering_text(scan_mod._scan_filtered_fused, args,
                                 statics)
            bucket = {"n_pad": n_pad, "p_pad": p_pad, "w": w}
            entries.append({
                "key": "scan_filtered " + entry_key(
                    bucket, "presorted" if presorted else "merge"),
                "bucket": bucket,
                "impl": "presorted" if presorted else "merge",
                "static_args": statics,
                "in_avals": [_aval_str(a) for a in args],
                "out_avals": [_aval_str(o) for o in
                              jax.tree_util.tree_leaves(out)],
                # inputs are LIVE slab-cache entries (cols + vals):
                # donation is forbidden by design
                "donation": None,
                "variant_axes": {},
                "executables": 1,
                "prewarmed": True,
                "quarantine_key": [1, n_pad],
                "lowering_sha256": _lowering_sha256(text),
            })
    # the per-source vals concat (row-aligned twin of concat_staged):
    # one representative — real k varies with the source count, like
    # concat_staged_fused in the restage_concat family
    n_in, k, n_pad = 1 << 16, 4, 1 << 18
    from yugabyte_tpu.ops.scan import _VAL_ROWS
    parts = tuple(jax.ShapeDtypeStruct((_VAL_ROWS, n_in), jnp.uint32)
                  for _ in range(k))
    args = (parts, jax.ShapeDtypeStruct((k,), jnp.int32))
    statics = dict(n_pad=n_pad)
    out = jax.eval_shape(
        lambda *a: scan_mod._concat_vals_fused(*a, **statics), *args)
    text = lowering_text(scan_mod._concat_vals_fused, args, statics)
    bucket = {"n_pad": n_pad}
    entries.append({
        "key": "concat_vals " + entry_key(bucket),
        "bucket": bucket,
        "static_args": statics,
        "in_avals": [_aval_str(a) for a in
                     jax.tree_util.tree_leaves(args)],
        "out_avals": [_aval_str(o) for o in
                      jax.tree_util.tree_leaves(out)],
        "donation": None,
        "variant_axes": {},
        "executables": 1,
        "prewarmed": False,
        "quarantine_key": None,
        "lowering_sha256": _lowering_sha256(text),
    })
    return {"entries": entries}


def _gen_scan_agg() -> dict:
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import scan as scan_mod
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    w = scan_mod._PREWARM_W
    for n_pad in scan_mod._PREWARM_NPADS:
        combos = [(p, c, True) for p in scan_mod.PRED_SLOTS
                  for c in scan_mod.AGG_SLOTS] + [(1, 1, False)]
        for p_pad, c_pad, has_vals in combos:
          for presorted in (False, True):
            sdt = jax.ShapeDtypeStruct
            args = _scan_pushdown_args(jax, jnp, n_pad, w, p_pad,
                                       has_vals) + (
                sdt((c_pad,), jnp.uint32), sdt((c_pad,), jnp.uint32),
                sdt((c_pad,), jnp.uint32))
            statics = dict(w=w, p_pad=p_pad, c_pad=c_pad,
                           has_vals=has_vals, presorted=presorted)
            out = jax.eval_shape(
                lambda *a: scan_mod._scan_agg_fused(*a, **statics),
                *args)
            text = lowering_text(scan_mod._scan_agg_fused, args, statics)
            bucket = {"c_pad": c_pad, "n_pad": n_pad, "p_pad": p_pad,
                      "w": w}
            impl = ("vals" if has_vals else "novals") + (
                "-presorted" if presorted else "-merge")
            entries.append({
                "key": "scan_agg " + entry_key(bucket, impl),
                "bucket": bucket,
                "impl": impl,
                "static_args": statics,
                "in_avals": [_aval_str(a) for a in args],
                "out_avals": [_aval_str(o) for o in
                              jax.tree_util.tree_leaves(out)],
                "donation": None,
                "variant_axes": {},
                "executables": 1,
                "prewarmed": True,
                "quarantine_key": [1, n_pad],
                "lowering_sha256": _lowering_sha256(text),
            })
    return {"entries": entries}


def _scan_group_args(jax, jnp, n_pad: int, w: int, c_pad: int, t_pad: int):
    from yugabyte_tpu.ops import scan_group as sg
    from yugabyte_tpu.ops.scan import VAL_WORDS
    sdt = jax.ShapeDtypeStruct
    u32, i32 = jnp.uint32, jnp.int32
    p = sg.PRED_PAD
    return _scan_pushdown_args(jax, jnp, n_pad, w, 1, True)[:14] + (
        sdt((c_pad,), u32), sdt((p,), i32), sdt((p,), i32), sdt((p,), u32),
        sdt((p,), u32), sdt((p, VAL_WORDS), u32), sdt((p,), i32),
        sdt((2,), i32), sdt((t_pad, sg.MAX_FACTORS), i32),
        sdt((t_pad, sg.MAX_FACTORS), i32), sdt((t_pad, sg.MAX_FACTORS), u32),
        sdt((t_pad, sg.MAX_FACTORS), u32))


def _gen_scan_group_agg() -> dict:
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import scan as scan_mod
    from yugabyte_tpu.ops import scan_group as sg
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    w = scan_mod._PREWARM_W
    for n_pad in scan_mod._PREWARM_NPADS:
        for c_pad, t_pad in sg.SHAPE_CLASSES:
            for minmax, presorted in ((False, True), (False, False),
                                      (True, True)):
                args = _scan_group_args(jax, jnp, n_pad, w, c_pad, t_pad)
                statics = dict(w=w, c_pad=c_pad, t_pad=t_pad, minmax=minmax,
                               presorted=presorted)
                out = jax.eval_shape(
                    lambda *a: sg._scan_group_agg_fused(*a, **statics),
                    *args)
                text = lowering_text(sg._scan_group_agg_fused, args,
                                     statics)
                bucket = {"c_pad": c_pad, "n_pad": n_pad, "t_pad": t_pad,
                          "w": w}
                impl = ("minmax" if minmax else "sums") + (
                    "-presorted" if presorted else "-merge")
                entries.append({
                    "key": "scan_group_agg " + entry_key(bucket, impl),
                    "bucket": bucket,
                    "impl": impl,
                    "static_args": statics,
                    "in_avals": [_aval_str(a) for a in args],
                    "out_avals": [_aval_str(o) for o in
                                  jax.tree_util.tree_leaves(out)],
                    "donation": None,
                    "variant_axes": {},
                    "executables": 1,
                    "prewarmed": False,
                    "quarantine_key": [1, n_pad],
                    "lowering_sha256": _lowering_sha256(text),
                })
    return {"entries": entries}


def _gen_gather_staged() -> dict:
    """Write-through gather lattice, derived from _PREWARM_SHAPES: every
    prewarm bucket's merge is immediately followed by one survivor scan
    over its n_pad = k_pad*m keep mask and per-span output gathers whose
    top n_out_pad bucket is m (prewarm_buckets warms exactly these)."""
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import run_merge
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    w = 4
    r = _ROW_WORDS + w
    pos_pads = sorted({k_pad * m for (k_pad, m, _w, _c)
                       in run_merge._PREWARM_SHAPES})
    for n_pad in pos_pads:
        args = (jax.ShapeDtypeStruct((n_pad,), jnp.bool_),)
        out = jax.eval_shape(run_merge._survivor_positions, *args)
        text = lowering_text(run_merge._survivor_positions, args, {})
        bucket = {"n_pad": n_pad}
        entries.append({
            "key": "survivor_positions " + entry_key(bucket),
            "bucket": bucket,
            "static_args": {},
            "in_avals": [_aval_str(a) for a in args],
            "out_avals": [_aval_str(o) for o in
                          jax.tree_util.tree_leaves(out)],
            # the keep mask is the CHAINED buffer: dead after this scan,
            # so the donated twin reuses its HBM in place (the handle's
            # copy is poisoned — ops/run_merge.survivor_positions)
            "donation": {"donate_argnums": [0], "variants": 2},
            "variant_axes": {"donate": 2},
            "executables": 2,
            "prewarmed": True,
            "quarantine_key": None,
            "lowering_sha256": _lowering_sha256(text),
        })
    span_buckets = sorted({(k_pad * m, m) for (k_pad, m, _w, _c)
                           in run_merge._PREWARM_SHAPES})
    for n_pad, n_out_pad in span_buckets:
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        args = (jax.ShapeDtypeStruct((r, n_pad), jnp.uint32),
                jax.ShapeDtypeStruct((n_pad,), jnp.int32),
                jax.ShapeDtypeStruct((n_pad,), jnp.int32),
                jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
                i32, i32)
        statics = dict(n_out_pad=n_out_pad)
        out = jax.eval_shape(
            lambda *a: run_merge._gather_staged_output(*a, **statics),
            *args)
        text = lowering_text(run_merge._gather_staged_output, args,
                             statics)
        bucket = {"n_out_pad": n_out_pad, "n_pad": n_pad, "w": w}
        entries.append({
            "key": "gather_staged_output " + entry_key(bucket),
            "bucket": bucket,
            "static_args": statics,
            "in_avals": [_aval_str(a) for a in args],
            "out_avals": [_aval_str(o) for o in
                          jax.tree_util.tree_leaves(out)],
            "donation": None,
            "variant_axes": {},
            "executables": 1,
            "prewarmed": True,
            "quarantine_key": None,
            "lowering_sha256": _lowering_sha256(text),
        })
    return {"entries": entries}


def _gen_restage_concat() -> dict:
    """Device-side re-staging of cache-resident cols: the run-major form
    (_restage_concat) per prewarm bucket — warmed, it fronts every merge
    of the chained path — plus one representative of the radix-path
    concat (_concat_staged_fused), which only the skew fallback uses."""
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import run_merge
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    w = 4
    r = _ROW_WORDS + w
    for (k_pad, m, _w, _c) in sorted(set(run_merge._PREWARM_SHAPES)):
        parts = tuple(jax.ShapeDtypeStruct((r, m), jnp.uint32)
                      for _ in range(k_pad))
        args = (parts, jax.ShapeDtypeStruct((k_pad,), jnp.int32))
        statics = dict(w=w, m=m, k_pad=k_pad)
        out = jax.eval_shape(
            lambda *a: run_merge._restage_concat(*a, **statics), *args)
        text = lowering_text(run_merge._restage_concat, args, statics)
        bucket = {"k_pad": k_pad, "m": m, "w": w}
        entries.append({
            "key": "restage_concat " + entry_key(bucket),
            "bucket": bucket,
            "static_args": statics,
            "in_avals": [_aval_str(a) for a in
                         jax.tree_util.tree_leaves(args)],
            "out_avals": [_aval_str(o) for o in
                          jax.tree_util.tree_leaves(out)],
            # inputs are LIVE slab-cache entries — donation is forbidden
            # here by design (the cache must survive the merge)
            "donation": None,
            "variant_axes": {},
            "executables": 1,
            "prewarmed": True,
            "quarantine_key": [k_pad, m],
            "lowering_sha256": _lowering_sha256(text),
        })
    n_in, k, n_pad = 1 << 16, 4, 1 << 18
    parts = tuple(jax.ShapeDtypeStruct((r, n_in), jnp.uint32)
                  for _ in range(k))
    args = (parts, jax.ShapeDtypeStruct((k,), jnp.int32))
    statics = dict(w=w, n_pad=n_pad)
    out = jax.eval_shape(
        lambda *a: run_merge._concat_staged_fused(*a, **statics), *args)
    text = lowering_text(run_merge._concat_staged_fused, args, statics)
    bucket = {"n_pad": n_pad, "w": w}
    entries.append({
        "key": "concat_staged_fused " + entry_key(bucket),
        "bucket": bucket,
        "static_args": statics,
        "in_avals": [_aval_str(a) for a in
                     jax.tree_util.tree_leaves(args)],
        "out_avals": [_aval_str(o) for o in
                      jax.tree_util.tree_leaves(out)],
        "donation": None,
        "variant_axes": {},
        "executables": 1,
        "prewarmed": False,
        "quarantine_key": None,
        "lowering_sha256": _lowering_sha256(text),
    })
    return {"entries": entries}


def _gen_pallas_merge() -> dict:
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import pallas_merge, run_merge

    entries = []
    for (k_pad, m, w, n_cmp) in sorted(run_merge._PREWARM_SHAPES):
        r = _ROW_WORDS + w
        n = k_pad * m
        rp = ((r + 1 + 7) // 8) * 8
        tile = min(pallas_merge.default_tile(rp), m)
        cmp_rows = tuple(_full_cmp_rows(w))
        u32 = jax.ShapeDtypeStruct((), jnp.uint32)
        args = (jax.ShapeDtypeStruct((r, n), jnp.uint32),
                jax.ShapeDtypeStruct((n,), jnp.int32),
                u32, u32, u32, u32)
        statics = dict(k_pad=k_pad, m=m, w=w, cmp_rows_t=cmp_rows,
                       tile=tile, is_major=True, retain_deletes=False,
                       snapshot=False, interpret=True)
        out = jax.eval_shape(
            lambda *a: pallas_merge._pallas_merge_gc_fused(*a, **statics),
            *args)
        bucket = {"k_pad": k_pad, "m": m, "n_cmp": n_cmp, "w": w}
        entries.append({
            "key": entry_key(bucket, "pallas"),
            "bucket": bucket,
            "impl": "pallas",
            "static_args": {k: (list(v) if isinstance(v, tuple) else v)
                            for k, v in statics.items()},
            "in_avals": [_aval_str(a) for a in args],
            "out_avals": [_aval_str(o) for o in
                          jax.tree_util.tree_leaves(out)],
            "donation": None,
            # Mosaic lowering needs a real TPU target, so the manifest
            # records abstract eval only; the cmp_rows_t static means the
            # PRUNED schedule widens this family beyond the full-schedule
            # point warmed here (bounded in practice: schedules are
            # prefix-stable and the miss counters watch the tail)
            "variant_axes": {"is_major": 2},
            "executables": 2,
            "prewarmed": True,
            "quarantine_key": [k_pad, m],
            "lowering_sha256": None,
        })
    return {"entries": entries}


def _gen_chunk_carve() -> dict:
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import run_merge
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    w = 4
    r = _ROW_WORDS + w
    m, m_c, w_route = 1 << 20, 1 << 18, 4
    for k_pad in (2, 4):
        n_iters = int(m).bit_length() + 1
        args = (jax.ShapeDtypeStruct((r, k_pad * m), jnp.uint32),
                jax.ShapeDtypeStruct((k_pad,), jnp.int32),
                jax.ShapeDtypeStruct((7, w_route), jnp.uint32))
        statics = dict(k_pad=k_pad, m=m, w_route=w_route, n_iters=n_iters)
        out = jax.eval_shape(
            lambda *a: run_merge._chunk_split_search(*a, **statics), *args)
        text = lowering_text(run_merge._chunk_split_search, args, statics)
        bucket = {"k_pad": k_pad, "m": m, "n_iters": n_iters,
                  "w_route": w_route}
        entries.append({
            "key": "chunk_split_search " + entry_key(bucket),
            "bucket": bucket,
            "static_args": statics,
            "in_avals": [_aval_str(a) for a in args],
            "out_avals": [_aval_str(o) for o in
                          jax.tree_util.tree_leaves(out)],
            "donation": None,
            "variant_axes": {},
            "executables": 1,
            "prewarmed": False,
            "quarantine_key": [k_pad, m],
            "lowering_sha256": _lowering_sha256(text),
        })
        cargs = (jax.ShapeDtypeStruct((r, k_pad * m), jnp.uint32),
                 jax.ShapeDtypeStruct((k_pad,), jnp.int32),
                 jax.ShapeDtypeStruct((k_pad,), jnp.int32))
        cstatics = dict(m=m, m_c=m_c, k_pad=k_pad)
        out = jax.eval_shape(
            lambda *a: run_merge._carve_chunk(*a, **cstatics), *cargs)
        text = lowering_text(run_merge._carve_chunk, cargs, cstatics)
        bucket = {"k_pad": k_pad, "m": m, "m_c": m_c}
        entries.append({
            "key": "carve_chunk " + entry_key(bucket),
            "bucket": bucket,
            "static_args": cstatics,
            "in_avals": [_aval_str(a) for a in cargs],
            "out_avals": [_aval_str(o) for o in
                          jax.tree_util.tree_leaves(out)],
            "donation": None,
            "variant_axes": {},
            "executables": 1,
            "prewarmed": False,
            "quarantine_key": [k_pad, m],
            "lowering_sha256": _lowering_sha256(text),
        })
    return {"entries": entries}


def _gen_point_read_probe() -> dict:
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import point_read
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    sdt = jax.ShapeDtypeStruct
    i32 = sdt((), jnp.int32)
    u32 = sdt((), jnp.uint32)
    for b in point_read.BATCH_BUCKETS:
        for w in point_read._PREWARM_WIDTHS:
            args = (sdt((b, w), jnp.uint32), sdt((b,), jnp.int32))
            statics = dict(w=w)
            out = jax.eval_shape(
                lambda *a: point_read._fnv64_fused(*a, **statics), *args)
            text = lowering_text(point_read._fnv64_fused, args, statics)
            bucket = {"b": b, "w": w}
            entries.append({
                "key": "fnv64 " + entry_key(bucket),
                "bucket": bucket,
                "static_args": statics,
                "in_avals": [_aval_str(a) for a in args],
                "out_avals": [_aval_str(o) for o in
                              jax.tree_util.tree_leaves(out)],
                "donation": None,
                "variant_axes": {},
                "executables": 1,
                "prewarmed": True,
                "quarantine_key": None,
                "lowering_sha256": _lowering_sha256(text),
            })
        for mw in point_read._PREWARM_MWORDS:
            args = (sdt((b,), jnp.uint32), sdt((b,), jnp.uint32),
                    sdt((mw,), jnp.uint32), u32, i32)
            out = jax.eval_shape(point_read._bloom_probe_fused, *args)
            text = lowering_text(point_read._bloom_probe_fused, args, {})
            bucket = {"b": b, "m_words": mw}
            entries.append({
                "key": "bloom_probe " + entry_key(bucket),
                "bucket": bucket,
                "static_args": {},
                "in_avals": [_aval_str(a) for a in args],
                "out_avals": [_aval_str(o) for o in
                              jax.tree_util.tree_leaves(out)],
                "donation": None,
                "variant_axes": {},
                "executables": 1,
                "prewarmed": True,
                "quarantine_key": None,
                "lowering_sha256": _lowering_sha256(text),
            })
    return {"entries": entries}


def _gen_point_read_locate() -> dict:
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import point_read
    from yugabyte_tpu.storage.learned_index import LINDEX_SEGMENTS
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    sdt = jax.ShapeDtypeStruct
    i32 = sdt((), jnp.int32)
    u32 = sdt((), jnp.uint32)
    for b in point_read.BATCH_BUCKETS:
        for w in point_read._PREWARM_WIDTHS:
            for n_pad in point_read._PREWARM_NPADS:
                for use_model in (False, True):
                    args = (sdt((8 + w, n_pad), jnp.uint32), i32,
                            sdt((b, w), jnp.uint32), sdt((b,), jnp.int32),
                            u32, u32,
                            sdt((LINDEX_SEGMENTS + 1,), jnp.uint32),
                            sdt((LINDEX_SEGMENTS + 1,), jnp.uint32),
                            sdt((LINDEX_SEGMENTS + 1,), jnp.int32),
                            i32, i32)
                    statics = dict(w=w, use_model=use_model)
                    out = jax.eval_shape(
                        lambda *a: point_read._locate_gather_fused(
                            *a, **statics), *args)
                    text = lowering_text(point_read._locate_gather_fused,
                                         args, statics)
                    bucket = {"b": b, "n_pad": n_pad, "w": w}
                    impl = "model" if use_model else "exact"
                    entries.append({
                        "key": "locate_gather " + entry_key(bucket, impl),
                        "bucket": bucket,
                        "impl": impl,
                        "static_args": statics,
                        "in_avals": [_aval_str(a) for a in args],
                        "out_avals": [_aval_str(o) for o in
                                      jax.tree_util.tree_leaves(out)],
                        # inputs are LIVE slab-cache entries: donation is
                        # forbidden by design (the cache must survive)
                        "donation": None,
                        "variant_axes": {},
                        "executables": 1,
                        "prewarmed": True,
                        "quarantine_key": [1, n_pad],
                        "lowering_sha256": _lowering_sha256(text),
                    })
    return {"entries": entries}


def _gen_index_fit() -> dict:
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import point_read
    from yugabyte_tpu.storage.learned_index import LINDEX_SEGMENTS
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    sdt = jax.ShapeDtypeStruct
    i32 = sdt((), jnp.int32)
    for w in point_read._PREWARM_WIDTHS:
        for n_pad in point_read._PREWARM_NPADS:
            args = (sdt((8 + w, n_pad), jnp.uint32), i32)
            statics = dict(n_segments=LINDEX_SEGMENTS, w=w)
            out = jax.eval_shape(
                lambda *a: point_read._index_fit_fused(*a, **statics),
                *args)
            text = lowering_text(point_read._index_fit_fused, args,
                                 statics)
            bucket = {"n_pad": n_pad, "w": w}
            entries.append({
                "key": "index_fit " + entry_key(bucket),
                "bucket": bucket,
                "static_args": statics,
                "in_avals": [_aval_str(a) for a in args],
                "out_avals": [_aval_str(o) for o in
                              jax.tree_util.tree_leaves(out)],
                "donation": None,
                "variant_axes": {},
                "executables": 1,
                "prewarmed": True,
                "quarantine_key": None,
                "lowering_sha256": _lowering_sha256(text),
            })
    return {"entries": entries}


def _gen_block_decode() -> dict:
    """Device block-codec decode lattice: the _PREWARM_DECODE (n_pad,
    w_pad) points.  Shapes-only compile keys (no static args — the
    gather-free program is keyed by its padded column shapes alone)."""
    import jax
    from yugabyte_tpu.ops import block_codec
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    for n_pad, w_pad in sorted(block_codec._PREWARM_DECODE):
        args = block_codec.decode_avals(n_pad, w_pad)
        out = jax.eval_shape(block_codec._block_decode_fused, *args)
        text = lowering_text(block_codec._block_decode_fused, args, {})
        bucket = {"n_pad": n_pad, "w": w_pad}
        entries.append({
            "key": "block_decode " + entry_key(bucket),
            "bucket": bucket,
            "static_args": {},
            "in_avals": [_aval_str(a) for a in args],
            "out_avals": [_aval_str(o) for o in
                          jax.tree_util.tree_leaves(out)],
            # the raw-word upload is TRANSIENT (values were sliced host-
            # side before the upload), so the donated twin reuses its HBM
            # for the cols output on capable backends
            "donation": {"donate_argnums": [0], "variants": 2},
            "variant_axes": {"donate": 2},
            "executables": 2,
            "prewarmed": True,
            "quarantine_key": [1, n_pad],
            "lowering_sha256": _lowering_sha256(text),
        })
    return {"entries": entries}


def _gen_block_encode() -> dict:
    """Device block-codec encode lattice: one shapes-only program per
    span-gather bucket (_PREWARM_DECODE mirrors the span n_out_pad
    vocabulary); NEVER donated — the same span cols install into the
    slab cache after the SST hits disk."""
    import jax
    import jax.numpy as jnp
    from yugabyte_tpu.ops import block_codec
    from yugabyte_tpu.utils.jax_setup import lowering_text

    entries = []
    sdt = jax.ShapeDtypeStruct
    for n_pad, w_pad in sorted(block_codec._PREWARM_DECODE):
        args = (sdt((_ROW_WORDS + w_pad, n_pad), jnp.uint32),)
        out = jax.eval_shape(block_codec._block_encode_fused, *args)
        text = lowering_text(block_codec._block_encode_fused, args, {})
        bucket = {"n_pad": n_pad, "w": w_pad}
        entries.append({
            "key": "block_encode " + entry_key(bucket),
            "bucket": bucket,
            "static_args": {},
            "in_avals": [_aval_str(a) for a in args],
            "out_avals": [_aval_str(o) for o in
                          jax.tree_util.tree_leaves(out)],
            "donation": None,
            "variant_axes": {},
            "executables": 1,
            "prewarmed": True,
            "quarantine_key": [1, n_pad],
            "lowering_sha256": _lowering_sha256(text),
        })
    return {"entries": entries}


def _gen_dist_compact() -> dict:
    # shard_map needs a real mesh, so these entries are declared (no
    # lowering fingerprint, like pallas_merge) against the 8-device
    # bench mesh: capacity is quantized to a power of two in
    # distributed_compact before the lru_cache key, and
    # prewarm_dist_compact warms exactly this lattice on the server's
    # actual mesh. Drift is caught by the source fingerprint.
    from yugabyte_tpu.parallel import dist_compact as dist_mod

    n_shards = 8
    entries = []
    for capacity in sorted(dist_mod._PREWARM_CAPACITIES):
        bucket = {"capacity": capacity, "n_shards": n_shards}
        entries.append({
            "key": "dist_compact " + entry_key(bucket),
            "bucket": bucket,
            "static_args": {"capacity": capacity,
                            "retain_deletes": False},
            "in_avals": None,   # mesh-dependent; see compile_keys
            "out_avals": None,
            # the no-retry twin donates the sharded input cols so XLA
            # reuses their HBM for the exchange scratch
            "donation": {"donate_argnums": [0], "variants": 2},
            "variant_axes": {"is_major": 2, "donate": 2},
            "executables": 4,
            "prewarmed": True,
            "quarantine_key": [n_shards, capacity],
            "lowering_sha256": None,
        })
    for (k_pad, m, w, n_cmp) in sorted(dist_mod._PREWARM_POOL_SHAPES):
        bucket = {"k_pad": k_pad, "m": m, "n_cmp": n_cmp,
                  "slots": n_shards, "w": w}
        entries.append({
            "key": "pool_wave " + entry_key(bucket),
            "bucket": bucket,
            "static_args": {"k_pad": k_pad, "m": m, "w": w,
                            "n_cmp": n_cmp, "retain_deletes": False},
            "in_avals": None,
            "out_avals": None,
            # wave inputs may be live cache-partition entries: the wave
            # program never donates
            "donation": None,
            "variant_axes": {"is_major": 2},
            "executables": 2,
            "prewarmed": True,
            "quarantine_key": [k_pad, m],
            "lowering_sha256": None,
        })
    return {
        "entries": entries,
        "compile_keys": {
            "capacity": "power-of-two >= 64 (quantized in "
                        "distributed_compact before the lru_cache key)",
            "n_shards": "mesh-determined (8-device bench mesh declared)",
            "is_major": [True, False],
            "retain_deletes": [False],
        },
    }


_GENERATORS = {
    "run_merge_fused": _gen_run_merge_fused,
    "merge_gc_fused": _gen_merge_gc_fused,
    "scan_fused": _gen_scan_fused,
    "scan_filtered": _gen_scan_filtered,
    "scan_agg": _gen_scan_agg,
    "scan_group_agg": _gen_scan_group_agg,
    "gather_staged": _gen_gather_staged,
    "restage_concat": _gen_restage_concat,
    "pallas_merge": _gen_pallas_merge,
    "chunk_carve": _gen_chunk_carve,
    "point_read_probe": _gen_point_read_probe,
    "point_read_locate": _gen_point_read_locate,
    "index_fit": _gen_index_fit,
    "block_decode": _gen_block_decode,
    "block_encode": _gen_block_encode,
    "dist_compact": _gen_dist_compact,
}


def generate(root: str = REPO_ROOT) -> dict:
    """Regenerate the full manifest (imports jax; run under
    JAX_PLATFORMS=cpu — eval_shape/lower only, nothing executes)."""
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "kernel_manifest.generate must run device-free: set "
            "JAX_PLATFORMS=cpu (the committed fingerprints are the CPU "
            f"lowering), got backend {jax.default_backend()!r}")
    families = {}
    for name, spec in FAMILIES.items():
        rec = _GENERATORS[name]()
        entries = rec.get("entries", [])
        rec.update({
            "source_fingerprint": source_fingerprint(name, root),
            "budget": spec["budget"],
            "distinct_executables": (
                sum(e["executables"] for e in entries)
                if entries else None),
        })
        for e in entries:
            errs = bucket_lattice_errors(e.get("bucket", {}))
            if errs:
                raise RuntimeError(
                    f"declared bucket off the lattice in {name}: "
                    f"{e['key']}: {'; '.join(errs)}")
        n = rec["distinct_executables"]
        if spec["budget"] is not None and n is not None \
                and n > spec["budget"]:
            raise RuntimeError(
                f"compile-surface budget exceeded for {name}: {n} "
                f"declared executables > budget {spec['budget']} — "
                "shrink the lattice or raise the budget in "
                "tools/analysis/kernel_manifest.py (a reviewed decision)")
        families[name] = rec
    return {
        "version": 1,
        "platform": "cpu",
        "jax_version": jax.__version__,
        "families": families,
    }


def manifest_bytes(manifest: dict) -> bytes:
    return (json.dumps(manifest, indent=1, sort_keys=True) + "\n").encode()


def surface_counts(manifest: Optional[dict] = None) -> Dict[str, int]:
    """family -> distinct-executable count from the committed manifest
    (0 for fingerprint-only families); used by the bench report and the
    kernel_compile_surface gauges."""
    if manifest is None:
        manifest = load_manifest()
    out: Dict[str, int] = {}
    if not manifest:
        return out
    for name, rec in sorted(manifest.get("families", {}).items()):
        out[name] = int(rec.get("distinct_executables") or 0)
    return out


def quarantine_surface_keys(manifest: Optional[dict] = None
                            ) -> List[Tuple[int, int]]:
    """The (k_pad, m) offload-policy quarantine keys of every declared
    bucket — the shape vocabulary storage/offload_policy.py speaks."""
    if manifest is None:
        manifest = load_manifest()
    keys = set()
    if manifest:
        for rec in manifest.get("families", {}).values():
            for e in rec.get("entries", ()):
                qk = e.get("quarantine_key")
                if qk:
                    keys.add((int(qk[0]), int(qk[1])))
    return sorted(keys)


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    import sys
    import time

    ap = argparse.ArgumentParser(
        prog="python -m tools.analysis.kernel_manifest",
        description="kernel compile-surface manifest: fast drift check / "
                    "device-free regeneration")
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--check", action="store_true",
                   help="fast source-fingerprint + budget check against "
                        "the committed JSON (no jax import; < 5s)")
    g.add_argument("--verify", action="store_true",
                   help="regenerate in memory (JAX_PLATFORMS=cpu, "
                        "eval_shape/lower only) and byte-compare with "
                        "the committed JSON")
    g.add_argument("--write", action="store_true",
                   help="regenerate and write the committed JSON")
    ap.add_argument("--path", default=MANIFEST_PATH)
    args = ap.parse_args(argv)

    if args.check:
        t0 = time.monotonic()
        problems = check_manifest(load_manifest(args.path))
        for fam, code, msg in problems:
            print(f"[{fam}/{code}] {msg}", file=sys.stderr)
        dt = time.monotonic() - t0
        print(f"kernel_manifest --check: {len(problems)} problem(s) "
              f"in {dt:.2f}s")
        return 1 if problems else 0

    # --verify / --write import jax: force the device-free CPU backend
    # BEFORE the first jax import so nothing touches an accelerator
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    t0 = __import__("time").monotonic()
    manifest = generate()
    data = manifest_bytes(manifest)
    dt = __import__("time").monotonic() - t0
    if args.write:
        with open(args.path, "wb") as fh:
            fh.write(data)
        print(f"wrote {args.path} ({len(data)} bytes) in {dt:.1f}s")
        return 0
    try:
        with open(args.path, "rb") as fh:
            committed = fh.read()
    except OSError:
        committed = b""
    if committed != data:
        print("kernel_manifest --verify: regenerated manifest differs "
              f"from {args.path} — run --write, review the surface "
              "diff, and commit it", file=sys.stderr)
        return 1
    print(f"kernel_manifest --verify: byte-identical ({dt:.1f}s)")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
