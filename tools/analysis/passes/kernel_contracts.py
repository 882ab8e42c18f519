"""kernel-contracts: recompile hazards, compile-surface drift, and
prewarm/policy coverage for every jitted kernel family.

The compile surface is the product's scarcest budget (a cold compile
costs minutes per bucket on a TPU); ROADMAP item 5 demands every kernel
land inside the bucket/prewarm/cache discipline. This pass makes that a
check, in three coupled pieces:

1. RECOMPILE HAZARDS — static, whole-program (the ProjectIndex jit
   registry: decorated roots, `w = jax.jit(f, ...)` wrappers, and
   lru_cache-decorated jit FACTORIES whose parameters are compile keys,
   e.g. parallel/dist_compact.dist_compact_fn):
   - `weak-scalar-operand`: a Python numeric literal passed in a TRACED
     position — weak-typed scalars re-specialize the executable per
     dtype promotion; wrap in jnp.<dtype>(...) or np.asarray.
   - `unhashable-static`: a list/dict/set literal passed to a static
     parameter of a CROSS-module jit callable (same-module sites are
     jit-trace-safety's); statics must be hashable.
   - `jit-in-loop` / `jit-per-call`: `jax.jit(...)` (or
     `partial(jax.jit, ...)`) constructed inside a loop or per-call
     function body mints a fresh trace cache every evaluation; hoist to
     module level or memoize the builder with functools.lru_cache (the
     dist_compact_fn idiom — lru_cache-decorated builders are exempt).
   - `captured-host-array`: a module-level numpy array read inside a jit
     root constant-folds into the HLO (the multi-MB-literal compile blowup
     merge_network's `pos` operand exists to prevent); pass it as an
     operand instead.
   - `unquantized-static`: a shape-flavored static argument (k_pad, m,
     w, n_cmp, ...) whose value does not route through the quantization
     lattice — quantize_width/_quantize_cmp/run_bucket/bucket_size/
     default_tile, a `.bit_length()` derivation or a `1 << ...` mint —
     so every distinct runtime value would compile a fresh executable.
     Resolution is conservative: a binding the pass cannot see (a
     parameter, loop target, or opaque unpacking) is accepted; only a
     visible non-lattice derivation (e.g. `x.shape[1] // k`) is flagged.

2. MANIFEST DRIFT + BUDGET — the committed compile-surface manifest
   (tools/analysis/kernel_manifest.json) must match the current kernel
   sources (per-family AST fingerprints) and stay within each family's
   distinct-executable budget. Drift fails tier-1 until the manifest is
   regenerated (`python -m tools.analysis.kernel_manifest --write`) and
   the surface diff reviewed.

3. PREWARM + POLICY COVERAGE — every manifest bucket must either be
   covered by prewarm_buckets/PrewarmKernelsOp (`prewarmed: true`) or be
   a justified baseline entry (`unwarmed-bucket` findings carry a stable
   per-bucket fingerprint, so each deliberately-cold bucket is one
   justified line in tools/analysis/baseline.txt, not a code comment);
   prewarm shapes that match no reachable bucket are `overwarmed-bucket`
   findings; and each bucket's offload-policy quarantine key must be the
   (k_pad, m) projection storage/offload_policy.bucket_key speaks
   (`policy-key-mismatch`).

Waive a deliberate hazard with `# yblint: disable=kernel-contracts`.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.analysis.core import AnalysisPass, FileContext, Finding
from tools.analysis.project_index import ProjectIndex, dotted_name

PASS_NAME = "kernel-contracts"

_MANIFEST_ANCHOR = "yugabyte_tpu/ops/run_merge.py"

# static parameter names that carry shapes into the compile key — the
# lattice check applies only to these (booleans and impl selectors are
# 2-valued and bounded by construction)
_SHAPE_STATICS = {"k_pad", "m", "m_c", "w", "w_route", "n_cmp", "n_sort",
                  "n_out_pad", "n_iters", "tile", "capacity", "n_pad",
                  "n", "width"}

# the quantizer vocabulary: a call to one of these produces a lattice
# point by construction
_QUANTIZERS = {"quantize_width", "_quantize_cmp", "run_bucket",
               "bucket_size", "default_tile", "packed_run_ns"}

# pass-through callables: quantized iff every argument is
_TRANSPARENT_CALLS = {"min", "max", "int", "abs", "tuple", "round", "len"}
# len() of a runtime container is NOT a lattice point
_TRANSPARENT_CALLS.discard("len")

# attribute reads accepted as lattice points (set by staging code that
# quantized them at construction)
_LATTICE_ATTRS = {"k_pad", "m", "w", "n_cmp", "n_pad", "n_sort",
                  "cmp_rows", "n_out_pad", "m_c", "tile"}
# attribute reads that are raw runtime shapes — the classic per-size
# recompile hazard when they reach a static position
_RAW_SHAPE_ATTRS = {"shape", "size", "ndim", "nbytes"}

_NP_MODULES = {"np", "numpy", "onp"}
_NP_ARRAY_CTORS = {"array", "arange", "zeros", "ones", "full", "empty",
                   "asarray", "concatenate", "tile", "eye", "linspace"}

_CACHE_DECORATORS = {"lru_cache", "cache"}


def _is_jit(node: ast.AST) -> bool:
    return dotted_name(node) in ("jax.jit", "jit")


def _jit_partial(node: ast.AST) -> Optional[ast.Call]:
    if (isinstance(node, ast.Call)
            and dotted_name(node.func) in ("functools.partial", "partial")
            and node.args and _is_jit(node.args[0])):
        return node
    return None


def _param_names(fn: ast.AST) -> List[str]:
    a = fn.args
    return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
            + [p.arg for p in a.kwonlyargs])


def _static_names(call: ast.Call, params: Sequence[str],
                  mi) -> Set[str]:
    """static_argnames/static_argnums constants -> parameter names,
    resolving a bare Name spec through the module constants (the
    `_FUSED_STATICS` idiom)."""
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            if isinstance(kw.value, ast.Name):
                v = mi.constants.get(kw.value.id)
                if isinstance(v, tuple):
                    out |= {s for s in v if isinstance(s, str)}
                    continue
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    out.add(c.value)
        elif kw.arg == "static_argnums":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) \
                        and isinstance(c.value, int) \
                        and 0 <= c.value < len(params):
                    out.add(params[c.value])
    return out


class _JitRoot:
    """One jitted callable (or lru_cache jit factory): its params and
    which of them are compile keys."""

    __slots__ = ("fq", "params", "statics", "is_factory", "node",
                 "relpath")

    def __init__(self, fq: str, params: Sequence[str], statics: Set[str],
                 is_factory: bool, node: Optional[ast.AST],
                 relpath: str):
        self.fq = fq
        self.params = list(params)
        self.statics = statics
        self.is_factory = is_factory
        self.node = node
        self.relpath = relpath


def _has_cache_decorator(fn: ast.AST) -> bool:
    for dec in fn.decorator_list:
        d = dec.func if isinstance(dec, ast.Call) else dec
        if dotted_name(d).rpartition(".")[2] in _CACHE_DECORATORS:
            return True
    return False


def _build_registry(index: ProjectIndex) -> Dict[str, _JitRoot]:
    reg: Dict[str, _JitRoot] = {}
    for mi in index.modules.values():
        ctx = mi.ctx
        for node in ctx.nodes_of(ast.FunctionDef, ast.AsyncFunctionDef):
            params = _param_names(node)
            for dec in node.decorator_list:
                call = dec if isinstance(dec, ast.Call) \
                    and _is_jit(dec.func) else _jit_partial(dec)
                statics: Optional[Set[str]] = None
                if _is_jit(dec):
                    statics = set()
                elif call is not None:
                    statics = _static_names(call, params, mi)
                if statics is not None:
                    fq = mi.modname + "." + ctx.qualname(node)
                    reg[fq] = _JitRoot(fq, params, statics, False, node,
                                       mi.relpath)
                    break
            else:
                # lru_cache-decorated factory that builds a jit inside:
                # its parameters ARE the compile key
                if _has_cache_decorator(node) and any(
                        isinstance(c, ast.Call)
                        and (_is_jit(c.func)
                             or _jit_partial(c) is not None)
                        for c in ast.walk(node)):
                    fq = mi.modname + "." + ctx.qualname(node)
                    reg[fq] = _JitRoot(fq, params, set(params), True,
                                       node, mi.relpath)
        for asn in ctx.nodes_of(ast.Assign):
            v = asn.value
            call = None
            target_fn = None
            if isinstance(v, ast.Call) and _is_jit(v.func) and v.args \
                    and isinstance(v.args[0], ast.Name):
                call, target_fn = v, v.args[0].id
            elif isinstance(v, ast.Call) \
                    and _jit_partial(v.func) is not None and v.args \
                    and isinstance(v.args[0], ast.Name):
                call, target_fn = _jit_partial(v.func), v.args[0].id
            if call is None:
                continue
            fi = index.lookup_function(index.resolve(mi, target_fn))
            params = _param_names(fi.node) if fi is not None else []
            statics = _static_names(call, params, mi)
            for t in asn.targets:
                if isinstance(t, ast.Name):
                    fq = mi.modname + "." + t.id
                    reg[fq] = _JitRoot(fq, params, statics, False,
                                       fi.node if fi else None,
                                       mi.relpath)
    return reg


# ---------------------------------------------------------------------------
# Lattice-discipline expression check
# ---------------------------------------------------------------------------

class _LatticeChecker:
    """Is this expression a quantized lattice point?  Conservative:
    unresolvable bindings are accepted (missed hazards, never invented
    ones); visibly shape-derived values are rejected."""

    def __init__(self, index: ProjectIndex, mi, env: Dict[str, object]):
        self.index = index
        self.mi = mi
        self.env = env          # local name -> assigned expr | None
        self._visiting: Set[str] = set()

    def ok(self, expr: ast.AST, depth: int = 0) -> bool:
        if depth > 12 or expr is None:
            return True
        if isinstance(expr, ast.Constant):
            return True
        if isinstance(expr, ast.UnaryOp):
            return self.ok(expr.operand, depth + 1)
        if isinstance(expr, (ast.Compare, ast.BoolOp)):
            return True         # boolean-valued: 2-point lattice
        if isinstance(expr, ast.IfExp):
            return self.ok(expr.body, depth + 1) \
                and self.ok(expr.orelse, depth + 1)
        if isinstance(expr, ast.Name):
            if expr.id in self._visiting:
                return True
            if expr.id not in self.env:
                # module-level int constant, parameter, loop target, or
                # otherwise out of sight: accept
                return True
            bound = self.env[expr.id]
            if bound is None:
                return True     # opaque binding (unpacking, for-target)
            self._visiting.add(expr.id)
            try:
                return self.ok(bound, depth + 1)
            finally:
                self._visiting.discard(expr.id)
        if isinstance(expr, ast.Attribute):
            if expr.attr in _RAW_SHAPE_ATTRS:
                return False
            return True         # lattice attrs and unknown carriers
        if isinstance(expr, ast.Subscript):
            return self.ok(expr.value, depth + 1)
        if isinstance(expr, ast.BinOp):
            if isinstance(expr.op, ast.LShift):
                return True     # `1 << ...` mints a power of two
            return self.ok(expr.left, depth + 1) \
                and self.ok(expr.right, depth + 1)
        if isinstance(expr, ast.GeneratorExp):
            return all(self.ok(g.iter, depth + 1)
                       for g in expr.generators)
        if isinstance(expr, (ast.Tuple, ast.List)):
            return all(self.ok(e, depth + 1) for e in expr.elts)
        if isinstance(expr, ast.Call):
            leaf = dotted_name(expr.func).rpartition(".")[2]
            if leaf == "bit_length":
                return True
            if leaf in _QUANTIZERS:
                return True
            fq = self.index.resolve(self.mi, dotted_name(expr.func))
            if fq and fq.rpartition(".")[2] in _QUANTIZERS:
                return True
            if leaf in _TRANSPARENT_CALLS:
                return all(self.ok(a, depth + 1) for a in expr.args)
            return True         # unknown callable: accept (no-FP bias)
        return True


def _local_env(fn: ast.AST) -> Dict[str, object]:
    """name -> assigned expr for simple assignments; None for opaque
    bindings (tuple-unpack of a non-tuple, loop targets, with-as)."""
    env: Dict[str, object] = {}

    def opaque(target: ast.AST) -> None:
        for n in ast.walk(target):
            if isinstance(n, ast.Name):
                env.setdefault(n.id, None)

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name):
                env[t.id] = node.value
            elif isinstance(t, ast.Tuple) \
                    and isinstance(node.value, ast.Tuple) \
                    and len(t.elts) == len(node.value.elts):
                for te, ve in zip(t.elts, node.value.elts):
                    if isinstance(te, ast.Name):
                        env[te.id] = ve
                    else:
                        opaque(te)
            else:
                opaque(t)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            opaque(node.target)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            opaque(node.target)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if item.optional_vars is not None:
                    opaque(item.optional_vars)
    return env


# ---------------------------------------------------------------------------
# Manifest drift + coverage (pure over the committed JSON; fixture tests
# inject synthetic manifests/prewarm shapes directly)
# ---------------------------------------------------------------------------

def coverage_problems(manifest: Optional[dict],
                      prewarm_shapes: Optional[Sequence] = None
                      ) -> List[Tuple[str, str, str]]:
    """(code, fingerprint-token, message) coverage findings over a
    manifest dict: unwarmed-but-reachable buckets, warmed-but-unreachable
    prewarm shapes, and quarantine keys the offload policy would not
    compute for the bucket."""
    out: List[Tuple[str, str, str]] = []
    if not manifest:
        return out
    fams = manifest.get("families", {})
    for name in sorted(fams):
        for e in fams[name].get("entries", ()):
            token = f"{name} {e.get('key')}"
            if not e.get("prewarmed"):
                out.append((
                    "unwarmed-bucket", token,
                    f"reachable bucket {e.get('key')!r} of kernel family "
                    f"{name!r} is not covered by prewarm_buckets/"
                    "PrewarmKernelsOp — its first real launch pays the "
                    "full cold compile; warm it, or justify the cold "
                    "start in tools/analysis/baseline.txt"))
            qk = e.get("quarantine_key")
            b = e.get("bucket", {})
            if qk is not None and "k_pad" in b and "m" in b \
                    and list(qk) != [b["k_pad"], b["m"]]:
                out.append((
                    "policy-key-mismatch", token,
                    f"bucket {e.get('key')!r} of {name!r} declares "
                    f"quarantine key {qk} but offload_policy.bucket_key "
                    f"would compute ({b['k_pad']}, {b['m']}) — the "
                    "device-fault quarantine would never match this "
                    "bucket"))
    if prewarm_shapes:
        rm = fams.get("run_merge_fused", {})
        reachable = {(e["bucket"].get("k_pad"), e["bucket"].get("m"),
                      e["bucket"].get("w"), e["bucket"].get("n_cmp"))
                     for e in rm.get("entries", ())}
        for shape in prewarm_shapes:
            t = tuple(int(x) for x in shape)
            if len(t) == 4 and t not in reachable:
                out.append((
                    "overwarmed-bucket",
                    "run_merge_fused prewarm "
                    f"k_pad={t[0]} m={t[1]} w={t[2]} n_cmp={t[3]}",
                    f"prewarm shape {t} matches no reachable manifest "
                    "bucket — it warms an executable nothing launches "
                    "(stale prewarm list or stale manifest)"))
    return out


class KernelContractsPass(AnalysisPass):
    name = PASS_NAME
    needs_index = True

    def __init__(self, manifest_path: Optional[str] = None):
        from tools.analysis.kernel_manifest import MANIFEST_PATH
        self.manifest_path = manifest_path or MANIFEST_PATH

    def applies_to(self, relpath: str) -> bool:
        return relpath.endswith(".py")

    # ------------------------------------------------------------------ run
    def run(self, ctx: FileContext, index: Optional[ProjectIndex] = None
            ) -> List[Finding]:
        if index is None:
            index = ProjectIndex([ctx])
        mi = index.by_relpath.get(ctx.relpath)
        if mi is None:
            return []
        reg: Dict[str, _JitRoot] = index.memo(
            "kernel_contracts.registry", lambda: _build_registry(index))
        findings: List[Finding] = []
        self._check_construction_sites(ctx, findings)
        if reg:
            self._check_call_sites(ctx, index, mi, reg, findings)
            self._check_captured_arrays(ctx, mi, reg, findings)
        if ctx.relpath == _MANIFEST_ANCHOR:
            findings.extend(self._manifest_findings(ctx, mi))
        return findings

    # ------------------------------------------- jit construction placement
    def _check_construction_sites(self, ctx: FileContext,
                                  findings: List[Finding]) -> None:
        decorator_nodes: Set[int] = set()
        for fn in ctx.nodes_of(ast.FunctionDef, ast.AsyncFunctionDef):
            for dec in fn.decorator_list:
                for n in ast.walk(dec):
                    decorator_nodes.add(id(n))
        for call in ctx.nodes_of(ast.Call):
            is_ctor = _is_jit(call.func) or _jit_partial(call) is not None
            if not is_ctor or id(call) in decorator_nodes:
                continue
            # the inner `partial(jax.jit, ...)` of a partial(...)(f) chain
            # is covered by its enclosing call; skip the nested node
            parent = ctx.parent(call)
            if isinstance(parent, ast.Call) and parent.func is call:
                continue
            in_loop = any(isinstance(a, (ast.For, ast.While, ast.AsyncFor))
                          for a in ctx.ancestors(call))
            fn = ctx.enclosing_function(call)
            if in_loop:
                findings.append(ctx.finding(
                    self.name, "jit-in-loop", call,
                    "jax.jit constructed inside a loop mints a fresh "
                    "trace cache per iteration — hoist it to module "
                    "level (or an lru_cache builder)"))
            elif fn is not None and not _has_cache_decorator(fn):
                findings.append(ctx.finding(
                    self.name, "jit-per-call", call,
                    "jax.jit constructed inside a function body compiles "
                    "on every call — hoist to module level or memoize "
                    "the builder with functools.lru_cache (the "
                    "dist_compact_fn idiom)"))

    # ------------------------------------------------------------ call sites
    def _local_aliases(self, index, mi, fn_node: ast.AST,
                       reg: Dict[str, _JitRoot]) -> Dict[str, _JitRoot]:
        out: Dict[str, _JitRoot] = {}
        for node in ast.walk(fn_node):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                continue
            v = node.value
            cands = [v.body, v.orelse] if isinstance(v, ast.IfExp) else [v]
            for c in cands:
                fq = index.resolve(mi, dotted_name(c))
                if fq in reg:
                    out[node.targets[0].id] = reg[fq]
                    break
        return out

    def _resolve_root(self, index, mi, func: ast.AST,
                      aliases: Dict[str, _JitRoot],
                      reg: Dict[str, _JitRoot]
                      ) -> Tuple[Optional[_JitRoot], int]:
        """(root, positional offset).  `fn.lower(...)` / `fn.eval_shape`
        forward their arguments to the jitted signature unchanged."""
        if isinstance(func, ast.Attribute) \
                and func.attr in ("lower", "eval_shape"):
            root, _ = self._resolve_root(index, mi, func.value, aliases,
                                         reg)
            return root, 0
        if isinstance(func, ast.Name) and func.id in aliases:
            return aliases[func.id], 0
        fq = index.resolve(mi, dotted_name(func))
        return (reg.get(fq), 0) if fq else (None, 0)

    def _check_call_sites(self, ctx: FileContext, index, mi,
                          reg: Dict[str, _JitRoot],
                          findings: List[Finding]) -> None:
        for fn_node in ctx.nodes_of(ast.FunctionDef, ast.AsyncFunctionDef):
            aliases = self._local_aliases(index, mi, fn_node, reg)
            env = None
            for call in ast.walk(fn_node):
                if not isinstance(call, ast.Call):
                    continue
                root, _off = self._resolve_root(index, mi, call.func,
                                                aliases, reg)
                if root is None:
                    continue
                if env is None:
                    env = _local_env(fn_node)
                checker = _LatticeChecker(index, mi, env)
                self._check_one_call(ctx, mi, call, root, checker,
                                     findings)

    def _check_one_call(self, ctx: FileContext, mi, call: ast.Call,
                        root: _JitRoot, checker: _LatticeChecker,
                        findings: List[Finding]) -> None:
        pairs: List[Tuple[Optional[str], ast.AST]] = []
        for i, a in enumerate(call.args):
            if isinstance(a, ast.Starred):
                break
            pairs.append((root.params[i] if i < len(root.params) else None,
                          a))
        for kw in call.keywords:
            if kw.arg:
                pairs.append((kw.arg, kw.value))
        cross_module = root.relpath != ctx.relpath
        for pname, value in pairs:
            is_static = pname is not None and pname in root.statics
            if is_static:
                if cross_module and isinstance(
                        value, (ast.List, ast.Dict, ast.Set)):
                    findings.append(ctx.finding(
                        self.name, "unhashable-static", value,
                        f"static arg {pname!r} of "
                        f"{root.fq.rpartition('.')[2]} passed a "
                        f"{type(value).__name__.lower()} literal — "
                        "statics must be hashable (use a tuple)"))
                    continue
                if pname in _SHAPE_STATICS and not checker.ok(value):
                    findings.append(ctx.finding(
                        self.name, "unquantized-static", value,
                        f"shape static {pname!r} of "
                        f"{root.fq.rpartition('.')[2]} bypasses the "
                        "quantization lattice (quantize_width/"
                        "_quantize_cmp/run_bucket/bucket_size/"
                        "bit_length) — every distinct runtime value "
                        "compiles a fresh executable"))
            elif not root.is_factory:
                if isinstance(value, ast.Constant) \
                        and isinstance(value.value, (int, float)) \
                        and not isinstance(value.value, bool):
                    findings.append(ctx.finding(
                        self.name, "weak-scalar-operand", value,
                        f"Python scalar literal passed in traced "
                        f"position {pname or '<pos>'} of "
                        f"{root.fq.rpartition('.')[2]} — weak-typed "
                        "scalars re-specialize the executable under "
                        "dtype promotion; wrap in jnp.<dtype>(...)"))

    # ----------------------------------------------------- captured arrays
    def _check_captured_arrays(self, ctx: FileContext, mi,
                               reg: Dict[str, _JitRoot],
                               findings: List[Finding]) -> None:
        np_arrays: Set[str] = set()
        for stmt in ctx.tree.body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and isinstance(stmt.value, ast.Call):
                d = dotted_name(stmt.value.func)
                mod, _, leaf = d.rpartition(".")
                if mod in _NP_MODULES and leaf in _NP_ARRAY_CTORS:
                    np_arrays.add(stmt.targets[0].id)
        if not np_arrays:
            return
        root_nodes = [r.node for r in reg.values()
                      if r.relpath == ctx.relpath and r.node is not None]
        for fn in root_nodes:
            stores = {n.id for n in ast.walk(fn)
                      if isinstance(n, ast.Name)
                      and isinstance(n.ctx, (ast.Store, ast.Del))}
            params = set(_param_names(fn))
            for n in ast.walk(fn):
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                        and n.id in np_arrays \
                        and n.id not in stores and n.id not in params:
                    findings.append(ctx.finding(
                        self.name, "captured-host-array", n,
                        f"module-level numpy array {n.id!r} captured "
                        "inside a jit root constant-folds into the HLO "
                        "(multi-MB literals blow up the compile) — pass "
                        "it as an operand"))

    # ------------------------------------------------- manifest + coverage
    def _manifest_findings(self, ctx: FileContext, mi) -> List[Finding]:
        from tools.analysis.kernel_manifest import (check_manifest,
                                                    load_manifest)
        manifest = load_manifest(self.manifest_path)
        out: List[Finding] = []
        for fam, code, msg in check_manifest(manifest):
            out.append(Finding(ctx.relpath, 1, self.name, code, msg,
                               symbol="<manifest>", src=f"family {fam}"))
        prewarm = mi.constants.get("_PREWARM_SHAPES")
        for code, token, msg in coverage_problems(manifest, prewarm):
            out.append(Finding(ctx.relpath, 1, self.name, code, msg,
                               symbol="<manifest>", src=token))
        return out
