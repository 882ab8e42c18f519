"""jit-trace-safety: no host syncs, tracer branches or lattice-widening
static args inside jit-traced code.

The offload pipeline lives or dies on two properties of its jitted
kernels (ops/run_merge.py, ops/merge_gc.py, ops/scan.py):

  1. nothing inside a traced function forces a host sync — `.item()`,
     `np.asarray(...)`/`float(...)`/`int(...)`/`bool(...)` on a tracer,
     or `print` of a tracer all block the async dispatch queue and stall
     the stage-overlapped compaction pipeline;
  2. the compile-key lattice stays small — a Python `if`/`while` on a
     tracer raises ConcretizationError at trace time, and a non-hashable
     (or un-quantized) static argument either fails or mints a fresh
     executable per distinct value, the recompile storm the shape-bucket
     lattice in run_merge.py exists to prevent.

Mechanics (per file, no cross-file resolution — conservative misses,
not false positives):

- jit roots: functions decorated `@jax.jit` / `@jit` /
  `@functools.partial(jax.jit, ...)` / `@partial(jax.jit, ...)`, and
  module-level wrappers `w = jax.jit(f, ...)` or
  `w = functools.partial(jax.jit, ...)(f)`. Static parameters come from
  `static_argnames=` / `static_argnums=` constants.
- taint: non-static parameters of a root are tracers; assignment
  propagates taint intra-function; calls to same-module functions
  propagate taint from actual arguments to formal parameters (so helper
  functions reached from a jit root are checked against the tracer-ness
  of what each call site actually passes).
- tracer-ness stops at shape metadata: `x.shape` / `x.ndim` / `x.dtype`
  / `x.size` / `len(x)` of a tracer are static — branching on them is
  fine and common.

Waive a deliberate violation with `# yblint: disable=jit-trace-safety`.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.analysis.core import AnalysisPass, FileContext, Finding

PASS_NAME = "jit-trace-safety"

# attributes of a tracer that are static Python values at trace time
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "nbytes", "itemsize",
                 "aval", "sharding", "device"}
# builtins whose call on a tracer forces a concretization / host sync
_HOST_SYNC_BUILTINS = {"float", "int", "bool", "complex"}
# numpy converters that force a device->host transfer of a tracer
_NUMPY_CONVERTERS = {"asarray", "array", "asanyarray", "ascontiguousarray"}
_NUMPY_MODULE_NAMES = {"np", "numpy", "onp"}
# calls through which taint does NOT flow to the result / the test
_TAINT_STOPPERS = {"len", "isinstance", "hasattr", "getattr", "type",
                   "id", "repr"}


def _dotted(node: ast.AST) -> str:
    """'jax.jit' for Attribute chains / Names; '' for anything else."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jit_callable(node: ast.AST) -> bool:
    return _dotted(node) in ("jax.jit", "jit")


def _jit_partial_call(node: ast.AST) -> Optional[ast.Call]:
    """`functools.partial(jax.jit, ...)` / `partial(jax.jit, ...)` -> the
    Call node (whose keywords carry the static arg spec)."""
    if (isinstance(node, ast.Call)
            and _dotted(node.func) in ("functools.partial", "partial")
            and node.args and _is_jit_callable(node.args[0])):
        return node
    return None


def _static_names_from_call(call: ast.Call, params: Sequence[str],
                            const_env: Optional[Dict[str, Set[str]]] = None
                            ) -> Set[str]:
    """static_argnames/static_argnums constants -> parameter names.
    A bare Name (e.g. `static_argnames=_FUSED_STATICS`) resolves through
    the module-level string-tuple constants in const_env."""
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            if isinstance(kw.value, ast.Name) and const_env \
                    and kw.value.id in const_env:
                out |= const_env[kw.value.id]
                continue
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    out.add(c.value)
        elif kw.arg == "static_argnums":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, int):
                    if 0 <= c.value < len(params):
                        out.add(params[c.value])
    return out


def _param_names(fn: ast.AST) -> List[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
    names += [p.arg for p in a.kwonlyargs]
    return names


class _FnInfo:
    __slots__ = ("node", "params", "tainted_params", "is_root")

    def __init__(self, node: ast.AST):
        self.node = node
        self.params = _param_names(node)
        self.tainted_params: Set[str] = set()
        self.is_root = False


class JitTraceSafetyPass(AnalysisPass):
    name = PASS_NAME

    def run(self, ctx: FileContext) -> List[Finding]:
        fns: Dict[str, _FnInfo] = {}
        for node in ctx.nodes_of(ast.FunctionDef, ast.AsyncFunctionDef):
            # module-level and class-level defs are callable by name;
            # nested defs only from their parent (still indexed — call
            # resolution is by bare name, shadowing is rare in this tree)
            fns.setdefault(node.name, _FnInfo(node))

        statics_of: Dict[str, Set[str]] = {}
        jit_wrappers: Dict[str, str] = {}  # wrapper name -> function name
        # a local, not an attribute: one pass instance runs many files
        # on the analyzer's worker threads at once
        const_env = self._module_str_constants(ctx)
        self._find_roots(ctx, fns, statics_of, jit_wrappers, const_env)
        if not any(i.is_root for i in fns.values()):
            return []

        self._propagate(ctx, fns)

        findings: List[Finding] = []
        for info in fns.values():
            if info.tainted_params:
                findings.extend(self._check_function(ctx, info))
        findings.extend(self._check_static_call_sites(
            ctx, fns, statics_of, jit_wrappers))
        return findings

    # ------------------------------------------------------------ roots
    def _module_str_constants(self, ctx: FileContext) -> Dict[str, Set[str]]:
        """Module-level `NAME = ("a", "b", ...)` string tuples (the idiom
        for shared static_argnames specs)."""
        env: Dict[str, Set[str]] = {}
        for stmt in ctx.tree.body:
            if not isinstance(stmt, ast.Assign) or len(stmt.targets) != 1 \
                    or not isinstance(stmt.targets[0], ast.Name):
                continue
            v = stmt.value
            if isinstance(v, (ast.Tuple, ast.List)) and v.elts and all(
                    isinstance(e, ast.Constant) and isinstance(e.value, str)
                    for e in v.elts):
                env[stmt.targets[0].id] = {e.value for e in v.elts}
        return env

    def _find_roots(self, ctx: FileContext, fns: Dict[str, _FnInfo],
                    statics_of: Dict[str, Set[str]],
                    jit_wrappers: Dict[str, str],
                    const_env: Dict[str, Set[str]]) -> None:
        for name, info in fns.items():
            for dec in info.node.decorator_list:
                statics: Optional[Set[str]] = None
                if _is_jit_callable(dec):
                    statics = set()
                elif isinstance(dec, ast.Call) and _is_jit_callable(dec.func):
                    statics = _static_names_from_call(dec, info.params,
                                                     const_env)
                elif _jit_partial_call(dec) is not None:
                    statics = _static_names_from_call(
                        _jit_partial_call(dec), info.params,
                        const_env)
                if statics is not None:
                    info.is_root = True
                    info.tainted_params |= (
                        set(info.params) - statics
                        - {"self", "cls"})
                    statics_of[name] = statics
        # wrapper assignments: w = jax.jit(f, ...) or
        # w = functools.partial(jax.jit, ...)(f)
        for asn in ctx.nodes_of(ast.Assign):
            v = asn.value
            target_fn: Optional[str] = None
            statics: Set[str] = set()
            if isinstance(v, ast.Call) and _is_jit_callable(v.func) \
                    and v.args and isinstance(v.args[0], ast.Name):
                target_fn = v.args[0].id
                if target_fn in fns:
                    statics = _static_names_from_call(
                        v, fns[target_fn].params, const_env)
            elif isinstance(v, ast.Call) \
                    and _jit_partial_call(v.func) is not None \
                    and v.args and isinstance(v.args[0], ast.Name):
                target_fn = v.args[0].id
                if target_fn in fns:
                    statics = _static_names_from_call(
                        _jit_partial_call(v.func), fns[target_fn].params,
                        const_env)
            if target_fn and target_fn in fns:
                info = fns[target_fn]
                info.is_root = True
                info.tainted_params |= (set(info.params) - statics
                                        - {"self", "cls"})
                statics_of[target_fn] = statics
                for t in asn.targets:
                    if isinstance(t, ast.Name):
                        jit_wrappers[t.id] = target_fn

    # ------------------------------------------------- taint propagation
    def _propagate(self, ctx: FileContext, fns: Dict[str, _FnInfo]) -> None:
        """Fixpoint over call edges: tainted actual -> tainted formal."""
        for _ in range(len(fns) + 2):
            changed = False
            for info in fns.values():
                if not info.tainted_params:
                    continue
                local = self._local_taint(ctx, info)
                for call in ast.walk(info.node):
                    if not isinstance(call, ast.Call):
                        continue
                    callee = call.func.id \
                        if isinstance(call.func, ast.Name) else None
                    if callee not in fns or callee == info.node.name:
                        continue
                    tgt = fns[callee]
                    for i, arg in enumerate(call.args):
                        if i < len(tgt.params) \
                                and self._tracer_expr(arg, local) \
                                and tgt.params[i] not in tgt.tainted_params:
                            tgt.tainted_params.add(tgt.params[i])
                            changed = True
                    for kw in call.keywords:
                        if kw.arg and kw.arg in tgt.params \
                                and self._tracer_expr(kw.value, local) \
                                and kw.arg not in tgt.tainted_params:
                            tgt.tainted_params.add(kw.arg)
                            changed = True
            if not changed:
                return

    def _local_taint(self, ctx: FileContext, info: _FnInfo) -> Set[str]:
        """Tainted local names: params + assignment-propagated values."""
        tainted = set(info.tainted_params)
        for _ in range(8):
            changed = False
            for node in ast.walk(info.node):
                targets: List[ast.AST] = []
                value: Optional[ast.AST] = None
                if isinstance(node, ast.Assign):
                    targets, value = node.targets, node.value
                elif isinstance(node, ast.AugAssign):
                    targets, value = [node.target], node.value
                elif isinstance(node, ast.AnnAssign) and node.value:
                    targets, value = [node.target], node.value
                elif isinstance(node, ast.NamedExpr):
                    targets, value = [node.target], node.value
                if value is None or not self._tracer_expr(value, tainted):
                    continue
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name) and n.id not in tainted:
                            tainted.add(n.id)
                            changed = True
            if not changed:
                break
        return tainted

    def _tracer_expr(self, node: ast.AST, tainted: Set[str]) -> bool:
        """Does evaluating this expression touch a tracer VALUE (as
        opposed to static metadata like .shape / len())?"""
        if isinstance(node, ast.Name):
            return node.id in tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self._tracer_expr(node.value, tainted)
        if isinstance(node, ast.Call):
            fname = _dotted(node.func)
            if fname in _TAINT_STOPPERS:
                return False
            # method calls on tracers (x.astype, x.reshape) keep taint
            return (self._tracer_expr(node.func, tainted)
                    or any(self._tracer_expr(a, tainted)
                           for a in node.args)
                    or any(self._tracer_expr(k.value, tainted)
                           for k in node.keywords))
        if isinstance(node, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return False  # `x is None` is an identity check, no sync
        if isinstance(node, (ast.BinOp, ast.BoolOp, ast.UnaryOp,
                             ast.Compare, ast.Subscript, ast.IfExp,
                             ast.Tuple, ast.List, ast.Starred)):
            return any(self._tracer_expr(c, tainted)
                       for c in ast.iter_child_nodes(node)
                       if isinstance(c, ast.expr))
        return False

    # ------------------------------------------------------------ checks
    def _check_function(self, ctx: FileContext,
                        info: _FnInfo) -> List[Finding]:
        tainted = self._local_taint(ctx, info)
        out: List[Finding] = []
        own_nested = {n for fn in ast.walk(info.node)
                      if isinstance(fn, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))
                      and fn is not info.node
                      for n in ast.walk(fn)}
        for node in ast.walk(info.node):
            if node in own_nested:
                continue  # nested defs are analyzed via call-site taint
            if isinstance(node, ast.Call):
                out.extend(self._check_call(ctx, node, tainted))
            elif isinstance(node, (ast.If, ast.While)):
                if self._tracer_expr(node.test, tainted):
                    out.append(ctx.finding(
                        self.name, "tracer-branch", node,
                        "Python branch on a tracer value inside jit-traced "
                        "code — use jnp.where/lax.cond, or branch on "
                        "static metadata (.shape/len) instead"))
            elif isinstance(node, ast.Assert):
                if self._tracer_expr(node.test, tainted):
                    out.append(ctx.finding(
                        self.name, "tracer-branch", node,
                        "assert on a tracer value inside jit-traced code "
                        "concretizes at trace time"))
        return out

    def _check_call(self, ctx: FileContext, node: ast.Call,
                    tainted: Set[str]) -> List[Finding]:
        out: List[Finding] = []
        f = node.func
        # x.item() / x.tolist() on a tracer
        if isinstance(f, ast.Attribute) and f.attr in ("item", "tolist") \
                and self._tracer_expr(f.value, tainted):
            out.append(ctx.finding(
                self.name, "host-sync", node,
                f".{f.attr}() on a tracer forces a device->host sync "
                "inside jit-traced code"))
            return out
        fname = _dotted(f)
        # float(x) / int(x) / bool(x) on a tracer
        if fname in _HOST_SYNC_BUILTINS and node.args \
                and self._tracer_expr(node.args[0], tainted):
            out.append(ctx.finding(
                self.name, "host-sync", node,
                f"{fname}() on a tracer concretizes it (host sync / "
                "ConcretizationError) inside jit-traced code"))
            return out
        # np.asarray(x) and friends on a tracer
        if "." in fname:
            mod, _, leaf = fname.rpartition(".")
            if mod in _NUMPY_MODULE_NAMES and leaf in _NUMPY_CONVERTERS \
                    and node.args \
                    and self._tracer_expr(node.args[0], tainted):
                out.append(ctx.finding(
                    self.name, "host-sync", node,
                    f"{fname}() on a tracer downloads it to host inside "
                    "jit-traced code — keep it jnp, or hoist out of jit"))
                return out
        # print of a tracer
        if fname == "print" and any(self._tracer_expr(a, tainted)
                                    for a in node.args):
            out.append(ctx.finding(
                self.name, "print-tracer", node,
                "print of a tracer inside jit-traced code (host sync at "
                "trace/run time) — use jax.debug.print"))
        return out

    # --------------------------------------------- static-arg call sites
    def _check_static_call_sites(self, ctx: FileContext,
                                 fns: Dict[str, _FnInfo],
                                 statics_of: Dict[str, Set[str]],
                                 jit_wrappers: Dict[str, str]
                                 ) -> List[Finding]:
        """Call sites of known jit callables: a static arg passed a
        list/dict/set literal is unhashable and fails (or forces object-
        identity caching) at dispatch."""
        out: List[Finding] = []
        callables: Dict[str, str] = {}
        for name, statics in statics_of.items():
            if statics:
                callables[name] = name
        for wname, fname in jit_wrappers.items():
            if statics_of.get(fname):
                callables[wname] = fname
        if not callables:
            return out
        for call in ctx.nodes_of(ast.Call):
            cname = call.func.id if isinstance(call.func, ast.Name) else None
            if cname not in callables:
                continue
            statics = statics_of[callables[cname]]
            for kw in call.keywords:
                if kw.arg in statics and isinstance(
                        kw.value, (ast.List, ast.Dict, ast.Set)):
                    out.append(ctx.finding(
                        self.name, "unhashable-static", kw.value,
                        f"static arg {kw.arg!r} of {cname} passed a "
                        f"{type(kw.value).__name__.lower()} literal — "
                        "statics must be hashable (use a tuple)"))
        return out
