"""jit-hygiene and dtype-discipline rules (device code paths only).

The recompile/host-sync contract these rules enforce: every `jax.jit`
or `pjit` entry point in the device directories (engine.DEVICE_DIRS)
must route Python scalars through `static_argnames`, must not branch
Python control flow on traced values, and must not force a host sync
(`float()`, `bool()`, `.item()`, `np.asarray()` ...) on a traced value
inside the jitted body. Dtype discipline: no float64 (and no implicit
promotion to it) inside jitted bodies — device accumulators are
explicit f32 (config `hist_dtype`).

What does NOT fire, by design:

- `x is None` / `x is not None` branches on traced parameters: a
  None-vs-array change alters the pytree *structure*, which retraces
  anyway — these are structural dispatch, not value-dependent control
  flow.
- anything reached through `.shape` / `.ndim` / `.dtype` / `.size`:
  static at trace time.
- host-side code outside jitted bodies (the serving request path bins
  rows in f64 on the host deliberately — exact threshold semantics).
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .dataflow import branch_tests, dotted_name
from .engine import Finding, ParsedFile, Rule

__all__ = ["JitStaticScalarRule", "JitPythonControlFlowRule",
           "JitHostSyncRule", "JitDonationReuseRule", "DtypeF64Rule",
           "DtypePromotionRule", "iter_jitted_functions"]

#: attribute reads that are static at trace time
_STATIC_ATTRS = ("shape", "ndim", "dtype", "size")

#: call names that force a host sync / concretization on a traced value
_HOST_SYNC_FUNCS = ("float", "int", "bool", "complex")
_HOST_SYNC_METHODS = ("item", "tolist", "to_py")
_HOST_MODULES = ("np", "numpy")

_SCALAR_ANNOTATIONS = ("int", "float", "bool", "str")


def _dec_is_jit(expr: ast.expr) -> Tuple[bool, Set[str]]:
    """(is_jit, static_argnames) for one decorator / call expression.

    Recognizes `jax.jit`, `jit`, `pjit`, and
    `functools.partial(jax.jit, static_argnames=(...))` forms.
    """
    name = _dotted_name(expr)
    if name and name.split(".")[-1] in ("jit", "pjit"):
        return True, set()
    if isinstance(expr, ast.Call):
        fn = _dotted_name(expr.func)
        if fn and fn.split(".")[-1] == "partial" and expr.args:
            inner = _dotted_name(expr.args[0])
            if inner and inner.split(".")[-1] in ("jit", "pjit"):
                return True, _static_names_from_call(expr)
        if fn and fn.split(".")[-1] in ("jit", "pjit"):
            return True, _static_names_from_call(expr)
    return False, set()


#: shared with rules_pallas; the canonical implementation lives in
#: dataflow (returns '' — falsy, like the old None — for non-chains)
_dotted_name = dotted_name


def _static_names_from_call(call: ast.Call) -> Set[str]:
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            for node in ast.walk(kw.value):
                if isinstance(node, ast.Constant) and \
                        isinstance(node.value, str):
                    names.add(node.value)
    return names


def _donated_names_from_call(call: ast.Call) -> Set[str]:
    """Parameter names listed in a donate_argnames=... keyword
    (mirrors _static_names_from_call; donate_argnums is index-form and
    has no name to resolve here)."""
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "donate_argnames":
            for node in ast.walk(kw.value):
                if isinstance(node, ast.Constant) and \
                        isinstance(node.value, str):
                    names.add(node.value)
    return names


def _donated_from_jit_expr(expr: ast.expr) -> Set[str]:
    """Donated parameter names when `expr` is a jit/pjit wrapping call
    (`jax.jit(fn, donate_argnames=...)` or the
    `functools.partial(jax.jit, donate_argnames=...)` decorator form),
    else empty."""
    if not isinstance(expr, ast.Call):
        return set()
    fn = _dotted_name(expr.func)
    if fn and fn.split(".")[-1] == "partial" and expr.args:
        inner = _dotted_name(expr.args[0])
        if inner and inner.split(".")[-1] in ("jit", "pjit"):
            return _donated_names_from_call(expr)
    if fn and fn.split(".")[-1] in ("jit", "pjit"):
        return _donated_names_from_call(expr)
    return set()


def iter_jitted_functions(tree: ast.AST):
    """Yield (func_def, static_names, via) for every jit entry point:
    decorated functions and `jax.jit(fn)` call forms whose target is a
    function defined in the same enclosing scope."""
    # map scope -> {name: FunctionDef} for call-form resolution
    for scope in ast.walk(tree):
        if not isinstance(scope, (ast.Module, ast.FunctionDef,
                                  ast.AsyncFunctionDef, ast.ClassDef)):
            continue
        local_defs = {n.name: n for n in ast.iter_child_nodes(scope)
                      if isinstance(n, ast.FunctionDef)}
        for node in ast.iter_child_nodes(scope):
            if isinstance(node, ast.FunctionDef):
                for dec in node.decorator_list:
                    is_jit, static = _dec_is_jit(dec)
                    if is_jit:
                        yield node, static, "decorator"
                        break
        # call form: jax.jit(fn, ...) anywhere inside this scope's
        # direct statements (return jax.jit(sharded), x = jit(f))
        for node in ast.walk(scope):
            if isinstance(node, ast.Call):
                fn = _dotted_name(node.func)
                if not fn or fn.split(".")[-1] not in ("jit", "pjit"):
                    continue
                if not node.args or not isinstance(node.args[0], ast.Name):
                    continue
                target = local_defs.get(node.args[0].id)
                if target is not None:
                    yield target, _static_names_from_call(node), "call"


def _param_names(func: ast.FunctionDef) -> List[ast.arg]:
    return list(func.args.posonlyargs) + list(func.args.args) + \
        list(func.args.kwonlyargs)


def _offending_names(expr: ast.expr, traced: Set[str]) -> List[ast.Name]:
    """Occurrences of traced names in `expr` that are value-dependent:
    skips `is None` comparisons and `.shape`-like attribute bases."""
    out: List[ast.Name] = []

    def visit(node: ast.AST) -> None:
        if isinstance(node, ast.Compare) and node.ops and \
                all(isinstance(op, (ast.Is, ast.IsNot))
                    for op in node.ops):
            return                      # structural None dispatch
        if isinstance(node, ast.Attribute) and \
                node.attr in _STATIC_ATTRS:
            return                      # static at trace time
        if isinstance(node, ast.Name) and node.id in traced:
            out.append(node)
            return
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(expr)
    return out


def _enclosing_classes(parsed: ParsedFile) -> Dict[int, str]:
    """id(method node) -> enclosing class name, for call resolution of
    `self.m()` inside jitted methods."""
    out: Dict[int, str] = {}
    if parsed.tree is None:
        return out
    for node in ast.walk(parsed.tree):
        if isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    out[id(sub)] = node.name
    return out


def _jit_bodies(parsed: ParsedFile):
    """(func, traced_param_names) for each jit entry in a device file."""
    if parsed.tree is None or not parsed.in_device_dir():
        return
    seen = set()
    for func, static, _via in iter_jitted_functions(parsed.tree):
        if id(func) in seen:
            continue
        seen.add(id(func))
        traced = {a.arg for a in _param_names(func)} - static - {"self"}
        yield func, static, traced


class JitStaticScalarRule(Rule):
    id = "JIT001"
    doc = ("jitted function parameter with a Python-scalar default or "
           "int/float/bool/str annotation is not in static_argnames — "
           "each distinct value retraces and recompiles the program")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        findings: List[Finding] = []
        for func, static, _traced in _jit_bodies(parsed):
            params = _param_names(func)
            defaults = list(func.args.defaults)
            kw_defaults = list(func.args.kw_defaults)
            # map param -> default expr (positional defaults right-align)
            pos = list(func.args.posonlyargs) + list(func.args.args)
            default_of: Dict[str, ast.expr] = {}
            for arg, dflt in zip(pos[len(pos) - len(defaults):], defaults):
                default_of[arg.arg] = dflt
            for arg, dflt in zip(func.args.kwonlyargs, kw_defaults):
                if dflt is not None:
                    default_of[arg.arg] = dflt
            for arg in params:
                if arg.arg in static or arg.arg == "self":
                    continue
                scalar = False
                dflt = default_of.get(arg.arg)
                if isinstance(dflt, ast.Constant) and \
                        isinstance(dflt.value, (bool, int, float, str)):
                    scalar = True
                ann = arg.annotation
                if isinstance(ann, ast.Name) and \
                        ann.id in _SCALAR_ANNOTATIONS:
                    scalar = True
                if scalar:
                    findings.append(self.finding(
                        parsed, arg.lineno,
                        f"jitted function '{func.name}': scalar "
                        f"parameter '{arg.arg}' must be listed in "
                        f"static_argnames (traced scalars recompile "
                        f"per value)"))
        return findings


class JitPythonControlFlowRule(Rule):
    id = "JIT002"
    doc = ("Python if/while/for-range control flow on a traced value "
           "inside a jitted body — either a trace error or a silent "
           "per-value recompile; use lax.cond/select or mark the "
           "argument static")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        findings: List[Finding] = []
        for func, _static, traced in _jit_bodies(parsed):
            for node, tests in branch_tests(func):
                for test in tests:
                    for name in _offending_names(test, traced):
                        findings.append(self.finding(
                            parsed, getattr(name, "lineno", node.lineno),
                            f"jitted function '{func.name}': Python "
                            f"control flow on traced value "
                            f"'{name.id}' (host-sync / recompile "
                            f"hazard)"))
        return findings


class JitHostSyncRule(Rule):
    id = "JIT003"
    doc = ("float()/int()/bool()/.item()/np.* applied to a traced value "
           "inside a jitted body — forces a device->host sync at trace "
           "time (or a concretization error); with the interprocedural "
           "engine, also when the sync happens inside a helper the "
           "traced value is passed to")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        findings: List[Finding] = []
        facts = getattr(self, "facts", None)
        class_of = _enclosing_classes(parsed) if facts is not None else {}
        for func, _static, traced in _jit_bodies(parsed):
            for node in ast.walk(func):
                if not isinstance(node, ast.Call):
                    continue
                label = self._host_call_label(node)
                if label is None:
                    continue
                args = list(node.args) + \
                    [kw.value for kw in node.keywords]
                hit = None
                for arg in args:
                    names = _offending_names(arg, traced)
                    if names:
                        hit = names[0]
                        break
                # method form: x.item() syncs its receiver
                if hit is None and isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _HOST_SYNC_METHODS:
                    names = _offending_names(node.func.value, traced)
                    if names:
                        hit = names[0]
                if hit is not None:
                    findings.append(self.finding(
                        parsed, node.lineno,
                        f"jitted function '{func.name}': host sync "
                        f"'{label}' on traced value '{hit.id}'"))
            if facts is None:
                continue
            # interprocedural: the sync lives in a helper (possibly
            # modules away); flag the call site that feeds a traced
            # value into the helper's syncing parameter
            for call, callee, hits in facts.host_sync_callees(
                    parsed.path, func, class_of.get(id(func))):
                for pname, arg in hits:
                    names = _offending_names(arg, traced)
                    if not names:
                        continue
                    label, spath, sline = callee.host_sync_params[pname]
                    where = os.path.basename(spath)
                    findings.append(self.finding(
                        parsed, call.lineno,
                        f"jitted function '{func.name}': traced value "
                        f"'{names[0].id}' reaches host sync '{label}' "
                        f"through '{callee.name}()' parameter "
                        f"'{pname}' ({where}:{sline})"))
                    break
        return findings

    @staticmethod
    def _host_call_label(node: ast.Call) -> Optional[str]:
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in _HOST_SYNC_FUNCS:
            return f"{fn.id}()"
        if isinstance(fn, ast.Attribute):
            if fn.attr in _HOST_SYNC_METHODS:
                return f".{fn.attr}()"
            base = _dotted_name(fn.value)
            if base in _HOST_MODULES:
                return f"{base}.{fn.attr}()"
        return None


class JitDonationReuseRule(Rule):
    id = "JIT004"
    doc = ("a Python name is read again after being passed as a donated "
           "argument (donate_argnames) to a jitted call — the donated "
           "buffer is deleted on non-CPU backends, so any later use of "
           "that name dies at runtime; rebind the name from the call's "
           "result before reading it")

    # Scope, by design: only call sites whose callee resolves IN THE
    # SAME FILE to a jit wrapping that lists donate_argnames (decorated
    # def, or `name = jax.jit(fn, donate_argnames=...)` assignment), and
    # only donated arguments passed as bare names. Attribute-form args
    # (self.train_score) are deliberately not tracked — attribute
    # rebinding is object-ownership territory the name-flow analysis
    # cannot see, and flagging them would drown the rule in noise.
    # Ordering is textual (line order), so a loop back-edge reuse is out
    # of reach; the `name = jitted(name, ...)` rebind idiom is clean.

    def check(self, parsed: ParsedFile) -> List[Finding]:
        if parsed.tree is None or not parsed.in_device_dir():
            return []
        defs = {n.name: n for n in ast.walk(parsed.tree)
                if isinstance(n, ast.FunctionDef)}
        # callable name -> (donated param names, signature def or None)
        registry: Dict[str, Tuple[Set[str],
                                  Optional[ast.FunctionDef]]] = {}
        for node in ast.walk(parsed.tree):
            if isinstance(node, ast.FunctionDef):
                for dec in node.decorator_list:
                    donated = _donated_from_jit_expr(dec)
                    if donated:
                        registry[node.name] = (donated, node)
                        break
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                donated = _donated_from_jit_expr(node.value)
                if donated:
                    target = None
                    if node.value.args and \
                            isinstance(node.value.args[0], ast.Name):
                        target = defs.get(node.value.args[0].id)
                    registry[node.targets[0].id] = (donated, target)
        if not registry:
            return []
        findings: List[Finding] = []
        scopes = [parsed.tree] + [n for n in ast.walk(parsed.tree)
                                  if isinstance(n, ast.FunctionDef)]
        for scope in scopes:
            findings.extend(self._check_scope(parsed, scope, registry))
        return findings

    def _check_scope(self, parsed: ParsedFile, scope: ast.AST,
                     registry) -> List[Finding]:
        nodes = self._scope_nodes(scope)
        calls = [n for n in nodes if isinstance(n, ast.Call) and
                 isinstance(n.func, ast.Name) and n.func.id in registry]
        if not calls:
            return []
        names = [n for n in nodes if isinstance(n, ast.Name)]
        stmts = [n for n in nodes
                 if isinstance(n, (ast.Assign, ast.AnnAssign,
                                   ast.AugAssign, ast.NamedExpr))]
        findings: List[Finding] = []
        for call in calls:
            donated, sig = registry[call.func.id]
            exprs = [kw.value for kw in call.keywords
                     if kw.arg in donated]
            if sig is not None:
                params = [a.arg for a in _param_names(sig)]
                for idx, arg in enumerate(call.args):
                    if isinstance(arg, ast.Starred):
                        break
                    if idx < len(params) and params[idx] in donated:
                        exprs.append(arg)
            tracked = {e.id for e in exprs if isinstance(e, ast.Name)}
            end = (getattr(call, "end_lineno", None) or call.lineno,
                   getattr(call, "end_col_offset", None) or 0)
            for var in sorted(tracked):
                if self._rebound_by_call_stmt(stmts, call, var):
                    continue
                events = sorted(
                    (n for n in names if n.id == var and
                     (n.lineno, n.col_offset) > end),
                    key=lambda n: (n.lineno, n.col_offset))
                for n in events:
                    if isinstance(n.ctx, (ast.Store, ast.Del)):
                        break
                    findings.append(self.finding(
                        parsed, n.lineno,
                        f"'{var}' read after being donated to jitted "
                        f"call '{call.func.id}' (buffer deleted on "
                        f"device; rebind from the call's result first)"))
        return findings

    @staticmethod
    def _scope_nodes(scope: ast.AST) -> List[ast.AST]:
        """Nodes belonging to `scope` directly: nested function/class
        bodies form their own scopes and are skipped."""
        out: List[ast.AST] = []
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef, ast.Lambda)):
                continue
            out.append(n)
            stack.extend(ast.iter_child_nodes(n))
        return out

    @staticmethod
    def _rebound_by_call_stmt(stmts, call: ast.Call, var: str) -> bool:
        """True when the statement holding `call` assigns `var` itself —
        the `score = advance(score, ...)` rebind idiom."""
        for st in stmts:
            if not any(n is call for n in ast.walk(st)):
                continue
            targets = st.targets if isinstance(st, ast.Assign) \
                else [st.target]
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name) and n.id == var:
                        return True
        return False


class DtypeF64Rule(Rule):
    id = "DTYPE001"
    doc = ("float64 reference inside a jitted body — device "
           "accumulators are explicit f32/bf16 (hist_dtype); f64 "
           "either errors (x64 disabled) or silently halves MXU "
           "throughput")

    _F64 = ("float64", "double")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        findings: List[Finding] = []
        for func, _static, _traced in _jit_bodies(parsed):
            for node in ast.walk(func):
                label = None
                if isinstance(node, ast.Attribute) and \
                        node.attr in self._F64:
                    label = f".{node.attr}"
                elif isinstance(node, ast.Constant) and \
                        isinstance(node.value, str) and \
                        node.value in self._F64:
                    label = f"'{node.value}'"
                if label is not None:
                    findings.append(self.finding(
                        parsed, node.lineno,
                        f"jitted function '{func.name}': float64 "
                        f"reference {label} in device code"))
        return findings


class DtypePromotionRule(Rule):
    id = "DTYPE002"
    doc = ("implicit promotion to float64 inside a jitted body: "
           "dtype=float / .astype(float) resolve to f64 under x64 and "
           "make the accumulator dtype platform-dependent — spell the "
           "f32 dtype explicitly")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        findings: List[Finding] = []
        for func, _static, _traced in _jit_bodies(parsed):
            for node in ast.walk(func):
                if not isinstance(node, ast.Call):
                    continue
                line = None
                for kw in node.keywords:
                    if kw.arg == "dtype" and \
                            isinstance(kw.value, ast.Name) and \
                            kw.value.id == "float":
                        line = kw.value.lineno
                if isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "astype" and node.args and \
                        isinstance(node.args[0], ast.Name) and \
                        node.args[0].id == "float":
                    line = node.lineno
                if line is not None:
                    findings.append(self.finding(
                        parsed, line,
                        f"jitted function '{func.name}': builtin "
                        f"'float' as a dtype (resolves to float64); "
                        f"use an explicit f32 dtype"))
        return findings
