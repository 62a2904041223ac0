"""TRACE rules: contracts over the *traced* hot path (tracecheck.py).

The AST rules reason about source tokens; these rules reason about the
jaxpr the compiler actually receives. Each entry in
``tracecheck.TRACE_MANIFEST`` is traced under abstract inputs (CPU,
nothing executes) and the resulting program is checked against the
entry's declared contract. A sort routed through a helper module, an
f64 upcast introduced by promotion, a `jax.debug.print` left in a
scan body, a donation that silently stopped aliasing, a Python scalar
baked into the program — all invisible to the lexical rules, all
violations here.

Modes:

- **real**: when the scan set contains the analyzer's own package
  (its ``config.py``), the rules trace the production manifest.
  Findings anchor at each entry's target function definition.
- **fixture**: when a scanned file is named ``trace_manifest.py``, it
  is imported and its ``TRACE_MANIFEST`` / ``WAIVERS`` (and optional
  ``DISPATCH_ROWS``) are checked instead — this is how
  tests/analysis_fixtures/trace_bad/ pins one finding per rule
  without planting violations in the package.

All six rules share one trace pass per run: the first rule to fire
builds the report bundle and stashes it on the ProjectContext; trace
reports are served from the incremental cache (cache.py) when the
entry's dependency files are unchanged.
"""

from __future__ import annotations

import ast
import importlib.util
import os
from typing import Dict, List, Optional, Sequence, Tuple

from .engine import Finding, ParsedFile, ProjectRule
from . import tracecheck

__all__ = [
    "TraceSortFreeRule", "TraceF64Rule", "TraceCallbackRule",
    "TraceDonationRule", "TraceRetraceStableRule",
    "TraceManifestCoverageRule",
]

_FIXTURE_BASENAME = "trace_manifest.py"
_fixture_counter = [0]


class _Bundle:
    """One trace pass: manifest + per-entry reports + anchors."""

    def __init__(self, entries, waivers, dispatch_rows,
                 anchor_of, default_path):
        self.entries = list(entries)
        self.waivers = dict(waivers)
        self.dispatch_rows = list(dispatch_rows)
        self.anchor_of = anchor_of          # entry -> (path, line)
        self.default_path = default_path    # coverage findings anchor
        self.reports: Dict[str, tracecheck.TraceReport] = {}

    def report(self, entry) -> tracecheck.TraceReport:
        rep = self.reports.get(entry.name)
        if rep is None:
            rep = tracecheck.build_report(entry)
            self.reports[entry.name] = rep
        return rep


def _find_def_line(files: Sequence[ParsedFile], rel_file: str,
                   fn_name: str) -> Optional[Tuple[str, int]]:
    suffix = rel_file.replace("/", os.sep)
    for parsed in files:
        if not os.path.normpath(parsed.path).endswith(suffix):
            continue
        if parsed.tree is None:
            return parsed.path, 1
        for node in ast.walk(parsed.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name == fn_name:
                return parsed.path, node.lineno
        return parsed.path, 1
    return None


def _load_fixture_manifest(path: str):
    """Import a fixture trace_manifest.py under a unique module name
    (repeated scans in one test process must not alias each other)."""
    _fixture_counter[0] += 1
    name = f"_tpulint_trace_fixture_{_fixture_counter[0]}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _entry_key(cache, entry) -> Optional[str]:
    if cache is None:
        return None
    contract = (entry.sort_free, entry.forbid_callbacks, entry.x64_mode,
                entry.donate, entry.stable_over)
    return cache.trace_key(entry.name, entry.deps, repr(contract))


def _bundle(files: Sequence[ParsedFile], ctx) -> Optional[_Bundle]:
    cached = getattr(ctx, "_trace_bundle", "unset")
    if cached != "unset":
        return cached
    bundle = None
    # real mode only for the analyzer's own package — a fixture
    # mini-project shipping a config.py must not trigger production
    # trace builds
    own_pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkg_scan = any(
        os.path.basename(f.path) == "config.py"
        and os.path.dirname(os.path.abspath(f.path)) == own_pkg
        for f in files)
    fixture = next((f for f in files
                    if os.path.basename(f.path) == _FIXTURE_BASENAME),
                   None)
    if pkg_scan:
        from .rules_faults import DISPATCH_MANIFEST
        anchors = {}
        for entry in tracecheck.TRACE_MANIFEST:
            hit = _find_def_line(files, entry.target_file,
                                 entry.target_fn)
            anchors[entry.name] = hit or (
                os.path.join(ctx.package_dir, "analysis",
                             "tracecheck.py"), 1)
        bundle = _Bundle(
            tracecheck.TRACE_MANIFEST, tracecheck.WAIVERS,
            [(r[0], r[1], r[2]) for r in DISPATCH_MANIFEST],
            lambda e: anchors[e.name],
            os.path.join(ctx.package_dir, "analysis", "tracecheck.py"))
        cache = getattr(ctx, "lint_cache", None)
        for entry in bundle.entries:
            key = _entry_key(cache, entry)
            hit = cache.get_trace_report(key) if key else None
            if hit is not None:
                bundle.reports[entry.name] = \
                    tracecheck.TraceReport.from_dict(hit)
            else:
                rep = bundle.report(entry)
                if key and rep.error is None:
                    cache.put_trace_report(key, rep.to_dict())
    elif fixture is not None:
        try:
            mod = _load_fixture_manifest(fixture.path)
        except Exception as exc:
            bundle = _Bundle((), {}, (), lambda e: (fixture.path, 1),
                             fixture.path)
            bundle.load_error = f"{type(exc).__name__}: {exc}"
            ctx._trace_bundle = bundle
            return bundle
        rows = getattr(mod, "DISPATCH_ROWS", ())
        bundle = _Bundle(
            getattr(mod, "TRACE_MANIFEST", ()),
            getattr(mod, "WAIVERS", {}), rows,
            lambda e: (fixture.path, e.line or 1), fixture.path)
    ctx._trace_bundle = bundle
    return bundle


class _TraceRule(ProjectRule):
    severity = "error"

    def _anchored(self, bundle, entry, message: str) -> Finding:
        path, line = bundle.anchor_of(entry)
        return Finding(rule=self.id, severity=self.severity, path=path,
                       line=line, message=message)


class TraceSortFreeRule(_TraceRule):
    id = "TRACE001"
    doc = ("traced hot entry contains a `sort` primitive — the semantic "
           "form of PERF001's lexical argsort ban; catches sorts routed "
           "through helpers or alternate spellings (jnp.sort, top_k)")

    def check_project(self, files, ctx) -> List[Finding]:
        bundle = _bundle(files, ctx)
        out: List[Finding] = []
        if bundle is None:
            return out
        for entry in bundle.entries:
            if not entry.sort_free:
                continue
            rep = bundle.report(entry)
            if rep.error is None and rep.has_sort:
                out.append(self._anchored(
                    bundle, entry,
                    f"traced program of '{entry.name}' "
                    f"({entry.target_fn}) contains a `sort` primitive; "
                    f"the entry's contract is sort-free — O(n log n) "
                    f"with poor MXU utilization on the hot path"))
        return out


class TraceF64Rule(_TraceRule):
    id = "TRACE002"
    doc = ("traced hot entry emits strongly-typed float64 values — "
           "f64 runs at a fraction of f32 throughput on TPU and "
           "doubles every buffer it touches")

    def check_project(self, files, ctx) -> List[Finding]:
        bundle = _bundle(files, ctx)
        out: List[Finding] = []
        if bundle is None:
            return out
        for entry in bundle.entries:
            rep = bundle.report(entry)
            if rep.error is None and rep.f64:
                out.append(self._anchored(
                    bundle, entry,
                    f"traced program of '{entry.name}' emits "
                    f"strongly-typed float64 from "
                    f"{', '.join(rep.f64)} — keep the hot path f32"))
        return out


class TraceCallbackRule(_TraceRule):
    id = "TRACE003"
    doc = ("traced hot entry contains a host callback primitive "
           "(pure_callback/io_callback/debug_callback/debug_print) — "
           "each one is a device->host round trip serializing the "
           "dispatch pipeline")

    def check_project(self, files, ctx) -> List[Finding]:
        bundle = _bundle(files, ctx)
        out: List[Finding] = []
        if bundle is None:
            return out
        for entry in bundle.entries:
            if not entry.forbid_callbacks:
                continue
            rep = bundle.report(entry)
            if rep.error is None and rep.callbacks:
                out.append(self._anchored(
                    bundle, entry,
                    f"traced program of '{entry.name}' contains host "
                    f"callback primitive(s) "
                    f"{', '.join(rep.callbacks)} — remove jax.debug/"
                    f"callback calls from the hot path"))
        return out


class TraceDonationRule(_TraceRule):
    id = "TRACE004"
    doc = ("entry declares buffer donation but the lowering records no "
           "input/output aliasing — JAX keeps both buffers silently, "
           "doubling peak memory on the largest arrays")

    def check_project(self, files, ctx) -> List[Finding]:
        bundle = _bundle(files, ctx)
        out: List[Finding] = []
        if bundle is None:
            return out
        for entry in bundle.entries:
            if not entry.donate:
                continue
            rep = bundle.report(entry)
            if rep.error is None and rep.donation_consumed is False:
                out.append(self._anchored(
                    bundle, entry,
                    f"'{entry.name}' declares donation but the lowered "
                    f"program has no input/output aliasing "
                    f"(no {tracecheck._DONATION_MARKER}) — the donated "
                    f"buffer is copied, not reused"))
        return out


class TraceRetraceStableRule(_TraceRule):
    id = "TRACE005"
    doc = ("re-tracing an entry with different values for its "
           "dispatch-stable scalars changed the jaxpr — the scalar is "
           "baked into the program and every new value recompiles")

    def check_project(self, files, ctx) -> List[Finding]:
        bundle = _bundle(files, ctx)
        out: List[Finding] = []
        if bundle is None:
            return out
        for entry in bundle.entries:
            if entry.stable_over is None:
                continue
            rep = bundle.report(entry)
            if rep.error is None and rep.stable is False:
                out.append(self._anchored(
                    bundle, entry,
                    f"'{entry.name}' re-traced with different "
                    f"{entry.stable_over} values yields a different "
                    f"jaxpr — the value is static to the program and "
                    f"each distinct value triggers a recompile"))
        return out


class TraceManifestCoverageRule(_TraceRule):
    id = "TRACE006"
    doc = ("TRACE_MANIFEST integrity: every DISPATCH_MANIFEST device "
           "entry must be covered by a trace entry or waived with a "
           "reason; entries must trace successfully; waivers must not "
           "be stale")

    def check_project(self, files, ctx) -> List[Finding]:
        bundle = _bundle(files, ctx)
        out: List[Finding] = []
        if bundle is None:
            return out

        def at_default(message: str) -> Finding:
            return Finding(rule=self.id, severity=self.severity,
                           path=bundle.default_path, line=1,
                           message=message)

        load_error = getattr(bundle, "load_error", None)
        if load_error is not None:
            return [at_default(
                f"fixture trace manifest failed to import: {load_error}")]
        covered = set()
        for entry in bundle.entries:
            covered.update(tuple(site) for site in entry.covers)
            rep = bundle.report(entry)
            if rep.error is not None:
                out.append(self._anchored(
                    bundle, entry,
                    f"trace entry '{entry.name}' failed to trace: "
                    f"{rep.error} — the contract is unverifiable"))
            elif entry.x64_mode and rep.x64_error is not None:
                out.append(self._anchored(
                    bundle, entry,
                    f"trace entry '{entry.name}' declares "
                    f"x64_mode but the enable_x64 trace failed: "
                    f"{rep.x64_error}"))
        rows = {tuple(r) for r in bundle.dispatch_rows}
        for row in sorted(rows):
            if row not in covered and row not in bundle.waivers:
                out.append(at_default(
                    f"dispatch site {row} is neither covered by a "
                    f"TRACE_MANIFEST entry nor waived in WAIVERS — add "
                    f"a trace entry or a waiver with a reason"))
        for waived in sorted(bundle.waivers):
            if waived not in rows:
                out.append(at_default(
                    f"stale waiver {waived}: no such DISPATCH_MANIFEST "
                    f"row — delete it"))
            elif waived in covered:
                out.append(at_default(
                    f"waiver {waived} is redundant: the site is covered "
                    f"by a TRACE_MANIFEST entry — delete the waiver"))
        return out
