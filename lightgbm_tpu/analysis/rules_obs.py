"""Observability bracket audit (OBS001).

PR-10 adds the crash flight recorder (observability/flightrec.py): on a
watchdog abort or injected rank death, the post-mortem bundle is only
as good as the events that reached the ring. Every collective site
(rules_spmd.COLLECTIVE_MANIFEST) and device-dispatch fault site
(rules_faults.DISPATCH_MANIFEST) must therefore sit inside an
observability bracket — a span, a collective-guard bracket, or a
``record_*`` recorder call — so the last thing a dying rank did has a
name in ``postmortem_<rank>.json``.

A bracket is recognised as a call, anywhere in the function body
(nested defs included), whose final dotted segment is one of
`BRACKET_CALLS` or starts with ``record_``. Device-side learner entry
points run inside traced code where a host-side recorder call cannot
live; their bracket is audited in the host caller that dispatches them
(`DELEGATED_SITES`).

The rule is gated on the scanned set containing the flight recorder
itself (observability/flightrec.py): fixture trees that model other
subsystems (analysis_fixtures/fault_bad, spmd_registry_bad) are not
expected to carry observability plumbing.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Tuple

from .dataflow import call_name
from .engine import Finding, ParsedFile, ProjectContext, ProjectRule
from .rules_faults import DISPATCH_MANIFEST, _DIR_HINTS
from .rules_spmd import COLLECTIVE_MANIFEST

__all__ = ["ObservabilityBracketRule", "BRACKET_CALLS",
           "DELEGATED_SITES"]

_FLIGHTREC_BASENAME = "flightrec.py"

#: call names (final dotted segment) that count as an observability
#: bracket: the watchdog collective bracket and its context manager,
#: the bracketed collective wrappers (whose bodies feed the recorder),
#: span/profiler brackets, and the phase timer
BRACKET_CALLS = frozenset({
    "collective_guard",          # watchdog module-level bracket
    "guard",                     # CollectiveGuard.guard(...)
    "guarded_allgather",         # bracketed collective choke point
    "checkpoint_agree",          # delegates to guarded_allgather
    "_allgather_find_mappers",   # delegates to guarded_allgather
    "span",                      # registry.trace.span(...)
    "capture",                   # profiler.capture(...)
    "timeit",                    # global_timer phase bracket
})

#: any call whose name starts with this also counts (record_span,
#: record_collective, record_fused_block, record_streaming_chunk, ...)
BRACKET_PREFIX = "record_"

#: (manifest basename, function) -> (basename, dir hint, function) of
#: the host caller that owns the bracket for that site
DELEGATED_SITES = {
    ("grower.py", "grow_tree"): ("gbdt.py", "boosting", "_grow"),
    ("grower_mxu.py", "grow_tree_mxu"): ("gbdt.py", "boosting", "_grow"),
    ("histogram_mxu.py", "quantize_gradients"):
        ("gbdt.py", "boosting", "_grow"),
    ("loader.py", "_ingest_chunk_step"):
        ("loader.py", "streaming", "build_streamed_dataset"),
    ("hist_agg.py", "reduce_scatter_hist"):
        ("gbdt.py", "boosting", "_grow"),
}


def _obs_manifest() -> List[Tuple[str, Optional[str], str, str]]:
    """(basename, dir hint, function, provenance) rows to audit —
    the union of the collective registry and the fault-site dispatch
    manifest, with delegated device entries rewritten to their host
    caller. Provenance names the manifest row(s) behind each target,
    for the finding message."""
    rows: Dict[Tuple[str, Optional[str], str], List[str]] = {}

    def _add(basename: str, hint: Optional[str], fn: str,
             origin: str) -> None:
        target = DELEGATED_SITES.get((basename, fn))
        if target is not None:
            basename, hint, fn = target
            origin += " (delegated to host caller)"
        rows.setdefault((basename, hint, fn), []).append(origin)

    for basename, hint, fn, site, _mode, _tests in COLLECTIVE_MANIFEST:
        _add(basename, hint, fn, f"collective site '{site}'")
    for basename, fn, site in DISPATCH_MANIFEST:
        _add(basename, _DIR_HINTS.get((basename, fn)), fn,
             f"fault site '{site}'")
    return [(b, h, f, "; ".join(sorted(set(origins))))
            for (b, h, f), origins in sorted(
                rows.items(), key=lambda kv: (kv[0][0], kv[0][2]))]


def _function_has_bracket(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if name and (name in BRACKET_CALLS or
                     name.startswith(BRACKET_PREFIX)):
            return True
    return False


class ObservabilityBracketRule(ProjectRule):
    id = "OBS001"
    doc = ("every registered collective site and device-dispatch fault "
           "site must run inside an observability bracket (a span, "
           "collective-guard bracket, bracketed collective wrapper, or "
           "record_* recorder call) so the crash flight recorder's "
           "postmortem bundle can name what a dying rank was doing")

    def check_project(self, files: Sequence[ParsedFile],
                      ctx: ProjectContext) -> List[Finding]:
        # gate: only audit trees that carry the flight recorder — the
        # subsystem whose bundles this bracketing exists to feed
        if not any(os.path.basename(p.path) == _FLIGHTREC_BASENAME and
                   "observability" in
                   os.path.normpath(p.path).split(os.sep)
                   for p in files):
            return []
        findings: List[Finding] = []
        for basename, hint, fn_name, origin in _obs_manifest():
            target = None
            for parsed in files:
                if os.path.basename(parsed.path) != basename or \
                        parsed.tree is None:
                    continue
                parts = os.path.normpath(parsed.path).split(os.sep)
                if hint is not None and hint not in parts:
                    continue
                target = parsed
                break
            if target is None:
                continue        # file not in scanned set; nothing to say
            fn = None
            for node in ast.walk(target.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and \
                        node.name == fn_name:
                    fn = node
                    break
            if fn is None:
                findings.append(Finding(
                    rule=self.id, severity=self.severity,
                    path=target.path, line=1,
                    message=f"bracket target '{fn_name}' ({origin}) "
                    f"not found in {basename} — update the OBS001 "
                    f"delegation map if it moved"))
                continue
            if not _function_has_bracket(fn):
                findings.append(Finding(
                    rule=self.id, severity=self.severity,
                    path=target.path, line=fn.lineno,
                    message=f"'{fn_name}' carries {origin} but no "
                    f"observability bracket — wrap the site in a span/"
                    f"collective guard or add a record_* recorder call "
                    f"so postmortem bundles can name it"))
        return findings
