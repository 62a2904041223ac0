"""Hot-path performance rule (device code paths only).

PERF001 guards the round-6 partition win: the slot-grouped scatter
kernels used to order rows with `jnp.argsort` — O(N log N) work per
level where the blocked-prefix-sum scan partition does O(N) with the
per-slot counts the router already emits (docs/Performance.md "Row
partitioning", Parallel Scan on Ascend arXiv:2505.15112).  A sort quietly
reintroduced into any registered device hot-path function would
silently reinstate the old cost at exactly the shapes where it hurts
(N = millions of rows, every tree level), so the manifest below pins
the entry points whose inner loops are row-linear by design.

The rule flags lexical `argsort` calls (``jnp.argsort``,
``jax.numpy.argsort``, ``np.argsort`` — any dotted tail) anywhere
inside a manifest function, including nested helpers (scan/cond
bodies defined inline).  The retained bit-parity oracle branch in
``partition_rows`` carries an explicit line suppression naming
PERF001 — visible, auditable, and the ONLY sanctioned sort on the
partition path.

Functions not in the manifest do not fire: argsort is a fine tool in
host-side setup (bin boundary construction, EFB greedy bundling) where
it runs once per Dataset rather than once per level.

Since the TRACE family landed, PERF001 is the *lexical fallback*: the
authoritative sort-free guarantee is TRACE001, which traces the hot
entries to jaxprs and rejects the `sort` primitive however it was
spelled or wherever the helper lives. PERF001 stays because it is
instant, points at the exact offending source line, and works on code
that does not trace yet.
"""

from __future__ import annotations

import ast
import os
from typing import List

from .dataflow import dotted_name as _dotted_name
from .engine import Finding, ParsedFile, Rule

__all__ = ["PerfHotPathSortRule", "HOT_PATH_MANIFEST"]

#: (module basename, function name) -> registered device hot-path
#: entry points whose whole lexical body must stay sort-free. Nested
#: defs (one_pass, sweep, scan bodies) are covered by their enclosing
#: entry. Kept as an explicit manifest — not "every function in
#: learner/" — so host-side preprocessing keeps its freedom.
HOT_PATH_MANIFEST = {
    ("histogram_pallas.py", "partition_rows"),
    ("histogram_pallas.py", "partition_table"),
    ("histogram_pallas.py", "partition_stream"),
    ("histogram_pallas.py", "_layout"),
    ("histogram_pallas.py", "_stable_positions"),
    ("histogram_pallas.py", "build_histograms_scatter"),
    ("histogram_pallas.py", "build_histograms_pallas"),
    ("histogram_mxu.py", "route_rows_mxu"),
    ("histogram_mxu.py", "prepare_hist_operands"),
    ("histogram_mxu.py", "build_histograms_mxu"),
    ("histogram_mxu.py", "build_histograms_mxu_v2"),
    ("histogram_mxu.py", "fused_route_hist_mxu"),
    ("grower.py", "grow_tree"),
    ("grower_mxu.py", "grow_tree_mxu"),
}

_SORT_TAILS = ("argsort",)


class PerfHotPathSortRule(Rule):
    """PERF001: `argsort` inside a registered device hot-path
    function."""

    id = "PERF001"
    severity = "error"
    doc = ("O(N log N) `argsort` inside a registered device hot-path "
           "function (HOT_PATH_MANIFEST, rules_perf.py) — the stream "
           "partition made these paths row-linear; route the ordering "
           "through partition_table or, for a retained "
           "parity oracle, suppress the exact line (lexical fallback; "
           "TRACE001 checks the traced program)")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        if parsed.tree is None or not parsed.in_device_dir():
            return []
        base = os.path.basename(parsed.path)
        if not any(mod == base for mod, _ in HOT_PATH_MANIFEST):
            return []
        out: List[Finding] = []
        for node in ast.walk(parsed.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if (base, node.name) not in HOT_PATH_MANIFEST:
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                name = _dotted_name(sub.func)
                if name and name.split(".")[-1] in _SORT_TAILS:
                    out.append(self.finding(
                        parsed, sub.lineno,
                        f"argsort in device hot path "
                        f"'{node.name}' ({name}): the scan partition "
                        f"keeps this path O(N); see "
                        f"docs/Performance.md"))
        return out
