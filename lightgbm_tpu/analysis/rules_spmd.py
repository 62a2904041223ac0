"""SPMD collective-discipline rules (COLL001-COLL004).

Multihost training is SPMD: every rank runs the same program, and every
collective (`psum`, `all_gather`, `process_allgather`, the package's
own `_allgather_find_mappers` / `mapper_sync` wrappers) is a barrier
all ranks must reach together, the same number of times, with the same
operand shapes. The failure modes are nasty because they are *silent
at the failing rank*: a branch taken on rank-local state routes one
rank around the collective and the peers hang (or, worse, the gather
completes against the wrong rank's data and the model is silently
wrong). PR 7's `stream_bin_parity` bug was exactly this shape — one
rank raised on a rank-local coverage check while its peers sat in the
mapper allgather.

The rules run on the CFG + rank-taint engine in `dataflow.py`:

- **COLL001** — a collective reachable under a rank-divergent branch
  whose other arm does not perform the matching collective (the
  deadlock shape). Also: collectives inside loops with rank-divergent
  trip counts, and `psum(x) if <tainted> else x` expressions.
- **COLL002** — a `raise` guarded by a rank-divergent condition with a
  collective downstream in the same function and no collective
  participation before the raise (the stranded-peer shape). Branching
  on a collective *result* is the sanctioned agreement-sync idiom:
  collective results are rank-uniform, so such guards are not tainted.
- **COLL003** — a rank-variable-shaped operand fed to a fixed-shape
  collective without padding to a static wire shape (`np.pad` and the
  other `dataflow.SHAPE_SANITIZERS` clear the taint).
- **COLL004** — cross-file registry: every function containing a
  collective call must appear in `COLLECTIVE_MANIFEST`, mapping it to
  a fault site (so the reliability harness can kill the collective)
  and to a test file that exercises it by name — new collectives
  cannot land untested.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .engine import Finding, ParsedFile, ProjectContext, ProjectRule, Rule
from .dataflow import (CFG, COLLECTIVE_CALLABLES, RankTaint, call_name,
                       collective_calls, iter_top_functions, stmt_exprs)

__all__ = ["CollectiveBranchRule", "CollectiveRaiseRule",
           "CollectiveShapeRule", "CollectiveRegistryRule",
           "COLLECTIVE_MANIFEST"]


# ---------------------------------------------------------------------------
# shared per-function analysis (memoized: three rules share it)

class _FunctionAnalysis:
    """CFG + taint + guard chains for one top-level function.

    `extra` carries interprocedurally-resolved collective spellings
    (helpers that transitively psum/allgather, from
    callgraph.collective_call_names) — the taint launder, the
    reachability sets and the participate-before check all treat them
    exactly like the base collectives."""

    def __init__(self, fn: ast.FunctionDef, shape_seeds: bool,
                 extra: frozenset = frozenset()):
        self.fn = fn
        self.all_collectives = COLLECTIVE_CALLABLES | extra
        self.extra = extra
        self.cfg = CFG(fn)
        self.taint = RankTaint(fn, shape_seeds=shape_seeds,
                               extra_collectives=extra)
        #: id(stmt) -> chain of (guard stmt, arm statements) from the
        #: outermost enclosing branch/loop inward
        self.guards: Dict[int, Tuple[Tuple[ast.stmt, List[ast.stmt]], ...]] \
            = {}
        self._map_guards(fn.body, ())
        #: CFG node -> collective callee names in the node's OWN exprs
        self.node_collectives: Dict[object, Set[str]] = {}
        for node in self.cfg.nodes:
            names: Set[str] = set()
            for expr in stmt_exprs(node.stmt):
                for sub in ast.walk(expr):
                    if isinstance(sub, ast.Call) and \
                            call_name(sub) in self.all_collectives:
                        names.add(call_name(sub))
            if names:
                self.node_collectives[node] = names

    def _map_guards(self, stmts: Sequence[ast.stmt],
                    chain: Tuple) -> None:
        for stmt in stmts:
            self.guards[id(stmt)] = chain
            if isinstance(stmt, (ast.If, ast.While)):
                arm = chain + (((stmt, stmt.body)),)
                self._map_guards(stmt.body, arm)
                if stmt.orelse:
                    self._map_guards(stmt.orelse,
                                     chain + ((stmt, stmt.orelse),))
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                self._map_guards(stmt.body, chain + ((stmt, stmt.body),))
                if stmt.orelse:
                    self._map_guards(stmt.orelse, chain)
            else:
                for field in ("body", "orelse", "finalbody"):
                    block = getattr(stmt, field, None)
                    if isinstance(block, list) and block and \
                            isinstance(block[0], ast.stmt):
                        self._map_guards(block, chain)
                for handler in getattr(stmt, "handlers", ()) or ():
                    self._map_guards(handler.body, chain)
                for case in getattr(stmt, "cases", ()) or ():
                    self._map_guards(case.body, chain)

    # -- queries --------------------------------------------------------
    def reach_collectives(self, start) -> Set[str]:
        """Collective names on any path from CFG node `start`."""
        names: Set[str] = set()
        for node in self.cfg.reachable(start):
            names |= self.node_collectives.get(node, set())
        return names

    def stranded_raises(self) -> List[Tuple[ast.stmt, ast.stmt, str]]:
        """COLL002 candidates: (raise stmt, guarding branch, downstream
        collective name)."""
        out: List[Tuple[ast.stmt, ast.stmt, str]] = []
        for node in self.cfg.nodes:
            if node.kind != "raise":
                continue
            r = node.stmt
            chain = self.guards.get(id(r), ())
            tainted = [(g, arm) for g, arm in chain
                       if self.taint.stmt_test_tainted(g)]
            if not tainted:
                continue
            guard, arm = tainted[-1]            # innermost divergent guard
            if self._participates_before(arm, r):
                continue
            gnode = self.cfg.node(guard)
            if gnode is None:
                continue
            downstream: Set[str] = set()
            for nd in self.cfg.reachable(gnode, avoid=node):
                downstream |= self.node_collectives.get(nd, set())
            if downstream:
                out.append((r, guard, sorted(downstream)[0]))
        return out

    def _participates_before(self, arm: Sequence[ast.stmt],
                             raise_stmt: ast.stmt) -> bool:
        """A collective call inside the guarded arm, textually before
        the raise, means this rank joins the barrier before failing
        (the participate-then-raise idiom)."""
        r_line = raise_stmt.lineno
        for stmt in arm:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call) and \
                        call_name(node) in self.all_collectives and \
                        node.lineno < r_line:
                    return True
        return False


_CACHE: Dict[Tuple[str, int, frozenset], _FunctionAnalysis] = {}


def _extra_collectives(rule: Rule, parsed: ParsedFile) -> frozenset:
    """Interprocedural collective spellings for this file, when the
    analyzer attached callgraph facts to the rule."""
    facts = getattr(rule, "facts", None)
    if facts is None:
        return frozenset()
    return facts.collective_call_names(parsed.path)


def _analyses(parsed: ParsedFile,
              extra: frozenset = frozenset()
              ) -> Iterator[_FunctionAnalysis]:
    """One analysis per top function that contains a collective call
    (base or interprocedurally-resolved)."""
    if parsed.tree is None:
        return
    shape_seeds = not parsed.in_device_dir()
    for fn in iter_top_functions(parsed.tree):
        if not collective_calls(fn, extra):
            continue
        key = (parsed.path, fn.lineno, extra)
        fa = _CACHE.get(key)
        if fa is None or fa.fn is not fn:
            fa = _FunctionAnalysis(fn, shape_seeds, extra)
            _CACHE[key] = fa
        yield fa


# ---------------------------------------------------------------------------

class CollectiveBranchRule(Rule):
    id = "COLL001"
    doc = ("collective call reachable under a rank-divergent branch "
           "whose other arm performs no matching collective — ranks "
           "that take the other path strand their peers in the "
           "barrier; hoist the collective out of the branch or make "
           "the condition an agreement (branch on a collective result)")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        findings: List[Finding] = []
        for fa in _analyses(parsed, _extra_collectives(self, parsed)):
            raise_guards = {id(g) for _, g, _ in fa.stranded_raises()}
            for node in fa.cfg.nodes:
                stmt = node.stmt
                if isinstance(stmt, ast.If) and \
                        fa.taint.expr_tainted(stmt.test):
                    if id(stmt) in raise_guards:
                        continue        # reported as COLL002
                    then_c = fa.reach_collectives(node.succs[0])
                    else_c = fa.reach_collectives(node.succs[1])
                    if then_c != else_c:
                        odd = sorted(then_c ^ else_c)[0]
                        findings.append(self.finding(
                            parsed, stmt.lineno,
                            f"function '{fa.fn.name}': collective "
                            f"'{odd}' is reached on only one arm of a "
                            f"branch on rank-local state — peers on "
                            f"the other arm never enter the barrier"))
                elif isinstance(stmt, (ast.While, ast.For)) and \
                        fa.taint.stmt_test_tainted(stmt):
                    inner = {call_name(c)
                             for c in collective_calls(stmt, fa.extra)}
                    # names in the loop header don't iterate with the body
                    header = set()
                    for expr in stmt_exprs(stmt):
                        for sub in ast.walk(expr):
                            if isinstance(sub, ast.Call) and \
                                    call_name(sub) in fa.all_collectives:
                                header.add(call_name(sub))
                    inner -= header
                    if inner:
                        findings.append(self.finding(
                            parsed, stmt.lineno,
                            f"function '{fa.fn.name}': collective "
                            f"'{sorted(inner)[0]}' inside a loop whose "
                            f"trip count is rank-local — ranks fall "
                            f"out of the barrier after different "
                            f"iteration counts"))
            # conditional-expression form: psum(x) if <tainted> else x
            for node in ast.walk(fa.fn):
                if not isinstance(node, ast.IfExp) or \
                        not fa.taint.expr_tainted(node.test):
                    continue
                then_c = {call_name(c)
                          for c in collective_calls(node.body, fa.extra)}
                else_c = {call_name(c)
                          for c in collective_calls(node.orelse, fa.extra)}
                if then_c != else_c:
                    findings.append(self.finding(
                        parsed, node.lineno,
                        f"function '{fa.fn.name}': conditional "
                        f"expression runs collective "
                        f"'{sorted(then_c ^ else_c)[0]}' on only one "
                        f"arm of a rank-divergent condition"))
        return findings


class CollectiveRaiseRule(Rule):
    id = "COLL002"
    doc = ("raise guarded by a rank-divergent condition with a "
           "collective downstream in the same function — one rank "
           "aborts while its peers block in the barrier (the PR-7 "
           "stream_bin_parity bug shape); allgather an agreement flag "
           "first, or participate in the collective before raising")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        findings: List[Finding] = []
        for fa in _analyses(parsed, _extra_collectives(self, parsed)):
            for r, guard, coll in fa.stranded_raises():
                findings.append(self.finding(
                    parsed, r.lineno,
                    f"function '{fa.fn.name}': raise under "
                    f"rank-divergent condition (line {guard.lineno}) "
                    f"while peers proceed to collective '{coll}' — "
                    f"sync agreement (allgather an error flag) or "
                    f"join the collective before raising"))
        return findings


class CollectiveShapeRule(Rule):
    id = "COLL003"
    doc = ("rank-variable-shaped operand fed to a fixed-shape "
           "collective — gather shapes must be identical on every "
           "rank; pad to a static wire shape (np.pad / np.zeros) and "
           "ship the true length alongside")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        findings: List[Finding] = []
        for fa in _analyses(parsed, _extra_collectives(self, parsed)):
            for call in collective_calls(fa.fn, fa.extra):
                for arg in call.args:
                    if fa.taint.expr_shape_tainted(arg):
                        findings.append(self.finding(
                            parsed, call.lineno,
                            f"function '{fa.fn.name}': operand of "
                            f"collective '{call_name(call)}' has a "
                            f"rank-local shape — pad to the fixed "
                            f"wire shape before gathering"))
                        break
        return findings


# ---------------------------------------------------------------------------
# COLL004: cross-file collective-site registry

#: (file basename, parent-dir hint, function, fault site, coverage mode,
#:  test files that must exercise the function by name).
#: Coverage modes: "body" — the function itself injects the site
#: (literal or registered wrapper, rules_faults.SITE_WRAPPERS);
#: "delegate" — its collectives are calls to other manifest functions;
#: "dispatch" — a device collective whose site fires at the dispatch
#: boundary (rules_faults.DISPATCH_MANIFEST carries the site).
COLLECTIVE_MANIFEST = (
    ("comm.py", "parallel", "guarded_allgather", "collective_psum",
     "body", ("test_watchdog.py", "test_multihost.py")),
    ("comm.py", "parallel", "checkpoint_agree", "collective_psum",
     "delegate", ("test_checkpoint.py", "test_multihost.py")),
    ("basic.py", None, "_allgather_find_mappers", "collective_psum",
     "body", ("test_multihost.py", "test_streaming.py")),
    ("basic.py", None, "_distributed_bin_mappers", "collective_psum",
     "delegate", ("test_multihost.py",)),
    ("basic.py", None, "_streaming_mapper_sync", "collective_psum",
     "delegate", ("test_streaming.py", "test_multihost.py")),
    ("loader.py", "streaming", "build_streamed_dataset",
     "streaming_ingest", "body", ("test_streaming.py",)),
    ("gbdt.py", "boosting", "_setup_train", "collective_psum",
     "body", ("test_multihost.py",)),
    ("gbdt.py", "boosting", "_setup_parallel", "collective_psum",
     "body", ("test_multihost.py",)),
    ("gbdt.py", "boosting", "_sync_renewed_leaves", "collective_psum",
     "body", ("test_multihost.py",)),
    ("gbdt.py", "boosting", "_boost_from_average", "collective_psum",
     "body", ("test_multihost.py",)),
    ("grower.py", "learner", "grow_tree", "collective_psum",
     "dispatch", ("test_distributed.py",)),
    ("grower_mxu.py", "learner", "grow_tree_mxu", "collective_psum",
     "dispatch", ("test_distributed.py",)),
    ("histogram_mxu.py", "learner", "quantize_gradients",
     "collective_psum", "dispatch",
     ("test_distributed.py", "test_hist_backends.py")),
    ("hist_agg.py", "distributed", "build_feature_shards",
     "distributed_hist_agg", "body", ("test_distributed_learner.py",)),
    ("hist_agg.py", "distributed", "reduce_scatter_hist",
     "collective_psum", "dispatch", ("test_distributed_learner.py",)),
    ("binning.py", "distributed", "merge_streaming_sketch",
     "collective_psum", "delegate", ("test_distributed_learner.py",)),
    # elastic membership (distributed/elastic.py): the epoch-agreement
    # gather and the reshard row-count exchange both delegate to
    # guarded_allgather (the shrink VOTE itself is deliberately NOT a
    # collective — it rides the heartbeat directory because the old
    # world's collectives just failed)
    ("elastic.py", "distributed", "epoch_agree", "collective_psum",
     "delegate", ("test_elastic.py",)),
    ("elastic.py", "distributed", "reshard_offsets", "collective_psum",
     "delegate", ("test_elastic.py",)),
)


class CollectiveRegistryRule(ProjectRule):
    id = "COLL004"
    doc = ("every function containing a collective call must be "
           "registered in rules_spmd.COLLECTIVE_MANIFEST with a fault "
           "site the reliability harness can fire and a test file "
           "that exercises it by name — new collectives cannot land "
           "untested")

    def check_project(self, files: Sequence[ParsedFile],
                      ctx: ProjectContext) -> List[Finding]:
        # fixture isolation: only meaningful when a package root
        # (config.py) is in the scanned set, like the registry rules
        if not any(os.path.basename(f.path) == "config.py"
                   for f in files):
            return []
        findings: List[Finding] = []
        findings += self._check_manifest(files, ctx)
        findings += self._check_discovery(files)
        return findings

    # -- manifest rows --------------------------------------------------
    def _check_manifest(self, files: Sequence[ParsedFile],
                        ctx: ProjectContext) -> List[Finding]:
        from .rules_faults import DISPATCH_MANIFEST, _function_covers_site
        from .rules_registry import _known_sites
        findings: List[Finding] = []
        faults = next(
            (f for f in files
             if os.path.basename(f.path) == "faults.py"
             and f.tree is not None), None)
        known = _known_sites(faults)[0] if faults is not None else None
        dispatch_sites = {site for _, _, site in DISPATCH_MANIFEST}
        manifest_fns = {row[2] for row in COLLECTIVE_MANIFEST}
        for basename, hint, fn_name, site, mode, test_files in \
                COLLECTIVE_MANIFEST:
            target = self._resolve(files, basename, hint)
            if target is None:
                continue        # file not in scanned set; nothing to say
            fn = self._find_fn(target, fn_name)
            if fn is None:
                findings.append(Finding(
                    rule=self.id, severity=self.severity,
                    path=target.path, line=1,
                    message=f"collective manifest names '{fn_name}' "
                            f"which does not exist in {basename}"))
                continue
            if known is not None and site not in known:
                findings.append(self.finding(
                    target, fn.lineno,
                    f"collective entry '{fn_name}' maps to unknown "
                    f"fault site '{site}' (not in "
                    f"reliability/faults.py KNOWN_SITES)"))
            if mode == "body" and not _function_covers_site(fn, site):
                findings.append(self.finding(
                    target, fn.lineno,
                    f"collective entry '{fn_name}' declares fault "
                    f"site '{site}' but neither uses the literal nor "
                    f"calls a registered wrapper — the reliability "
                    f"harness cannot kill this collective"))
            elif mode == "delegate" and not any(
                    call_name(c) in manifest_fns
                    for c in collective_calls(fn)):
                findings.append(self.finding(
                    target, fn.lineno,
                    f"collective entry '{fn_name}' is marked "
                    f"delegate but calls no other manifest function"))
            elif mode == "dispatch" and site not in dispatch_sites:
                findings.append(self.finding(
                    target, fn.lineno,
                    f"collective entry '{fn_name}' is marked dispatch "
                    f"but site '{site}' is not in "
                    f"rules_faults.DISPATCH_MANIFEST"))
            named = self._named_in_tests(ctx, fn_name, test_files)
            if named is False:
                findings.append(self.finding(
                    target, fn.lineno,
                    f"collective entry '{fn_name}' is not exercised "
                    f"by name in any of: {', '.join(test_files)}"))
        return findings

    # -- reverse discovery ----------------------------------------------
    def _check_discovery(self, files: Sequence[ParsedFile]
                         ) -> List[Finding]:
        registered = {(row[0], row[2]) for row in COLLECTIVE_MANIFEST}
        findings: List[Finding] = []
        for parsed in files:
            if parsed.tree is None:
                continue
            parts = os.path.normpath(parsed.path).split(os.sep)
            if "analysis" in parts:
                continue        # the analyzer names collectives, by trade
            basename = os.path.basename(parsed.path)
            for fn in iter_top_functions(parsed.tree):
                calls = collective_calls(fn)
                if not calls or (basename, fn.name) in registered:
                    continue
                findings.append(self.finding(
                    parsed, fn.lineno,
                    f"unregistered collective entry point: "
                    f"'{fn.name}' calls "
                    f"'{call_name(calls[0])}' but is not in "
                    f"rules_spmd.COLLECTIVE_MANIFEST (map it to a "
                    f"fault site and a multihost test)"))
        return findings

    # -- helpers ---------------------------------------------------------
    @staticmethod
    def _resolve(files: Sequence[ParsedFile], basename: str,
                 hint: Optional[str]) -> Optional[ParsedFile]:
        for parsed in files:
            if os.path.basename(parsed.path) != basename or \
                    parsed.tree is None:
                continue
            parts = os.path.normpath(parsed.path).split(os.sep)
            if hint is not None and hint not in parts:
                continue
            return parsed
        return None

    @staticmethod
    def _find_fn(parsed: ParsedFile,
                 fn_name: str) -> Optional[ast.FunctionDef]:
        for fn in iter_top_functions(parsed.tree):
            if fn.name == fn_name:
                return fn
        return None

    @staticmethod
    def _named_in_tests(ctx: ProjectContext, fn_name: str,
                        test_files: Sequence[str]) -> Optional[bool]:
        seen_any = False
        for name in test_files:
            path = os.path.join(ctx.tests_dir, name)
            try:
                with open(path, "r") as fh:
                    text = fh.read()
            except OSError:
                continue
            seen_any = True
            if fn_name in text:
                return True
        # no named test file readable (fixture runs): nothing to say
        return False if seen_any else None
