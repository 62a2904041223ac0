"""tpulint (lightgbm_tpu.analysis) tier-1 tests.

Two halves: (1) the package itself must be clean — zero unsuppressed
findings, the contract that makes the analyzer a guard for every later
PR; (2) fixture files under tests/analysis_fixtures/ prove each rule
fires on a known-bad example at the exact line, that inline
suppressions downgrade without hiding, and that exempt look-alike
idioms stay silent.
"""

import json
import os
import subprocess
import sys

import pytest

import lightgbm_tpu
from lightgbm_tpu.analysis import Analyzer, all_rules

pytestmark = pytest.mark.lint

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "analysis_fixtures")
PACKAGE_DIR = os.path.dirname(os.path.abspath(lightgbm_tpu.__file__))

ALL_RULE_IDS = (
    "COLL001", "COLL002", "COLL003", "COLL004",
    "DTYPE001", "DTYPE002", "FAULT001", "JIT001", "JIT002", "JIT003",
    "JIT004", "LOCK001", "LOCK002", "OBS001", "PALLAS001", "PERF001",
    "REG001", "REG002", "REG003", "REG004", "REG005", "SUP001",
    "TRACE001", "TRACE002", "TRACE003", "TRACE004", "TRACE005",
    "TRACE006",
)


def run_on(*relpaths):
    paths = [os.path.join(FIXTURES, p) for p in relpaths]
    return Analyzer().run(paths)


def hits(findings):
    """(rule, line) pairs, suppressed included."""
    return {(f.rule, f.line) for f in findings}


# one full-package scan shared by every package-level assertion in
# this file (the cold scan builds the trace reports; the wall-time
# test below runs its own warm scan so the <10s budget is meaningful)
@pytest.fixture(scope="module")
def package_findings():
    return Analyzer().run([PACKAGE_DIR])


# ----------------------------------------------------------------------
# the tier-1 gate: the package is clean
def test_package_has_zero_unsuppressed_findings(package_findings):
    active = [f for f in package_findings if not f.suppressed]
    assert not active, "tpulint violations:\n" + "\n".join(
        f.render() for f in active)


def test_rule_catalogue_complete():
    assert tuple(r.id for r in all_rules()) == ALL_RULE_IDS
    for rule in all_rules():
        assert rule.doc, f"rule {rule.id} has no doc string"
        assert rule.severity in ("error", "warning")


# ----------------------------------------------------------------------
# each rule fires on its known-bad fixture at the exact line
def test_jit_rules_fire():
    findings = run_on("learner/jit_bad.py")
    assert hits(findings) == {
        ("JIT001", 11),   # scalar_leak: lr annotated scalar, not static
        ("JIT001", 18),   # control_flow: depth scalar default
        ("JIT002", 20),   # if depth > 2
        ("JIT002", 22),   # for _ in range(depth)
        ("JIT003", 29),   # float(x.sum())
        ("JIT003", 30),   # np.asarray(x)
        ("JIT003", 31),   # bool(x[0])
        ("JIT003", 32),   # x.max().item()
    }


def test_donation_reuse_rule_fires():
    findings = run_on("learner/donate_bad.py")
    assert hits(findings) == {
        ("JIT004", 17),   # out + score after score donated by keyword
        ("JIT004", 29),   # carry read after positional donation
    }
    # rebind-from-result, attribute receivers, and store-before-read
    # must stay silent
    assert not any("ok_" in (f.message or "") for f in findings)


def test_dtype_rules_fire():
    findings = run_on("learner/dtype_bad.py")
    assert hits(findings) == {
        ("DTYPE001", 9),    # jnp.float64 accumulator
        ("DTYPE001", 10),   # astype("float64")
        ("DTYPE001", 11),   # np.float64
        ("DTYPE002", 12),   # astype(float)
        ("DTYPE002", 13),   # dtype=float kwarg
    }


def test_lock_discipline_fires():
    findings = run_on("lock_bad.py")
    assert hits(findings) == {
        ("LOCK001", 17),    # peek: self._items read outside the lock
        ("LOCK001", 20),    # reset: self._count write outside the lock
    }
    # the `_locked` caller-holds contract stays silent
    assert not any("_drain_locked" in f.message for f in findings)


def test_lock_order_cycle_fires():
    findings = run_on("lock_cycle_bad.py")
    lock2 = [f for f in findings if f.rule == "LOCK002"]
    assert len(lock2) == 1
    assert "Alpha" in lock2[0].message and "Beta" in lock2[0].message


def test_suppression_reports_but_does_not_count():
    findings = run_on("learner/suppressed.py")
    assert hits(findings) == {("JIT003", 10), ("LOCK001", 23)}
    assert all(f.suppressed for f in findings)
    assert not [f for f in findings if not f.suppressed]


def test_pallas_kernel_rule_fires():
    findings = run_on("learner/pallas_bad.py")
    assert hits(findings) == {
        ("PALLAS001", 18),  # pallas_call without grid_spec/in+out_specs
        ("PALLAS001", 26),  # kernel closes over traced `scale`
        ("PALLAS001", 48),  # factory called with traced `scale`
    }
    # the static-factory + operand pattern (clean) must stay silent
    assert not any(f.line > 55 for f in findings)


def test_perf_hot_path_rule_fires():
    # manifest entry points (basename histogram_pallas.py) fire, the
    # nested helper is covered by its enclosing entry, the host-side
    # non-manifest function is exempt, and the oracle-shaped line
    # suppression downgrades without hiding
    findings = run_on("learner/histogram_pallas.py")
    assert hits(findings) == {
        ("PERF001", 12),   # partition_rows: direct argsort
        ("PERF001", 18),   # build_histograms_scatter: nested sweep
        ("PERF001", 30),   # build_histograms_pallas: suppressed oracle
        ("PERF001", 38),   # partition_table: a sort beside the kernel
    }
    assert {(f.line, f.suppressed) for f in findings} == {
        (12, False), (18, False), (30, True), (38, False)}
    assert all(f.rule == "PERF001" for f in findings)


def test_clean_fixture_is_silent():
    # is-None structural branches, .shape/.ndim statics, init-only
    # attrs and the _locked convention must not false-positive
    assert run_on("learner/clean.py") == []


def test_registry_rules_fire():
    findings = run_on("registry_bad")
    got = {(f.rule, os.path.basename(f.path), f.line) for f in findings}
    assert got == {
        ("REG001", "config.py", 1),    # stale doc row 'gamma'
        ("REG001", "config.py", 1),    # wrong total (same anchor line)
        ("REG001", "config.py", 11),   # task alias drift
        ("REG001", "config.py", 13),   # 'alpha' missing doc row
        ("REG001", "config.py", 14),   # alias collides with param name
        ("REG002", "config.py", 11),   # 'predict' unroutable
        ("REG002", "cli.py", 12),      # 'fit' dead branch
        ("REG003", "cli.py", 22),      # cfg.not_a_param
        ("REG004", "cli.py", 21),      # inject('site_zzz') unknown
        ("REG004", "faults.py", 5),    # site_b unwired + undocumented
        ("REG005", "cli.py", 5),       # rogue metric family
    }
    # site_b produces two distinct REG004 findings on the same line
    site_b = [f for f in findings
              if f.rule == "REG004" and "site_b" in f.message]
    assert len(site_b) == 2


def test_fault_coverage_rule_fires():
    findings = run_on("fault_bad")
    assert all(f.rule == "FAULT001" for f in findings)
    sites = {m for f in findings for m in
             ("fused_dispatch", "histogram_build", "collective_psum")
             if m in f.message}
    assert sites == {"fused_dispatch", "histogram_build",
                     "collective_psum"}
    assert len(findings) == 3


def test_observability_bracket_rule_fires():
    # guarded_allgather carries its fault site (FAULT001 quiet) but no
    # span/guard/record_* bracket; checkpoint_agree is covered by
    # delegating to the bracketed wrapper
    findings = run_on("obs_bad")
    assert hits(findings) == {("OBS001", 9)}
    (finding,) = findings
    assert "guarded_allgather" in finding.message


def test_observability_rule_gated_on_flightrec():
    # fixture trees without observability/flightrec.py model packages
    # that predate the flight recorder — OBS001 stays silent there
    assert not [f for f in run_on("fault_bad") if f.rule == "OBS001"]


# ----------------------------------------------------------------------
# SPMD collective-discipline rules (COLL001-COLL004) and the
# stale-suppression self-check (SUP001)
def test_spmd_rules_fire():
    findings = run_on("spmd/coll_bad.py")
    assert hits(findings) == {
        ("COLL001", 15),  # branch_deadlock: psum on one arm only
        ("COLL001", 22),  # loop_deadlock: rank-local trip count
        ("COLL001", 29),  # cond_expr_deadlock: psum(x) if r > 0 else x
        ("COLL002", 34),  # stranded_raise: bare raise, peers allgather
        ("COLL002", 44),  # pr7_bin_parity: the PR-7 bug shape
        ("COLL003", 50),  # ragged_gather: rows[:n] fed to allgather
        ("COLL001", 58),  # resize_epoch_vote: coordinator-only gather
    }


def test_pr7_bug_shape_is_caught():
    # re-introducing the PR-7 stream_bin_parity bug (rank-guarded
    # collective with a bare raise on the other arm) must be caught by
    # COLL001 or COLL002
    findings = run_on("spmd/coll_bad.py")
    pr7 = [f for f in findings
           if f.rule in ("COLL001", "COLL002")
           and "pr7_bin_parity" in f.message]
    assert pr7, "PR-7 bug shape not detected"


def test_spmd_clean_fixture_is_silent():
    # matching arms, agreement sync, participate-then-raise, np.pad to
    # a static wire shape, and rank-uniform config branches/loops
    assert run_on("spmd/coll_clean.py") == []


def test_collective_registry_discovery_fires():
    findings = run_on("spmd_registry_bad/pkg")
    active = {(f.rule, os.path.basename(f.path), f.line)
              for f in findings if not f.suppressed}
    assert active == {("COLL004", "sync.py", 5)}
    # the fixture's REG001 file-suppression is live, so SUP001 is quiet
    assert not any(f.rule == "SUP001" for f in findings)


def test_collective_manifest_covered_in_package(package_findings):
    # on the real package the manifest itself must be violation-free:
    # no COLL004 finding at all (covered entries + no unregistered
    # collective entry points)
    assert not [f for f in package_findings if f.rule == "COLL004"]


def test_stale_suppression_self_check():
    findings = run_on("stale_suppress.py")
    sup = {(f.rule, f.line) for f in findings if f.rule == "SUP001"}
    assert sup == {
        ("SUP001", 11),   # disable-file=LOCK002 suppresses nothing
        ("SUP001", 15),   # unknown rule id NOPE123
        ("SUP001", 19),   # disable=JIT003 on a clean line
    }
    # the live LOCK001 suppression is honored, not flagged
    assert {(f.rule, f.line, f.suppressed) for f in findings
            if f.rule == "LOCK001"} == {("LOCK001", 32, True)}


# ----------------------------------------------------------------------
# TRACE rule family: contracts checked on the traced program (jaxpr),
# driven by a machine-checked manifest
def test_trace_rules_fire():
    findings = run_on("trace_bad")
    assert hits(findings) == {
        ("TRACE001", 94),   # sorting_entry: jnp.sort in the jaxpr
        ("TRACE002", 97),   # f64_entry: strong float64 under x64
        ("TRACE003", 100),  # callback_entry: debug_print primitive
        ("TRACE004", 103),  # dead_donation_entry: donation unusable
        ("TRACE005", 107),  # baked_scalar_entry: static arg re-traces
        ("TRACE006", 1),    # manifest-level coverage findings
    }
    cov = [f for f in findings if f.rule == "TRACE006"]
    assert len(cov) == 2
    msgs = " | ".join(f.message for f in cov)
    assert "fused_dispatch" in msgs      # uncovered dispatch row
    assert "old_entry" in msgs           # stale waiver


def test_trace_clean_fixture_is_silent():
    # donation consumed, traced scalar stable across retraces, x64
    # trace clean, dispatch row covered, no waivers
    assert run_on("trace_clean") == []


def test_trace_manifest_covers_dispatch_sites():
    # the production manifest must cover or explicitly waive every
    # device-dispatch row — TRACE006 enforces this at lint time, this
    # test pins it structurally so a new dispatch site fails fast
    from lightgbm_tpu.analysis.rules_faults import DISPATCH_MANIFEST
    from lightgbm_tpu.analysis.tracecheck import TRACE_MANIFEST, WAIVERS
    covered = {c for e in TRACE_MANIFEST for c in e.covers}
    for row in DISPATCH_MANIFEST:
        key = tuple(row)
        assert key in covered or key in WAIVERS, \
            f"dispatch row {key} neither traced nor waived"
    # waivers must carry a reason and not shadow a covered row
    for key, reason in WAIVERS.items():
        assert reason.strip()
        assert key not in covered


# ----------------------------------------------------------------------
# interprocedural engine: findings that require the project call graph
def test_interproc_findings_fire_across_modules():
    findings = run_on("interproc_bad")
    got = {(f.rule, os.path.basename(f.path), f.line) for f in findings}
    assert got == {
        ("JIT003", "jit_sync.py", 12),  # float() two modules away
        ("COLL001", "work.py", 11),     # psum hidden inside the callee
        ("LOCK001", "ring.py", 15),     # _locked delegate, no lock held
    }
    # findings must name the callee and its definition site
    jit = next(f for f in findings if f.rule == "JIT003")
    assert "to_python_scalar" in jit.message
    assert "convert.py" in jit.message
    lock = next(f for f in findings if f.rule == "LOCK001")
    assert "append_locked" in lock.message
    assert "store.py" in lock.message


def test_interproc_findings_need_the_callgraph():
    # the same fixtures are provably invisible to the intraprocedural
    # engine: each file is clean in isolation
    bad = os.path.join(FIXTURES, "interproc_bad")
    assert Analyzer(interproc=False).run([bad]) == []


def test_interproc_clean_fixture_is_silent():
    # lock held around the delegate, shape-only helper, rank-uniform
    # collective call — the call graph must not over-taint these
    assert run_on("interproc_clean") == []


# ----------------------------------------------------------------------
# incremental cache: content-hash keys, dependent invalidation
def test_lint_cache_roundtrip_and_invalidation(tmp_path):
    from lightgbm_tpu.analysis.cache import LintCache
    src = tmp_path / "mod.py"
    dep = tmp_path / "helper.py"
    src.write_text("x = 1\n")
    dep.write_text("y = 2\n")

    cache = LintCache(str(tmp_path))
    key = cache.file_key(str(src), [str(dep)], interproc=True)
    assert cache.get_file_findings(key) is None
    cache.put_file_findings(key, [{"rule": "JIT003", "line": 3}])
    # a fresh instance (no memoized hashes) computes the same key and
    # reads the stored payload back
    fresh = LintCache(str(tmp_path))
    assert fresh.file_key(str(src), [str(dep)], interproc=True) == key
    assert fresh.get_file_findings(key) == [{"rule": "JIT003",
                                             "line": 3}]
    # toggling interproc changes the key
    assert cache.file_key(str(src), [str(dep)],
                          interproc=False) != key
    # editing only the *dependency* invalidates the dependent file
    dep.write_text("y = 3\n")
    assert LintCache(str(tmp_path)).file_key(
        str(src), [str(dep)], interproc=True) != key


def test_cache_engages_for_package_scans_only(package_findings):
    # the shared package scan (the fixture) ran with cache on
    from lightgbm_tpu.analysis.cache import CACHE_DIR_NAME
    repo_root = os.path.dirname(PACKAGE_DIR)
    assert os.path.isdir(os.path.join(repo_root, CACHE_DIR_NAME))
    # fixture scans must never sprinkle cache directories around
    assert not os.path.exists(os.path.join(FIXTURES, CACHE_DIR_NAME))


def test_full_package_analysis_wall_time(package_findings):
    # warm-cache scan (the shared module fixture paid the cold trace
    # builds): the per-commit lint loop must stay under the budget
    import time
    t0 = time.monotonic()
    Analyzer().run([PACKAGE_DIR])
    elapsed = time.monotonic() - t0
    assert elapsed < 10.0, f"tpulint took {elapsed:.1f}s on the package"


# ----------------------------------------------------------------------
# CLI contract: module entry point, exit codes, JSON schema
def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.analysis", *args],
        capture_output=True, text=True, timeout=120)


def test_cli_exit_codes_and_json():
    # --no-cache rides along: accepted, and findings are unchanged
    bad = _run_cli(os.path.join(FIXTURES, "lock_bad.py"), "--no-cache",
                   "--format=json")
    assert bad.returncode == 1
    payload = json.loads(bad.stdout)
    assert payload["unsuppressed"] == 2
    assert payload["suppressed"] == 0
    assert {f["rule"] for f in payload["findings"]} == {"LOCK001"}
    for f in payload["findings"]:
        assert set(f) == {"rule", "severity", "path", "line", "message",
                          "suppressed"}

    clean = _run_cli(os.path.join(FIXTURES, "learner", "clean.py"))
    assert clean.returncode == 0
    assert "0 finding(s)" in clean.stdout


def test_cli_sarif_format():
    res = _run_cli(os.path.join(FIXTURES, "lock_bad.py"),
                   "--format=sarif")
    assert res.returncode == 1        # findings still set the exit code
    doc = json.loads(res.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "tpulint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert rule_ids == set(ALL_RULE_IDS)
    results = run["results"]
    assert {r["ruleId"] for r in results} == {"LOCK001"}
    assert {r["locations"][0]["physicalLocation"]["region"]["startLine"]
            for r in results} == {17, 20}
    assert all("suppressions" not in r for r in results)

    # suppressed findings carry an inSource suppression record
    sup = _run_cli(os.path.join(FIXTURES, "learner", "suppressed.py"),
                   "--format=sarif")
    assert sup.returncode == 0
    sdoc = json.loads(sup.stdout)
    sresults = sdoc["runs"][0]["results"]
    assert sresults and all(
        r["suppressions"] == [{"kind": "inSource"}] for r in sresults)


def test_cli_list_rules():
    res = _run_cli("--list-rules")
    assert res.returncode == 0
    for rule_id in ALL_RULE_IDS:
        assert rule_id in res.stdout


def test_cli_no_interproc_flag():
    # the default-on behaviour is pinned in-process
    # (test_interproc_findings_fire_across_modules); here the flag must
    # drop the cross-module findings through the CLI
    off = _run_cli(os.path.join(FIXTURES, "interproc_bad"),
                   "--no-interproc", "--format=json")
    assert off.returncode == 0
    assert json.loads(off.stdout)["unsuppressed"] == 0
