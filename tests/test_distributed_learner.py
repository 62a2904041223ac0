"""Distributed training subsystem: byte-parity oracles + unit tests.

The crossbar contract (distributed/crossbar.py + docs/Distributed.md):
`tree_learner=data` under the exact reduce-scatter histogram flavor
grows trees byte-identical to `tree_learner=serial` — on the 8-virtual-
device mesh the conftest provisions, and trivially on a 1-device mesh
(serial fallback). The oracles compare `model_to_string()` up to the
embedded parameter dump (the `tree_learner` line necessarily differs)
and run the per-iteration sharded path (`fused_block_size=1`): the
fused block is deterministic but carries a documented 1-ulp score-
rounding difference (distributed/fused.py).

Also under test here, by name, for the COLL004/FAULT001 manifests:
`build_feature_shards`, `reduce_scatter_hist`, `merge_streaming_sketch`
and the `distributed_hist_agg` fault site.

Row counts divide the 8-device mesh (row_pad=0) — parity with padding
is exercised at small scale by the 1-device fallback test.
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.reliability.faults import InjectedFault, faults

pytestmark = [pytest.mark.distributed]

N, F = 800, 12          # divisible by 8: zero row padding on the mesh


def _make(task, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(N, F)
    if task == "regression":
        y = X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.1 * rng.randn(N)
        obj = "regression"
    elif task == "binary":
        y = (X[:, 0] + 0.5 * X[:, 1] ** 2 +
             0.3 * rng.randn(N) > 0.5).astype(np.float32)
        obj = "binary"
    else:
        centers = rng.randn(4, F) * 2
        d = ((X[:, None, :] - centers[None]) ** 2).sum(-1)
        y = d.argmin(1).astype(np.float32)
        obj = "multiclass"
    return X, y, obj


def _trees(bst):
    """Everything before the embedded parameter dump: the trees and
    learned state. `[tree_learner: ...]` in the dump differs by
    construction between the runs under comparison."""
    return bst.model_to_string().split("parameters:")[0]


def _train(task, extra, rounds=8):
    X, y, obj = _make(task)
    # enable_bundle=False keeps the crossbar's `auto` hist_agg on the
    # exact reduce-scatter flavor (EFB is a documented psum downgrade)
    params = {"objective": obj, "num_leaves": 15, "min_data_in_leaf": 5,
              "verbose": -1, "fused_block_size": 1,
              "enable_bundle": False, **extra}
    if obj == "multiclass":
        params["num_class"] = 4
    bst = lgb.train(params, lgb.Dataset(X, label=y),
                    num_boost_round=rounds)
    return bst


# ---------------------------------------------------------------------------
# the decision itself: crossbar.resolve_learner, asked by every run

_TPU4 = dict(platform="tpu", num_devices=4)
_CPU8 = dict(platform="cpu", num_devices=8)

# (tree_learner, what the choice depends on) -> (device, mode, hist_agg,
# warns of the throughput cliff). One case per row of the decision; the
# expectations are what each run got BEFORE the gate moved here, read
# off GBDT's attributes on the parent commit.
_DECISIONS = {
    "cpu_platform": ("serial", dict(platform="cpu"),
                     ("scatter", "serial", "psum", False)),
    "use_pallas_false": ("serial", dict(platform="tpu", use_pallas=False),
                         ("scatter", "serial", "psum", False)),
    "serial_mxu": ("serial", dict(platform="tpu"),
                   ("mxu", "serial", "psum", False)),
    "excluded_max_bin": (
        "serial", dict(platform="tpu", mxu_exclusions=["max_bin > 256"]),
        ("pallas", "serial", "psum", True)),
    "excluded_monotone_method": (
        "serial", dict(platform="tpu", mono_rescan=True,
                       mxu_exclusions=["monotone_constraints_method"]),
        ("pallas", "serial", "psum", True)),
    "excluded_cegb_lazy": (
        "serial", dict(platform="tpu",
                       mxu_exclusions=["cegb_penalty_feature_lazy"]),
        ("pallas", "serial", "psum", True)),
    "efb_without_efb_use_mxu": (
        "serial", dict(platform="tpu", has_efb=True,
                       mxu_exclusions=["efb config"]),
        ("scatter", "serial", "psum", False)),
    "efb_with_efb_use_mxu": ("serial", dict(platform="tpu", has_efb=True),
                             ("mxu", "serial", "psum", False)),
    "data_mxu": ("data", _TPU4, ("mxu", "data", "psum", False)),
    "data_excluded": (
        "data", dict(_TPU4, mxu_exclusions=["max_bin > 256"]),
        ("scatter", "data", "reduce_scatter", True)),
    "data_portable": ("data", _CPU8,
                      ("scatter", "data", "reduce_scatter", False)),
    "data_portable_psum_asked": ("data", dict(_CPU8, hist_agg="psum"),
                                 ("scatter", "data", "psum", False)),
    "data_portable_efb": ("data", dict(_CPU8, has_efb=True),
                          ("scatter", "data", "psum", False)),
    "data_multihost": ("data", dict(_CPU8, nproc=2),
                       ("scatter", "data", "psum", False)),
    "feature": ("feature", _TPU4, ("scatter", "feature", "psum", False)),
    "voting": ("voting", dict(_TPU4, num_features=12, top_k=20),
               ("scatter", "voting", "reduce_scatter", False)),
    "voting_votes_miss_columns": (
        "voting", dict(_CPU8, num_features=12, top_k=2),
        ("scatter", "voting", "psum", False)),
    "one_device_data": ("data", dict(platform="tpu", num_devices=1),
                        ("mxu", "serial", "psum", False)),
}


@pytest.mark.parametrize("case", sorted(_DECISIONS))
def test_resolve_learner_decision_table(case, monkeypatch):
    from lightgbm_tpu.distributed import crossbar
    warned = []
    monkeypatch.setattr(crossbar.Log, "warning",
                        lambda msg, *a: warned.append(msg % a))
    tree_learner, depends_on, expected = _DECISIONS[case]
    spec = crossbar.resolve_learner(tree_learner, **depends_on)
    cliff = [w for w in warned if "lower throughput" in w]
    assert (spec.device, spec.mode, spec.hist_agg, bool(cliff)) == expected
    assert len(cliff) <= 1
    assert spec == dataclasses.replace(
        crossbar.CROSSBAR[spec.device, spec.mode], hist_agg=spec.hist_agg)
    if case == "one_device_data":
        assert any("falling back to serial" in w for w in warned)


@pytest.mark.parametrize("mode,device,serial_mxu", [
    ("serial", "mxu", True), ("data", "mxu", False),
    ("serial", "pallas", False), ("serial", "scatter", False)])
def test_serial_mxu_is_the_unsharded_mxu_grower_only(mode, device,
                                                     serial_mxu):
    # what the packed 4-bit bins and the one-hot score update ask: the
    # sharded MXU learner keeps unpacked bins and the row gather
    from lightgbm_tpu.distributed.crossbar import CROSSBAR
    assert CROSSBAR[device, mode].serial_mxu is serial_mxu


def test_the_booster_keeps_one_value():
    # GBDT holds the resolved cell; _hist_impl is its serial kernel
    # path's NAME, read from and written through to that one value
    X, y, obj = _make("regression")
    g = lgb.Booster(params={"objective": obj, "verbose": -1},
                    train_set=lgb.Dataset(X, label=y)).gbdt
    assert (g._learner.mode, g._learner.device) == ("serial", "scatter")
    assert g._hist_impl == "scatter" and not g._learner.serial_mxu
    g._hist_impl = "mxu"
    assert g._learner.serial_mxu and g._hist_impl == "mxu"
    assert "_hist_impl" not in vars(g)
    sharded = lgb.Booster(
        params={"objective": obj, "verbose": -1, "tree_learner": "data"},
        train_set=lgb.Dataset(X, label=y)).gbdt
    assert sharded._learner.is_parallel and sharded._grower is not None
    assert sharded._hist_impl == "scatter"
    assert not sharded._learner.serial_mxu


# ---------------------------------------------------------------------------
# byte-parity oracles: serial vs the crossbar learners

def test_data_reduce_scatter_parity_regression():
    serial = _train("regression", {"tree_learner": "serial"})
    data = _train("regression", {"tree_learner": "data"})
    assert _trees(serial) == _trees(data)


@pytest.mark.slow
@pytest.mark.parametrize("task", ["binary", "multiclass"])
def test_data_reduce_scatter_parity_tasks(task):
    serial = _train(task, {"tree_learner": "serial"})
    data = _train(task, {"tree_learner": "data"})
    assert _trees(serial) == _trees(data)


def test_data_parity_one_device_mesh():
    # a 1-device mesh falls back to the serial learner (crossbar
    # downgrade): the model must be byte-identical, trivially
    serial = _train("regression", {"tree_learner": "serial"})
    data = _train("regression", {"tree_learner": "data",
                                 "num_devices": 1})
    assert _trees(serial) == _trees(data)


@pytest.mark.slow
def test_feature_parallel_parity():
    # each device scans its own feature partition with the serial
    # histogram order; the global argmax merge preserves byte parity
    # at this scale
    serial = _train("regression", {"tree_learner": "serial"})
    feat = _train("regression", {"tree_learner": "feature"})
    assert _trees(serial) == _trees(feat)


@pytest.mark.slow
def test_voting_parallel_full_cover_parity():
    # 2 * top_k >= F: every feature is vote-selected on every device,
    # so PV-Tree degrades to exact data-parallel aggregation
    serial = _train("regression", {"tree_learner": "serial"})
    vote = _train("regression", {"tree_learner": "voting", "top_k": 20})
    assert _trees(serial) == _trees(vote)


@pytest.mark.slow
def test_psum_flavor_is_numerically_close():
    # the psum fallback sums blocked partials: numerically (not
    # bitwise) equal to serial — predictions agree to float tolerance
    X, _, _ = _make("regression")
    serial = _train("regression", {"tree_learner": "serial"})
    psum = _train("regression", {"tree_learner": "data",
                                 "distributed_hist_agg": "psum"})
    np.testing.assert_allclose(serial.predict(X), psum.predict(X),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_fused_sharded_path_engages_and_is_deterministic():
    """The default engine posture (fused_block_size=10, pipeline=True)
    must dispatch through the sharded fused builder, and the result
    must not depend on block size or pipelining — the same-path
    determinism chaos resume replays."""
    from lightgbm_tpu.boosting import gbdt as G
    calls = {"n": 0}
    orig = G.GBDT._build_sharded_fused

    def spy(self):
        calls["n"] += 1
        return orig(self)

    G.GBDT._build_sharded_fused = spy
    try:
        m10 = _train("regression", {"tree_learner": "data",
                                    "fused_block_size": 10}, rounds=12)
        assert calls["n"] > 0, "sharded fused builder never engaged"
        m4 = _train("regression", {"tree_learner": "data",
                                   "fused_block_size": 4,
                                   "pipeline": False}, rounds=12)
        m10b = _train("regression", {"tree_learner": "data",
                                     "fused_block_size": 10}, rounds=12)
    finally:
        G.GBDT._build_sharded_fused = orig
    assert _trees(m10) == _trees(m10b)
    assert _trees(m10) == _trees(m4)


# ---------------------------------------------------------------------------
# unit tests: hist_agg + binning entry points, by name

def test_build_feature_shards_transposes_all_rows():
    import jax
    from lightgbm_tpu.distributed.hist_agg import (build_feature_shards,
                                                   feature_shard_width)
    from lightgbm_tpu.parallel import CommSpec, make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh(8)
    comm = CommSpec(axis="data", mode="data", num_devices=8,
                    hist_agg="reduce_scatter")
    rng = np.random.RandomState(3)
    bins = rng.randint(0, 17, size=(64, 10)).astype(np.int8)
    sharded = jax.device_put(bins, NamedSharding(mesh, P("data")))
    with mesh:
        bins_ft = build_feature_shards(mesh, comm, sharded)
    fp = feature_shard_width(10, 8)
    assert bins_ft.shape == (64, fp * 8)
    # device d's block holds ALL rows of features [d*fp, (d+1)*fp)
    got = np.concatenate(
        [np.asarray(s.data) for s in
         sorted(bins_ft.addressable_shards,
                key=lambda s: s.index[1].start or 0)],
        axis=1)
    want = np.pad(bins, ((0, 0), (0, fp * 8 - 10)))
    np.testing.assert_array_equal(got, want)


def test_reduce_scatter_hist_owns_summed_block():
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.distributed.hist_agg import reduce_scatter_hist
    from lightgbm_tpu.parallel import make_mesh
    from lightgbm_tpu.parallel.learner import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh(8)
    rng = np.random.RandomState(5)
    # per-device partial histograms [S=2, Fpad=16, B=4, 3]
    parts = rng.rand(8, 2, 16, 4, 3).astype(np.float32)

    import functools

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"),),
                       out_specs=P("data"), check_vma=False)
    def run(p):
        return reduce_scatter_hist(p[0], "data")[None]

    out = np.asarray(jax.jit(run)(jnp.asarray(
        parts.reshape(8, 2, 16, 4, 3))))
    total = parts.sum(0)        # the global histogram
    for d in range(8):
        np.testing.assert_allclose(out[d], total[:, 2 * d:2 * (d + 1)],
                                   rtol=1e-6)


def test_merge_streaming_sketch_single_process_is_none():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.distributed.binning import (distributed_mapper_sync,
                                                  merge_streaming_sketch)
    assert merge_streaming_sketch is not None  # exported entry point
    cfg = Config({"verbose": -1})
    # single-process: the loader bins locally; distribution is over
    # devices only (rows shard after binning)
    assert distributed_mapper_sync(cfg, cat=None) is None


def test_distributed_sketch_telemetry():
    from lightgbm_tpu.observability import registry
    registry.enable()
    try:
        from lightgbm_tpu.distributed.binning import _record_sketch
        before = registry.distributed_snapshot()
        _record_sketch(123)
        snap = registry.distributed_snapshot()
        assert snap["sketch_rows"] == before["sketch_rows"] + 123
        assert snap["sketch_merges"] == before["sketch_merges"] + 1
    finally:
        registry.disable()


# ---------------------------------------------------------------------------
# fault site: distributed_hist_agg

def test_distributed_hist_agg_fault_site_fires():
    X, y, _ = _make("regression")
    faults.schedule("distributed_hist_agg", fail=1)
    try:
        with pytest.raises(InjectedFault, match="distributed_hist_agg"):
            lgb.train({"objective": "regression", "num_leaves": 7,
                       "verbose": -1, "tree_learner": "data",
                       "enable_bundle": False,
                       "distributed_hist_agg": "reduce_scatter"},
                      lgb.Dataset(X, label=y), num_boost_round=1)
    finally:
        faults.clear()


# ---------------------------------------------------------------------------
# provision_virtual_devices: one-shot latch ordering hazard

def test_provision_after_backend_touch_raises_clearly():
    """A harness that touches the backend before provisioning latches
    the device count; the provision call must fail loudly with the
    ordering diagnosis, not hand back a 1-device 'mesh'."""
    code = (
        "import jax\n"
        "jax.devices()          # latch a 1-device CPU backend\n"
        "from lightgbm_tpu.parallel.mesh import provision_virtual_devices\n"
        "try:\n"
        "    provision_virtual_devices(8)\n"
        "except RuntimeError as e:\n"
        "    assert 'before any other JAX use' in str(e) or \\\n"
        "        'provision_virtual_devices' in str(e), e\n"
        "    print('LATCH_ERROR_OK')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env.pop("XLA_FLAGS", None)   # no pre-provisioned virtual devices
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr
    assert "LATCH_ERROR_OK" in out.stdout, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# chaos: rank death at the 8-device (2 ranks x 4 devices) geometry

@pytest.mark.slow
@pytest.mark.chaos
def test_rank_death_at_8_devices_resumes_byte_identical(tmp_path):
    """The distributed acceptance scenario: kill a rank mid-iteration
    out of the 8-device global mesh; the survivor aborts promptly and
    a coordinated-checkpoint resume finishes byte-identical to an
    unkilled reference run."""
    from lightgbm_tpu.reliability.faults import RANK_DEATH_EXIT_CODE
    from lightgbm_tpu.testing.chaos import (run_chaos_training,
                                            strip_rank_local_params)

    def model(workdir, rank):
        with open(os.path.join(workdir, f"model_{rank}.txt")) as f:
            return strip_rank_local_params(f.read())

    ref_dir = str(tmp_path / "ref")
    ref = run_chaos_training(
        ref_dir, rounds=8, ckpt_period=2,
        ckpt_dir=os.path.join(ref_dir, "ckpts"), timeout_s=30.0,
        devices_per_rank=4)
    for r in ref:
        assert r.returncode == 0, r.tail()
        assert "CHAOS_WORKER_DEVICES 8" in r.output, r.tail()
    ref_model = model(ref_dir, 0)

    chaos_dir = str(tmp_path / "chaos")
    chaos_ckpts = os.path.join(chaos_dir, "ckpts")
    res = {r.rank: r for r in run_chaos_training(
        chaos_dir, rounds=8, ckpt_period=2, ckpt_dir=chaos_ckpts,
        timeout_s=30.0, death_rank=1, death_iter=5,
        devices_per_rank=4)}
    assert res[1].returncode == RANK_DEATH_EXIT_CODE, res[1].tail()
    assert res[0].returncode not in (0, RANK_DEATH_EXIT_CODE), \
        res[0].tail()

    resume_dir = str(tmp_path / "resume")
    resumed = run_chaos_training(
        resume_dir, rounds=8, ckpt_period=2, ckpt_dir=chaos_ckpts,
        timeout_s=30.0, resume=True, devices_per_rank=4)
    for r in resumed:
        assert r.returncode == 0, r.tail()
    assert model(resume_dir, 0) == ref_model
    assert model(resume_dir, 1) == ref_model
