"""GBDT training driver: the TrainOneIter loop, bagging, scores, eval.

Redesign of the reference boosting layer (src/boosting/gbdt.cpp:266-572,
gbdt.h:35): objective gradients, bagging/GOSS, per-class tree training,
shrinkage, learner-side score updates and metric evaluation. TPU-shape
differences:

- gradients/hessians/scores are device-resident; the objective runs in JAX
  so there is no H2D gradient copy per iteration (contrast
  cuda_single_gpu_tree_learner.cpp:79-80).
- bagging is a mask, not an index subset (gbdt.cpp:183-264 copies subsets;
  masks keep shapes static and HBM traffic sequential). The `cnt_weight`
  channel of the histogram makes min_data_in_leaf count in-bag rows only.
- trees accumulate on device as stacked arrays for fast forest prediction;
  host copies materialize lazily for serialization.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data import BinnedDataset
from ..learner.grower import TreeArrays, grow_tree
from ..learner.predict import predict_binned_tree
from ..learner.renew import renew_tree_output
from ..learner.split import SplitHyperParams
from ..metrics import Metric
from ..objectives import ObjectiveFunction
from ..observability import registry as _obs
from ..observability import span
from ..observability.profile import profiler as _profiler
from ..reliability import (InjectedFault, counters, faults, guards,
                           retry_call)
from ..utils.log import Log, LightGBMError
from ..utils.file_io import open_file

__all__ = ["GBDT", "create_boosting"]

_FAULT_ENV = "LGBM_TPU_INJECT_FUSED_FAULT"


def _maybe_inject_fused_fault():
    """Fail upcoming fused dispatches on request, so the fallback ladder
    can be exercised without a real device outage. Env format: "N"
    (fail the next N dispatches) or "S:N" (let S dispatches through,
    then fail N).

    Shim over the unified fault registry (reliability/faults.py): the
    env var is only an initial-schedule *source* — the countdown lives
    in the in-process registry and the environment is never mutated
    (the old counter-in-env leaked state across tests and raced under
    threads)."""
    faults.schedule_from_env("fused_dispatch", _FAULT_ENV)
    faults.inject("fused_dispatch")


class GBDT:
    """Gradient Boosted Decision Trees driver (reference gbdt.h:35)."""

    def __init__(self, config: Config, train_set: Optional[BinnedDataset],
                 objective: Optional[ObjectiveFunction],
                 train_metrics: Optional[List[Metric]] = None):
        self.config = config
        self.objective = objective
        self.train_set = train_set
        self.train_metrics = train_metrics or []
        self.shrinkage_rate = float(config.learning_rate)
        self.num_class = max(int(config.num_class), 1)
        self.num_tree_per_iteration = (
            objective.num_model_per_iteration if objective else self.num_class)
        self.iter_ = 0
        self.trees: List[TreeArrays] = []       # flat: iter*K + class
        self.tree_class: List[int] = []
        self.linear_models: List = []           # LinearLeaves or None, per tree
        self._pending_nleaves = None            # device scalar, lagged poll
        self._earlier_nleaves = None            # the count before it
        self._exact_stop_poll = False
        self._stop_poll_every = 8               # host-sync amortization
        self.models_meta: List[dict] = []       # host-side per-tree info
        self.valid_sets: List[BinnedDataset] = []
        self.valid_names: List[str] = []
        self.valid_metrics: List[List[Metric]] = []
        self.best_iter = -1
        self._rng_key = jax.random.PRNGKey(int(config.seed))
        # checkpoint resume: iter_ stays ABSOLUTE over the merged model
        # (RNG fold-ins and bagging cadence key off it) while the trees
        # list only holds this instance's trees; the offset reconciles
        # the two for current_iteration()/rollback accounting
        self._iter_offset = 0

        if train_set is not None:
            self._setup_train(train_set)

    # ------------------------------------------------------------------
    def _setup_train(self, ds: BinnedDataset) -> None:
        cfg = self.config
        self.num_data = ds.num_data
        self.num_bins_d = jnp.asarray(ds.num_bins)
        self.missing_is_nan_d = jnp.asarray(ds.missing_types == 2)
        self.is_cat_d = jnp.asarray(ds.is_categorical)
        self.bmax = int(ds.num_bins.max()) if ds.num_features else 2
        # EFB (reference feature_group.h:25; efb.py): bundle mutually-
        # exclusive sparse features so histogram work scales with the
        # bundle count, not the raw feature count. Only the device bin
        # matrix changes shape; growers translate through static tables.
        self._efb = None
        try:
            nproc_now = jax.process_count()
        except RuntimeError:
            nproc_now = 1
        if cfg.enable_bundle and not cfg.linear_tree and ds.num_features:
            from ..efb import build_plan, bundle_matrix, make_device_tables
            plan_bins = np.asarray(ds.bins)
            if nproc_now > 1:
                # the greedy plan must be IDENTICAL on every rank or the
                # SPMD programs diverge. Same recipe as distributed bin-
                # mapper construction (dataset_loader.cpp:722-807):
                # deterministic fixed-size local row sample -> allgather
                # -> every rank plans over the identical pooled sample.
                from ..parallel.comm import guarded_allgather
                k_samp = max(1, 20000 // nproc_now)
                rs = np.random.RandomState(13)
                n_loc = plan_bins.shape[0]
                idx = rs.choice(n_loc, k_samp, replace=n_loc < k_samp)
                pooled = guarded_allgather(plan_bins[np.sort(idx)],
                                           label="efb_plan_sample")
                plan_bins = pooled.reshape(-1, plan_bins.shape[1])
            plan = build_plan(plan_bins, ds.num_bins,
                              ds.default_bins,
                              np.asarray(ds.is_categorical),
                              max_bundle_bins=256)
            if plan is not None and plan.effective:
                # feature metadata attaches the segmented-scan tables
                # (split_bundled.py); without them the MXU path falls
                # back to per-pass expansion
                seg = cfg.efb_segmented_scan
                self._efb = make_device_tables(
                    plan, ds.default_bins,
                    num_bins=ds.num_bins if seg else None,
                    missing_is_nan=(ds.missing_types == 2) if seg
                    else None,
                    is_cat=np.asarray(ds.is_categorical) if seg else None)
                self.bins = jnp.asarray(bundle_matrix(
                    np.asarray(ds.bins), plan))
        if self._efb is None:
            self.bins = jnp.asarray(ds.bins)
        k = self.num_tree_per_iteration
        shape = (self.num_data,) if k == 1 else (self.num_data, k)
        self.train_score = jnp.zeros(shape, jnp.float32)
        if ds.metadata.init_score is not None:
            init = np.asarray(ds.metadata.init_score, np.float32)
            self.train_score = jnp.asarray(init.reshape(shape))
            self._has_init_score = True
        else:
            self._has_init_score = False
        # monotone constraints (original-feature order -> used-feature order)
        self._monotone = None
        has_monotone = False
        if cfg.monotone_constraints:
            mc = np.zeros(ds.num_total_features, np.int32)
            arr = np.asarray(cfg.monotone_constraints, np.int32)
            mc[:len(arr)] = arr
            used = np.asarray(ds.used_features, np.int64)
            if np.any(mc[used] != 0):
                self._monotone = jnp.asarray(mc[used])
                has_monotone = True
            if cfg.monotone_constraints_method != "basic":
                Log.warning("monotone_constraints_method=%s approximated by "
                            "'basic' on TPU",
                            cfg.monotone_constraints_method)
        # interaction constraints (groups of original feature indices)
        self._interaction_groups = None
        if cfg.interaction_constraints:
            orig2used = {int(o): j
                         for j, o in enumerate(ds.used_features)}
            groups = []
            for grp in cfg.interaction_constraints:
                if not isinstance(grp, (list, tuple)):
                    grp = [grp]
                groups.append(tuple(sorted(
                    orig2used[int(fi)] for fi in grp
                    if int(fi) in orig2used)))
            self._interaction_groups = tuple(g for g in groups if g)
        self._forced = self._load_forced_splits(cfg, ds)
        self._setup_cegb(cfg, ds)
        self.hp = SplitHyperParams(
            lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
            min_gain_to_split=cfg.min_gain_to_split,
            min_data_in_leaf=cfg.min_data_in_leaf,
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
            max_delta_step=cfg.max_delta_step,
            path_smooth=cfg.path_smooth, cat_l2=cfg.cat_l2,
            cat_smooth=cfg.cat_smooth,
            max_cat_threshold=cfg.max_cat_threshold,
            max_cat_to_onehot=cfg.max_cat_to_onehot,
            min_data_per_group=cfg.min_data_per_group,
            has_monotone=has_monotone,
            monotone_penalty=cfg.monotone_penalty,
            extra_trees=cfg.extra_trees,
            has_categorical=bool(np.any(ds.is_categorical)),
            has_missing=bool(np.any(ds.missing_types == 2)),
            cat_columns=tuple(
                int(j) for j in np.flatnonzero(ds.is_categorical)))
        self._cat_bins = int(ds.num_bins[ds.is_categorical].sum())
        # intermediate/advanced monotone methods need leaf-wise growth
        # with per-pass bound recomputation — portable grower only
        self._mono_nonbasic = (
            cfg.monotone_constraints is not None and
            cfg.monotone_constraints_method != "basic")
        self._mono_method = (cfg.monotone_constraints_method
                             if self._mono_nonbasic else "basic")
        # histogram backend of the MXU growth path (config.hist_backend):
        # pinned by _resolved_hist_backend() at first use, which for the
        # serial learner is after objective binding (the const-hessian
        # gate decides the channel count the per-pass plan is made of)
        self._hist_backend = None
        self._setup_parallel(cfg)   # resolves self._learner
        if cfg.use_quantized_grad and self._learner.device != "mxu":
            Log.warning("use_quantized_grad only accelerates the MXU "
                        "growth path (active: %s); training runs "
                        "full-precision", self._learner.device)
        # 4-bit packed bin storage (reference dense_bin.hpp:42): when
        # every feature fits a nibble, re-upload the bin matrix packed
        # two-features-per-byte; the MXU kernels unpack in VMEM. Exact.
        self._packed4 = False
        if (self._learner.serial_mxu and cfg.bin_pack_4bit and
                self.bmax <= 16 and not cfg.linear_tree and
                self._efb is None):
            from ..learner.histogram_mxu import (fits_v2, pack_bins_4bit)
            # packing only pays when every growth pass stays on the
            # fused/v2 kernels (VMEM-resident histograms); the v1
            # wide-feature fallback would unpack the whole matrix per
            # call — worse than unpacked storage
            L_g = int(np.ceil(cfg.num_leaves * cfg.growth_overshoot)) \
                if cfg.growth_overshoot >= 1.0 else cfg.num_leaves
            if fits_v2(L_g + 1, ds.num_features, self.bmax,
                       cfg.gpu_use_dp, cfg.use_quantized_grad):
                # pack_bins_4bit refuses (None + warning) if any bin id
                # exceeds 15 — keep uint8 storage rather than truncate
                packed = pack_bins_4bit(ds.bins)
                if packed is not None:
                    self.bins = None  # free the unpacked copy first
                    self.bins = jnp.asarray(packed)
                    self._packed4 = True
                    Log.debug("bin matrix packed 4-bit: [%d, %d] bytes",
                              ds.num_data, self.bins.shape[1])
        # linear trees (reference LinearTreeLearner; raw values required,
        # dataset.cpp:418-420)
        self._linear = bool(cfg.linear_tree)
        self.raw = None
        self.valid_raws: List = []
        if self._linear:
            # config validation already forces tree_learner=serial for
            # linear trees, so self._grower is always None here
            if ds.raw is None:
                raise ValueError(
                    "linear_tree=true requires raw feature values; "
                    "reconstruct the dataset with linear_tree in params")
            else:
                self.raw = jnp.asarray(ds.raw)
                depth_cap = cfg.max_depth if cfg.max_depth > 0 else 31
                self._lin_dmax = max(1, min(ds.num_features, depth_cap, 31))
        self._bag_mask = jnp.ones(self.num_data, jnp.float32)
        self._boosted_from_average = [False] * k
        if self.objective is not None:
            self.objective.init(ds.metadata, ds.num_data)

    def _setup_cegb(self, cfg, ds) -> None:
        """Cost-effective gradient boosting penalties (reference
        cost_effective_gradient_boosting.hpp:23)."""
        self._cegb_cfg = None
        self._cegb_state = None
        lazy = cfg.cegb_penalty_feature_lazy
        coupled = cfg.cegb_penalty_feature_coupled
        has_lazy = bool(lazy)
        has_coupled = bool(coupled)
        if cfg.cegb_penalty_split <= 0 and not has_lazy and not has_coupled:
            return
        from ..learner.grower import CegbParams
        f = ds.num_features
        used = np.asarray(ds.used_features, np.int64)

        def _per_used(pen):
            pen = np.asarray(pen, np.float32)
            if len(pen) != ds.num_total_features:
                # the reference requires one penalty per feature
                # (config check on cegb_penalty_feature_* size)
                raise ValueError(
                    f"cegb per-feature penalty has {len(pen)} entries but "
                    f"the dataset has {ds.num_total_features} features")
            return jnp.asarray(pen[used])

        self._cegb_cfg = CegbParams(
            tradeoff=float(cfg.cegb_tradeoff),
            penalty_split=float(cfg.cegb_penalty_split),
            has_coupled=has_coupled, has_lazy=has_lazy)
        self._cegb_state = (
            _per_used(coupled) if has_coupled else jnp.zeros(f, jnp.float32),
            _per_used(lazy) if has_lazy else jnp.zeros(f, jnp.float32),
            jnp.zeros(f, bool),
            jnp.zeros((ds.num_data, f) if has_lazy else (1, 1), bool))

    @staticmethod
    def _load_forced_splits(cfg, ds):
        """Flatten the forced-splits JSON tree (reference ForceSplits,
        serial_tree_learner.cpp:459; JSON read at serial_tree_learner.cpp:53)
        into spec arrays (feature, threshold bin, left/right spec idx)."""
        fname = getattr(cfg, "forcedsplits_filename", "")
        if not fname:
            return None
        import json
        with open_file(fname) as fh:
            root = json.load(fh)
        if not root:
            return None
        orig2used = {int(o): j for j, o in enumerate(ds.used_features)}
        feat, tbin, left, right = [], [], [], []
        nodes = [root]          # BFS; spec idx = position in this list
        i = 0
        while i < len(nodes):
            nd = nodes[i]
            fo = int(nd["feature"])
            if fo not in orig2used:
                Log.warning("forced split on unused feature %d ignored", fo)
                feat.append(-1)
                tbin.append(0)
                left.append(-1)
                right.append(-1)
                i += 1
                continue
            fu = orig2used[fo]
            mapper = ds.mappers[fu]
            if mapper.is_categorical:
                Log.warning("forced split on categorical feature %d ignored "
                            "(numerical thresholds only)", fo)
                feat.append(-1)
                tbin.append(0)
                left.append(-1)
                right.append(-1)
                i += 1
                continue
            feat.append(fu)
            tbin.append(int(mapper._value_to_bin_scalar(
                float(nd["threshold"]))))
            for key, out in (("left", left), ("right", right)):
                child = nd.get(key)
                if child:
                    nodes.append(child)
                    out.append(len(nodes) - 1)
                else:
                    out.append(-1)
            i += 1
        if not feat or all(f < 0 for f in feat):
            return None
        return (jnp.asarray(feat, jnp.int32), jnp.asarray(tbin, jnp.int32),
                jnp.asarray(left, jnp.int32), jnp.asarray(right, jnp.int32))

    @property
    def _hist_impl(self) -> str:
        """NAME of the serial learner's kernel path, read from
        self._learner: "mxu" (grower_mxu.py, sort/gather-free
        one-hot-matmul growth) or the portable grower's "pallas"
        (grouped-rows histogram kernel) / "scatter" (pure-XLA segment
        adds), which is also what it reads under a sharded learner.
        Tests and tpu_aot SET it to force a path this host would not
        pick."""
        spec = self._learner
        return "scatter" if spec.is_parallel else spec.device

    @_hist_impl.setter
    def _hist_impl(self, device: str) -> None:
        self._learner = dataclasses.replace(self._learner, device=device)

    def _setup_parallel(self, cfg) -> None:
        """Learner resolution and, for a parallel one, its setup
        (reference CreateTreeLearner crossbar, tree_learner.cpp:16-64,
        + Network::Init)."""
        self.comm = None
        self.mesh = None
        self._grower = None
        self._row_pad = 0
        self._bins_ft = None
        ndev = 1
        if cfg.tree_learner != "serial":
            if cfg.num_machines > 1:
                # reference Network::Init from the machine list
                # (application.cpp:165); here a jax.distributed
                # rendezvous — afterwards jax.devices() spans all hosts
                # and the mesh collectives ride DCN between them
                from ..parallel.mesh import setup_multihost
                setup_multihost(cfg.num_machines, cfg.machines,
                                cfg.machine_list_filename,
                                cfg.local_listen_port)
            visible = len(jax.devices())
            if cfg.num_devices > visible:
                # a chip that did not come up must not turn into a
                # smaller (or serial) run with a warning nobody reads
                raise LightGBMError(
                    "num_devices=%d requested but JAX sees %d device(s) "
                    "(%s)" % (cfg.num_devices, visible,
                              jax.devices()[0].platform))
            ndev = cfg.num_devices if cfg.num_devices > 0 else visible
        _setup_t0 = time.time()
        # crossbar resolution (distributed/crossbar.py, the reference
        # CreateTreeLearner factory), asked by every run: the MXU gate
        # picks the device row, cfg.distributed_hist_agg the
        # histogram-merge column, the downgrades applied in ONE place
        from ..distributed.crossbar import resolve_learner
        platform = jax.default_backend()
        spec = self._learner = resolve_learner(
            cfg.tree_learner, platform=platform,
            use_pallas=cfg.use_pallas,
            mxu_exclusions=self._mxu_exclusions(cfg), num_devices=ndev,
            hist_agg=cfg.distributed_hist_agg,
            num_features=int(self.bins.shape[1]), top_k=cfg.top_k,
            nproc=jax.process_count(), has_efb=self._efb is not None,
            mono_rescan=self._mono_nonbasic)
        Log.debug("Tree learner: %s, kernel path %s (backend=%s)",
                  spec.mode, spec.device, platform)
        if not spec.is_parallel:
            return
        from ..parallel import CommSpec, make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        use_mxu = spec.device == "mxu"
        self._nproc = jax.process_count()
        if self._nproc > 1:
            from ..reliability.watchdog import maybe_start_watchdog
            maybe_start_watchdog(cfg)
        if self._nproc > 1 and cfg.tree_learner != "data":
            raise ValueError(
                "multi-machine training supports tree_learner=data "
                "(rows pre-partitioned per machine, reference "
                "dataset_loader.cpp:560-592); got %r" % cfg.tree_learner)
        self.mesh = make_mesh(ndev)
        self.comm = CommSpec(axis="data", mode=spec.mode,
                             num_devices=ndev, top_k=cfg.top_k,
                             hist_agg=spec.hist_agg)
        if self.comm.mode in ("data", "voting"):
            ndev_local = max(1, ndev // self._nproc)
            if self._nproc > 1:
                # global shape is inferred from the local shard, so all
                # machines pad to the LARGEST partition (padded rows
                # carry zero grad/hess/count — they contribute nothing)
                from ..parallel.comm import guarded_allgather
                sizes = guarded_allgather(
                    np.asarray(self.num_data, np.int64),
                    label="row_pad_sizes")
                target = int(-(-int(sizes.max()) // ndev_local)
                             * ndev_local)
                self._row_pad = target - self.num_data
            else:
                self._row_pad = (-self.num_data) % ndev_local
            if self._row_pad:
                self.bins = jnp.pad(self.bins,
                                    ((0, self._row_pad), (0, 0)))
            if self._nproc > 1:
                # keep this machine's rows for local score updates /
                # metrics (reference ranks evaluate on their partition)
                self._local_bins = self.bins
            self.bins = self._shard_rows(self.bins)
            if self.comm.hist_agg == "reduce_scatter":
                # one-time all_to_all feature-shard transpose: enables
                # the exact reduce-scatter histogram flavor in grow_tree
                from ..distributed.hist_agg import build_feature_shards
                self._bins_ft = build_feature_shards(
                    self.mesh, self.comm, self.bins)
        else:  # feature-parallel replicates rows (docs/Features.rst:109)
            self.bins = jax.device_put(
                self.bins, NamedSharding(self.mesh, P()))
        # per-node sampling / extra_trees / quantized rounding need a
        # per-iteration key; it rides into shard_map replicated so every
        # shard samples identically (the reference's cross-machine seed
        # sync, application.cpp:170-175)
        self._sharded_rng = (cfg.feature_fraction_bynode < 1.0 or
                             cfg.extra_trees or cfg.use_quantized_grad)
        if self._cegb_state is not None and \
                self.comm.mode in ("data", "voting"):
            # per-row lazy-charge flags shard with the rows; pad to the
            # sharded row count like bins (padded rows never charge)
            c, l, fu, rfu = self._cegb_state
            if self._row_pad and rfu.shape[0] > 1:
                rfu = jnp.pad(rfu, ((0, self._row_pad), (0, 0)))
            if rfu.shape[0] > 1:
                rfu = self._shard_rows(rfu)
            self._cegb_state = (c, l, fu, rfu)
        self._grower = self._create_grower()
        Log.info("Distributed learner: %s-parallel over %d devices%s "
                 "(hist_agg=%s)", self.comm.mode, ndev,
                 " (mxu)" if use_mxu else "", self.comm.hist_agg)
        _obs.record_distributed_setup(
            world=ndev * max(1, self._nproc),
            feature_shard_width=(int(self._bins_ft.shape[1]) // ndev
                                 if self._bins_ft is not None else 0),
            wall_seconds=time.time() - _setup_t0)

    def _create_grower(self):
        """The per-tree grower of the parallel learner self._learner
        over self.mesh: what train_one_iter dispatches (jitted, so
        compiled only by a run that leaves the fused block)."""
        from ..distributed.crossbar import create_tree_learner
        cfg = self.config
        use_mxu = self._learner.device == "mxu"
        return create_tree_learner(
            self._learner, self.mesh, self.comm,
            num_leaves=cfg.num_leaves, max_depth=cfg.max_depth,
            hp=self.hp, leafwise=self._mono_nonbasic,
            bmax=self.bmax, monotone=self._monotone,
            monotone_method=self._mono_method,
            interaction_groups=self._interaction_groups,
            feature_fraction_bynode=cfg.feature_fraction_bynode,
            with_rng=self._sharded_rng,
            forced=self._forced, cegb_cfg=self._cegb_cfg,
            with_cegb_state=self._cegb_cfg is not None,
            efb=self._efb, with_bins_ft=self._bins_ft is not None,
            interpret=getattr(self, "_mxu_interpret", False),
            mxu_kwargs=dict(
                hist_double_prec=cfg.gpu_use_dp,
                tail_split_cap=cfg.tail_split_cap,
                hist_subtraction=cfg.hist_subtraction,
                overshoot=cfg.growth_overshoot,
                bridge_gate=cfg.growth_bridge_gate,
                quantized_grad=cfg.use_quantized_grad,
                # const-hessian stays OFF for the sharded learner: its
                # kwargs are baked here, BEFORE objective.init() binds
                # sample weights, so the _const_hessian() gate cannot
                # be evaluated safely yet (a weighted dataset would get
                # the fast path wrongly enabled and train silently
                # wrong hessians)
                const_hessian=0.0,
                **({"hist_backend": self._resolved_hist_backend(),
                    "partition_impl": cfg.partition_impl}
                   if use_mxu else {})))

    def _shard_rows(self, arr):
        """Row-sharded global array over the mesh. Single-process: a
        device_put; multi-process: this process's rows become its shard
        of the global array (each machine holds its own partition, the
        reference's pre-partitioned load, dataset_loader.cpp:560-592)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(self.mesh, P("data"))
        if getattr(self, "_nproc", 1) > 1:
            return jax.make_array_from_process_local_data(
                sh, np.asarray(arr))
        return jax.device_put(arr, sh)

    def _local_rows(self, arr) -> jax.Array:
        """This process's rows of a row-sharded global array (index
        order), for the host-local score/metric bookkeeping."""
        if getattr(self, "_nproc", 1) <= 1:
            return arr
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        # shards live on different local devices; hop through host
        return jnp.asarray(np.concatenate(
            [np.asarray(s.data) for s in shards]))

    def _mxu_exclusions(self, cfg) -> List[str]:
        """Why the MXU growth path cannot be used (empty = usable): what
        crossbar.resolve_learner's gate reads. Forced
        splits and coupled/split CEGB ride the MXU path (round 4); only
        the lazy per-row CEGB penalty, non-basic monotone methods, wide
        bins, and unsuited EFB configs stay portable."""
        # the expanded-tensor budget only binds on the expansion
        # fallback; the segmented scan never materializes it
        efb_ok = self._efb is None or (
            cfg.efb_use_mxu and self._efb.bundle_bmax <= 256 and
            (self._efb.scan is not None or
             self._mxu_expand_bytes(cfg) <= 1 << 30))
        return [r for r, hit in [
            # the mxu kernels carry bin values through bf16 matmul
            # operands, exact only for max_bin <= 256
            ("max_bin > 256", self.bmax > 256),
            ("monotone_constraints_method", self._mono_nonbasic),
            ("cegb_penalty_feature_lazy",
             self._cegb_cfg is not None and self._cegb_cfg.has_lazy),
            ("efb config", not efb_ok)] if hit]

    def _mxu_expand_bytes(self, cfg) -> int:
        """Per-pass expanded scan tensor size under EFB on the MXU path
        ([s_max, F, bmax, 3] f32)."""
        import math as _math
        over = cfg.growth_overshoot if cfg.growth_overshoot >= 1.0 else 1.0
        s_max = int(_math.ceil(cfg.num_leaves * over)) + 1
        f = int(self.num_bins_d.shape[0])
        return s_max * f * self.bmax * 3 * 4

    def _const_hessian(self) -> float:
        """Constant-hessian fast-path gate (reference IsConstantHessian,
        objective_function.h:42): per-row hessians are exactly 1 x the
        count weight, so the kernels can drop the hessian channel and
        reconstruct it as the count — one fewer histogram dot channel
        and exact hessian sums. GOSS re-weights hessians independently
        of the count channel (amplified rows count 1), and user weights
        ride the hessian but not cnt_weight — both break the
        h == const x cnt identity, so they gate it off. Bagging keeps
        it (the mask scales hessian AND count identically). Must be
        evaluated AFTER objective.init() has bound weights.

        A custom objective (Booster.update(fobj=...)) supplies
        arbitrary per-row hessians, so the bound objective's
        is_constant_hessian promise no longer describes the gradients
        actually trained on — the reference neutralizes this by
        resetting objective to "none" in engine.train; the direct
        update(fobj) path flips `_custom_objective` instead (see
        set_custom_objective)."""
        if getattr(self, "_custom_objective", False):
            return 0.0
        if (self.objective is not None and
                getattr(self.objective, "is_constant_hessian", False) and
                getattr(self.objective, "weight", None) is None and
                self.config.boosting != "goss"):
            # the objective owns the actual constant (1.0 for the L1/L2
            # family, but e.g. a scaled-L2 objective declares its own) —
            # the kernels reconstruct hessian sums as const x count, so
            # a hardcoded 1.0 here would silently mis-train any
            # non-unit constant-hessian objective on the fast path
            return float(getattr(self.objective,
                                 "constant_hessian_value", 1.0))
        return 0.0

    def set_custom_objective(self) -> None:
        """Mark this booster as trained (at least once) on user-supplied
        gradients. Drops the constant-hessian fast path — the kernels
        would otherwise reconstruct hessian sums from row counts and
        silently mis-train on any fobj whose hessian isn't exactly the
        count weight — and invalidates caches that baked the old gate
        (the fused scan closure and the analytic MAC estimate)."""
        if not getattr(self, "_custom_objective", False):
            self._custom_objective = True
            self._fused_run = None
            self._obs_tree_macs = None
            self._hist_backend = None   # the plan counts channels

    def _resolved_hist_backend(self) -> str:
        """config.hist_backend as grow_tree_mxu takes it, pinned for the
        run. "auto" stays "auto": the grower then chooses a formulation
        per pass from static shapes (grower_mxu.pass_formulation), the
        same on every platform. EFB growth has bundle-space wiring in
        the one-hot sweep only, so there everything resolves to mxu.
        Records the run's per-pass plan (hist_pass_plan: static, no
        device sync) in the registry."""
        if self._hist_backend is not None:
            return self._hist_backend
        hb = self.config.hist_backend
        if self._efb is not None and hb != "mxu":
            if hb != "auto":
                Log.warning("hist_backend=%s has no EFB bundle-space "
                            "wiring; using mxu", hb)
            hb = "mxu"
        self._hist_backend = hb
        self._hist_plan = self._hist_pass_plan(hb)
        self._operand_builds = None     # read from the next trace
        _obs.record_hist_plan(hb, self._hist_plan, self._partition())
        return hb

    def _partition(self) -> str:
        """What config.partition_impl resolves to for this run's grouped
        passes (stream | rank | argsort): static."""
        from ..learner.histogram_pallas import resolve_partition
        return resolve_partition(self.config.partition_impl)

    def _hist_plan_attrs(self) -> dict:
        """The per-pass plan as attributes of a boosting.build_program
        span: which formulation each pass of the program being built
        uses, and which partition its grouped passes were built with.
        Empty off the MXU growth path."""
        if self._learner.device != "mxu":
            return {}
        self._resolved_hist_backend()
        return {"hist_plan": ",".join("%d:%s" % (sk, form)
                                      for _, sk, form in self._hist_plan),
                "partition": self._partition(),
                "grouped_passes_per_tree": sum(
                    form == "grouped" and stage != "fixup"
                    for stage, _, form in self._hist_plan)}

    def _cat_attrs(self) -> dict:
        """What of the dataset is categorical, as attributes of a
        boosting.build_program span: the program being built searches
        and routes by category sets (`has_cat`) over `cat_columns`
        columns of `cat_bins` bins together."""
        return {"has_cat": self.hp.has_categorical,
                "cat_columns": len(self.hp.cat_columns),
                "cat_bins": self._cat_bins}

    def _trace_operand_builds(self, program, *args, **kwargs) -> None:
        """Before a jitted growth program's first run: trace it and
        count where it builds its row-sized kernel operands
        (grower_mxu.operand_builds: once per tree, or in every pass).
        The run that follows finds this trace in jit's cache, so the
        program is traced once all the same. The counts go to the
        registry, beside the plan (_operand_build_attrs puts them on
        the boosting.build_program span)."""
        if getattr(self, "_operand_builds", None) is None and \
                self._hist_plan_attrs():
            from ..learner.grower_mxu import operand_builds
            built = operand_builds(program.trace(*args, **kwargs).jaxpr)
            self._operand_builds = built
            _obs.record_operand_builds(built["per_tree"],
                                       built["per_pass"])

    def _operand_build_attrs(self) -> dict:
        """The counts of _trace_operand_builds as span attributes; empty
        until a growth program has been traced."""
        built = getattr(self, "_operand_builds", None)
        if built is None:
            return {}
        return {"operand_builds_per_tree": ",".join(
                    "%s:%d" % kv for kv in built["per_tree"].items()),
                "operand_builds_per_pass": built["per_pass"],
                "id_columns_per_tree": built["id_columns_per_tree"],
                "id_columns_per_pass": built["id_columns_per_pass"]}

    def _hist_pass_plan(self, hist_backend: str) -> list:
        """[(stage, kernel slots, formulation)] of this booster's growth
        program (grower_mxu.hist_pass_plan); rows are ONE device's."""
        from ..learner.grower_mxu import hist_pass_plan
        from ..learner.histogram_pallas import hist_columns
        cfg = self.config
        sharded = self._learner.is_parallel
        ndev = int(self.mesh.devices.size) if sharded else 1
        return hist_pass_plan(
            rows=int(self.bins.shape[0]) // max(1, ndev),
            num_leaves=cfg.num_leaves, overshoot=cfg.growth_overshoot,
            tail_split_cap=cfg.tail_split_cap,
            hist_subtraction=cfg.hist_subtraction,
            bridge_gate=cfg.growth_bridge_gate, hist_backend=hist_backend,
            hist_double_prec=cfg.gpu_use_dp,
            quantized_grad=cfg.use_quantized_grad,
            # the sharded learner keeps const-hessian off (its kwargs)
            const_hessian=0.0 if sharded else self._const_hessian(),
            has_efb=self._efb is not None,
            columns=hist_columns(int(self.num_bins_d.shape[0]), self.bmax))

    def _mxu_grow_kwargs(self):
        """Static grow_tree_mxu settings — single source shared by the
        per-iteration path (_grow) and the fused scan (_build_fused) so
        the two cannot drift apart."""
        cfg = self.config
        return dict(
            efb=self._efb, forced=self._forced, cegb_cfg=self._cegb_cfg,
            # off for a sharded learner, whose per-tree grower is
            # built before the objective has bound its weights
            const_hessian=0.0 if self._learner.is_parallel
            else self._const_hessian(),
            num_leaves=cfg.num_leaves, max_depth=cfg.max_depth,
            hp=self.hp, bmax=self.bmax, monotone=self._monotone,
            interaction_groups=self._interaction_groups,
            feature_fraction_bynode=cfg.feature_fraction_bynode,
            hist_double_prec=cfg.gpu_use_dp,
            tail_split_cap=cfg.tail_split_cap,
            hist_subtraction=cfg.hist_subtraction,
            overshoot=cfg.growth_overshoot,
            bridge_gate=cfg.growth_bridge_gate,
            quantized_grad=cfg.use_quantized_grad,
            packed4=self._packed4,
            hist_backend=self._resolved_hist_backend(),
            partition_impl=cfg.partition_impl,
            interpret=getattr(self, "_mxu_interpret", False))

    @staticmethod
    def _transient_faults(warm: bool) -> Tuple:
        """Exception types a dispatch ladder may absorb (retry with
        back-off, degrade). A program's first call traces and compiles
        it, and what fails there is a broken kernel or program: the
        same on every retry and on the fallback's next block, minutes
        of compile each. So until the program has run once only an
        injected fault is taken for a transient one and everything
        else surfaces from lgb.train."""
        return (Exception,) if warm else (InjectedFault,)

    def _grow(self, g, h, cnt, feature_mask):
        """Growth dispatch with fault injection + retry (sites
        "histogram_build" and, for sharded growth, "collective_psum").
        Injection is host-side: inside the traced grower a raise would
        bake into the compiled program. Retrying `_grow_impl` is safe
        because it only mutates state (CEGB feat_used) after the
        dispatch returns."""
        cfg = self.config

        sharded = self._learner.is_parallel

        def _attempt():
            faults.inject("histogram_build")
            guard = None
            if sharded:
                from ..parallel.comm import check_collective_fault
                from ..reliability.watchdog import active_guard
                check_collective_fault()
                guard = active_guard()
            if guard is None:
                # device-profiler bracket (profile_spans=grow_tree): a
                # live capture forces a block_until_ready so the trace
                # window covers the async device work; otherwise the
                # dispatch stays fully async
                with _profiler.capture("sharded_grow" if sharded
                                       else "grow_tree") as capturing:
                    out = self._grow_impl(g, h, cnt, feature_mask)
                    if capturing:
                        jax.block_until_ready(out)
                return out
            # JAX dispatch is async: a peer dying mid-psum hangs the
            # host at the first result *read*, not the launch — so the
            # deadline bracket must cover block_until_ready, or the
            # watchdog would never see the stall
            with guard.guard("sharded_grow"):
                with _profiler.capture("sharded_grow"):
                    out = self._grow_impl(g, h, cnt, feature_mask)
                    with span("entry.wait_device", iter=self.iter_,
                              what="guarded_grow"):
                        jax.block_until_ready(out)
            return out

        # the grower's first call traces, lowers and compiles (or
        # fetches) the growth program
        warm = getattr(self, "_grow_warm", False)
        with contextlib.nullcontext() if warm else span(
                "boosting.build_program", iter=self.iter_, k=1,
                program="sharded_grow" if sharded else "grow_tree",
                **self._hist_plan_attrs(), **self._cat_attrs()) as build:
            out = retry_call(
                _attempt, attempts=cfg.retry_max_attempts,
                backoff_ms=cfg.retry_backoff_ms,
                backoff_max_ms=cfg.retry_backoff_max_ms,
                retry_on=self._transient_faults(warm),
                site="histogram_build")
            if build is not None:
                build.attrs.update(self._operand_build_attrs())
        self._grow_warm = True
        return out

    def _grow_impl(self, g, h, cnt, feature_mask):
        """Dispatch the grower self._learner names; returns
        (tree, row_node[:N])."""
        cfg = self.config
        spec = self._learner
        needs_rng = (self.hp.extra_trees or
                     cfg.feature_fraction_bynode < 1.0 or
                     cfg.use_quantized_grad)
        rng_key = jax.random.fold_in(
            jax.random.PRNGKey(cfg.extra_seed), self.iter_) \
            if needs_rng else None
        args = (self.bins, g, h, cnt, feature_mask, self.num_bins_d,
                self.missing_is_nan_d, self.is_cat_d)
        if spec.serial_mxu:
            from ..learner.grower_mxu import grow_tree_mxu
            kwargs = dict(rng_key=rng_key, cegb_state=self._cegb_state,
                          **self._mxu_grow_kwargs())
            self._trace_operand_builds(grow_tree_mxu, *args, **kwargs)
            out = grow_tree_mxu(*args, **kwargs)
        elif not spec.is_parallel:
            out = grow_tree(
                *args, num_leaves=cfg.num_leaves,
                max_depth=cfg.max_depth, hp=self.hp,
                leafwise=self._mono_nonbasic, bmax=self.bmax,
                monotone=self._monotone,
                interaction_groups=self._interaction_groups,
                feature_fraction_bynode=cfg.feature_fraction_bynode,
                rng_key=rng_key, hist_impl=spec.device,
                partition_impl=cfg.partition_impl,
                forced=self._forced, cegb_cfg=self._cegb_cfg,
                cegb_state=self._cegb_state,
                monotone_method=self._mono_method, efb=self._efb)
        else:
            if self._row_pad:
                g = jnp.pad(g, (0, self._row_pad))
                h = jnp.pad(h, (0, self._row_pad))
                cnt = jnp.pad(cnt, (0, self._row_pad))
            if self.comm.mode in ("data", "voting") and self._nproc > 1:
                g, h, cnt = (self._shard_rows(a) for a in (g, h, cnt))
            extra = ()
            if self._sharded_rng:
                extra = (jax.random.fold_in(
                    jax.random.PRNGKey(cfg.extra_seed), self.iter_),)
            if self._cegb_cfg is not None:
                extra = extra + (self._cegb_state,)
            if self._bins_ft is not None:
                extra = extra + (self._bins_ft,)
            args = (self.bins, g, h, cnt) + args[4:] + extra
            with self.mesh:
                self._trace_operand_builds(self._grower, *args)
                out = self._grower(*args)
        tree, row_node = out[:2]
        if self._cegb_cfg is not None:
            # feature-used flags persist across the whole model
            # (is_feature_used_in_split_ / is_feature_used_)
            fu, rfu = out[2]
            self._cegb_state = (self._cegb_state[0], self._cegb_state[1],
                                fu, rfu)
        if spec.is_parallel:
            row_node = self._local_rows(row_node)[:self.num_data]
        return tree, row_node

    def _sync_renewed_leaves(self, tree: TreeArrays, row_node, rw
                             ) -> TreeArrays:
        """Multi-machine L1-family leaf renewal sync (reference
        serial_tree_learner.cpp:747-757): each rank renews from its
        local percentiles; the final leaf value is the mean of the
        per-rank values over ranks that hold in-bag rows in the leaf."""
        m1 = tree.leaf_value.shape[0]
        cnts = np.zeros(m1, np.float64)
        np.add.at(cnts, np.asarray(row_node),
                  (np.asarray(rw[:len(row_node)]) > 0).astype(np.float64))
        lv = np.asarray(tree.leaf_value, np.float64)
        has = (cnts > 0).astype(np.float64)
        contrib = np.stack([np.where(has > 0, lv, 0.0), has])
        from ..parallel.comm import guarded_allgather
        total = guarded_allgather(
            contrib, label="leaf_renewal_sync").sum(axis=0)
        nz = np.maximum(total[1], 1.0)
        synced = np.where(total[1] > 0, total[0] / nz, lv)
        is_leaf = np.asarray(tree.is_leaf)
        new_lv = np.where(is_leaf, synced, lv).astype(np.float32)
        return tree._replace(leaf_value=jnp.asarray(new_lv))

    def _train_bins_unpacked(self) -> jax.Array:
        """Training bin matrix in unpacked [N, F] form for cold paths
        (rollback, DART drops) — transient device unpack when packed."""
        if not getattr(self, "_packed4", False):
            return self.bins
        from ..learner.histogram_mxu import unpack_bins_4bit
        return unpack_bins_4bit(self.bins, int(self.num_bins_d.shape[0]))

    def _predict_train_rows(self, tree: TreeArrays) -> jax.Array:
        """Tree outputs for the (unpadded) training rows."""
        bins = self._local_bins if getattr(self, "_nproc", 1) > 1 \
            else self._train_bins_unpacked()
        vals = predict_binned_tree(tree, bins, self.num_bins_d,
                                   self.missing_is_nan_d, self._efb)
        return vals[:self.num_data] if self._row_pad else vals

    def add_valid(self, ds: BinnedDataset, name: str,
                  metrics: List[Metric]) -> None:
        self.valid_sets.append(ds)
        self.valid_names.append(name)
        self.valid_metrics.append(metrics)
        k = self.num_tree_per_iteration
        shape = (ds.num_data,) if k == 1 else (ds.num_data, k)
        score = jnp.zeros(shape, jnp.float32)
        if ds.metadata.init_score is not None:
            score = jnp.asarray(
                np.asarray(ds.metadata.init_score, np.float32).reshape(shape))
        if not hasattr(self, "valid_scores"):
            self.valid_scores: List[jax.Array] = []
            self.valid_bins: List[jax.Array] = []
        self.valid_scores.append(score)
        self.valid_bins.append(jnp.asarray(ds.bins))
        if self._linear:
            if ds.raw is None:
                raise ValueError(
                    "linear_tree model needs raw values on validation "
                    "sets; construct them with linear_tree in params")
            self.valid_raws.append(jnp.asarray(ds.raw))
        else:
            self.valid_raws.append(None)
        # replay existing model on the new valid set
        for ti, (t, cls) in enumerate(zip(self.trees, self.tree_class)):
            vals = self._tree_values(t, self._lin(ti), self.valid_bins[-1],
                                     self.valid_raws[-1])
            vi = len(self.valid_scores) - 1
            if k == 1:
                self.valid_scores[vi] = self.valid_scores[vi] + vals
            else:
                self.valid_scores[vi] = \
                    self.valid_scores[vi].at[:, cls].add(vals)

    # ------------------------------------------------------------------
    # bagging (gbdt.cpp:183-264; GOSS goss.hpp:25-95)
    def _next_key(self) -> jax.Array:
        self._rng_key, sub = jax.random.split(self._rng_key)
        return sub

    def _bagging(self, grad: jax.Array, hess: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        cfg = self.config
        if cfg.boosting == "goss":
            return self._goss(grad, hess)
        if self._needs_bagging() and self.iter_ % cfg.bagging_freq == 0:
            key = jax.random.fold_in(
                jax.random.PRNGKey(cfg.bagging_seed), self.iter_)
            u = jax.random.uniform(key, (self.num_data,))
            if cfg.pos_bagging_fraction < 1.0 or \
                    cfg.neg_bagging_fraction < 1.0:
                pos = self.objective.label > 0
                frac = jnp.where(pos, cfg.pos_bagging_fraction,
                                 cfg.neg_bagging_fraction)
                self._bag_mask = (u < frac).astype(jnp.float32)
            else:
                self._bag_mask = (u < cfg.bagging_fraction) \
                    .astype(jnp.float32)
        mask = self._bag_mask
        if grad.ndim == 2:
            return grad * mask[:, None], hess * mask[:, None], mask
        return grad * mask, hess * mask, mask

    def _goss(self, grad, hess):
        """Gradient-based one-side sampling (goss.hpp:76-95)."""
        cfg = self.config
        top_rate, other_rate = cfg.top_rate, cfg.other_rate
        score_abs = jnp.abs(grad) * hess
        if score_abs.ndim == 2:
            score_abs = score_abs.sum(axis=1)
        n = self.num_data
        top_k = max(1, int(n * top_rate))
        other_k = max(1, int(n * other_rate))
        thresh = jax.lax.top_k(score_abs, top_k)[0][-1]
        is_top = score_abs >= thresh
        key = self._next_key()
        u = jax.random.uniform(key, (n,))
        rest_frac = other_rate / max(1.0 - top_rate, 1e-9)
        is_other = (~is_top) & (u < rest_frac)
        amplify = (1.0 - top_rate) / other_rate
        w = jnp.where(is_top, 1.0, jnp.where(is_other, amplify, 0.0)) \
            .astype(jnp.float32)
        cnt = jnp.where(is_top | is_other, 1.0, 0.0).astype(jnp.float32)
        del other_k
        if grad.ndim == 2:
            return grad * w[:, None], hess * w[:, None], cnt
        return grad * w, hess * w, cnt

    # ------------------------------------------------------------------
    def train_one_iter(self, gradients: Optional[jax.Array] = None,
                       hessians: Optional[jax.Array] = None) -> bool:
        """One boosting iteration (reference TrainOneIter gbdt.cpp:371-449).
        Returns True if training cannot continue (no splits made)."""
        cfg = self.config
        k = self.num_tree_per_iteration
        init_scores = [0.0] * k
        # the iteration's phase spans, in order: observe=true reads
        # the telemetry record off them at the end (the guard
        # skip-iteration early return below goes unrecorded: rare, and
        # its counters surface in the next record's deltas)
        it = self.iter_
        phases: List = []

        with span("boosting.gradients", iter=it) as sp:
            if gradients is None or hessians is None:
                init_scores = self._take_initial_bias()
                # the name the fused scan gives the same computation
                with jax.named_scope(
                        "objective." + getattr(self.objective, "name",
                                               "custom")):
                    gradients, hessians = self.objective.get_gradients(
                        self.train_score)
        phases.append(sp)

        guard = cfg.guard_nonfinite
        prev_scores = None
        if guard != "off":
            # pre-growth rail: non-finite gradients (exploding custom
            # objective, corrupted scores) poison every later iteration
            if not guards.all_finite(gradients, hessians):
                gradients, hessians = self._guard_gradients(
                    guard, gradients, hessians)
                if gradients is None:      # skip_iteration consumed it
                    return False
            # reference for the post-growth rail: scores are immutable
            # JAX arrays, so stashing them is a pair of references, and
            # restoring beats arithmetic rollback (subtracting a NaN
            # tree cannot un-NaN a score)
            prev_scores = (self.train_score,
                           list(getattr(self, "valid_scores", []) or []))

        with span("boosting.bagging", iter=it) as sp:
            grad, hess, cnt = self._bagging(gradients, hessians)
        phases.append(sp)

        should_continue = False
        for cls in range(k):
            g = grad if k == 1 else grad[:, cls]
            h = hess if k == 1 else hess[:, cls]
            # asynchronous: this is the host enqueueing the tree's
            # growth program, not the device growing it
            with span("entry.dispatch", iter=it) as sp:
                feature_mask = self._feature_mask()
                tree, row_node = self._grow(g, h, cnt, feature_mask)
            phases.append(sp)
            # a host pull of num_leaves waits for the whole tree just
            # dispatched, so the device sits idle until the host asks
            # for the next one. Instead of syncing on the fresh tree,
            # the stop
            # decision reads a PREVIOUS iteration's count, and even that
            # only every _stop_poll_every iterations — each stored count
            # starts an async D2H copy so the eventual int() finds the
            # value already on the host. The fresh tree always takes the
            # normal processing branch — shrinkage, score update, and the
            # device-side `ok` zeroing make a genuine no-split tree a
            # harmless all-zero tree, while a real tree (possible after a
            # dry iteration when bagging resamples) stays fully applied.
            # Stall detection is therefore delayed by up to
            # _stop_poll_every iterations (the extra trees are all-zero —
            # predictions unaffected). Subclasses that average over
            # iteration count (RF) set _exact_stop_poll to keep the
            # reference's immediate stop.
            if (self.iter_ == 0 and len(self.trees) < k) or \
                    self._exact_stop_poll:
                with span("entry.wait_device", iter=it, what="num_leaves"):
                    nleaves = int(tree.num_leaves)
                stop_hint = nleaves <= 1
            else:
                prev = self._pending_nleaves
                stop_hint = False
                if prev is not None and \
                        self.iter_ % self._stop_poll_every == 0:
                    with span("entry.wait_device", iter=it,
                              what="stop_poll"):
                        stop_hint = int(prev) <= 1
                nleaves = 2
            self._note_nleaves(tree.num_leaves)
            lin = None
            if nleaves > 1:
                if not stop_hint:
                    should_continue = True
                with span("boosting.shrink", iter=it) as sp:
                    if self.objective is not None and \
                            self.objective.need_renew_tree_output:
                        rw = cnt if self.objective.weight is None \
                            else cnt * self.objective.weight
                        tree = renew_tree_output(
                            tree, row_node, self.train_score if k == 1
                            else self.train_score[:, cls],
                            jnp.asarray(self.objective.label), rw,
                            self.objective.renew_percentile,
                            cfg.num_leaves)
                        if getattr(self, "_nproc", 1) > 1:
                            tree = self._sync_renewed_leaves(
                                tree, row_node, rw)
                    if self._linear:
                        from ..learner.linear import fit_linear_leaves
                        with span("boosting.linear_fit", iter=it):
                            lin = fit_linear_leaves(
                                tree, row_node, self.raw, g, h, cnt,
                                self.is_cat_d,
                                jnp.float32(cfg.linear_lambda),
                                dmax=self._lin_dmax)
                    # shrinkage (tree.cpp Shrinkage): scale leaf outputs
                    # and, for linear leaves, consts + coefficients. The
                    # `ok` factor zeroes trees that made no split
                    # (device-side stand-in for the reference's "no
                    # further splits" break)
                    ok = (tree.num_leaves > 1).astype(jnp.float32)
                    tree = tree._replace(
                        leaf_value=tree.leaf_value * self.shrinkage_rate
                        * ok)
                    if lin is not None:
                        lin = lin._replace(
                            const=lin.const * self.shrinkage_rate * ok,
                            coeff=lin.coeff * self.shrinkage_rate * ok)
                phases.append(sp)
                with span("boosting.update_score", iter=it) as sp:
                    self._update_score(tree, row_node, cls, lin)
                phases.append(sp)
            with span("entry.append_tree", iter=it) as sp:
                if nleaves > 1:
                    if abs(init_scores[cls]) > 1e-35:
                        # AddBias (gbdt.cpp:416-417): fold init into
                        # tree 0
                        tree = tree._replace(
                            leaf_value=jnp.where(
                                tree.split_feature < 0,
                                tree.leaf_value + init_scores[cls],
                                tree.leaf_value))
                        if lin is not None:
                            lin = lin._replace(const=jnp.where(
                                tree.split_feature < 0,
                                lin.const + init_scores[cls], lin.const))
                elif self.iter_ == 0 and len(self.trees) < k:
                    if self.objective is not None and \
                            not cfg.boost_from_average and \
                            not self._has_init_score:
                        init_scores[cls] = \
                            self.objective.boost_from_score(cls)
                        self._add_const_score(init_scores[cls], cls)
                    tree = self._constant_tree(init_scores[cls])
                self.trees.append(tree)
                self.tree_class.append(cls)
                self.linear_models.append(lin)
            phases.append(sp)
        self.iter_ += 1
        if guard != "off" and not guards.all_finite(
                self.train_score,
                *[self._guarded_tree_values(t) for t in self.trees[-k:]]):
            guards.trip("split gains/scores", guard, self.iter_ - 1)
            if guard in ("skip_iteration", "rollback"):
                # discard the offending iteration by exact restoration
                for _ in range(k):
                    self.trees.pop()
                    self.tree_class.pop()
                    self.linear_models.pop()
                self.train_score = prev_scores[0]
                for i, s in enumerate(prev_scores[1]):
                    self.valid_scores[i] = s
                self.iter_ -= 1
                if guard == "skip_iteration":
                    # keep the iteration slot (constant zero trees) so
                    # tree counts stay aligned with the boosting round
                    for cls in range(k):
                        self.trees.append(self._constant_tree(0.0))
                        self.tree_class.append(cls)
                        self.linear_models.append(None)
                    self.iter_ += 1
        if _obs.enabled:
            walls: Dict[str, float] = {}
            for sp in phases:
                walls[sp.name] = walls.get(sp.name, 0.0) + sp.duration
            _obs.record_train_iteration(
                self, it, phases[-1].end - phases[0].start, phases=walls,
                gradients=gradients, hessians=hessians,
                tree=self.trees[-1] if self.trees else None)
        return not should_continue

    @staticmethod
    def _guarded_tree_values(tree):
        """Leaf outputs of `tree`'s *valid* nodes only: slots past
        num_nodes and internal-node slots hold uninitialised padding
        (legitimately non-finite), so the guard must not read them."""
        idx = jnp.arange(tree.leaf_value.shape[0])
        valid = (idx < tree.num_nodes) & tree.is_leaf
        return jnp.where(valid, tree.leaf_value, 0.0)

    def _guard_gradients(self, guard, gradients, hessians):
        """Pre-growth non-finite rail (guard_nonfinite policies).
        Returns usable (gradients, hessians), or (None, None) when the
        skip_iteration policy consumed the whole iteration."""
        guards.trip("gradients/hessians", guard, self.iter_)
        k = self.num_tree_per_iteration
        if guard == "rollback" and self.iter_ > self._iter_offset and \
                self.objective is not None:
            # the bad gradients were computed from the current scores:
            # drop the iteration that produced them (reference
            # Boosting::RollbackOneIter) and recompute
            self.rollback_one_iter()
            gradients, hessians = self.objective.get_gradients(
                self.train_score)
            if guards.all_finite(gradients, hessians):
                return gradients, hessians
            guards.trip("gradients/hessians after rollback", guard,
                        self.iter_)
        if guard == "skip_iteration":
            # keep the iteration slot: constant zero trees contribute
            # nothing but keep tree counts aligned with boosting rounds
            for cls in range(k):
                self.trees.append(self._constant_tree(0.0))
                self.tree_class.append(cls)
                self.linear_models.append(None)
            self.iter_ += 1
            return None, None
        return (jnp.nan_to_num(gradients, nan=0.0, posinf=0.0, neginf=0.0),
                jnp.nan_to_num(hessians, nan=0.0, posinf=0.0, neginf=0.0))

    def _feature_mask(self) -> jax.Array:
        return self._feature_mask_at(self.iter_)

    def _feature_mask_at(self, it) -> jax.Array:
        """Per-iteration feature_fraction mask; `it` may be a traced
        iteration index (the fused multi-tree scan)."""
        cfg = self.config
        f = int(self.num_bins_d.shape[0])  # original features (not Fb)
        if cfg.feature_fraction >= 1.0:
            return jnp.ones(f, jnp.float32)
        key = jax.random.fold_in(
            jax.random.PRNGKey(cfg.feature_fraction_seed), it)
        kf = max(1, int(round(f * cfg.feature_fraction)))
        perm = jax.random.permutation(key, f)
        mask = jnp.zeros(f, jnp.float32).at[perm[:kf]].set(1.0)
        return mask

    # ------------------------------------------------------------------
    # fused multi-tree training (TPU pipelining; boosting/fused.py)
    def _needs_bagging(self) -> bool:
        cfg = self.config
        return cfg.bagging_freq > 0 and (
            cfg.bagging_fraction < 1.0 or cfg.pos_bagging_fraction < 1.0
            or cfg.neg_bagging_fraction < 1.0)

    def _fused_eligible(self) -> bool:
        """Whether K iterations can run as one on-device scan with
        behavior identical to K train_one_iter calls. Round 4 widened
        the ring: bagging masks are recomputed statelessly in-scan, GOSS
        consumes pre-drawn keys, and multiclass grows one tree per class
        per step (fused.py)."""
        cfg = self.config
        # guard rails need per-iteration host checks; the fused scan has
        # no host boundary to interpose on (docs/Reliability.md)
        serial_ok = self._learner.serial_mxu
        return (type(self) is GBDT and cfg.boosting in ("gbdt", "goss")
                and cfg.guard_nonfinite == "off"
                and (serial_ok or self._sharded_fused_ok())
                and not self._linear
                and self.objective is not None
                and not self.objective.need_renew_tree_output
                and self._cegb_cfg is None)  # feat_used carries across
        #       trees (a scan-carry the fused body doesn't thread);
        #       forced splits are per-tree static and ride along.
        #       valid_sets ride along too (round 5): the stacked trees
        #       are replayed over each valid set AFTER the dispatch
        #       (_stacked_score_traj), reproducing the per-iteration
        #       score updates exactly

    def _sharded_fused_ok(self) -> bool:
        """Whether the data-parallel learner can run the fused
        multi-tree scan: the boosting loop moves inside shard_map, so
        the pipelined executor double-buffers multi-device training as
        it does the serial MXU path. The MXU grower takes the serial
        path's own scan with a `psum_axis` (boosting/fused.py), the
        portable grower the one in distributed/fused.py. What stays
        per-iteration, and why: more than one process (the watchdog's
        guarded collective needs a host boundary), GOSS (a global top-k
        over all rows), EFB, CEGB and rescan monotone (per-iteration
        host state), feature- and voting-parallel (no sharded scan
        body), several trees an iteration, and an objective whose
        gradients read other rows (a ranking objective's query
        tables)."""
        cfg = self.config
        return (self._learner.is_parallel
                and getattr(self, "_nproc", 1) <= 1
                and self.comm.mode == "data"
                and cfg.boosting == "gbdt"
                and self.num_tree_per_iteration == 1
                and self._efb is None
                and not self._mono_nonbasic
                and not getattr(self.objective, "table_state", ()))

    def _fused_sample_fn(self):
        """In-scan bagging/GOSS (fused.py contract): returns
        (sample_fn | None, needs_keys). Both reproduce the per-iteration
        path exactly — bagging is stateless on (seed, resample
        iteration); GOSS consumes the same _next_key draws."""
        cfg = self.config
        n = self.num_data
        if cfg.boosting == "goss":
            top_rate, other_rate = cfg.top_rate, cfg.other_rate
            top_k = max(1, int(n * top_rate))

            def goss_fn(grad, hess, it, key):
                score_abs = jnp.abs(grad) * hess
                if score_abs.ndim == 2:
                    score_abs = score_abs.sum(axis=1)
                thresh = jax.lax.top_k(score_abs, top_k)[0][-1]
                is_top = score_abs >= thresh
                u = jax.random.uniform(key, (n,))
                rest_frac = other_rate / max(1.0 - top_rate, 1e-9)
                is_other = (~is_top) & (u < rest_frac)
                amplify = (1.0 - top_rate) / other_rate
                w = jnp.where(is_top, 1.0,
                              jnp.where(is_other, amplify, 0.0)) \
                    .astype(jnp.float32)
                cnt = (is_top | is_other).astype(jnp.float32)
                if grad.ndim == 2:
                    return grad * w[:, None], hess * w[:, None], cnt
                return grad * w, hess * w, cnt

            return goss_fn, True
        if self._needs_bagging():
            use_posneg = (cfg.pos_bagging_fraction < 1.0 or
                          cfg.neg_bagging_fraction < 1.0)
            label = jnp.asarray(self.objective.label) if use_posneg \
                else None

            def bag_fn(grad, hess, it, key):
                # the mask the per-iteration path STORED at the last
                # resample boundary, recomputed statelessly
                it_rs = it - it % cfg.bagging_freq
                k2 = jax.random.fold_in(
                    jax.random.PRNGKey(cfg.bagging_seed), it_rs)
                u = jax.random.uniform(k2, (n,))
                if use_posneg:
                    frac = jnp.where(label > 0, cfg.pos_bagging_fraction,
                                     cfg.neg_bagging_fraction)
                    mask = (u < frac).astype(jnp.float32)
                else:
                    mask = (u < cfg.bagging_fraction).astype(jnp.float32)
                if self._learner.is_parallel:
                    # inside shard_map: every device drew the whole
                    # mask, and takes its own rows of it (a padded row
                    # is never in the bag)
                    mask = jax.lax.dynamic_slice_in_dim(
                        jnp.pad(mask, (0, self._row_pad)),
                        jax.lax.axis_index(self.comm.axis)
                        * grad.shape[0], grad.shape[0])
                if grad.ndim == 2:
                    return grad * mask[:, None], hess * mask[:, None], mask
                return grad * mask, hess * mask, mask

            return bag_fn, False
        return None, False

    def _build_sharded_fused(self):
        """Fused-scan builder for the PORTABLE data-parallel grower
        (distributed/fused.py); the sharded MXU grower takes
        _build_fused's own."""
        from ..distributed.fused import build_sharded_fused_train
        cfg = self.config
        self._fused_needs_keys = False
        bagging = None
        if self._needs_bagging():
            bagging = dict(
                freq=cfg.bagging_freq, seed=cfg.bagging_seed,
                fraction=cfg.bagging_fraction,
                pos_fraction=cfg.pos_bagging_fraction,
                neg_fraction=cfg.neg_bagging_fraction,
                use_posneg=(cfg.pos_bagging_fraction < 1.0 or
                            cfg.neg_bagging_fraction < 1.0))
        # the exact static settings create_tree_learner bakes into the
        # per-iteration sharded grower — same partial, same compiled
        # growth body, so fused blocks match per-iteration training
        grow_kwargs = dict(
            num_leaves=cfg.num_leaves, max_depth=cfg.max_depth,
            hp=self.hp, leafwise=self._mono_nonbasic, bmax=self.bmax,
            monotone=self._monotone, monotone_method=self._mono_method,
            interaction_groups=self._interaction_groups,
            feature_fraction_bynode=cfg.feature_fraction_bynode,
            forced=self._forced)
        return build_sharded_fused_train(
            mesh=self.mesh, comm=self.comm, objective=self.objective,
            bins=self.bins, bins_ft=self._bins_ft,
            num_data=self.num_data, row_pad=self._row_pad,
            feature_mask_fn=self._feature_mask_at,
            num_bins=self.num_bins_d,
            missing_is_nan=self.missing_is_nan_d, is_cat=self.is_cat_d,
            grow_kwargs=grow_kwargs, shrinkage=self.shrinkage_rate,
            extra_seed=cfg.extra_seed, needs_rng=self._sharded_rng,
            bagging=bagging)

    def _build_fused(self):
        from .fused import build_fused_train
        cfg = self.config
        if self._learner.device != "mxu":
            return self._build_sharded_fused()
        needs_rng = (cfg.feature_fraction_bynode < 1.0 or cfg.extra_trees
                     or cfg.use_quantized_grad)
        sample_fn, needs_keys = self._fused_sample_fn()
        self._fused_needs_keys = needs_keys
        return build_fused_train(
            objective=self.objective, bins=self.bins,
            feature_mask_fn=self._feature_mask_at,
            num_bins=self.num_bins_d, missing_is_nan=self.missing_is_nan_d,
            is_cat=self.is_cat_d, grower_kwargs=self._mxu_grow_kwargs(),
            shrinkage=self.shrinkage_rate, extra_seed=cfg.extra_seed,
            needs_rng=needs_rng, sample_fn=sample_fn,
            num_class=self.num_tree_per_iteration,
            # None on one device; the data-parallel learner's scan runs
            # inside shard_map over it
            mesh=self.mesh, row_pad=self._row_pad)

    def train_many(self, k: int) -> bool:
        """K boosting iterations with one device dispatch (and at most
        one amortized host sync) — behavior-identical to K
        train_one_iter calls when eligible, else a plain loop. Returns
        True when training cannot continue (lagged stall detection, as
        in train_one_iter).

        Resilience: a runtime failure of a fused program that has
        already run (a preempted slice, a launch fault) is retried and
        then falls back to the per-iteration path for this batch; after
        two consecutive fused failures the fused path is disabled for
        the rest of this booster's life. A failure of a block length's
        first dispatch — the one that compiles it — propagates
        (_transient_faults)."""
        return self.finalize_block(self.train_many_dispatch(k))

    def finalize_block(self, handle: dict) -> bool:
        """Second half of train_many: put the dispatched block's trees
        on self.trees. Its only effect is the tree list — scores, RNG,
        iter_, valid trajectories and the stall poll were already
        advanced by train_many_dispatch, so the pipelined executor
        defers this call until the NEXT block has been dispatched.

        It dispatches nothing: the per-tree views were made by the one
        split program train_many_dispatch enqueued right behind the
        block (`programs` on the span says how many device programs
        the unpack itself ran). It WAITS for its own block, and for
        that block only: the views of block k are ready when block k
        is, whatever was enqueued after it. That wait (`waited_ms`) is
        the executor's backpressure: the host runs one block ahead of
        the device and never two. The span's wall after the wait is
        left on the handle as `host_s`."""
        if handle["mode"] == "fused":
            views, kcls = handle.pop("trees"), handle["kcls"]
            it0, k = handle["iter"], handle["k"]
            with span("entry.unpack_block", iter=it0, k=k,
                      programs=0) as unpack:
                jax.block_until_ready(views)
                ready_at = time.perf_counter()
                unpack.attrs["waited_ms"] = (ready_at - unpack.start) * 1e3
                # the split program made these beside the views: they
                # are there, reading them waits for nothing
                nodes, cat_nodes = np.asarray(handle.pop("decides"))
                unpack.attrs.update(nodes=int(nodes),
                                    cat_nodes=int(cat_nodes))
                ran = self._growth_counts(handle.pop("counters"))
                unpack.attrs.update(ran)
                handle["ran"] = ran
                for n, tree in enumerate(views):
                    with span("entry.unpack_tree", iter=it0, k=k,
                              tree=it0 * kcls + n):
                        self.trees.append(tree)
                        self.tree_class.append(n % kcls)
                        self.linear_models.append(None)
            handle["host_s"] = unpack.end - ready_at
            self._obs_close_block(it0, unpack.end)
        return handle["stop"]

    def _growth_counts(self, stacked) -> dict:
        """What a fused block's trees ran, summed over the block on the
        host from the counters the scan stacked ([k(, num_class), C]
        int32, grower_mxu.GROWTH_COUNTERS; None from the portable
        sharded scan, which counts nothing: no attribute then).
        `passes` is the passes of either formulation, `trees` the
        trees they are summed over and `rows` the rows ONE pass sweeps
        (the dataset's, over the whole mesh), so that a reader of the
        span needs nothing else to turn them into passes a tree and
        live rows into a share."""
        if stacked is None:
            return {}
        from ..learner.grower_mxu import GROWTH_COUNTERS
        per_tree = np.asarray(stacked, np.int64) \
            .reshape(-1, len(GROWTH_COUNTERS))
        ran = dict(zip(GROWTH_COUNTERS, map(int, per_tree.sum(axis=0))))
        return dict(passes=ran["onehot_passes"] + ran["grouped_passes"],
                    **ran, trees=len(per_tree), rows=int(self.num_data))

    def _obs_close_block(self, iter0: Optional[int], now: float) -> None:
        """observe=true: the telemetry record of the fused block still
        open, made when the next block is dispatched or when the block
        itself is unpacked, whichever comes first. Its wall runs from
        its own dispatch to `now` (a span's clock reading): the device
        works through blocks back to back, so dispatch to dispatch is
        what a block costs, and no sync is made to measure it.
        `iter0` names the block being unpacked (None: close whichever
        is open)."""
        open_ = getattr(self, "_obs_block", None)
        if open_ is None or (iter0 is not None and open_[0] != iter0):
            return
        self._obs_block = None
        _obs.record_fused_block(self, open_[0], open_[1], now - open_[2])

    def _fused_warm_at(self, k: int) -> bool:
        """Whether the fused closure has run a block of length k once:
        k is a static argument, so each new length is a new program."""
        return getattr(self, "_fused_run", None) is not None \
            and k in self._fused_warm

    @staticmethod
    def _buffer_deleted(arr) -> bool:
        """True when a donated jax.Array's buffer is gone (TPU donation
        consumes the input; CPU ignores donation so this stays False)."""
        fn = getattr(arr, "is_deleted", None)
        try:
            return bool(fn()) if fn is not None else False
        except Exception:
            return False

    def train_many_dispatch(self, k: int) -> dict:
        """First half of train_many: run the k iterations (fused
        dispatch when eligible, else the per-iteration loop) and leave
        everything EXCEPT the per-tree unpacking done. Returns an
        opaque handle for finalize_block; until finalize_block runs,
        self.trees lags self.iter_ by the fused block.

        The split exists for the pipelined executor
        (pipeline/executor.py): the tree list has no effect on the
        next dispatch's inputs, so the executor enqueues the next block
        first and comes for this one's trees while that one runs. This
        half waits for no block in flight: the fused program, the one
        split program that makes the block's per-tree views and the
        valid replay are enqueued, and the stop poll reads a count that
        is already there."""
        # per-iteration valid-score trajectory of this batch (engine
        # block dispatch evaluates/early-stops from it). EVERY path
        # through this method — fused, per-iteration fallback, stalled —
        # completes the full k iterations and leaves a k-point
        # trajectory, so block size and eval cadence never depend on
        # eligibility or faults.
        self._fused_valid_traj = None
        traj_pts = [[] for _ in self.valid_sets] if self.valid_sets \
            else None

        def _snap():
            if traj_pts is not None:
                for i in range(len(traj_pts)):
                    traj_pts[i].append(self.valid_scores[i])

        def _seal():
            if traj_pts is not None and traj_pts[0]:
                self._fused_valid_traj = [jnp.stack(p) for p in traj_pts]

        stop = False
        cfg = self.config
        # A block that starts at iteration 0 runs whole in the fused
        # program on the MXU path, serial or data-parallel: the
        # boost_from_average constant goes onto the scores first and
        # into tree 0's leaves afterwards, which is all train_one_iter
        # does differently there. So such a run compiles ONE growth
        # program, not the per-iteration grower plus the scan around it
        # (minutes each at 255 leaves). Kept on the per-iteration path:
        # the portable sharded grower (its byte-parity contract,
        # distributed/fused.py), and boost_from_average=false without
        # init scores, where a root that cannot split takes its value
        # from the objective instead.
        fused_ok = self._fused_eligible() and not getattr(
            self, "_fused_disabled", False)
        fuse_first = (
            self.iter_ == 0 and k > 0 and fused_ok
            and self._learner.device == "mxu"
            and (cfg.boost_from_average or self._has_init_score))
        if self.iter_ == 0 and k > 0 and not fuse_first:
            # the first iteration owns boost_from_average / init-score
            # plumbing (host-side floats); run it on the normal path
            stop = self.train_one_iter()
            k -= 1
            _snap()
            if stop:
                # stalled at iteration 0: still complete the batch
                # (constant trees), like every other path here
                for _ in range(k):
                    self.train_one_iter()
                    _snap()
                _seal()
                return {"mode": "done", "stop": True}
        if k <= 0:
            _seal()
            return {"mode": "done", "stop": stop}
        if not fused_ok:
            for _ in range(k):
                stop = self.train_one_iter() or stop
                _snap()
            _seal()
            return {"mode": "done", "stop": stop}
        saved_rng = self._rng_key
        if fuse_first:
            # onto the scores now; _take_initial_bias hands the values
            # to whoever ends up building tree 0
            self._initial_bias = [self._boost_from_average(cls) for cls
                                  in range(self.num_tree_per_iteration)]

        def _attempt():
            # every attempt rewinds the RNG stream first: whether the
            # dispatch succeeds on attempt 1 or 3, it must consume the
            # IDENTICAL key sequence — a transient fault must not
            # change the trained model
            self._rng_key = saved_rng
            if self._buffer_deleted(self.train_score):
                # a previous attempt donated the score buffer to a
                # dispatch that failed after consuming it; retrying
                # would feed XLA a dead buffer — fail with a clear
                # diagnosis instead
                raise LightGBMError(
                    "train-score buffer was donated to a failed fused "
                    "dispatch and deleted by the runtime; cannot retry")
            try:
                _maybe_inject_fused_fault()
                # k is a static argument: a block length's first run
                # traces, lowers and compiles (or fetches) its program
                with contextlib.nullcontext() if self._fused_warm_at(k) \
                        else span("boosting.build_program",
                                  program="fused_train", iter=iter0, k=k,
                                  devices=1 if self.mesh is None
                                  else int(self.mesh.devices.size),
                                  **self._hist_plan_attrs(),
                                  **self._cat_attrs()) as build:
                    if getattr(self, "_fused_run", None) is None:
                        self._fused_run = self._build_fused()
                        self._fused_warm = set()
                    keys = None
                    if getattr(self, "_fused_needs_keys", False):
                        # the same _next_key sequence the per-iteration
                        # GOSS path would draw, pre-drawn as scan inputs
                        keys = jnp.stack([self._next_key()
                                          for _ in range(k)])
                    run, it0 = self._fused_run, \
                        jnp.asarray(self.iter_, jnp.int32)
                    if build is not None and hasattr(run, "arguments"):
                        # (the portable sharded scan has no such
                        # operands)
                        self._trace_operand_builds(
                            run.program, *run.arguments(
                                self.train_score, it0, k=k,
                                sample_keys=keys))
                        build.attrs.update(self._operand_build_attrs())
                    return run(self.train_score, it0, k=k,
                               sample_keys=keys)
            except Exception:
                self._fused_run = None  # closure may hold dead executables
                raise

        iter0 = self.iter_
        transient = self._transient_faults(self._fused_warm_at(k))
        try:
            # capped-exponential-backoff retries before degrading: a
            # transient launch failure should not cost the fused path.
            # The span times the host enqueueing the block (and, the
            # first time, building its program): the scan is
            # asynchronous and nothing here waits for it
            with span("entry.dispatch", iter=iter0, k=k) as dispatch:
                score, stacked, ran = retry_call(
                    _attempt, attempts=cfg.retry_max_attempts,
                    backoff_ms=cfg.retry_backoff_ms,
                    backoff_max_ms=cfg.retry_backoff_max_ms,
                    retry_on=transient, site="fused_dispatch")
                # whether this block was enqueued behind a running one
                # (asks, waits for nothing): the last tree before it
                # had not reported its leaf count yet
                prev = self._pending_nleaves
                dispatch.attrs["in_flight"] = \
                    prev is not None and not self._is_ready(prev)
        except transient as exc:
            # rewind the RNG stream so the per-iteration fallback draws
            # the IDENTICAL key sequence the fused dispatch consumed —
            # a transient fault must not change the trained model
            self._rng_key = saved_rng
            if self._buffer_deleted(self.train_score):
                # donation consumed the score carry before the fault
                # landed: the per-iteration fallback would read a dead
                # buffer, so surface the truth instead of degrading
                raise LightGBMError(
                    "fused dispatch failed after its donated train-score "
                    "buffer was consumed; per-iteration fallback is "
                    "impossible — restart from the last checkpoint"
                ) from exc
            self._fused_failures = getattr(self, "_fused_failures", 0) + 1
            self._fused_run = None  # closure may hold dead executables
            counters.inc("fallbacks")
            if self._fused_failures >= 2:
                self._fused_disabled = True
            Log.warning(
                "fused multi-tree dispatch failed (%s: %s); falling back "
                "to per-iteration training for this batch%s"
                % (type(exc).__name__, exc,
                   " and disabling the fused path" if
                   getattr(self, "_fused_disabled", False) else ""))
            for _ in range(k):
                stop = self.train_one_iter() or stop
                _snap()
            _seal()
            return {"mode": "done", "stop": stop}
        self._fused_failures = 0
        self._fused_warm.add(k)
        if _obs.enabled:
            # one telemetry record per block (no host boundary inside
            # it), closed at the next dispatch or at its own unpacking
            self._obs_close_block(None, dispatch.start)
            self._obs_block = (iter0, k, dispatch.start)
        self.train_score = score
        kcls = self.num_tree_per_iteration
        model_trees = stacked
        if fuse_first:
            # AddBias: the valid replay below wants the trees as grown
            # (valid scores already carry the constant), the model
            # wants tree 0 with it folded in
            model_trees = self._add_bias_to_first(
                stacked, self._take_initial_bias(), kcls)
        if self.valid_sets:
            # replay the stacked block over each valid set — one scanned
            # dispatch per set yields the exact per-iteration valid-score
            # trajectory (the engine's block path evaluates metrics /
            # early stopping at every inner iteration from it); any
            # normal-path points already snapped (iteration 0) lead it
            from .fused import stacked_score_traj
            trajs = []
            for i in range(len(self.valid_sets)):
                # any snapped lead points alias the very buffer donated
                # below as score0 — stack them into a fresh array FIRST
                # (on TPU the dispatch deletes the donated input)
                lead = jnp.stack(traj_pts[i]) \
                    if traj_pts is not None and traj_pts[i] else None
                fin, traj = stacked_score_traj(
                    stacked, self.valid_scores[i], self.valid_bins[i],
                    self.num_bins_d, self.missing_is_nan_d,
                    num_class=kcls)
                if lead is not None:
                    traj = jnp.concatenate([lead, traj])
                self.valid_scores[i] = fin
                trajs.append(traj)
            self._fused_valid_traj = trajs
        self.iter_ += k
        # the block's trees as the tree list wants them, its last leaf
        # count and what its trees ran: ONE program, enqueued right
        # behind the block, so finalize_block finds them ready the
        # moment the block is
        from .fused import split_block
        views, pending, decides, ran = split_block(model_trees, ran)
        # lagged stall poll (see train_one_iter): a stalled model keeps
        # producing all-zero trees, so checking a batch's last tree
        # roughly every _stop_poll_every ITERATIONS is enough — poll
        # when this batch crossed a poll boundary, whatever its size
        crossed = (self.iter_ // self._stop_poll_every !=
                   (self.iter_ - k) // self._stop_poll_every)
        stop_hint = False
        if not self._exact_stop_poll and crossed:
            # the newest count that is already there: the last block's
            # when the host has waited for it (valid sets, train_many),
            # the one before when the last block is still running. No
            # block in flight is waited for, so under the pipelined
            # executor a stalled model trains one more block of
            # constant trees before the stop is seen
            seen = next((c for c in (self._pending_nleaves,
                                     self._earlier_nleaves)
                         if c is not None and self._is_ready(c)), None)
            if seen is not None:
                with span("entry.wait_device", iter=iter0, k=k,
                          what="stop_poll"):
                    stop_hint = int(seen) <= 1
        self._note_nleaves(pending)
        return {"mode": "fused", "trees": views, "k": k, "kcls": kcls,
                "stop": stop_hint, "iter": iter0, "decides": decides,
                "counters": ran,
                "in_flight": dispatch.attrs["in_flight"]}

    @staticmethod
    def _is_ready(arr) -> bool:
        """Whether a device value has been computed; waits for
        nothing."""
        return bool(arr.is_ready())

    def _note_nleaves(self, pending) -> None:
        """A fresh tree's leaf count (a device scalar) for the lagged
        stop poll, its copy to the host started now."""
        try:
            pending.copy_to_host_async()
        except Exception:
            pass
        self._earlier_nleaves = self._pending_nleaves
        self._pending_nleaves = pending

    @staticmethod
    def _add_bias_to_first(stacked: TreeArrays, bias: List[float],
                           kcls: int) -> TreeArrays:
        """Stacked block with each class's constant folded into the
        leaves of its first tree (train_one_iter's AddBias)."""
        lv = stacked.leaf_value
        for cls, b in enumerate(bias):
            if abs(b) <= 1e-35:
                continue
            at = (0, cls) if kcls > 1 else (0,)
            lv = lv.at[at].set(jnp.where(stacked.split_feature[at] < 0,
                                         lv[at] + b, lv[at]))
        return stacked._replace(leaf_value=lv)

    def _constant_tree(self, value: float) -> TreeArrays:
        m1 = 2 * self.config.num_leaves - 1 + 1
        zf = jnp.zeros(m1, jnp.float32)
        zi = jnp.zeros(m1, jnp.int32)
        zb = jnp.zeros(m1, bool)
        return TreeArrays(
            split_feature=jnp.full(m1, -1, jnp.int32), threshold_bin=zi,
            default_left=zb, is_cat=zb,
            cat_bitset=jnp.zeros((m1, (self.bmax + 31) // 32), jnp.uint32),
            left=jnp.full(m1, -1, jnp.int32),
            right=jnp.full(m1, -1, jnp.int32),
            parent=jnp.full(m1, -1, jnp.int32),
            leaf_value=zf.at[0].set(value), sum_grad=zf, sum_hess=zf,
            count=zf, gain=zf, depth=zi, is_leaf=zb.at[0].set(True),
            num_nodes=jnp.asarray(1, jnp.int32),
            num_leaves=jnp.asarray(1, jnp.int32))

    def _take_initial_bias(self) -> List[float]:
        """Per-class boost_from_average score for whoever builds tree 0
        to fold into its leaves (AddBias, gbdt.cpp:416-417). The scores
        get the constant exactly once: a fused block that started at
        iteration 0 and then fell back leaves the values it already
        applied here for train_one_iter to pick up."""
        stash = getattr(self, "_initial_bias", None)
        self._initial_bias = None
        if stash is not None:
            return stash
        return [self._boost_from_average(cls)
                for cls in range(self.num_tree_per_iteration)]

    def _boost_from_average(self, cls: int) -> float:
        cfg = self.config
        if (self.trees or self._boosted_from_average[cls] or
                self._has_init_score or self.objective is None or
                not cfg.boost_from_average):
            return 0.0
        init = self.objective.boost_from_score(cls)
        if getattr(self, "_nproc", 1) > 1:
            # reference gbdt.cpp:335-344: init scores are averaged across
            # machines (GlobalSyncUpByMean), each rank having computed
            # from its local partition
            from ..parallel.comm import guarded_allgather
            init = float(np.mean(guarded_allgather(
                np.float32(init), label="boost_from_average")))
        if abs(init) > 1e-35:
            self._add_const_score(init, cls)
            Log.info("Start training from score %f", init)
            self._boosted_from_average[cls] = True
            return init
        return 0.0

    def _add_const_score(self, value: float, cls: int) -> None:
        k = self.num_tree_per_iteration
        if k == 1:
            self.train_score = self.train_score + value
            for i in range(len(self.valid_sets)):
                self.valid_scores[i] = self.valid_scores[i] + value
        else:
            self.train_score = self.train_score.at[:, cls].add(value)
            for i in range(len(self.valid_sets)):
                self.valid_scores[i] = \
                    self.valid_scores[i].at[:, cls].add(value)

    def _lin(self, idx: int):
        """Linear leaf model of tree idx (None for constant leaves)."""
        return self.linear_models[idx] \
            if idx < len(self.linear_models) else None

    def _tree_values(self, tree: TreeArrays, lin, bins: jax.Array,
                     raw, efb=None) -> jax.Array:
        """Per-row outputs of one tree on a binned matrix (linear-aware).
        `efb` must be passed iff `bins` is the bundled training matrix
        (validation matrices stay unbundled)."""
        if lin is None:
            return predict_binned_tree(tree, bins, self.num_bins_d,
                                       self.missing_is_nan_d, efb)
        from ..learner.linear import linear_leaf_values
        from ..learner.predict import leaf_node_tree
        leaf = leaf_node_tree(tree, bins, self.num_bins_d,
                              self.missing_is_nan_d, efb)
        return linear_leaf_values(tree, lin, leaf, raw)

    def _update_score(self, tree: TreeArrays, row_node: jax.Array,
                      cls: int, lin=None) -> None:
        """Learner-side score update: leaf value via row->node gather
        (score_updater.hpp:21-110 AddScore(tree_learner) equivalent)."""
        if lin is None:
            if self._learner.serial_mxu:
                # per-row gathers are the slow op on a TPU; the one-hot
                # matmul lookup kernel keeps the lookup on the MXU
                from ..learner.histogram_mxu import node_values_mxu
                vals = node_values_mxu(
                    row_node, tree.leaf_value,
                    interpret=getattr(self, "_mxu_interpret", False))
            else:
                # an XLA gather, about 10 ns a row on a v5e where the
                # one-hot lookup costs 0.6 (26 ms a tree at 2,625,000
                # rows a chip: PERF.md, PR 36). The sharded MXU learner
                # pays it only off the fused block (a spec
                # _sharded_fused_ok refuses, a block that degraded)
                vals = tree.leaf_value[row_node]
        else:
            from ..learner.linear import linear_leaf_values
            vals = linear_leaf_values(tree, lin, row_node, self.raw)
        k = self.num_tree_per_iteration
        if k == 1:
            self.train_score = self.train_score + vals
        else:
            self.train_score = self.train_score.at[:, cls].add(vals)
        for i in range(len(self.valid_sets)):
            vvals = self._tree_values(tree, lin, self.valid_bins[i],
                                      self.valid_raws[i]
                                      if self.valid_raws else None)
            if k == 1:
                self.valid_scores[i] = self.valid_scores[i] + vvals
            else:
                self.valid_scores[i] = \
                    self.valid_scores[i].at[:, cls].add(vvals)

    # ------------------------------------------------------------------
    def rollback_one_iter(self) -> None:
        """Drop the last iteration (gbdt.cpp:451-467)."""
        if self.iter_ <= self._iter_offset:
            # nothing of this instance's own to roll back (checkpointed
            # base iterations are immutable)
            return
        k = self.num_tree_per_iteration
        for cls in range(k):
            tree = self.trees.pop()
            cls_id = self.tree_class.pop()
            lin = self.linear_models.pop() if self.linear_models else None
            if lin is None:
                vals = self._predict_train_rows(tree)
            else:
                vals = self._tree_values(tree, lin,
                                         self._train_bins_unpacked(),
                                         self.raw, self._efb)[:self.num_data]
            if k == 1:
                self.train_score = self.train_score - vals
            else:
                self.train_score = self.train_score.at[:, cls_id].add(-vals)
            for i in range(len(self.valid_sets)):
                vv = self._tree_values(tree, lin, self.valid_bins[i],
                                       self.valid_raws[i])
                if k == 1:
                    self.valid_scores[i] = self.valid_scores[i] - vv
                else:
                    self.valid_scores[i] = \
                        self.valid_scores[i].at[:, cls_id].add(-vv)
        self.iter_ -= 1

    # ------------------------------------------------------------------
    def eval_train(self) -> Dict[str, float]:
        return self._eval(self.train_score, self.train_metrics,
                          self.train_set)

    def eval_valid(self, i: int) -> Dict[str, float]:
        return self._eval(self.valid_scores[i], self.valid_metrics[i],
                          self.valid_sets[i])

    def _eval(self, score: jax.Array, metrics: List[Metric],
              ds: BinnedDataset) -> Dict[str, float]:
        out: Dict[str, float] = {}
        if not metrics:
            return out
        score_np = np.asarray(score)
        convert = (lambda s: np.asarray(
            self.objective.convert_output(jnp.asarray(s)))) \
            if self.objective is not None else None
        for m in metrics:
            if hasattr(m, "evaluate_multi"):
                out.update(m.evaluate_multi(score_np))
            else:
                out[m.name] = m.evaluate(score_np, convert)
        return out

    # ------------------------------------------------------------------
    @property
    def num_iterations_trained(self) -> int:
        return self.iter_

    def current_iteration(self) -> int:
        return self.iter_ - self._iter_offset

    # ------------------------------------------------------------------
    # checkpoint/resume (reliability/checkpoint.py bundles)
    def training_state(self):
        """(json-state, arrays) beyond what the model text carries:
        exact f32 scores, RNG stream position, mid-period bagging mask,
        boost-from-average flags and the lagged stop-poll hint. With
        these restored, replaying iterations k..N reproduces an
        uninterrupted run bit-for-bit (fold-in RNG draws key off the
        absolute iter_, which resume preserves)."""
        state = {
            "boosting": self.config.boosting,
            "num_class": self.num_class,
            "shrinkage_rate": float(self.shrinkage_rate),
            "boosted_from_average": [bool(b) for b in
                                     self._boosted_from_average],
            "has_init_score": bool(self._has_init_score),
            "num_valid": len(getattr(self, "valid_scores", []) or []),
        }
        if self._pending_nleaves is not None:
            # host sync is fine here — checkpointing is already IO-bound
            state["pending_nleaves"] = int(self._pending_nleaves)
        arrays = {
            "train_score": np.asarray(self.train_score),
            "rng_key": np.asarray(self._rng_key),
            "bag_mask": np.asarray(self._bag_mask),
        }
        for i, s in enumerate(getattr(self, "valid_scores", []) or []):
            arrays[f"valid_score_{i}"] = np.asarray(s)
        return state, arrays

    def restore_training_state(self, iteration: int, state: Dict,
                               arrays: Dict) -> None:
        """Continue a checkpointed run: `iteration` boosting rounds live
        in the attached base model; this instance trains the rest from
        the exact device state the killed run held."""
        cfg = self.config
        if int(state.get("num_class", self.num_class)) != self.num_class:
            raise LightGBMError(
                "checkpoint num_class=%s does not match num_class=%d" %
                (state.get("num_class"), self.num_class))
        if state.get("boosting", cfg.boosting) != cfg.boosting:
            raise LightGBMError(
                "checkpoint boosting=%r does not match boosting=%r" %
                (state.get("boosting"), cfg.boosting))
        if cfg.boosting not in ("gbdt", "goss"):
            Log.warning(
                "resume is exact for gbdt/goss boosting; %r resumes "
                "best-effort (sampling state beyond the RNG key is "
                "rebuilt)" % cfg.boosting)
        if getattr(self, "_cegb_cfg", None) is not None:
            Log.warning(
                "cegb feature-used state is not checkpointed; resumed "
                "CEGB penalties restart from a clean slate")
        if state.get("reshard_total_rows") is not None:
            arrays = self._reshard_restore_arrays(
                int(state["reshard_total_rows"]), arrays)
        score = jnp.asarray(arrays["train_score"])
        if score.shape != self.train_score.shape:
            raise LightGBMError(
                "checkpoint train_score shape %s does not match the "
                "training set (%s) — resume needs the same dataset" %
                (score.shape, self.train_score.shape))
        self.iter_ = int(iteration)
        self._iter_offset = int(iteration)
        self.train_score = score
        self._rng_key = jnp.asarray(arrays["rng_key"])
        if "bag_mask" in arrays:
            self._bag_mask = jnp.asarray(arrays["bag_mask"])
        self.shrinkage_rate = float(
            state.get("shrinkage_rate", self.shrinkage_rate))
        bfa = state.get("boosted_from_average")
        if bfa is not None:
            self._boosted_from_average = [bool(b) for b in bfa]
        if state.get("pending_nleaves") is not None:
            self._pending_nleaves = jnp.asarray(
                int(state["pending_nleaves"]), jnp.int32)
        for i in range(len(getattr(self, "valid_scores", []) or [])):
            key = f"valid_score_{i}"
            if key in arrays:
                self.valid_scores[i] = jnp.asarray(arrays[key])

    def _reshard_restore_arrays(self, total_rows: int,
                                arrays: Dict) -> Dict:
        """Elastic resume (distributed/elastic.py): the resharded
        loader handed every rank the GLOBAL row-order arrays of a
        bundle written by a DIFFERENT world size; slice this rank's
        contiguous row block so the shape check below sees the same
        local arrays an uninterrupted run at this world would hold.
        Valid sets are row-partitioned too but on their own totals, so
        each gets its own offset exchange."""
        from ..distributed.elastic import reshard_offsets, reshard_slice
        local = int(self.num_data)
        offset, tot = reshard_offsets(local, label="elastic_reshard")
        if tot != int(total_rows):
            raise LightGBMError(
                "elastic reshard: checkpoint holds %d global training "
                "rows but the new world's partitions sum to %d — the "
                "reincarnated run loaded a different dataset" %
                (int(total_rows), tot))
        valid = {k: v for k, v in arrays.items()
                 if k.startswith("valid_score_")}
        out = reshard_slice(
            {k: v for k, v in arrays.items() if k not in valid},
            offset, local, tot)
        for i, s in enumerate(getattr(self, "valid_scores", []) or []):
            key = f"valid_score_{i}"
            if key not in valid:
                continue
            varr = np.asarray(valid[key])
            vlocal = int(np.asarray(s).shape[0])
            voff, vtot = reshard_offsets(
                vlocal, label="elastic_reshard_valid")
            if varr.ndim and varr.shape[0] == vtot:
                varr = varr[voff:voff + vlocal]
            out[key] = varr
        return out


def create_boosting(config: Config, train_set, objective, metrics):
    """Factory (reference Boosting::CreateBoosting, boosting.cpp:38-58)."""
    from .dart import DART
    from .rf import RF
    if config.boosting in ("gbdt", "goss"):
        return GBDT(config, train_set, objective, metrics)
    if config.boosting == "dart":
        return DART(config, train_set, objective, metrics)
    if config.boosting == "rf":
        return RF(config, train_set, objective, metrics)
    Log.fatal("Unknown boosting type %s", config.boosting)
