"""train() / cv() entry points (reference python-package/lightgbm/engine.py).

Same callback protocol and return types as the reference engine.py:27 train
and :393 cv, including early stopping via EarlyStopException and
`cv_agg` aggregated results.
"""

from __future__ import annotations

import collections
import contextlib
import copy
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset
from .config import PARAM_ALIASES
from .observability import span
from .utils.log import Log

__all__ = ["train", "cv", "CVBooster"]


def _resolve_num_boost_round(params: Dict[str, Any],
                             num_boost_round: int) -> int:
    for alias in ("num_iterations", "num_iteration", "n_iter", "num_tree",
                  "num_trees", "num_round", "num_rounds", "nrounds",
                  "num_boost_round", "n_estimators", "max_iter"):
        if alias in params:
            return int(params.pop(alias))
    return num_boost_round


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj=None, feval=None, init_model=None,
          feature_name="auto", categorical_feature="auto",
          keep_training_booster: bool = False,
          callbacks: Optional[List] = None,
          resume_from: Optional[str] = None) -> Booster:
    # boosting.init: from here to the first dispatch (Booster and GBDT
    # construction, placing the bins, the objective's set-up, valid
    # sets, a resume); _train ends it once the booster is ready, and an
    # exception on the way ends it here
    with contextlib.ExitStack() as init:
        init_span = init.enter_context(span("boosting.init"))

        def set_up_done(booster: Booster) -> None:
            gb = booster.gbdt
            init_span.attrs.update(
                rows=int(getattr(gb, "num_data", 0) or 0),
                devices=int(getattr(getattr(gb, "mesh", None), "size", 1)
                            or 1))
            init.close()

        return _train(set_up_done, params, train_set, num_boost_round,
                      valid_sets, valid_names, fobj, feval, init_model,
                      feature_name, categorical_feature, callbacks,
                      resume_from)


def _train(set_up_done, params, train_set, num_boost_round, valid_sets,
           valid_names, fobj, feval, init_model, feature_name,
           categorical_feature, callbacks, resume_from) -> Booster:
    params = copy.deepcopy(params or {})
    num_boost_round = _resolve_num_boost_round(params, num_boost_round)
    from .streaming import ChunkSource
    if isinstance(train_set, ChunkSource):
        # out-of-core source handed straight to train(): wrap it so
        # Dataset.construct routes through the two-pass streaming loader
        train_set = Dataset(train_set, params=dict(params))
    if valid_sets is not None:
        vs = valid_sets if isinstance(valid_sets, list) else [valid_sets]
        valid_sets = [Dataset(v, reference=train_set, params=dict(params))
                      if isinstance(v, ChunkSource) else v for v in vs]
    resume_state = None
    if resume_from is not None:
        # kill-and-resume (docs/Reliability.md): restore the exact
        # training state from a checkpoint bundle. Unlike init_model
        # continuation below — which re-seeds init scores through a
        # host predict and restarts the RNG stream — resume restores
        # the checkpointed f32 scores / RNG / bagging state verbatim,
        # so the finished model is byte-identical to an uninterrupted
        # run. num_boost_round stays the TOTAL iteration count.
        if init_model is not None:
            raise ValueError("resume_from and init_model are exclusive: "
                             "a checkpoint bundle already carries its model")
        from .reliability.checkpoint import (load_checkpoint,
                                             load_checkpoint_resharded,
                                             bundle_world)
        # under multihost (setup_multihost ran before train, like the
        # reference CLI) each rank loads its own shard of a coordinated
        # bundle; world validation rejects topology changes — unless
        # elastic_resize is on, where a world mismatch is exactly the
        # reincarnation path: every rank reads ALL shards of the old
        # world's bundle and re-slices its own contiguous row block at
        # restore time (docs/Distributed.md Elasticity)
        import jax
        try:
            _world = jax.process_count()
        except RuntimeError:
            _world = 1
        _elastic = bool(params.get("elastic_resize", False))
        _bundle_world = bundle_world(resume_from) if _elastic else None
        if _bundle_world is not None and _bundle_world != _world:
            resume_state = load_checkpoint_resharded(resume_from)
        elif _world > 1:
            resume_state = load_checkpoint(
                resume_from, rank=jax.process_index(), world=_world)
        else:
            resume_state = load_checkpoint(resume_from)
        init_model = None
    if fobj is not None:
        params["objective"] = "none"
    first_metric_only = bool(params.get("first_metric_only", False))

    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    base_model = None
    if init_model is not None:
        # continued training (reference: input_model seeds init scores,
        # application.cpp:91-94; the final model keeps the old trees,
        # Python Booster(model_file=...) + train). Scores are seeded with
        # the base model's raw predictions BEFORE dataset construction
        # (raw features are still present), and the base trees are merged
        # into the final model so predict/save include them.
        #
        # Bounded-divergence caveat: when a PREVIOUS train() call ended
        # in a mid-block early stop (fused block path below), that
        # booster's train_score carries the rollback's add-then-subtract
        # ULP residue — at most one f32 rounding per rolled-back tree.
        # Continuing from it trains the first new trees against
        # gradients of those scores, so a continued model can diverge
        # from a never-stopped reference by that same bounded residue;
        # seeding here via base-model PREDICTIONS (recomputed, not the
        # stored train_score) keeps the divergence to the residue itself
        # rather than compounding it.
        base_model = init_model if isinstance(init_model, Booster) else \
            Booster(model_file=init_model)

        def _seed(ds):
            if ds is None:
                return
            existing = ds.init_score
            if existing is None and ds._binned is not None:
                existing = ds._binned.metadata.init_score
            if existing is not None and \
                    not getattr(ds, "_seeded_init_score", False):
                # base trees are prepended to the final model, so an extra
                # USER init_score would double-count — refuse rather than
                # silently produce shifted predictions. Scores that _seed
                # itself wrote on a previous train() are overwritten below
                # (iterative continuation reuses the same Dataset).
                raise ValueError(
                    "cannot combine init_model with a dataset that "
                    "already has init_score")
            if ds.data is None:
                raise ValueError(
                    "init_model continuation needs raw data on the "
                    "datasets; pass free_raw_data=False or un-constructed "
                    "Datasets")
            if isinstance(ds.data, ChunkSource):
                # continued boosting over a streamed dataset (the
                # continuous loop's refresh path): the raw matrix never
                # materializes host-side, so seed init scores chunk by
                # chunk through a fresh pass of the restartable source
                # — row order matches the loader's pass-2 binning order
                parts = [base_model.predict(X, raw_score=True)
                         for X, _ in ds.data.chunks()]
                if not parts:
                    raise ValueError(
                        "init_model continuation over an exhausted "
                        "stream: the source yielded no chunks to seed "
                        "init scores from")
                init = np.concatenate(parts, axis=0)
            else:
                init = base_model.predict(ds.data, raw_score=True)
            ds.init_score = init
            ds._seeded_init_score = True
            if ds._binned is not None:
                # dataset already constructed: construct() won't re-read
                # init_score, so push it into the binned metadata directly
                ds._binned.metadata.init_score = \
                    np.asarray(init, np.float32)

        _seed(train_set)
        if valid_sets is not None:
            vs = valid_sets if isinstance(valid_sets, list) else [valid_sets]
            for vd in vs:
                if isinstance(vd, Dataset) and vd is not train_set:
                    _seed(vd)
    else:
        # a plain train() after a continued one must not inherit the seed
        # the previous call wrote into this Dataset
        def _unseed(ds):
            if ds is not None and getattr(ds, "_seeded_init_score", False):
                ds.init_score = None
                ds._seeded_init_score = False
                if ds._binned is not None:
                    ds._binned.metadata.init_score = None

        _unseed(train_set)
        if valid_sets is not None:
            vs = valid_sets if isinstance(valid_sets, list) else [valid_sets]
            for vd in vs:
                if isinstance(vd, Dataset):
                    _unseed(vd)

    booster = Booster(params=params, train_set=train_set)
    if resume_state is not None:
        # the checkpointed model's trees ride in front of the resumed
        # ones exactly like continued training, but WITHOUT init-score
        # seeding: the restored train_score already contains their
        # contribution in the exact f32 bits the killed run held
        base_model = Booster(model_str=resume_state.model_str)
    if base_model is not None:
        booster._base_model = base_model

    is_valid_contain_train = False
    train_data_name = "training"
    reduced_valid_sets = []
    name_valid_sets = []
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        if isinstance(valid_names, str):
            valid_names = [valid_names]
        for i, valid_data in enumerate(valid_sets):
            if valid_data is train_set:
                is_valid_contain_train = True
                if valid_names is not None:
                    train_data_name = valid_names[i]
                continue
            if not isinstance(valid_data, Dataset):
                raise TypeError("Training only accepts Dataset object")
            reduced_valid_sets.append(valid_data)
            name_valid_sets.append(valid_names[i] if valid_names is not None
                                   else f"valid_{i}")
    booster.train_data_name = train_data_name
    for vd, name in zip(reduced_valid_sets, name_valid_sets):
        booster.add_valid(vd, name)

    start_iter = 0
    if resume_state is not None:
        booster._restore_training_state(resume_state)
        start_iter = resume_state.iteration
        Log.info(f"resuming training from checkpoint "
                 f"{resume_state.path!r} at iteration {start_iter}")

    cbs = set(callbacks or [])
    if params.get("early_stopping_round", 0) and \
            int(params["early_stopping_round"]) > 0:
        cbs.add(callback_mod.early_stopping(
            int(params["early_stopping_round"]), first_metric_only))
    cfg = booster.config
    if getattr(cfg, "checkpoint_period", 0) > 0 and cfg.checkpoint_dir \
            and not any(getattr(cb, "is_checkpoint", False) for cb in cbs):
        cbs.add(callback_mod.checkpoint(
            cfg.checkpoint_period, cfg.checkpoint_dir, cfg.checkpoint_keep))
    if resume_state is not None:
        history = resume_state.state.get("eval_history")
        if history:
            for cb in cbs:
                if hasattr(cb, "_seed_history"):
                    cb._seed_history(history)
    callbacks_before = {cb for cb in cbs
                        if getattr(cb, "before_iteration", False)}
    callbacks_after = cbs - callbacks_before
    callbacks_before = sorted(callbacks_before,
                              key=lambda cb: getattr(cb, "order", 0))
    callbacks_after = sorted(callbacks_after,
                             key=lambda cb: getattr(cb, "order", 0))

    booster.best_iteration = -1
    # block dispatch (TPU host-boundary amortization): when nothing in
    # the loop needs a per-iteration host boundary — no before_iteration
    # callbacks, no custom fobj/feval, no training-set metrics — train
    # fused_block_size iterations per device dispatch and run the
    # per-iteration metric/callback protocol from the block's valid-score
    # trajectory (GBDT.train_many). Results are identical to b=1: every
    # iteration is still evaluated, and an early stop mid-block rolls
    # the extra trees back before propagating. (Exception: the
    # row-sharded fused path may carry 1-ulp score rounding vs b=1 —
    # see distributed/fused.py; it is deterministic for any block size.)
    block = int(getattr(booster.config, "fused_block_size", 1) or 1)
    # after-callbacks must not read model state: at inner iteration j
    # the booster already holds the whole block's trees. The library's
    # own eval-driven callbacks are marked block_safe; any custom
    # callback forces the per-iteration cadence.
    cbs_block_safe = all(getattr(cb, "block_safe", False)
                         for cb in callbacks_after)
    use_blocks = (block > 1 and fobj is None and feval is None
                  and not callbacks_before and cbs_block_safe
                  and not is_valid_contain_train
                  and getattr(booster.gbdt, "_fused_eligible",
                              lambda: False)())
    # pipelined executor (pipeline/executor.py): same block dispatch,
    # but host work (tree unpacking, scheduling, observability) overlaps
    # the next block's device compute, and valid metrics can reduce
    # in-graph. Bit-identical models either way — pipeline=false keeps
    # this loop as the parity oracle.
    use_pipeline = use_blocks and bool(getattr(cfg, "pipeline", False))

    def _eval_at(i):
        evaluation_result_list = []
        if valid_sets is not None or feval is not None:
            if is_valid_contain_train:
                evaluation_result_list.extend(booster.eval_train(feval))
            if reduced_valid_sets:
                evaluation_result_list.extend(booster.eval_valid(feval))
        for cb in callbacks_after:
            cb(callback_mod.CallbackEnv(
                model=booster, params=params, iteration=i,
                begin_iteration=start_iter, end_iteration=num_boost_round,
                evaluation_result_list=evaluation_result_list))
        return evaluation_result_list

    set_up_done(booster)
    evaluation_result_list = []
    try:
        if use_pipeline and start_iter < num_boost_round:
            from .pipeline import run_pipelined

            def _run_cbs(i, evlist):
                for cb in callbacks_after:
                    cb(callback_mod.CallbackEnv(
                        model=booster, params=params, iteration=i,
                        begin_iteration=start_iter,
                        end_iteration=num_boost_round,
                        evaluation_result_list=evlist))

            es_rounds = int(params.get("early_stopping_round", 0) or 0)
            evaluation_result_list = run_pipelined(
                booster, start_iter=start_iter,
                num_boost_round=num_boost_round, base_block=block,
                run_callbacks=_run_cbs,
                has_valid=bool(reduced_valid_sets),
                stopping_rounds=es_rounds)
            i = num_boost_round   # fully trained; the loop below no-ops
        else:
            i = start_iter
        while i < num_boost_round:
            b = min(block, num_boost_round - i) if use_blocks else 1
            if b > 1:
                booster.update_batch(b)
                gb = booster.gbdt
                traj = getattr(gb, "_fused_valid_traj", None)
                if traj is not None and reduced_valid_sets:
                    # evaluate every inner iteration from the trajectory
                    # (the last point IS the final score, so valid
                    # scores end the loop in their live state)
                    for j in range(b):
                        for vi in range(len(traj)):
                            gb.valid_scores[vi] = traj[vi][j]
                        try:
                            evaluation_result_list = _eval_at(i + j)
                        except callback_mod.EarlyStopException:
                            # restore block-final scores, roll the
                            # post-stop trees back, then pin the valid
                            # scores to the exact trajectory point (the
                            # rollback's add-then-subtract would leave
                            # ULP-level residue; train_score keeps the
                            # subtractive form — the booster is normally
                            # returned at this point, and the residue is
                            # bounded by one rounding per rolled tree;
                            # a later train(init_model=this_booster)
                            # inherits that bounded divergence — see the
                            # continued-training note above)
                            for vi in range(len(traj)):
                                gb.valid_scores[vi] = traj[vi][b - 1]
                            for _ in range(b - 1 - j):
                                booster.rollback_one_iter()
                            for vi in range(len(traj)):
                                gb.valid_scores[vi] = traj[vi][j]
                            raise
                        except BaseException:
                            # any other exit (custom abort,
                            # KeyboardInterrupt): leave the booster
                            # consistent — trees hold the full block, so
                            # scores must too
                            for vi in range(len(traj)):
                                gb.valid_scores[vi] = traj[vi][b - 1]
                            raise
                elif reduced_valid_sets:
                    # belt-and-braces, believed unreachable: train_many
                    # seals a full trajectory on every completing path
                    # (fused, fault fallback, ineligible, stalled).
                    # Were it ever to fire, evaluation degrades to
                    # block-end cadence rather than reading stale
                    # intermediate valid scores.
                    evaluation_result_list = _eval_at(i + b - 1)
                else:
                    # no valid sets: no eval work, but user callbacks
                    # still fire once per iteration
                    for j in range(b):
                        evaluation_result_list = _eval_at(i + j)
                i += b
                continue
            # one tree (one per class): its phases open inside
            # GBDT.train_one_iter as children of this span
            with span("entry.tree", iter=i):
                if callbacks_before:
                    with span("entry.callbacks", iter=i, when="before"):
                        for cb in callbacks_before:
                            cb(callback_mod.CallbackEnv(
                                model=booster, params=params, iteration=i,
                                begin_iteration=start_iter,
                                end_iteration=num_boost_round,
                                evaluation_result_list=None))
                booster.update(fobj=fobj)
                with span("entry.callbacks", iter=i):
                    evaluation_result_list = _eval_at(i)
            i += 1
    except callback_mod.EarlyStopException as es:
        # with continued training, iteration indexing covers the merged
        # model (base trees first), matching predict(num_iteration=...).
        # On resume the loop index is already absolute over the merged
        # model, so there is no base offset to add.
        base_iters = base_model.current_iteration() \
            if base_model is not None and resume_state is None else 0
        booster.best_iteration = base_iters + es.best_iteration + 1
        evaluation_result_list = es.best_score
    except Exception as exc:
        # unhandled training failure: leave a flight-recorder bundle
        # (when a bundle directory is configured) before propagating
        from .observability.flightrec import recorder as _flightrec
        _flightrec.record_exception("engine.train", exc)
        _flightrec.flush("exception")
        raise
    if booster.best_iteration < 0:
        booster.best_iteration = booster.current_iteration()
    try:
        booster.best_score = collections.defaultdict(collections.OrderedDict)
        for data_name, eval_name, score, _ in evaluation_result_list or []:
            booster.best_score[data_name][eval_name] = score
    except Exception:
        pass
    return booster


class CVBooster:
    """Ensemble of per-fold boosters (reference engine.py:298)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def _append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold: int, params,
                  seed: int, stratified: bool, shuffle: bool):
    full_data.construct()
    num_data = full_data.num_data()
    if folds is not None:
        if not hasattr(folds, "__iter__") and not hasattr(folds, "split"):
            raise AttributeError(
                "folds should be a generator or iterator of (train_idx, "
                "test_idx) tuples or scikit-learn splitter object")
        if hasattr(folds, "split"):
            group_info = full_data.get_group()
            if group_info is not None:
                group_info = np.asarray(group_info, np.int32)
                flatted_group = np.repeat(
                    range(len(group_info)), repeats=group_info)
            else:
                flatted_group = np.zeros(num_data, np.int32)
            folds = folds.split(X=np.empty(num_data),
                                y=full_data.get_label(),
                                groups=flatted_group)
    else:
        rng = np.random.RandomState(seed)
        if stratified:
            y = np.asarray(full_data.get_label())
            order = np.argsort(y, kind="stable")
            if shuffle:
                # shuffle within class for stratification
                folds_assign = np.empty(num_data, np.int32)
                folds_assign[order] = np.arange(num_data) % nfold
                perm_in = rng.permutation  # noqa: F841
            else:
                folds_assign = np.empty(num_data, np.int32)
                folds_assign[order] = np.arange(num_data) % nfold
            folds = [(np.where(folds_assign != k)[0],
                      np.where(folds_assign == k)[0]) for k in range(nfold)]
        else:
            idx = rng.permutation(num_data) if shuffle \
                else np.arange(num_data)
            folds = [(np.concatenate([idx[:k * num_data // nfold],
                                      idx[(k + 1) * num_data // nfold:]]),
                      idx[k * num_data // nfold:
                          (k + 1) * num_data // nfold])
                     for k in range(nfold)]
    ret = []
    for train_idx, test_idx in folds:
        tr = np.sort(np.asarray(train_idx))
        te = np.sort(np.asarray(test_idx))
        train_sub = full_data.subset(tr, params)
        valid_sub = full_data.subset(te, params)
        ret.append((train_sub, valid_sub, tr, te))
    return ret


def _agg_cv_result(raw_results):
    """Collapse per-fold eval lists into cv_agg entries.

    Each fold yields (data_name, metric_name, value, higher_better)
    tuples; folds are aggregated per "data_name metric_name" key into
    ("cv_agg", key, mean, higher_better, std), preserving first-seen
    key order (the reference engine's cv display contract)."""
    by_key: Dict[str, Tuple[bool, List[float]]] = {}
    for fold in raw_results:
        for data_name, metric_name, value, higher_better, *_ in fold:
            slot = by_key.setdefault(f"{data_name} {metric_name}",
                                     (higher_better, []))
            slot[1].append(value)
    return [("cv_agg", key, float(np.mean(vals)), hb, float(np.std(vals)))
            for key, (hb, vals) in by_key.items()]


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True,
       shuffle: bool = True, metrics=None, fobj=None, feval=None,
       init_model=None, feature_name="auto", categorical_feature="auto",
       fpreproc=None, seed: int = 0, callbacks=None, eval_train_metric=False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    params = copy.deepcopy(params or {})
    num_boost_round = _resolve_num_boost_round(params, num_boost_round)
    init_full = None
    if init_model is not None:
        # continuation: the base model's raw predictions seed every
        # fold's init scores (reference cv: train_set._set_predictor,
        # engine.py:548-562)
        base_model = init_model if isinstance(init_model, Booster) else \
            Booster(model_file=init_model)
        if train_set.data is None or isinstance(train_set.data, str):
            raise ValueError(
                "cv(init_model=...) needs in-memory raw data on the "
                "dataset; pass free_raw_data=False with an array/frame "
                "(file-backed Datasets are not supported here)")
        existing = train_set.init_score
        if existing is None and train_set._binned is not None:
            existing = train_set._binned.metadata.init_score
        if existing is not None:
            # same contract as train(): base trees' predictions become
            # the init scores, so a user init_score would double-count
            raise ValueError(
                "cannot combine init_model with a dataset that already "
                "has init_score")
        init_full = np.asarray(
            base_model.predict(train_set.data, raw_score=True), np.float64)
    if fobj is not None:
        params["objective"] = "none"
    if metrics:
        params["metric"] = metrics
    if params.get("objective", "") in ("lambdarank", "rank_xendcg") or \
            train_set.group is not None:
        stratified = False

    results = collections.defaultdict(list)
    cvfolds = _make_n_folds(train_set, folds, nfold, params, seed,
                            stratified, shuffle)
    cvbooster = CVBooster()
    boosters = []
    for train_sub, valid_sub, tr_idx, te_idx in cvfolds:
        if init_full is not None:
            # subsets are already constructed; push into binned metadata
            # (the path Booster reads init scores from)
            for sub, idx in ((train_sub, tr_idx), (valid_sub, te_idx)):
                sub.init_score = init_full[idx]
                sub._binned.metadata.init_score = np.ascontiguousarray(
                    init_full[idx], np.float64)
        if fpreproc is not None:
            train_sub, valid_sub, params = fpreproc(
                train_sub, valid_sub, params.copy())
        bst = Booster(params=params, train_set=train_sub)
        bst.add_valid(valid_sub, "valid")
        boosters.append(bst)
        cvbooster._append(bst)

    cbs = set(callbacks or [])
    if params.get("early_stopping_round", 0) and \
            int(params["early_stopping_round"]) > 0:
        cbs.add(callback_mod.early_stopping(
            int(params["early_stopping_round"]),
            bool(params.get("first_metric_only", False))))
    callbacks_before = sorted(
        (cb for cb in cbs if getattr(cb, "before_iteration", False)),
        key=lambda cb: getattr(cb, "order", 0))
    callbacks_after = sorted(
        (cb for cb in cbs if not getattr(cb, "before_iteration", False)),
        key=lambda cb: getattr(cb, "order", 0))

    try:
        for i in range(num_boost_round):
            raw_results = []
            for bst in boosters:
                for cb in callbacks_before:
                    cb(callback_mod.CallbackEnv(
                        model=bst, params=params, iteration=i,
                        begin_iteration=0, end_iteration=num_boost_round,
                        evaluation_result_list=None))
                bst.update(fobj=fobj)
                res = bst.eval_valid(feval)
                if eval_train_metric:
                    res = bst.eval_train(feval) + res
                raw_results.append(res)
            agg = _agg_cv_result(raw_results)
            for _, key, mean, _, std in agg:
                results[key + "-mean"].append(mean)
                results[key + "-stdv"].append(std)
            for cb in callbacks_after:
                cb(callback_mod.CallbackEnv(
                    model=cvbooster, params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=agg))
    except callback_mod.EarlyStopException as es:
        cvbooster.best_iteration = es.best_iteration + 1
        for bst in boosters:
            bst.best_iteration = cvbooster.best_iteration
        for k in results:
            results[k] = results[k][:cvbooster.best_iteration]
    if return_cvbooster:
        results["cvbooster"] = cvbooster
    return dict(results)
