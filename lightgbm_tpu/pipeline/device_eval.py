"""In-graph valid-set metrics over a fused block's score trajectory.

The non-pipelined block loop pulls each valid set's FULL per-iteration
score matrix to the host ([block, N] or [block, N, C] f32) and runs
metrics.py on it — a device-to-host copy the next dispatch waits
behind. Here the metric reductions themselves ride the
device: one vmapped dispatch per valid set turns the trajectory into a
[block, n_metrics] f32 array, so the early-stop/callback protocol syncs
a few hundred bytes per block instead of the score matrices.

Fidelity contract: formulas mirror metrics.py term-for-term (weighted
mean = (loss * w).sum() / sum_weight, the same eps floors, the same
convert_output application), but arithmetic is f32 on device while
metrics.py computes in np.float64 — logged metric VALUES may differ in
the trailing digits. Trees, scores and split decisions never flow
through this module, so models are unaffected; only an exactly-tied
early-stop comparison could flip, which is why the parity suite pins
best_iteration across both eval paths. The one deliberate deviation:
upper clip bounds use 1e-7 where metrics.py uses 1e-15, because
1 - 1e-15 rounds to 1.0 in f32 and log(1 - p) would hit -inf.

Engagement is all-or-nothing per run: if ANY metric on ANY valid set
has no device kernel (the rank/AUC families need per-query sorts), the
executor falls back to host evaluation for everything — mixed cadences
would complicate the sync schedule for no measured win.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["DeviceEval", "build_device_eval"]

_EPS = 1e-15          # lower clip floor (f32-representable; metrics.py)
_EPS_HI = 1e-7        # upper clip margin: 1 - 1e-15 == 1.0 in f32

# metrics.py _PointwiseMetric family with a direct jnp transcription.
# cross_entropy_lambda is excluded (its weighted link function folds the
# weight INSIDE the loss, a different averaging contract), as are the
# sort-based families (auc, average_precision, auc_mu, ndcg, map).
_POINTWISE = frozenset((
    "l2", "rmse", "l1", "quantile", "huber", "fair", "poisson", "mape",
    "gamma", "gamma_deviance", "tweedie", "binary_logloss",
    "binary_error", "cross_entropy", "kullback_leibler"))
_MULTI = frozenset(("multi_logloss", "multi_error"))


def _point_loss(m, p, y):
    """jnp transcription of metrics.py point_loss for metric m."""
    n, cfg = m.name, m.config
    if n in ("l2", "rmse"):
        return (p - y) ** 2
    if n == "l1":
        return jnp.abs(p - y)
    if n == "quantile":
        a = float(cfg.alpha)
        d = y - p
        return jnp.where(d >= 0, a * d, (a - 1.0) * d)
    if n == "huber":
        a = float(cfg.alpha)
        d = jnp.abs(p - y)
        return jnp.where(d <= a, 0.5 * d * d, a * (d - 0.5 * a))
    if n == "fair":
        c = float(cfg.fair_c)
        x = jnp.abs(p - y)
        return c * x - c * c * jnp.log1p(x / c)
    if n == "poisson":
        pp = jnp.maximum(p, 1e-10)
        return pp - y * jnp.log(pp)
    if n == "mape":
        return jnp.abs((y - p) / jnp.maximum(1.0, jnp.abs(y)))
    if n == "gamma":
        theta = -1.0 / jnp.maximum(p, _EPS)
        b = -jnp.log(-theta)
        # psi=1 makes metrics.py's c term log(y) - log(y); keep it so
        # non-positive labels propagate the same NaNs
        return -(y * theta - b) - (jnp.log(y) - jnp.log(y))
    if n == "gamma_deviance":
        x = y / jnp.maximum(p, 1e-9)
        return 2.0 * (x - jnp.log(jnp.maximum(x, 1e-9)) - 1.0)
    if n == "tweedie":
        rho = float(cfg.tweedie_variance_power)
        pp = jnp.maximum(p, 1e-10)
        a = y * jnp.power(pp, 1.0 - rho) / (1.0 - rho)
        b = jnp.power(pp, 2.0 - rho) / (2.0 - rho)
        return -a + b
    if n in ("binary_logloss", "cross_entropy"):
        pp = jnp.clip(p, _EPS, 1.0 - _EPS_HI)
        return -(y * jnp.log(pp) + (1.0 - y) * jnp.log(1.0 - pp))
    if n == "binary_error":
        return ((p > 0.5) != (y > 0)).astype(jnp.float32)
    if n == "kullback_leibler":
        pp = jnp.clip(p, _EPS, 1.0 - _EPS_HI)
        yy = jnp.clip(y, _EPS, 1.0 - _EPS_HI)
        return (yy * jnp.log(yy / pp) +
                (1.0 - yy) * jnp.log((1.0 - yy) / (1.0 - pp)))
    raise KeyError(n)


def _supported(m, num_class: int) -> bool:
    n = getattr(m, "name", None)
    if num_class > 1:
        return n in _MULTI
    return n in _POINTWISE


class DeviceEval:
    """Per-valid-set compiled trajectory evaluators plus the metadata
    to rebuild the engine's evaluation_result_list protocol on host."""

    def __init__(self, fns, names, valid_names):
        self.fns = fns                # per valid set: fn(traj)->[b, nm]
        self.names = names            # per valid set: metric name list
        self.valid_names = valid_names

    def dispatch(self, trajs) -> List[Optional[jax.Array]]:
        """Launch the metric reductions for every valid set (async —
        returns device arrays without syncing)."""
        return [fn(trajs[vi]) if fn is not None else None
                for vi, fn in enumerate(self.fns)]

    def evlist_at(self, mhost: List[Optional[np.ndarray]], j: int) -> List:
        """(valid_name, metric_name, value, higher_better) tuples for
        inner iteration j, replicating GBDT._eval's dict collapse of
        duplicate metric names and Booster.eval_valid's tuple shape."""
        res = []
        for vi, vn in enumerate(self.valid_names):
            if mhost[vi] is None:
                continue
            vals = {}
            for mi, name in enumerate(self.names[vi]):
                vals[name] = float(mhost[vi][j, mi])
            for name, v in vals.items():
                higher = name.split("@")[0] in (
                    "auc", "ndcg", "map", "average_precision", "auc_mu")
                res.append((vn, name, v, higher))
        return res


def build_device_eval(booster) -> Optional[DeviceEval]:
    """DeviceEval over every valid set of `booster`, or None when any
    metric anywhere lacks a device kernel (host-eval fallback)."""
    gb = booster.gbdt
    valid_metrics = getattr(gb, "valid_metrics", None)
    if not valid_metrics:
        return None
    num_class = int(getattr(gb, "num_tree_per_iteration", 1))
    for ms in valid_metrics:
        for m in ms:
            if not _supported(m, num_class):
                return None
    obj = gb.objective
    fns, names = [], []
    for ms in valid_metrics:
        if not ms:
            fns.append(None)
            names.append([])
            continue
        fns.append(_make_set_fn(ms, obj, num_class))
        names.append([m.name for m in ms])
    return DeviceEval(fns, names, list(booster.name_valid_sets))


def _make_set_fn(ms, obj, num_class: int):
    """Compile fn(traj [b, N] | [b, N, C]) -> [b, len(ms)] f32 for one
    valid set's metric list."""
    label = jnp.asarray(ms[0].label, jnp.float32)
    weight = None if ms[0].weight is None \
        else jnp.asarray(ms[0].weight, jnp.float32)
    sum_weight = float(ms[0].sum_weight)
    idx = None
    if num_class > 1:
        idx = jnp.asarray(ms[0].label.astype(np.int64), jnp.int32)

    def avg(loss):
        if weight is None:
            return jnp.mean(loss)
        return jnp.sum(loss * weight) / sum_weight

    def one_point(s):
        conv = None   # convert_output(s), computed once, shared

        def converted():
            nonlocal conv
            if conv is None:
                conv = obj.convert_output(s) if obj is not None else s
            return conv

        vals = []
        for m in ms:
            if m.name == "multi_logloss":
                p = converted()
                pt = jnp.take_along_axis(p, idx[:, None], axis=1)[:, 0]
                vals.append(avg(-jnp.log(jnp.clip(pt, _EPS, None))))
            elif m.name == "multi_error":
                k = int(m.config.multi_error_top_k)
                tp = jnp.take_along_axis(s, idx[:, None], axis=1)
                rank = (s > tp).sum(axis=1)
                vals.append(avg((rank >= k).astype(jnp.float32)))
            else:
                p = converted() if getattr(m, "convert_score", True) else s
                v = avg(_point_loss(m, p, label))
                if m.name == "rmse":
                    v = jnp.sqrt(v)
                vals.append(v)
        return jnp.stack(vals)

    return jax.jit(jax.vmap(one_point))
