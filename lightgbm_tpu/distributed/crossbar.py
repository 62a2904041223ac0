"""Learner-factory crossbar: device x parallelism -> grower.

The reference resolves its tree learner through one factory,
``TreeLearner::CreateTreeLearner`` (tree_learner.cpp:16-64): a crossbar
of device type {cpu, gpu, cuda} x learner type {serial, feature, data,
voting}. Our device column collapses to XLA (the same jitted growth
body runs on CPU/TPU), but the crossbar survives as the single registry
EVERY run of `boosting/gbdt.py`, serial included, resolves its grower
through — with device rows of our own: the MXU growth path and the
portable grower (pallas or scatter histograms), each crossed with the
parallelism mode.

``resolve_learner`` picks the row: the kernel-path gate, the one-device
fallback and the mode/device/hist_agg validation live HERE only;
``create_tree_learner`` builds the actual shard_map'ped grower for it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from ..utils.log import Log

__all__ = ["LearnerSpec", "CROSSBAR", "resolve_learner",
           "create_tree_learner"]


@dataclasses.dataclass(frozen=True)
class LearnerSpec:
    """One crossbar cell: how tree growth is dispatched.

    Mirrors the reference's (device, learner) template instantiation
    (serial_tree_learner.cpp / *_parallel_tree_learner.cpp): `mode` is
    the parallelism column, `device` the kernel row, `hist_agg` the
    histogram merge algorithm for the row-sharded modes."""
    mode: str                 # "serial" | "data" | "feature" | "voting"
    device: str               # "mxu" | portable: "pallas" | "scatter"
    hist_agg: str = "psum"    # "psum" | "reduce_scatter" (data/voting)
    rows_sharded: bool = False    # bins/grad/hess/cnt sharded over mesh
    supports_multihost: bool = False

    @property
    def is_parallel(self) -> bool:
        return self.mode != "serial"

    @property
    def serial_mxu(self) -> bool:
        """The un-sharded MXU grower: what the packed 4-bit bins and the
        per-iteration path's one-hot score update are wired for (the
        fused block's is on the MXU for the sharded grower too)."""
        return self.mode == "serial" and self.device == "mxu"


#: the factory table (reference tree_learner.cpp:16-64). Keys are
#: (device, mode); values carry the sharding + merge contract of the
#: cell. reduce_scatter rides only the portable data/voting rows: the
#: MXU grower keeps its per-pass psum (its histogram lives inside the
#: kernel), and feature-parallel has no histogram merge at all.
CROSSBAR = {
    ("scatter", "serial"): LearnerSpec("serial", "scatter"),
    ("pallas", "serial"): LearnerSpec("serial", "pallas"),
    ("mxu", "serial"): LearnerSpec("serial", "mxu"),
    ("scatter", "data"): LearnerSpec(
        "data", "scatter", hist_agg="reduce_scatter", rows_sharded=True,
        supports_multihost=True),
    ("mxu", "data"): LearnerSpec(
        "data", "mxu", hist_agg="psum", rows_sharded=True,
        supports_multihost=True),
    ("scatter", "feature"): LearnerSpec("feature", "scatter"),
    ("scatter", "voting"): LearnerSpec(
        "voting", "scatter", hist_agg="reduce_scatter",
        rows_sharded=True),
}


def resolve_learner(tree_learner: str, *, platform: str = "cpu",
                    use_pallas: bool = True,
                    mxu_exclusions: Sequence[str] = (),
                    num_devices: int = 1,
                    hist_agg: str = "auto", num_features: int = 0,
                    top_k: int = 20, nproc: int = 1,
                    has_efb: bool = False,
                    mono_rescan: bool = False) -> LearnerSpec:
    """Resolve one crossbar cell from what the choice depends on.

    A parallel learner over one device is the serial one. On the chip
    (`use_pallas`, a `platform` other than "cpu") the MXU grower runs
    the serial and the data-parallel learner unless `mxu_exclusions`
    (GBDT._mxu_exclusions) names a reason; then the serial learner keeps
    the portable grower's Pallas histograms (scatter under EFB, which
    that kernel has no bundle-space form of) and the data-parallel one
    the portable body inside shard_map. Feature- and voting-parallel
    have a portable body only. `hist_agg` is downgraded where the
    reduce-scatter path cannot hold its contract:

    - multihost (nproc > 1): the chaos/resume guarantees are proven on
      the psum merge; gloo's all_to_all support is not, so cross-host
      runs keep psum.
    - EFB: histograms build in bundle space and expand per device; a
      feature-sharded scan would need the expansion split mid-bundle.
    - non-basic monotone methods: the whole-tree histogram cache wants
      every feature on every device.
    - voting with 2*top_k < F: the vote-selected columns are not a
      contiguous block, so ownership does not cover them; classic
      PV-Tree psum applies.

    `hist_agg="auto"` means "reduce_scatter wherever exact", explicit
    "psum"/"reduce_scatter" are honored (with the same safety
    downgrades)."""
    if tree_learner != "serial" and num_devices <= 1:
        Log.warning("tree_learner=%s requested but only one device "
                    "visible; falling back to serial", tree_learner)
        tree_learner = "serial"
    device = "scatter"
    if use_pallas and platform != "cpu" and \
            tree_learner in ("serial", "data"):
        if not mxu_exclusions:
            device = "mxu"
        else:
            if tree_learner == "serial" and not has_efb:
                device = "pallas"
            # the EFB exclusion is the default by design (config
            # efb_use_mxu) — only the genuine perf cliffs warn
            hard = [r for r in mxu_exclusions if r != "efb config"]
            if hard:
                Log.warning(
                    "%s training runs on the portable %s grower (MXU "
                    "path excluded by: %s) — expect ~10x lower "
                    "throughput on TPU", tree_learner, device,
                    ", ".join(hard))
    key = (device, tree_learner)
    if key not in CROSSBAR:
        raise ValueError(
            f"no tree learner for device={device!r} "
            f"tree_learner={tree_learner!r} (crossbar rows: "
            f"{sorted(CROSSBAR)})")
    spec = CROSSBAR[key]
    agg = spec.hist_agg
    if hist_agg != "auto":
        agg = hist_agg
    if agg == "reduce_scatter":
        blocked = (nproc > 1 or has_efb or mono_rescan
                   or device == "mxu"
                   or spec.mode not in ("data", "voting")
                   or (spec.mode == "voting"
                       and num_features > 0
                       and 2 * top_k < num_features))
        if blocked:
            agg = "psum"
    if not spec.rows_sharded:
        agg = "psum"    # no histogram merge happens at all
    return dataclasses.replace(spec, hist_agg=agg)


def create_tree_learner(spec: LearnerSpec, mesh, comm, **kwargs
                        ) -> Optional[object]:
    """Instantiate the grower for a resolved crossbar cell (the factory
    half of CreateTreeLearner). Serial cells return None — the caller
    keeps its un-shard_map'ped growth dispatch; parallel cells return
    the jitted shard_map grower from parallel/learner.py with the
    cell's device row selecting the MXU or portable body."""
    if not spec.is_parallel:
        return None
    _record_epoch_resolve(spec)
    from ..parallel.learner import make_sharded_grower
    return make_sharded_grower(mesh, comm, use_mxu=spec.device == "mxu",
                               **kwargs)


def _record_epoch_resolve(spec: LearnerSpec) -> None:
    """Elastic reincarnation re-resolves the learner through this same
    crossbar at the shrunken world; leave a flight-recorder breadcrumb
    when that happens (epoch > 0) so a postmortem shows which cell the
    resized run landed on. Never raises — forensics must not block the
    factory."""
    try:
        from .elastic import current_epoch
        epoch = current_epoch()
        if epoch > 0:
            from ..observability.flightrec import recorder
            recorder.record("resize", "crossbar_resolve", epoch=epoch,
                            mode=spec.mode, device=spec.device,
                            hist_agg=spec.hist_agg)
    except Exception:       # pragma: no cover - forensics only
        pass
