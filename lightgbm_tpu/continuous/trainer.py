"""The continuous train -> refresh -> serve driver.

State layout under ``loop_dir``::

    GENERATION               # atomic json marker: the COMMIT point of
                             # a cycle (generation, bundle, next_chunk,
                             # quarantined windows)
    gens/ckpt_%07d/          # one checkpoint bundle per PUBLISHED
                             # generation (bundle key = generation
                             # number, not tree count)
    work/CYCLE               # generation number being built
    work/ckpt/               # stream-state side files + mid-train
                             # checkpoint bundles for the cycle
    postmortems/attempt_*/   # flight-recorder flush per failed cycle

One cycle (``_run_cycle_once``)::

    ingest window -> refresh train -> cut gens bundle -> publish
        |                 |                 |               |
    streaming_ingest  histogram_build  checkpoint_io   serving_hot_swap
                                                       serving_hot_swap_commit
                                                       loop_publish

The GENERATION marker is the cycle's single commit point: everything
before it is redone deterministically from durable state on recovery
(identical bytes — stream-state resume, checkpoint resume, idempotent
re-save and re-swap), and a complete gens bundle NEWER than the marker
is by definition a torn publish, discarded by ``_recover`` before it
can ever be served. The marker is only advanced AFTER the serving swap
succeeds, so the registry is never behind the marker.

``run`` wraps each cycle in a capped-exponential crash-loop budget
(reliability/backoff.py): a window that keeps failing after
``loop_poison_retries`` full recover/rebuild attempts is quarantined —
skipped, logged, counted in the freshness metric family — instead of
wedging the loop forever.
"""

from __future__ import annotations

import json
import os
import shutil
import time
from typing import Dict, List, Optional

from ..basic import Booster, Dataset
from ..observability import registry as _obs
from ..observability.flightrec import recorder
from ..reliability import counters, faults, pin_bundle
from ..reliability.backoff import BackoffPolicy
from ..reliability.checkpoint import (latest_checkpoint, load_checkpoint,
                                      save_checkpoint, _bundle_iter,
                                      _is_complete, _listdir)
from ..streaming import ChunkSource, WindowSource
from ..utils.log import Log, LightGBMError
from ..utils.timer import global_timer

__all__ = ["ContinuousTrainer", "MARKER", "CYCLE_TAG"]

#: the loop's commit point: a json file naming the live generation,
#: the gens bundle it was published from, and the stream cursor
MARKER = "GENERATION"
#: names the generation the work dir is building; a tag that does not
#: match marker.generation + 1 marks the work dir as stale
CYCLE_TAG = "CYCLE"
_MARKER_VERSION = 1


class ContinuousTrainer:
    """Drives train -> refresh -> serve cycles over a `ChunkSource`.

    `source` is the stream of fresh rows (windowed per cycle by
    `loop_window_chunks`), `server` the live `serving.Server` the
    generations are published into. `publish_transform`, when given,
    rewrites the model text once per generation before it is saved and
    served (it must be idempotent: a recovered cycle re-applies it to
    a model whose base trees were already transformed). `sleep` is the
    backoff clock, injectable so chaos tests do not wait wall-time.
    """

    def __init__(self, config, source: ChunkSource, server,
                 params: Optional[Dict] = None,
                 publish_transform=None, sleep=time.sleep):
        if not config.loop_dir:
            raise LightGBMError(
                "ContinuousTrainer needs loop_dir: the loop's durable "
                "state (generation marker, bundles, stream cursor) "
                "lives there")
        self.config = config
        self.source = source
        self.server = server
        self.params = dict(params or {})
        self.publish_transform = publish_transform
        self.backoff = BackoffPolicy(config.loop_backoff_ms,
                                     config.loop_backoff_max_ms,
                                     sleep=sleep)
        self.loop_dir = config.loop_dir
        self.gens_dir = os.path.join(self.loop_dir, "gens")
        self.work_dir = os.path.join(self.loop_dir, "work")
        self.work_ckpt = os.path.join(self.work_dir, "ckpt")
        self.post_dir = os.path.join(self.loop_dir, "postmortems")
        for d in (self.gens_dir, self.work_ckpt, self.post_dir):
            os.makedirs(d, exist_ok=True)
        self.marker_path = os.path.join(self.loop_dir, MARKER)
        # live state, (re)filled by _recover from the durable marker
        self.generation = 0
        self.next_chunk = 0
        self.quarantined: List[int] = []
        self._live_model_str: Optional[str] = None
        self._fault_count = 0

    # ------------------------------------------------------------------
    # durable marker + work-cycle tag
    def _read_marker(self) -> Optional[Dict]:
        try:
            with open(self.marker_path) as f:
                marker = json.load(f)
        except (OSError, ValueError):
            return None
        if marker.get("format_version") != _MARKER_VERSION:
            Log.warning("continuous: ignoring generation marker with "
                        f"format_version="
                        f"{marker.get('format_version')!r}")
            return None
        return marker

    def _write_marker(self, generation: int, bundle: Optional[str],
                      next_chunk: int, quarantined: List[int]) -> None:
        payload = {"format_version": _MARKER_VERSION,
                   "generation": int(generation),
                   "bundle": bundle,
                   "next_chunk": int(next_chunk),
                   "quarantined": [int(q) for q in quarantined]}
        tmp = self.marker_path + f".tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, sort_keys=True)
        os.replace(tmp, self.marker_path)

    def _cycle_tag(self) -> Optional[int]:
        try:
            with open(os.path.join(self.work_dir, CYCLE_TAG)) as f:
                return int(f.read().strip())
        except (OSError, ValueError):
            return None

    def _write_cycle_tag(self, generation: int) -> None:
        path = os.path.join(self.work_dir, CYCLE_TAG)
        tmp = path + f".tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(f"{int(generation)}\n")
        os.replace(tmp, path)

    def _wipe_work(self) -> None:
        shutil.rmtree(self.work_dir, ignore_errors=True)
        os.makedirs(self.work_ckpt, exist_ok=True)

    # ------------------------------------------------------------------
    # recovery: runs at the top of EVERY cycle, so the in-process retry
    # path exercises exactly the code a freshly restarted process runs
    def _recover(self) -> None:
        marker = self._read_marker()
        if marker is None:
            self.generation = 0
            self.next_chunk = 0
            self.quarantined = []
            bundle_name = None
        else:
            self.generation = int(marker["generation"])
            self.next_chunk = int(marker["next_chunk"])
            self.quarantined = [int(q) for q in
                                marker.get("quarantined", [])]
            bundle_name = marker.get("bundle")
        # torn-publish sweep: a COMPLETE gens bundle newer than the
        # marker was cut by a cycle that died before its commit point —
        # it was never published durably, so it is discarded here and
        # rebuilt deterministically (identical bytes) by the next cycle
        for name in _listdir(self.gens_dir):
            it = _bundle_iter(name)
            if it is None or it <= self.generation:
                continue
            shutil.rmtree(os.path.join(self.gens_dir, name),
                          ignore_errors=True)
            counters.inc("loop_torn_publishes")
            _obs.record_freshness_torn_publish(it)
            Log.warning(
                "continuous: discarded torn generation bundle %s "
                "(newer than committed generation %d)", name,
                self.generation)
        # re-assert the pin: a kill between marker write and pin write
        # must not let keep_last pruning age out the live generation
        pin_bundle(self.gens_dir, bundle_name)
        # seed the freshness gauge with the recovered live generation —
        # a restarted process that never publishes (exhausted stream)
        # must still report the generation it serves, not 0
        if self.generation:
            _obs.record_freshness_recover(self.generation)
        # a work dir building anything but the next generation is
        # stale (left by a quarantined or already-published cycle)
        if self._cycle_tag() != self.generation + 1:
            self._wipe_work()
        self._live_model_str = None
        if bundle_name is not None:
            bundle = os.path.join(self.gens_dir, bundle_name)
            if not _is_complete(bundle):
                raise LightGBMError(
                    f"continuous: generation marker names bundle "
                    f"{bundle_name!r} but no complete bundle is there "
                    f"— loop_keep pruning and the pin file disagree?")
            self._live_model_str = load_checkpoint(bundle).model_str
            # restart semantics: (re)load the live generation into the
            # serving registry only when it is not already there — an
            # in-process retry must not churn the served entry
            name = self.config.loop_model_name
            if name not in self.server.registry:
                self.server.load_model(name,
                                       model_str=self._live_model_str)
                Log.info("continuous: restored generation %d into "
                         "serving entry %r", self.generation, name)

    # ------------------------------------------------------------------
    # one cycle: ingest -> refresh -> generation cut -> publish
    def _cycle_params(self) -> Dict:
        p = dict(self.params)
        # the same dict serves Dataset params (stream-state side files)
        # and train params (auto checkpoint callback): both kinds of
        # mid-cycle durability land under work/ckpt
        p["checkpoint_dir"] = self.work_ckpt
        if int(p.get("checkpoint_period", 0) or 0) <= 0:
            p["checkpoint_period"] = 1
        return p

    def _run_cycle_once(self) -> None:
        cfg = self.config
        gen = self.generation + 1
        self._write_cycle_tag(gen)
        t0 = time.perf_counter()
        params = self._cycle_params()
        window = WindowSource(self.source, self.next_chunk,
                              cfg.loop_window_chunks)
        ds = Dataset(window, params=params, free_raw_data=False)
        with global_timer.timeit("loop_ingest", fine=True):
            ds.construct()
        from ..engine import train
        found = latest_checkpoint(self.work_ckpt)
        if found is not None:
            # kill-mid-train recovery: resume the exact f32/RNG/bagging
            # state from the cycle's last committed bundle — the
            # finished refresh is byte-identical to an unkilled one
            booster = train(params, ds,
                            num_boost_round=cfg.loop_rounds,
                            resume_from=found)
        elif self._live_model_str is not None:
            booster = train(params, ds,
                            num_boost_round=cfg.loop_rounds,
                            init_model=Booster(
                                model_str=self._live_model_str))
        else:
            booster = train(params, ds,
                            num_boost_round=cfg.loop_rounds)
        model_str = booster.model_to_string()
        if self.publish_transform is not None:
            model_str = self.publish_transform(model_str)
        # generation cut: bundle key is the GENERATION number (not the
        # cumulative tree count — quarantined windows add no trees, and
        # the keyspace must still advance). checkpoint_io injects
        # inside save_checkpoint, making this the kill-at-cut site;
        # keep_last pruning runs here too, with the pinned live bundle
        # exempt.
        bundle = save_checkpoint(
            self.gens_dir, gen, model_str,
            state={"generation": gen,
                   "next_chunk": self.next_chunk + cfg.loop_window_chunks,
                   "cum_iteration": booster.current_iteration(),
                   "quarantined": [int(q) for q in self.quarantined]},
            arrays={}, keep_last=cfg.loop_keep)
        self._publish(gen, model_str, bundle, t0)
        self._wipe_work()
        self.generation = gen
        self.next_chunk += cfg.loop_window_chunks
        self._live_model_str = model_str

    def _publish(self, gen: int, model_str: str, bundle: str,
                 t0: float) -> None:
        """Swap the new generation into the serving registry, then
        commit it: marker advance -> pin. A kill anywhere in this
        sequence is survivable — before the marker write the bundle is
        torn (discarded + rebuilt identically by recovery), after it
        the recovery path re-pins and re-loads idempotently."""
        cfg = self.config
        name = cfg.loop_model_name
        if name in self.server.registry:
            self.server.hot_swap(name, model_str=model_str)
        else:
            self.server.load_model(name, model_str=model_str)
        # registered fault site: the new generation is serving but the
        # marker still names the old one — the torn-publish window
        faults.inject("loop_publish")
        self._write_marker(gen, os.path.basename(bundle),
                           self.next_chunk + cfg.loop_window_chunks,
                           self.quarantined)
        pin_bundle(self.gens_dir, bundle)
        _obs.record_freshness_publish(gen, time.perf_counter() - t0,
                                      cfg.loop_freshness_slo_s)
        counters.inc("loop_publishes")
        Log.info("continuous: published generation %d (window chunks "
                 "[%d:%d)) into serving entry %r", gen, self.next_chunk,
                 self.next_chunk + cfg.loop_window_chunks, name)

    # ------------------------------------------------------------------
    # poison-window quarantine
    def _quarantine(self) -> None:
        widx = self.next_chunk
        self.quarantined.append(widx)
        self._wipe_work()
        self.next_chunk += self.config.loop_window_chunks
        # same generation, same bundle: a quarantine advances only the
        # cursor — the live model is untouched
        marker = self._read_marker()
        bundle_name = marker.get("bundle") if marker else None
        self._write_marker(self.generation, bundle_name,
                           self.next_chunk, self.quarantined)
        counters.inc("loop_quarantined_windows")
        _obs.record_freshness_quarantine(widx)
        Log.warning(
            "continuous: quarantined poison window at chunk %d after "
            "%d failed attempts; loop continues at chunk %d", widx,
            self.config.loop_poison_retries, self.next_chunk)

    # ------------------------------------------------------------------
    def _window_empty(self) -> bool:
        """True when the next window holds no rows — the loop's clean
        exhaustion probe. Sized sources answer from metadata; unsized
        ones pay one restartable probe pass for the first chunk."""
        window = WindowSource(self.source, self.next_chunk,
                              self.config.loop_window_chunks)
        if window.num_rows is not None:
            return window.num_rows == 0
        it = window.chunks()
        try:
            return next(it, None) is None
        finally:
            it.close()

    def run(self, max_windows: Optional[int] = None) -> int:
        """Process windows until the source is exhausted or the window
        budget (`max_windows`, default `loop_windows`; 0 = unlimited)
        is spent. Returns the number of generations published. Both
        published and quarantined windows count against the budget."""
        cfg = self.config
        limit = max_windows if max_windows is not None \
            else (cfg.loop_windows or None)
        published = 0
        processed = 0
        attempts = 0
        while limit is None or processed < limit:
            self._recover()
            if self._window_empty():
                break
            try:
                self._run_cycle_once()
            except Exception as exc:  # noqa: BLE001 - crash-loop budget
                attempts += 1
                self._fault_count += 1
                recorder.record_exception("continuous_loop", exc)
                out_dir = os.path.join(
                    self.post_dir, f"attempt_{self._fault_count:04d}")
                os.makedirs(out_dir, exist_ok=True)
                recorder.flush("loop_fault", out_dir=out_dir,
                               extra={"generation": self.generation + 1,
                                      "window_chunk": self.next_chunk,
                                      "attempt": attempts})
                counters.inc("loop_cycle_failures")
                Log.warning(
                    "continuous: cycle for generation %d failed "
                    "(attempt %d/%d): %s: %s", self.generation + 1,
                    attempts, cfg.loop_poison_retries,
                    type(exc).__name__, exc)
                if attempts >= cfg.loop_poison_retries:
                    self._quarantine()
                    processed += 1
                    attempts = 0
                else:
                    self.backoff.wait(attempts - 1)
                continue
            published += 1
            processed += 1
            attempts = 0
        return published
