"""Two-pass out-of-core dataset construction over a ChunkSource.

Pass 1 streams chunks into a `ReservoirSketch` (sketch.py) and collects
stream-borne labels; the frozen sample then feeds the SAME
`find_bin_mappers` call the in-memory path makes, so a covering sketch
(`stream_sample_rows >= N`) yields bit-identical bin boundaries — and a
byte-identical model. Pass 2 re-streams and quantizes each chunk
straight into the preallocated uint8/16 bin matrix, double-buffering
the NEXT chunk's host parse (a worker thread) against the CURRENT
chunk's binning (main thread) — the ingestion analogue of the pipeline
executor's dispatch/finalize overlap. Peak host memory is
O(chunk + sketch + bin matrix), never the dense [N, F] float matrix.

Array-backed sources (`source.array` set: in-memory NumPy, `.npy`
memmap) skip the sketch pass entirely — bin finding samples the matrix
directly, exactly as `BinnedDataset.from_raw` would, and pass 2 bins
zero-copy row slices. This is also the route all-numeric in-memory
input takes (no whole-matrix float64 conversion).

Mid-stream durability: with a `checkpoint_dir`, pass 1 persists the
sketch + stream cursor (and pass-1 end freezes the mappers) via the
same tmp+rename atomicity as reliability/checkpoint.py bundles, in
side files a `latest_checkpoint` scan ignores. A killed ingest resumes
pass 1 at the saved chunk with the identical RNG stream; a kill in
pass 2 skips pass 1 entirely and re-quantizes (host-only work). The
`streaming_ingest` fault site makes the kill injectable.
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..binning import BinMapper, bin_columns, find_bin_mappers
from ..data import BinnedDataset, Metadata, _select_used_features
from ..observability import registry as _obs
from ..observability import span
from ..reliability.counters import counters
from ..reliability.faults import faults
from ..utils.log import Log, LightGBMError
from .sketch import ReservoirSketch
from .sources import ChunkSource

__all__ = ["StreamStats", "build_streamed_dataset"]

_STATE_JSON = "stream_state.json"
_STATE_NPZ = "stream_state.npz"
_STATE_VERSION = 2
#: pass-1 state saves are throttled: rewriting the sketch + labels is
#: O(rows seen), so saving only after rows grow by this factor keeps
#: total checkpoint I/O O(N) over the stream instead of O(N^2/chunk);
#: a time floor bounds lost work on slow streams regardless
_SAVE_GROWTH = 1.25
_SAVE_INTERVAL_S = 30.0


class StreamStats:
    """Per-ingest accounting, attached to the result as
    `dataset.stream_stats` unconditionally (bench.py reads it with
    observability off; registry.record_streaming_chunk mirrors chunk
    records into the unified snapshot when observability is on)."""

    def __init__(self, source_desc: str = ""):
        self.source = source_desc
        self.chunks = 0            # pass-2 chunks quantized
        self.rows = 0
        self.bytes = 0             # raw chunk bytes seen across passes
        self.sketch_chunks = 0     # pass-1 chunks sketched
        self.sample_rows = 0
        self.exact = False         # sketch held every row (parity mode)
        self.resumed_from_chunk = 0
        self.pass1_s = 0.0
        self.pass2_s = 0.0
        self.parse_s = 0.0         # overlapped host parse inside pass 2
        self.bin_s = 0.0

    @property
    def overlap_frac(self) -> float:
        """Fraction of the pass-2 wall covered by overlapped parsing of
        the next chunk — the double-buffering win (0 = fully serial)."""
        if self.pass2_s <= 0:
            return 0.0
        return min(1.0, self.parse_s / self.pass2_s)

    @property
    def rows_per_sec(self) -> float:
        wall = self.pass1_s + self.pass2_s
        return self.rows / wall if wall > 0 else 0.0

    def as_dict(self) -> dict:
        return {
            "source": self.source,
            "chunks": self.chunks,
            "rows": self.rows,
            "bytes": self.bytes,
            "sketch_chunks": self.sketch_chunks,
            "sample_rows": self.sample_rows,
            "exact": bool(self.exact),
            "resumed_from_chunk": self.resumed_from_chunk,
            "pass1_s": round(self.pass1_s, 6),
            "pass2_s": round(self.pass2_s, 6),
            "parse_s": round(self.parse_s, 6),
            "bin_s": round(self.bin_s, 6),
            "overlap_frac": round(self.overlap_frac, 4),
            "rows_per_sec": round(self.rows_per_sec, 1),
        }


def _ingest_chunk_step(chunk_index: int) -> None:
    """Per-chunk dispatch point for both passes; the injectable failure
    surface of streamed ingestion (reliability/faults.py site table)."""
    faults.inject("streaming_ingest")


# ---- stream-state side files (pass-1 durability) ----------------------
# Plain files, not ckpt_* bundles: latest_checkpoint() must keep
# resolving TRAINING state only, while ingestion keeps its own cursor.

def _state_paths(ckpt_dir: str):
    return (os.path.join(ckpt_dir, _STATE_JSON),
            os.path.join(ckpt_dir, _STATE_NPZ))


def _save_stream_state(ckpt_dir: str, state: Dict,
                       arrays: Dict[str, np.ndarray]) -> None:
    os.makedirs(ckpt_dir, exist_ok=True)
    jpath, npath = _state_paths(ckpt_dir)
    # the npz carries a copy of the json cursor: the two files are
    # renamed in separate os.replace calls, so a kill between them
    # leaves a torn pair that load detects and discards instead of
    # resuming with a cursor from chunk k over a sketch from chunk k+1
    seq = np.asarray([int(state["next_chunk"]), int(state["rows"])],
                     np.int64)
    tmp = npath + ".tmp"
    with open(tmp, "wb") as fh:
        np.savez(fh, _seq=seq,
                 **{k: np.asarray(v) for k, v in arrays.items()})
    os.replace(tmp, npath)
    tmp = jpath + ".tmp"
    with open(tmp, "w") as fh:
        json.dump({"format_version": _STATE_VERSION, **state}, fh,
                  sort_keys=True)
    os.replace(tmp, jpath)


def _load_stream_state(ckpt_dir: str):
    jpath, npath = _state_paths(ckpt_dir)
    if not (os.path.isfile(jpath) and os.path.isfile(npath)):
        return None, None
    with open(jpath) as fh:
        state = json.load(fh)
    if state.get("format_version") != _STATE_VERSION:
        Log.warning("streaming: ignoring stream state with "
                    f"format_version={state.get('format_version')!r}")
        return None, None
    with np.load(npath) as z:
        arrays = {k: z[k] for k in z.files}
    seq = arrays.pop("_seq", None)
    if seq is None or int(seq[0]) != int(state["next_chunk"]) \
            or int(seq[1]) != int(state["rows"]):
        Log.warning(
            "streaming: stream state json/npz pair is inconsistent "
            "(torn save); discarding and restarting pass 1")
        return None, None
    return state, arrays


def _clear_stream_state(ckpt_dir: str) -> None:
    for p in _state_paths(ckpt_dir):
        try:
            os.remove(p)
        except OSError:
            pass


def build_streamed_dataset(
        source: ChunkSource, *,
        label: Optional[np.ndarray] = None,
        weight: Optional[np.ndarray] = None,
        group: Optional[np.ndarray] = None,
        init_score: Optional[np.ndarray] = None,
        max_bin: int = 255, min_data_in_bin: int = 3,
        sample_cnt: int = 200000, use_missing: bool = True,
        zero_as_missing: bool = False,
        categorical_features: Optional[Sequence[int]] = None,
        seed: int = 1,
        feature_names: Optional[List[str]] = None,
        mappers: Optional[List[BinMapper]] = None,
        feature_pre_filter: bool = True,
        pre_filter_with_mappers: bool = False,
        used_override: Optional[np.ndarray] = None,
        sample_rows: int = 200000,
        bin_parity: bool = False,
        mapper_sync: Optional[Callable[[np.ndarray],
                                       List[BinMapper]]] = None,
        checkpoint_dir: Optional[str] = None) -> BinnedDataset:
    """Construct a BinnedDataset from a ChunkSource in two passes.

    `sample_cnt`/`seed` are the `bin_construct_sample_cnt` /
    `data_random_seed` the in-memory path would use — the sketch sample
    is fed to `find_bin_mappers` with exactly those, which is what makes
    the covering case bit-identical. `sample_rows` caps the reservoir;
    `bin_parity=True` turns a non-covering sketch into a hard error
    instead of an approximation. `mappers`/`used_override` align the
    result with a reference dataset's bins (validation sets).
    `mapper_sync`, when set (multihost pure streams), replaces the local
    `find_bin_mappers` call: it receives the pass-1 sketch sample and
    must return the mapper list every rank agrees on (a collective —
    every rank reaches it exactly once per ingest). A `None` sample
    means this rank's stream yielded no rows: the sync must still join
    the collective and then raise identically on every rank, so a
    lone empty partition fails the job loudly instead of hanging it.
    The returned dataset carries `stream_stats`.
    """
    if mapper_sync is not None and bin_parity:
        # parity is a single-process guarantee; multihost boundaries
        # come from the cross-host sample union, and letting the
        # per-rank coverage check raise would strand peer ranks in the
        # mapper collective — fail identically on every rank instead
        raise LightGBMError(
            "stream_bin_parity requires num_machines=1: multihost bin "
            "boundaries come from the cross-host sample union, not the "
            "local covering sketch")
    stats = StreamStats(source.describe())
    label_parts: List[np.ndarray] = []
    sk: Optional[ReservoirSketch] = None
    all_mappers = mappers
    num_features = source.num_features
    num_rows = source.num_rows
    start_chunk = 0

    # ---- resume -------------------------------------------------------
    saved, saved_arrays = (None, None)
    if checkpoint_dir:
        saved, saved_arrays = _load_stream_state(checkpoint_dir)
    if saved is not None and mapper_sync is not None \
            and saved.get("phase") != "sketch":
        # post-sketch state skips the mapper collective; a rank resuming
        # past it while its peers enter it would hang the allgather, so
        # multihost resume only trusts sketch-phase state (pass 1 then
        # ends in the collective on every rank)
        Log.warning("streaming: discarding post-sketch stream state "
                    "under multihost — re-running pass 1 so the bin "
                    "mapper collective runs on every rank")
        saved, saved_arrays = None, None
    if saved is not None and source.array is None:
        num_features = int(saved["num_features"])
        num_rows = int(saved["rows"])
        if len(saved_arrays.get("labels", ())):
            label_parts.append(np.asarray(saved_arrays["labels"],
                                          np.float32))
        if saved["phase"] == "sketch":
            sk = ReservoirSketch.from_state(
                {k[3:]: v for k, v in saved_arrays.items()
                 if k.startswith("sk_")})
            start_chunk = int(saved["next_chunk"])
        elif all_mappers is None:
            all_mappers = [BinMapper.from_dict(d)
                           for d in saved["mappers"]]
            stats.sample_rows = int(saved.get("sample_rows", 0))
            stats.exact = bool(saved.get("exact", False))
        stats.resumed_from_chunk = int(saved["next_chunk"])
        counters.inc("stream_resumes")
        Log.info(f"streaming: resuming {saved['phase']} pass at chunk "
                 f"{saved['next_chunk']}")

    # ---- pass 1: sketch the stream, freeze the bin boundaries ---------
    if all_mappers is None and source.array is not None:
        # array-backed fast path: the matrix is random-access, so bin
        # finding samples it directly — the very call from_raw makes —
        # and no sketch buffer ever exists
        t0 = time.perf_counter()
        all_mappers = find_bin_mappers(
            source.array, max_bin=max_bin,
            min_data_in_bin=min_data_in_bin, sample_cnt=sample_cnt,
            use_missing=use_missing, zero_as_missing=zero_as_missing,
            categorical_features=categorical_features, seed=seed)
        stats.pass1_s = time.perf_counter() - t0
        stats.sample_rows = min(int(num_rows), int(sample_cnt))
        stats.exact = True
        num_features = source.num_features
    elif all_mappers is None:
        t_pass1 = time.perf_counter()
        rows_before = 0 if sk is None else num_rows
        counted = 0
        ci = start_chunk
        next_save_rows = 0
        last_save_t = time.monotonic()
        for X, y in source.chunks(start_chunk=start_chunk):
            t0 = time.perf_counter()
            _ingest_chunk_step(ci)
            X = np.asarray(X)
            if num_features is None:
                num_features = X.shape[1]
            if sk is None:
                sk = ReservoirSketch(num_features, sample_rows, seed=seed)
            sk.add_chunk(X)
            if y is not None:
                label_parts.append(np.asarray(y, np.float32))
            counted += X.shape[0]
            stats.sketch_chunks += 1
            stats.bytes += X.nbytes
            ci += 1
            wall = time.perf_counter() - t0
            if _obs.enabled:
                _obs.record_streaming_chunk("sketch", ci - 1, t0, wall,
                                            X.shape[0], X.nbytes)
            rows_total = int((rows_before or 0) + counted)
            # a save rewrites the whole sketch + label buffer (O(rows)),
            # so only save after the stream grew by _SAVE_GROWTH (total
            # I/O stays O(N)) or the time floor elapsed
            if checkpoint_dir and (
                    rows_total >= next_save_rows or
                    time.monotonic() - last_save_t >= _SAVE_INTERVAL_S):
                arrays = {"sk_" + k: v for k, v in sk.state_dict().items()}
                arrays["labels"] = np.concatenate(label_parts) \
                    if label_parts else np.empty(0, np.float32)
                _save_stream_state(checkpoint_dir, {
                    "phase": "sketch", "next_chunk": ci,
                    "num_features": int(num_features),
                    "rows": rows_total,
                }, arrays)
                next_save_rows = int(rows_total * _SAVE_GROWTH) + 1
                last_save_t = time.monotonic()
        if sk is None:
            if mapper_sync is not None:
                # an empty local stream is rank-local state: join the
                # mapper collective with a None sample so every peer
                # raises the same error instead of hanging in the
                # allgather waiting for this rank (tpulint COLL002)
                mapper_sync(None)
            raise LightGBMError("streaming: source yielded no chunks")
        num_rows = (rows_before or 0) + counted
        stats.sample_rows = sk.sample_rows
        stats.exact = sk.is_exact
        if not sk.is_exact:
            Log.info(
                f"streaming: sketch sampled {sk.sample_rows} of "
                f"{sk.rows_seen} rows; bin boundaries are approximate "
                "(raise stream_sample_rows for exact parity)")
        if mapper_sync is not None:
            # multihost: the collective derives one mapper list from
            # every rank's sketch sample, so ranks streaming disjoint
            # partitions still bin against identical boundaries
            all_mappers = mapper_sync(sk.sample())
        else:
            # parity is checked on the local-binning arm only: the
            # mapper_sync+bin_parity combination was rejected at entry,
            # and a rank-local raise between sketching and the mapper
            # collective strands peers in the allgather (tpulint
            # COLL002 — the PR-7 multihost bug shape)
            if bin_parity and not sk.is_exact:
                raise LightGBMError(
                    f"stream_bin_parity: sketch capacity {sk.capacity} "
                    f"< {sk.rows_seen} rows seen — boundaries would be "
                    "approximate; raise stream_sample_rows to cover "
                    "the stream or drop stream_bin_parity")
            # identical call to the in-memory path: with a covering
            # sketch the sample IS the data in stream order, so
            # boundaries (and the model) are bit-identical;
            # non-covering, the reservoir stands in for the population
            all_mappers = find_bin_mappers(
                sk.sample(), max_bin=max_bin,
                min_data_in_bin=min_data_in_bin, sample_cnt=sample_cnt,
                use_missing=use_missing, zero_as_missing=zero_as_missing,
                categorical_features=categorical_features, seed=seed)
        sk = None   # sketch buffer is dead weight from here on
        stats.pass1_s = time.perf_counter() - t_pass1
        if _obs.enabled:
            _obs.record_streaming_sketch(stats.sample_rows, stats.exact)
        if checkpoint_dir:
            _save_stream_state(checkpoint_dir, {
                "phase": "bin", "next_chunk": 0,
                "num_features": int(num_features),
                "rows": int(num_rows),
                "sample_rows": int(stats.sample_rows),
                "exact": bool(stats.exact),
                "mappers": [m.to_dict() for m in all_mappers],
            }, {"labels": np.concatenate(label_parts)
                if label_parts else np.empty(0, np.float32)})
    elif saved is None:
        stats.exact = True   # boundaries supplied, nothing sketched

    if num_features is None:
        # unsized source binned against supplied mappers (aligned
        # validation data): the mapper list defines the width
        num_features = len(all_mappers)
    if len(all_mappers) != num_features:
        raise ValueError(f"got {len(all_mappers)} bin mappers for "
                         f"{num_features} features")

    # ---- feature selection (reference feature_pre_filter) -------------
    if used_override is not None:
        # align with a reference dataset's used set (validation data):
        # bin exactly its columns, skipping triviality re-selection
        used = np.asarray(used_override, dtype=np.int32)
        used_mappers = [all_mappers[f] for f in used]
        max_num_bin = max([m.num_bin for m in used_mappers], default=2)
        dtype = np.uint8 if max_num_bin <= 256 else np.uint16
    else:
        used, used_mappers, dtype = _select_used_features(
            all_mappers, feature_pre_filter and
            (mappers is None or pre_filter_with_mappers))

    # ---- pass 2: re-stream and quantize, parse overlapped with bin ----
    collect_labels = not label_parts and label is None and source.has_label
    sized = num_rows is not None
    binned = np.empty((num_rows, len(used)), dtype=dtype) if sized else None
    grow_parts: List[np.ndarray] = []
    t_pass2 = time.perf_counter()
    it = source.chunks()

    def _pull():
        t = time.perf_counter()
        c = next(it, None)
        return c, time.perf_counter() - t

    row0, ci = 0, 0
    # the quantizing pass under the name from_raw gives it: one span a
    # dataset (the chunks inside are counted, not spanned)
    with span("dataset_quantize"), \
            ThreadPoolExecutor(max_workers=1) as pool:
        fut = pool.submit(_pull)
        while True:
            chunk, parse_s = fut.result()
            if chunk is None:
                break
            # the worker parses chunk k+1 while this thread bins chunk k
            fut = pool.submit(_pull)
            X, y = chunk
            t0 = time.perf_counter()
            _ingest_chunk_step(ci)
            X = np.asarray(X)
            q = bin_columns(X, used, used_mappers, dtype)
            if binned is not None:
                binned[row0:row0 + X.shape[0]] = q
            else:
                grow_parts.append(q)
            if collect_labels and y is not None:
                label_parts.append(np.asarray(y, np.float32))
            bin_s = time.perf_counter() - t0
            stats.chunks += 1
            stats.rows += X.shape[0]
            stats.bytes += X.nbytes
            stats.bin_s += bin_s
            stats.parse_s += parse_s
            row0 += X.shape[0]
            ci += 1
            if _obs.enabled:
                _obs.record_streaming_chunk("bin", ci - 1, t0,
                                            bin_s + parse_s,
                                            X.shape[0], X.nbytes)
    if binned is None:
        if not grow_parts:
            raise LightGBMError("streaming: source yielded no chunks")
        binned = np.concatenate(grow_parts, axis=0)
    elif row0 != num_rows:
        raise LightGBMError(
            f"streaming: pass 2 saw {row0} rows but pass 1 counted "
            f"{num_rows} — the source is not restartable or the data "
            "changed between passes")
    stats.pass2_s = time.perf_counter() - t_pass2
    if checkpoint_dir:
        _clear_stream_state(checkpoint_dir)

    # ---- assemble -----------------------------------------------------
    if label is None and label_parts:
        label = np.concatenate(label_parts)
    md = Metadata(int(binned.shape[0]), label=label, weight=weight,
                  group=group, init_score=init_score)
    ds = BinnedDataset(binned, used_mappers, used,
                       int(num_features), md, feature_names)
    ds.stream_stats = stats
    # in-memory arrays ride this spine for every Dataset; only real
    # streams are worth a visible line
    (Log.debug if source.array is not None else Log.info)(
        f"streaming: ingested {stats.rows} rows x {num_features} "
        f"features in {stats.chunks} chunks "
        f"({stats.rows_per_sec:.0f} rows/s, overlap "
        f"{stats.overlap_frac:.0%}, sample {stats.sample_rows}"
        f"{' exact' if stats.exact else ''})")
    return ds
