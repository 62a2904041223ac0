"""User-facing Dataset and Booster (reference python-package/lightgbm/basic.py).

The reference reaches the C++ core through ctypes over the 80-function C API
(c_api.h:53-1361); here `Booster` drives the JAX boosting core directly —
there is no FFI hop, but the public surface mirrors basic.py:
`Dataset(data, label, ...)` with lazy construction (basic.py:1163
_lazy_init) and `Booster(params, train_set)` (basic.py:2594) with
update/eval/predict/save_model/feature_importance.
"""

from __future__ import annotations

import io
import json
from typing import Any, Dict, List, Optional, Union
from typing import Sequence as TypingSequence

import numpy as np

from .binning import BinMapper
from .config import Config, param_dict_to_config
from .data import BinnedDataset, Metadata
from .metrics import METRIC_ALIASES, create_metric
from .objectives import create_objective
from .observability import span
from .utils.log import Log, LightGBMError
from .utils.file_io import open_file

__all__ = ["Dataset", "Booster", "LightGBMError"]


class Sequence:
    """Generic row-chunk provider for streamed Dataset construction
    (reference lightgbm.Sequence, basic.py; the C path is ChunkedArray +
    LGBM_DatasetPushRows). Subclasses implement __len__ and
    __getitem__ supporting slices returning 2-D row blocks; batch_size
    bounds how many rows are materialized at once."""

    batch_size = 4096

    def __len__(self):  # pragma: no cover - interface
        raise NotImplementedError

    def __getitem__(self, idx):  # pragma: no cover - interface
        raise NotImplementedError


def _is_chunked(data) -> bool:
    """list of row chunks (2-D arrays / Sequences) or a single Sequence:
    the streamed construction path."""
    if isinstance(data, Sequence):
        return True
    if isinstance(data, list) and data and not isinstance(data[0], list):
        return all(
            isinstance(c, Sequence) or
            (hasattr(c, "ndim") and getattr(c, "ndim", 0) == 2)
            for c in data)
    return False


def _is_sparse(data) -> bool:
    """scipy sparse matrix/array, duck-typed (no hard scipy import)."""
    return hasattr(data, "tocsc") and hasattr(data, "nnz")


def _is_pandas_df(data) -> bool:
    return hasattr(data, "dtypes") and hasattr(data, "columns") and \
        hasattr(data, "select_dtypes")


def _data_from_pandas(df, pandas_categorical=None):
    """DataFrame -> (f64 matrix, feature names, categorical column
    indices, pandas_categorical). Mirrors the reference's
    _data_from_pandas (basic.py:541-624): category-dtype columns are
    encoded as their category codes; the per-column category lists are
    remembered (training) or applied (prediction, so codes follow the
    TRAINING ordering regardless of the frame's own categories);
    unseen categories / NaN become NaN."""
    cat_cols = [str(c) for c in df.select_dtypes(
        include=["category"]).columns]
    names = [str(c) for c in df.columns]
    if pandas_categorical is None:   # training
        # .tolist() yields native python scalars so the model-file JSON
        # round-trips int/float categories exactly (np.int64 would
        # stringify and never match at predict time)
        pandas_categorical = [df[c].cat.categories.tolist()
                              for c in cat_cols]
    else:                            # prediction with a trained model
        if len(cat_cols) != len(pandas_categorical):
            raise ValueError(
                "train and valid dataset categorical_feature do not "
                "match.")
    df = df.copy(deep=False)
    for col, cats in zip(cat_cols, pandas_categorical):
        codes = df[col].cat.set_categories(cats).cat.codes
        df[col] = np.where(codes.values < 0, np.nan,
                           codes.values.astype(np.float64))
    X = np.ascontiguousarray(
        df.astype(np.float64).values, dtype=np.float64)
    cat_idx = [names.index(c) for c in cat_cols]
    return X, names, cat_idx, pandas_categorical


def _to_2d_float(data) -> np.ndarray:
    if hasattr(data, "values") and not isinstance(data, np.ndarray):
        data = data.values  # pandas
    if _is_sparse(data):
        return np.ascontiguousarray(data.toarray(), dtype=np.float64)
    arr = np.asarray(data)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if hasattr(arr, "toarray"):
        arr = arr.toarray()
    return np.ascontiguousarray(arr, dtype=np.float64)


def _numeric_2d_view(data) -> Optional[np.ndarray]:
    """All-numeric input that can skip `_to_2d_float`'s full float64
    copy: already a 2-D float ndarray (or memmap). Binning reads f32
    natively (cext) and casts chunk-wise otherwise, so these route
    through the streaming spine zero-copy (docs/Streaming.md)."""
    if isinstance(data, np.ndarray) and data.ndim == 2 and \
            data.dtype in (np.float32, np.float64) and data.shape[0] > 0:
        return data
    return None


def _load_svmlight_or_csv(path: str) -> np.ndarray:
    """Minimal text loader: CSV/TSV with optional label in first column.
    (Reference Parser auto-detect, src/io/parser.cpp.)"""
    with open_file(path) as fh:
        first = fh.readline()
    delim = "\t" if "\t" in first else ","
    with open_file(path) as fh:
        return np.loadtxt(fh, delimiter=delim)


def _sample_chunked_rows(chunks, take: int, seed: int) -> np.ndarray:
    """Materialize a row sample from a list of chunks/Sequences without
    loading more than one batch window at a time (the streamed analog of
    the reference's pre-allgather sampling, dataset_loader.cpp:722)."""
    lens = [len(c) if not hasattr(c, "shape") else c.shape[0]
            for c in chunks]
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    num_data = int(offsets[-1])
    rng = np.random.RandomState(seed)
    if num_data <= take:
        idx = np.arange(num_data)
    elif num_data > 4 * take:
        idx = np.unique(rng.randint(0, num_data, size=take))
    else:
        idx = np.sort(rng.choice(num_data, size=take, replace=False))
    parts = []
    for ci in range(len(chunks)):
        sel = idx[(idx >= offsets[ci]) & (idx < offsets[ci + 1])]
        if len(sel) == 0:
            continue
        local = sel - offsets[ci]
        step = getattr(chunks[ci], "batch_size", 65536) or 65536
        for lo in range(0, lens[ci], step):
            hi = min(lo + step, lens[ci])
            sel_b = local[(local >= lo) & (local < hi)]
            if len(sel_b) == 0:
                continue
            block = np.asarray(chunks[ci][lo:hi], dtype=np.float64)
            parts.append(block.reshape(hi - lo, -1)[sel_b - lo])
    return np.concatenate(parts, axis=0)


def _multihost_process_count() -> int:
    import jax
    try:
        return jax.process_count()
    except RuntimeError:
        return 1


def _allgather_find_mappers(sample, cfg, cat, sparse_in=False):
    """Collective half of distributed bin finding: every rank ships an
    equal-size subsample of its local `sample` rows via allgather and
    all ranks derive IDENTICAL BinMappers from the union — the TPU form
    of the reference's per-rank FindBin + Allgather of serialized
    mappers (dataset_loader.cpp:722-807). Must be called by every rank
    at the same program point.

    `sample=None` signals that this rank failed rank-local validation
    (e.g. its stream partition was empty): the rank still joins the
    agreement gather below, and then EVERY rank raises the same error.
    That agreement-before-data protocol is what makes rank-local
    failure safe here — a bare raise before the row allgather would
    strand peers in the collective (tpulint COLL002, the PR-7
    stream_bin_parity bug shape)."""
    import jax
    from .binning import find_bin_mappers
    from .parallel.comm import guarded_allgather
    from .reliability.watchdog import maybe_start_watchdog
    maybe_start_watchdog(cfg)
    nproc = jax.process_count()
    # agreement sync: gather one ok-flag per rank before any rank ships
    # rows, so validation failure is raised identically everywhere
    ok = np.asarray(0 if sample is None else 1, np.int64)
    oks = guarded_allgather(ok, label="bin_mapper_agree").reshape(-1)
    if int(oks.min(initial=1)) == 0:
        bad = [r for r in range(oks.shape[0]) if int(oks[r]) == 0]
        raise LightGBMError(
            f"distributed bin finding: rank(s) {bad} produced no "
            f"sample rows (empty partition?) — all ranks abort "
            f"together")
    per = max(1, cfg.bin_construct_sample_cnt // nproc)
    n_local = sample.shape[0]
    # variable-size sample gather with fixed wire shapes: every rank
    # ships `per` rows (zero-padded) plus its true count, and the
    # padding is stripped after the gather — the reference's
    # variable-size mapper allgather (dataset_loader.cpp:722-807)
    n_samp = min(per, n_local)
    if n_local > n_samp:
        rng = np.random.RandomState(cfg.data_random_seed)
        idx = np.sort(rng.choice(n_local, size=n_samp, replace=False))
        sample = sample[idx]
    else:
        sample = sample[:n_samp]
    if sparse_in:
        sample = sample.toarray()  # densify the sample rows only
    sample = np.ascontiguousarray(sample, dtype=np.float64)
    if n_samp < per:
        sample = np.pad(sample, ((0, per - n_samp), (0, 0)))
    sizes = guarded_allgather(np.asarray(n_samp, np.int64),
                              label="bin_mapper_sizes")
    gathered = guarded_allgather(sample, label="bin_mapper_rows")
    union = np.concatenate(
        [gathered[r, :int(sizes[r])] for r in range(nproc)])
    return find_bin_mappers(
        union, max_bin=cfg.max_bin, min_data_in_bin=cfg.min_data_in_bin,
        sample_cnt=len(union), use_missing=cfg.use_missing,
        zero_as_missing=cfg.zero_as_missing, categorical_features=cat,
        seed=cfg.data_random_seed)


def _distributed_bin_mappers(X, cfg, cat, sparse_in):
    """Multi-machine bin finding over local random-access data: sample
    locally, then `_allgather_find_mappers`. Returns None
    single-process."""
    if _multihost_process_count() <= 1:
        return None
    import jax
    nproc = jax.process_count()
    per = max(1, cfg.bin_construct_sample_cnt // nproc)
    chunked = not (hasattr(X, "shape") or _is_sparse(X))
    if chunked:
        # streamed input: sample rows out of the local chunk iterator and
        # allgather exactly like the array path — the reference's
        # distributed loader samples from any local iterator the same way
        # (dataset_loader.cpp:722-807 sample-then-allgather)
        X = _sample_chunked_rows(X, per, cfg.data_random_seed)
        sparse_in = False
    return _allgather_find_mappers(X, cfg, cat, sparse_in)


def _streaming_mapper_sync(cfg, cat):
    """Multihost hook for pure streams (no random-access `.array`): the
    loader hands each rank's pass-1 sketch sample to this closure, which
    runs the same allgather the array path uses, so every rank freezes
    IDENTICAL bin boundaries before the collective histogram psum.
    Returns None single-process (the loader then bins locally).

    Resolution goes through `distributed.binning.distributed_mapper_sync`
    (sketch telemetry + the documented distributed-binning entry point);
    the fallback below keeps the delegate target explicit for the
    collective manifest: the closure ultimately runs
    `_allgather_find_mappers(sample, cfg, cat)` either way."""
    from .distributed.binning import distributed_mapper_sync
    sync = distributed_mapper_sync(cfg, cat)
    if sync is None and _multihost_process_count() > 1:
        return lambda sample: _allgather_find_mappers(sample, cfg, cat)
    return sync


class Dataset:
    """Lazily-constructed binned dataset (reference basic.py:1163)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._binned: Optional[BinnedDataset] = None
        self.used_indices = None

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        if self._binned is not None:
            return self
        # ingest.construct: parsing, bin finding (dataset_sample,
        # dataset_bounds) and quantizing (dataset_quantize) nest in it
        with span("ingest.construct") as sp:
            self._construct()
            cat = [m for m in self._binned.mappers if m.is_categorical]
            sp.attrs.update(rows=int(self._binned.num_data),
                            features=int(self._binned.num_total_features),
                            categorical=len(cat),
                            levels=sum(m.num_levels for m in cat),
                            levels_dropped=sum(
                                m.num_levels - (m.num_bin - 1) for m in cat))
        return self

    def _construct(self) -> "Dataset":
        cfg = param_dict_to_config(self.params)
        data = self.data
        if isinstance(data, str):
            if BinnedDataset.is_binary_file(data):
                # binary fast path (reference LoadFromBinFile,
                # dataset_loader.cpp:274): skip parsing + bin finding;
                # constructor-arg metadata overrides what the cache stored
                self._binned = BinnedDataset.load_binary(data)
                if self.reference is not None:
                    # a cached valid set must share the training dataset's
                    # bin mappers (reference Dataset::CheckAlign via
                    # LGBM_BoosterAddValidData: "different bin mappers
                    # with training data")
                    self.reference.construct()
                    ref = self.reference._binned
                    same = (
                        ref.num_total_features ==
                        self._binned.num_total_features and
                        np.array_equal(ref.used_features,
                                       self._binned.used_features) and
                        all(a.to_dict() == b.to_dict() for a, b in
                            zip(ref.mappers, self._binned.mappers)))
                    if not same:
                        raise ValueError(
                            "Cannot use binary dataset file as validation "
                            "data: it has different bin mappers than the "
                            "training data. Re-save it with "
                            "reference=<train dataset>.")
                md = self._binned.metadata
                self._binned.metadata = Metadata(
                    self._binned.num_data,
                    label=self.label if self.label is not None else md.label,
                    weight=self.weight if self.weight is not None
                    else md.weight,
                    group=np.asarray(self.group) if self.group is not None
                    else md.query_boundaries,
                    init_score=self.init_score
                    if self.init_score is not None else md.init_score)
                if self.free_raw_data:
                    self.data = None
                return self
            if cfg.stream_input:
                # out-of-core route: never materialize the text file —
                # chunks stream through the two-pass loader instead
                from .streaming import source_from_path
                # the raw label_column spec (index, digit string, or
                # name:) resolves per source format inside
                # source_from_path — Parquet maps it to a schema column
                lc = cfg.label_column if cfg.label_column else 0
                data = source_from_path(
                    data, chunk_rows=int(cfg.stream_chunk_rows),
                    label_col=None if self.label is not None else lc,
                    header=bool(cfg.header))
            else:
                raw = _load_svmlight_or_csv(data)
                if self.label is None:
                    self.label, raw = raw[:, 0], raw[:, 1:]
                data = raw
        from .streaming import ChunkSource
        stream_src = data if isinstance(data, ChunkSource) else None
        chunked_in = stream_src is None and _is_chunked(data)
        if chunked_in:
            data = [data] if isinstance(data, Sequence) else data
        sparse_in = stream_src is None and not chunked_in and \
            _is_sparse(data)
        pandas_cat = None
        pandas_cat_idx: List[int] = []
        if chunked_in:
            X = data  # row chunks; streamed two-pass construction
            names_from_df = None
        elif _is_pandas_df(data):
            # category-dtype columns: codes + remembered category lists
            # (reference basic.py:541-624); round-trips through the
            # model file's pandas_categorical JSON. Valid sets encode
            # with the TRAINING dataset's category order.
            ref_pc = None
            if self.reference is not None:
                self.reference.construct()
                ref_pc = getattr(self.reference._binned,
                                 "pandas_categorical", None)
            X, df_names, pandas_cat_idx, pandas_cat = \
                _data_from_pandas(data, ref_pc)
            names_from_df = df_names
        elif stream_src is not None:
            X = stream_src
            names_from_df = None
        else:
            # sparse stays sparse through binning (reference SparseBin /
            # __init_from_csr): only the uint8 bin matrix is densified
            if sparse_in:
                X = data
            else:
                X = None if cfg.linear_tree else _numeric_2d_view(data)
                if X is None:
                    X = _to_2d_float(data)
            names_from_df = None
        names: Optional[List[str]] = None
        if self.feature_name != "auto" and self.feature_name is not None:
            names = list(self.feature_name)
        elif names_from_df is not None:
            names = names_from_df
        elif hasattr(self.data, "columns"):
            names = [str(c) for c in self.data.columns]
        cat: List[int] = []
        if self.categorical_feature != "auto" and self.categorical_feature:
            for c in self.categorical_feature:
                if isinstance(c, str):
                    if names and c in names:
                        cat.append(names.index(c))
                else:
                    cat.append(int(c))
        elif cfg.categorical_feature:
            cat = [int(c) for c in str(cfg.categorical_feature).split(",")
                   if c != ""]
        elif pandas_cat_idx:
            cat = list(pandas_cat_idx)  # 'auto': category-dtype columns
        if stream_src is None and not chunked_in and not sparse_in and \
                not cfg.linear_tree and _numeric_2d_view(X) is not None:
            # all-numeric in-memory input rides the same ChunkSource
            # spine as disk streams — zero-copy row slices instead of a
            # separate whole-matrix float64 copy path
            from .streaming import ArraySource
            stream_src = ArraySource(X,
                                     chunk_rows=int(cfg.stream_chunk_rows))
        if stream_src is not None:
            self._binned = self._construct_streamed(
                stream_src, cfg, cat, names)
            self._binned.pandas_categorical = pandas_cat
            if self.free_raw_data:
                self.data = None
            return self
        construct_binned = (
            BinnedDataset.from_chunks if chunked_in
            else BinnedDataset.from_sparse if sparse_in
            else BinnedDataset.from_raw)
        n_rows = sum(len(c) for c in X) if chunked_in else X.shape[0]
        label = None if self.label is None else \
            np.asarray(self.label, dtype=np.float32).reshape(-1)
        md = Metadata(n_rows, label=label,
                      weight=None if self.weight is None else
                      np.asarray(self.weight, np.float32),
                      group=None if self.group is None else
                      np.asarray(self.group),
                      init_score=None if self.init_score is None else
                      np.asarray(self.init_score))
        ref_mappers: Optional[List[BinMapper]] = None
        if self.reference is not None:
            self.reference.construct()
            ref = self.reference._binned
            # align: valid sets reuse the training BinMappers
            # (reference LoadFromFileAlignWithOtherDataset,
            # dataset_loader.cpp:299)
            full = [None] * ref.num_total_features
            for j, f in enumerate(ref.used_features):
                full[int(f)] = ref.mappers[j]
            trivial = BinMapper()
            ref_mappers = [m if m is not None else trivial for m in full]
            self._binned = construct_binned(
                X, md, max_bin=cfg.max_bin,
                min_data_in_bin=cfg.min_data_in_bin,
                mappers=ref_mappers, feature_names=names,
                feature_pre_filter=False, keep_raw=cfg.linear_tree)
            # keep only the reference's used features
            keep = ref.used_features
            self._binned = BinnedDataset(
                self._binned.bins[:, keep], [ref_mappers[int(f)] for f in keep],
                keep, ref.num_total_features, md, names,
                raw=None if self._binned.raw is None
                else self._binned.raw[:, keep])
        else:
            dist_mappers = _distributed_bin_mappers(X, cfg, cat, sparse_in)
            self._binned = construct_binned(
                X, md, max_bin=cfg.max_bin,
                min_data_in_bin=cfg.min_data_in_bin,
                sample_cnt=cfg.bin_construct_sample_cnt,
                use_missing=cfg.use_missing,
                zero_as_missing=cfg.zero_as_missing,
                categorical_features=cat, seed=cfg.data_random_seed,
                feature_names=names,
                feature_pre_filter=cfg.feature_pre_filter,
                keep_raw=cfg.linear_tree, mappers=dist_mappers,
                pre_filter_with_mappers=dist_mappers is not None)
        self._binned.pandas_categorical = pandas_cat
        if self.free_raw_data:
            self.data = None
        return self

    def _construct_streamed(self, source, cfg, cat, names):
        """Two-pass construction over a ChunkSource (streaming/loader):
        the out-of-core route for disk streams and the zero-copy route
        for in-memory numeric arrays. Covering sketches reproduce the
        in-memory bin mappers bit-for-bit (docs/Streaming.md)."""
        from .streaming import build_streamed_dataset
        kwargs = dict(
            label=None if self.label is None else
            np.asarray(self.label, dtype=np.float32).reshape(-1),
            weight=None if self.weight is None else
            np.asarray(self.weight, np.float32),
            group=None if self.group is None else np.asarray(self.group),
            init_score=None if self.init_score is None else
            np.asarray(self.init_score),
            max_bin=cfg.max_bin, min_data_in_bin=cfg.min_data_in_bin,
            sample_cnt=cfg.bin_construct_sample_cnt,
            use_missing=cfg.use_missing,
            zero_as_missing=cfg.zero_as_missing,
            categorical_features=cat, seed=cfg.data_random_seed,
            feature_names=names,
            sample_rows=int(cfg.stream_sample_rows),
            bin_parity=bool(cfg.stream_bin_parity))
        if self.reference is not None:
            # align: valid sets reuse the training BinMappers and bin
            # exactly its used columns (reference
            # LoadFromFileAlignWithOtherDataset, dataset_loader.cpp:299)
            self.reference.construct()
            ref = self.reference._binned
            full = [None] * ref.num_total_features
            for j, f in enumerate(ref.used_features):
                full[int(f)] = ref.mappers[j]
            trivial = BinMapper()
            ref_mappers = [m if m is not None else trivial for m in full]
            return build_streamed_dataset(
                source, mappers=ref_mappers, feature_pre_filter=False,
                used_override=np.asarray(ref.used_features, np.int32),
                **kwargs)
        dist = None
        sync = None
        if source.array is not None:
            dist = _distributed_bin_mappers(source.array, cfg, cat, False)
        else:
            # pure stream (no random-access matrix): the loader's pass-1
            # sketch sample feeds this collective so every rank freezes
            # identical boundaries; None single-process
            sync = _streaming_mapper_sync(cfg, cat)
        return build_streamed_dataset(
            source, mappers=dist, mapper_sync=sync,
            feature_pre_filter=cfg.feature_pre_filter,
            pre_filter_with_mappers=dist is not None,
            checkpoint_dir=cfg.checkpoint_dir or None, **kwargs)

    # ------------------------------------------------------------------
    def num_data(self) -> int:
        self.construct()
        return self._binned.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._binned.num_total_features

    def get_label(self):
        if self.label is not None:
            return np.asarray(self.label)
        if self._binned is not None:
            return self._binned.metadata.label
        return None

    def get_weight(self):
        return self.weight

    def get_group(self):
        return self.group

    def get_init_score(self):
        return self.init_score

    def set_label(self, label):
        self.label = label
        if self._binned is not None:
            self._binned.metadata.label = np.asarray(
                label, np.float32).reshape(-1)
        return self

    def set_weight(self, weight):
        self.weight = weight
        if self._binned is not None and weight is not None:
            self._binned.metadata.weight = np.asarray(weight, np.float32)
        return self

    def set_group(self, group):
        self.group = group
        if self._binned is not None and group is not None:
            self._binned.metadata.__init__(
                self._binned.num_data, self._binned.metadata.label,
                self._binned.metadata.weight, np.asarray(group),
                self._binned.metadata.init_score)
        return self

    def set_init_score(self, init_score):
        self.init_score = init_score
        # a user-provided score replaces any continuation seed, so the
        # init_model double-count guard must see it as user-owned again
        self._seeded_init_score = False
        if self._binned is not None:
            self._binned.metadata.init_score = None if init_score is None \
                else np.asarray(init_score, np.float32)
        return self

    def set_field(self, name, data):
        return {"label": self.set_label, "weight": self.set_weight,
                "group": self.set_group,
                "init_score": self.set_init_score}[name](data)

    def get_field(self, name):
        return {"label": self.get_label, "weight": self.get_weight,
                "group": self.get_group,
                "init_score": self.get_init_score}[name]()

    def subset(self, used_indices: TypingSequence[int], params=None) -> "Dataset":
        self.construct()
        sub = Dataset(None, params=params or self.params)
        sub._binned = self._binned.subset(np.asarray(used_indices))
        sub._binned.pandas_categorical = getattr(
            self._binned, "pandas_categorical", None)
        sub.reference = self
        return sub

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        """Validation Dataset aligned with this one's bin mappers
        (reference basic.py Dataset.create_valid; the C path is
        LoadFromFileAlignWithOtherDataset, dataset_loader.cpp:299)."""
        return Dataset(data, label=label, weight=weight, group=group,
                       init_score=init_score, reference=self,
                       params=params or self.params,
                       free_raw_data=self.free_raw_data)

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        """Change the categorical features (reference basic.py
        set_categorical_feature, :2092-2100): after construction the
        binned data is dropped and lazily rebuilt — possible only while
        the raw data is retained (free_raw_data=False)."""
        if self.categorical_feature == categorical_feature:
            return self
        if self._binned is not None:
            if self.data is None:
                raise LightGBMError(
                    "Cannot set categorical feature after freed raw "
                    "data, set free_raw_data=False when construct "
                    "Dataset to avoid this.")
            from .utils.log import Log
            Log.warning("categorical_feature in Dataset is overridden.\n"
                        "New categorical_feature is %s",
                        sorted(list(categorical_feature))
                        if not isinstance(categorical_feature, str)
                        else categorical_feature)
            self._binned = None  # lazily re-constructed with the new set
        self.categorical_feature = categorical_feature
        return self

    def save_binary(self, filename: str) -> "Dataset":
        """Write the constructed dataset to a binary cache file
        (reference basic.py Dataset.save_binary / LGBM_DatasetSaveBinary)."""
        self.construct()
        if getattr(self, "_seeded_init_score", False):
            # continuation seeds are transient training state; persisting
            # them would silently shift any model later trained from the
            # cache (the loaded Dataset cannot know they were seeded)
            saved = self._binned.metadata.init_score
            self._binned.metadata.init_score = None
            try:
                self._binned.save_binary(filename)
            finally:
                self._binned.metadata.init_score = saved
        else:
            self._binned.save_binary(filename)
        return self

    @property
    def binned(self) -> BinnedDataset:
        self.construct()
        return self._binned


class Booster:
    """Training/prediction handle (reference basic.py:2594 + c_api.cpp:106).

    Thread-safety note: the reference guards the C Booster with a
    shared_mutex (c_api.cpp:827); here the GIL plus JAX's functional arrays
    make mutation points (update/save) naturally serialized.
    """

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        from .boosting.gbdt import create_boosting
        self.params = dict(params or {})
        self.config = param_dict_to_config(self.params)
        Log.set_verbosity(self.config.verbosity)
        from .observability import registry as _obs
        _obs.configure_from_config(self.config)
        self._model = None          # HostModel once finalized/loaded
        self.gbdt = None
        self.train_set = None
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._train_metric_objs = []
        if model_file is not None:
            with open_file(model_file) as fh:
                model_str = fh.read()
        if model_str is not None:
            from .tree import HostModel
            self._model = HostModel.from_string(model_str)
            return
        if train_set is None:
            raise LightGBMError("Booster needs train_set or a model")
        if not isinstance(train_set, Dataset):
            raise TypeError("train_set must be a Dataset")
        self.train_set = train_set
        merged = dict(train_set.params)
        merged.update(self.params)
        train_set.params = merged
        train_set.construct()
        cfg = self.config
        objective = create_objective(cfg.objective, cfg)
        metric_names = cfg.metric_list()
        if not metric_names and cfg.objective in METRIC_ALIASES:
            metric_names = [cfg.objective]
        metrics = [m for m in (create_metric(nm, cfg) for nm in metric_names)
                   if m is not None]
        binned = train_set.binned
        for m in metrics:
            m.init(binned.metadata, binned.num_data)
        self._metric_names = metric_names
        self.gbdt = create_boosting(cfg, binned, objective,
                                    metrics if cfg.is_provide_training_metric
                                    else metrics)
        self.name_valid_sets: List[str] = []
        self._valid_data: List[Dataset] = []

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.reference = self.train_set
        if self.config.linear_tree and data._binned is None:
            # valid sets need raw values too when leaves hold linear models
            data.params = dict(data.params or {}, linear_tree=True)
        data.construct()
        cfg = self.config
        metrics = [m for m in (create_metric(nm, cfg)
                               for nm in self._metric_names) if m is not None]
        for m in metrics:
            m.init(data.binned.metadata, data.binned.num_data)
        self.gbdt.add_valid(data.binned, name, metrics)
        self.name_valid_sets.append(name)
        self._valid_data.append(data)
        return self

    def update(self, train_set=None, fobj=None) -> bool:
        """One boosting iteration; returns True if no further splits
        (reference LGBM_BoosterUpdateOneIter)."""
        self._model = None
        if fobj is not None:
            import jax.numpy as jnp
            # user-supplied gradients: the configured objective's
            # constant-hessian promise no longer holds (engine.train
            # handles this by resetting objective to "none"; this direct
            # path must neutralize the fast-path gate itself)
            self.gbdt.set_custom_objective()
            score = self.gbdt.train_score
            grad, hess = fobj(np.asarray(score), self.train_set)
            return self.gbdt.train_one_iter(
                jnp.asarray(grad, jnp.float32).reshape(score.shape),
                jnp.asarray(hess, jnp.float32).reshape(score.shape))
        return self.gbdt.train_one_iter()

    def update_batch(self, num_iterations: int) -> bool:
        """Run several boosting iterations with a single device dispatch
        (the fused on-device scan, boosting/fused.py) when the
        configuration allows, else a plain update() loop. Semantically
        identical to calling update() num_iterations times; the win is
        one dispatch and at most one host sync per block instead of per
        tree. Returns True if training cannot continue."""
        self._model = None
        return self.gbdt.train_many(num_iterations)

    def update_batch_dispatch(self, num_iterations: int) -> dict:
        """update_batch split at the tree-unpack boundary: run the block
        (scores/RNG/valid trajectories fully advanced) and return a
        handle whose finalize_block call appends the trees. The
        pipelined executor (pipeline/executor.py) defers finalize into
        the next block's device window; update_batch == finalize_block(
        update_batch_dispatch(n)) exactly."""
        self._model = None
        return self.gbdt.train_many_dispatch(num_iterations)

    def finalize_block(self, handle: dict) -> bool:
        self._model = None
        return self.gbdt.finalize_block(handle)

    def rollback_one_iter(self) -> "Booster":
        self._model = None
        self.gbdt.rollback_one_iter()
        return self

    # ------------------------------------------------------------------
    # training-state serialization (reliability/checkpoint.py bundles)
    def _training_state(self):
        """(json-state, arrays) capturing everything `model_to_string`
        does NOT: the exact f32 score state, RNG stream position,
        mid-period bagging mask and boost-from-average flags. Together
        with the saved model text this is sufficient for
        `_restore_training_state` to continue the run bit-for-bit."""
        state, arrays = self.gbdt.training_state()
        state["best_iteration"] = int(self.best_iteration)
        return state, arrays

    def _restore_training_state(self, ckpt) -> None:
        """Restore from a `reliability.checkpoint.CheckpointState`.

        The caller (engine.train resume path) has already attached the
        checkpointed model as `_base_model`; this restores the live
        training state on top of it."""
        self._model = None
        self.gbdt.restore_training_state(ckpt.iteration, ckpt.state,
                                         ckpt.arrays)
        best = int(ckpt.state.get("best_iteration", -1))
        if best >= 0:
            self.best_iteration = best

    def current_iteration(self) -> int:
        if self.gbdt is not None:
            n = self.gbdt.current_iteration()
            base = getattr(self, "_base_model", None)
            if base is not None:
                n += base.current_iteration()  # continued training
            return n
        return self._model.num_iterations if self._model else 0

    @property
    def num_trees_per_iteration(self) -> int:
        if self.gbdt is not None:
            return self.gbdt.num_tree_per_iteration
        return self._model.num_tree_per_iteration if self._model else 1

    def num_model_per_iteration(self) -> int:
        return self.num_trees_per_iteration

    def num_trees(self) -> int:
        if self.gbdt is not None:
            n = len(self.gbdt.trees)
            base = getattr(self, "_base_model", None)
            if base is not None:
                n += base.num_trees()   # continued training keeps base trees
            return n
        return len(self._model.trees) if self._model else 0

    # ------------------------------------------------------------------
    train_data_name = "training"

    def eval_train(self, feval=None) -> List:
        res = []
        for name, val in self.gbdt.eval_train().items():
            higher = name in ("auc", "ndcg", "map", "average_precision",
                              "auc_mu") or name.split("@")[0] in ("ndcg", "map")
            res.append((self.train_data_name, name, val, higher))
        res.extend(self._custom_eval(feval, self.train_data_name, None))
        return res

    def eval_valid(self, feval=None) -> List:
        res = []
        for i, name in enumerate(self.name_valid_sets):
            for mname, val in self.gbdt.eval_valid(i).items():
                higher = mname.split("@")[0] in (
                    "auc", "ndcg", "map", "average_precision", "auc_mu")
                res.append((name, mname, val, higher))
            res.extend(self._custom_eval(feval, name, i))
        return res

    def _custom_eval(self, feval, data_name, valid_idx):
        if feval is None:
            return []
        funcs = feval if isinstance(feval, (list, tuple)) else [feval]
        if valid_idx is None:
            score, data = self.gbdt.train_score, self.train_set
        else:
            score, data = self.gbdt.valid_scores[valid_idx], \
                self._valid_data[valid_idx]
        out = []
        for fn in funcs:
            r = fn(np.asarray(score), data)
            rs = r if isinstance(r, list) else [r]
            for name, val, higher in rs:
                out.append((data_name, name, val, higher))
        return out

    # ------------------------------------------------------------------
    def _host_model(self):
        from .tree import HostModel
        if self._model is None:
            model = HostModel.from_gbdt(self.gbdt, self.train_set)
            base = getattr(self, "_base_model", None)
            if base is not None:
                # continued training: the saved/served model keeps the
                # base model's trees in front of the new ones (reference
                # Booster(model_file=...) + train semantics)
                bm = base._host_model()
                model.trees = list(bm.trees) + model.trees
                model.tree_class = list(bm.tree_class) + model.tree_class
                if not model.feature_names and bm.feature_names:
                    model.feature_names = bm.feature_names
                    model.feature_infos = bm.feature_infos
                    model.max_feature_idx = bm.max_feature_idx
            self._model = model
        return self._model

    def device_forest(self):
        """Memoized device-stacked serving forest (serving/forest.py).

        Repeated serving calls reuse the resident arrays instead of
        re-stacking the trees per call. Invalidation is by HostModel
        identity: every mutation point (update / update_batch /
        rollback_one_iter / model reload) clears `self._model`, so the
        next call here sees a fresh HostModel object and rebuilds."""
        model = self._host_model()
        cached = getattr(self, "_device_forest", None)
        if cached is not None and cached._model is model:
            return cached
        from .serving.forest import build_device_forest
        self._device_forest = build_device_forest(model)
        return self._device_forest

    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                validate_features: bool = False,
                pred_early_stop: bool = False,
                pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0,
                **kwargs) -> np.ndarray:
        model = self._host_model()
        kw = dict(start_iteration=start_iteration,
                  num_iteration=num_iteration, raw_score=raw_score,
                  pred_leaf=pred_leaf, pred_contrib=pred_contrib,
                  pred_early_stop=pred_early_stop,
                  pred_early_stop_freq=pred_early_stop_freq,
                  pred_early_stop_margin=pred_early_stop_margin)
        if _is_pandas_df(data) and model.pandas_categorical is not None:
            # encode category columns with the TRAINING category order
            # (reference basic.py predict-time _data_from_pandas)
            data, _, _, _ = _data_from_pandas(
                data, model.pandas_categorical)
        if _is_sparse(data):
            # densify in row chunks so wide-sparse inputs never need the
            # full dense matrix in memory (reference predicts CSR rows
            # natively, c_api.cpp PredictForCSR)
            csr = data.tocsr()
            if csr.shape[0] == 0:
                return model.predict(
                    np.zeros((0, csr.shape[1]), np.float64), **kw)
            chunk = max(1, int(32 << 20) // max(1, 8 * csr.shape[1]))
            outs = [model.predict(_to_2d_float(csr[i:i + chunk]), **kw)
                    for i in range(0, csr.shape[0], chunk)]
            if pred_contrib:
                # contribs are [n, F+1]: dense would defeat the chunking
                # on wide-sparse inputs; the reference also returns a
                # sparse matrix for sparse contrib input (c_api
                # PredictForCSR contrib path)
                import scipy.sparse as _sp
                return _sp.vstack([_sp.csr_matrix(o) for o in outs])
            return np.concatenate(outs, axis=0)
        return model.predict(_to_2d_float(data), **kw)

    def refit(self, data, label, decay_rate: Optional[float] = None,
              **kwargs) -> "Booster":
        """Refit leaf values on new data (reference gbdt.cpp:287 RefitTree)."""
        model = self._host_model()
        decay = self.config.refit_decay_rate if decay_rate is None \
            else decay_rate
        new_model = model.refit(_to_2d_float(data),
                                np.asarray(label, np.float32), decay,
                                self.config)
        new_booster = Booster(params=self.params,
                              model_str=new_model.to_string())
        return new_booster

    # ------------------------------------------------------------------
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "Booster":
        with open_file(filename, "w") as fh:
            fh.write(self.model_to_string(num_iteration, start_iteration,
                                          importance_type))
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        return self._host_model().to_string(
            num_iteration=num_iteration, start_iteration=start_iteration)

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> dict:
        return self._host_model().to_json(num_iteration, start_iteration)

    # ------------------------------------------------------------------
    def feature_name(self) -> List[str]:
        return self._host_model().feature_names

    def num_feature(self) -> int:
        return self._host_model().max_feature_idx + 1

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        return self._host_model().feature_importance(importance_type)

    def lower_bound(self):
        model = self._host_model()
        return min((t.leaf_value.min() for t in model.trees), default=0.0)

    def upper_bound(self):
        model = self._host_model()
        return max((t.leaf_value.max() for t in model.trees), default=0.0)

    def free_dataset(self) -> "Booster":
        self.train_set = None
        return self

    def free_network(self) -> "Booster":
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        self.params.update(params)
        self.config.update(params)
        if self.gbdt is not None:
            self.gbdt.shrinkage_rate = float(self.config.learning_rate)
            self.gbdt.config = self.config
            # the fused multi-tree scan bakes shrinkage/grower settings
            # into its compiled closure — rebuild on next update_batch
            self.gbdt._fused_run = None
        return self

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, _):
        return Booster(params=self.params, model_str=self.model_to_string())
