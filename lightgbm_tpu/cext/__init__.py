"""ctypes bridge to the native host runtime (lightgbm_tpu/cext/binning.cpp).

Reference analog: the C++ data layer (DatasetLoader/Parser/BinMapper hot
paths). The libraries are built from the tracked sources at first use
with the system compiler (g++ -O3 -shared) into this directory — no
binary is committed. A build is reused while the hash of its source,
recorded beside it at build time, still matches; file times mean
nothing after a checkout or a copy. Without a working compiler the
NumPy implementations take over, and a warning says why.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

from ..utils.log import Log

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "binning.cpp")
_LIB_PATH = os.path.join(_DIR, "libbinning.so")

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _source_hash(src: str) -> str:
    with open(src, "rb") as fh:
        return hashlib.sha256(fh.read()).hexdigest()


def _load_or_build(src: str, lib_path: str,
                   flag_sets=((),)) -> Optional[ctypes.CDLL]:
    """Load lib_path, first building it from src unless the hash
    recorded at its last build (lib_path + ".sha256") matches the
    source; None — with a warning naming the cause — when it cannot be
    built or loaded."""
    want = _source_hash(src)
    stamp = lib_path + ".sha256"
    have = ""
    if os.path.exists(lib_path) and os.path.exists(stamp):
        with open(stamp) as fh:
            have = fh.read().strip()
    if have != want:
        # build beside the target and rename: concurrent first users
        # (test workers, 2-rank harnesses) never load a half-written file
        tmp = "%s.%d.tmp" % (lib_path, os.getpid())
        err = "no flag set tried"
        for flags in flag_sets:
            cmd = (["g++", "-O3", "-shared", "-fPIC", "-std=c++17"]
                   + list(flags) + [src, "-o", tmp])
            try:
                subprocess.run(cmd, check=True, capture_output=True,
                               timeout=120)
            except subprocess.CalledProcessError as exc:
                err = exc.stderr.decode(errors="replace").strip()[-400:]
            except (OSError, subprocess.TimeoutExpired) as exc:
                err = "%s: %s" % (type(exc).__name__, exc)
            else:
                os.replace(tmp, lib_path)
                with open(stamp, "w") as fh:
                    fh.write(want + "\n")
                break
        else:
            if os.path.exists(tmp):
                os.remove(tmp)
            Log.warning("native library %s not built (%s); the NumPy "
                        "implementations are used",
                        os.path.basename(lib_path), err)
            return None
    try:
        return ctypes.CDLL(lib_path)
    except OSError as exc:
        Log.warning("native library %s not loaded (%s); the NumPy "
                    "implementations are used",
                    os.path.basename(lib_path), exc)
        return None


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    lib = _load_or_build(_SRC, _LIB_PATH, flag_sets=(("-fopenmp",), ()))
    if lib is None:
        return None
    c_dp = ctypes.POINTER(ctypes.c_double)
    c_ip = ctypes.POINTER(ctypes.c_int)
    lib.lgbt_greedy_find_bin.restype = ctypes.c_int
    lib.lgbt_greedy_find_bin.argtypes = [
        c_dp, c_ip, ctypes.c_int, ctypes.c_int, ctypes.c_long,
        ctypes.c_int, c_dp]
    lib.lgbt_distinct.restype = ctypes.c_int
    lib.lgbt_distinct.argtypes = [c_dp, ctypes.c_int, c_dp, c_ip]
    lib.lgbt_parse_delimited.restype = ctypes.c_long
    lib.lgbt_parse_delimited.argtypes = [
        ctypes.c_char_p, ctypes.c_char, ctypes.c_int, c_dp, ctypes.c_long,
        ctypes.c_int, c_ip]
    lib.lgbt_count_rows.restype = ctypes.c_long
    lib.lgbt_count_rows.argtypes = [ctypes.c_char_p, ctypes.c_char, c_ip]
    lib.lgbt_values_to_bins.restype = None
    lib.lgbt_values_to_bins.argtypes = [
        c_dp, ctypes.c_long, c_dp, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint8)]
    lib.lgbt_bin_matrix.restype = None
    lib.lgbt_bin_matrix.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_long, ctypes.c_int,
        c_ip, ctypes.c_int,
        c_dp, ctypes.POINTER(ctypes.c_long), c_ip, c_ip,
        ctypes.c_int, ctypes.c_void_p]
    lib.lgbt_sample_transpose.restype = None
    lib.lgbt_sample_transpose.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_long), ctypes.c_long, c_dp]
    lib.lgbt_find_numeric_bounds.restype = ctypes.c_int
    lib.lgbt_find_numeric_bounds.argtypes = [
        c_dp, ctypes.c_int, ctypes.c_long, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
        c_dp, c_ip, c_ip, c_dp, ctypes.POINTER(ctypes.c_long)]
    _lib = lib
    return _lib


def available() -> bool:
    return get_lib() is not None


def greedy_find_bin(distinct: np.ndarray, counts: np.ndarray, max_bin: int,
                    total_cnt: int, min_data_in_bin: int) -> np.ndarray:
    """Native GreedyFindBin; returns bin upper bounds (last = +inf)."""
    lib = get_lib()
    assert lib is not None
    distinct = np.ascontiguousarray(distinct, np.float64)
    counts = np.ascontiguousarray(counts, np.int32)
    out = np.empty(max_bin + 2, np.float64)
    n = lib.lgbt_greedy_find_bin(
        distinct.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        len(distinct), max_bin, total_cnt, min_data_in_bin,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    return out[:n]


def distinct_values(sorted_values: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    lib = get_lib()
    assert lib is not None
    sorted_values = np.ascontiguousarray(sorted_values, np.float64)
    vals = np.empty(len(sorted_values), np.float64)
    cnts = np.empty(len(sorted_values), np.int32)
    k = lib.lgbt_distinct(
        sorted_values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        len(sorted_values),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        cnts.ctypes.data_as(ctypes.POINTER(ctypes.c_int)))
    return vals[:k], cnts[:k].astype(np.int64)


def parse_delimited(path: str, delim: str = ",",
                    skip_rows: int = 0) -> Optional[np.ndarray]:
    """Native text parse to a dense [rows, cols] float64 matrix."""
    lib = get_lib()
    if lib is None:
        return None
    cols = ctypes.c_int(0)
    rows = lib.lgbt_count_rows(path.encode(), delim.encode(),
                               ctypes.byref(cols))
    if rows <= 0 or cols.value <= 0:
        return None
    rows -= skip_rows
    out = np.zeros((rows, cols.value), np.float64)
    got_cols = ctypes.c_int(0)
    got = lib.lgbt_parse_delimited(
        path.encode(), delim.encode(), skip_rows,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        rows, cols.value, ctypes.byref(got_cols))
    if got < 0:
        return None
    return out[:got, :got_cols.value]


def values_to_bins_u8(values: np.ndarray, bounds: np.ndarray,
                      num_search: int, nan_bin: int) -> np.ndarray:
    lib = get_lib()
    assert lib is not None
    values = np.ascontiguousarray(values, np.float64)
    bounds = np.ascontiguousarray(bounds, np.float64)
    out = np.empty(len(values), np.uint8)
    lib.lgbt_values_to_bins(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        len(values),
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        num_search, nan_bin,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out


def sample_transpose(X: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Fused X[idx].T + float64 cast: one native streaming pass instead of
    the gather / transpose / cast NumPy chain. X must be C-contiguous
    [N, F] float32 or float64; idx sorted int64 row indices. Returns a
    contiguous [F, len(idx)] float64 sample, bit-identical to
    np.ascontiguousarray(X[idx].T, dtype=np.float64)."""
    lib = get_lib()
    assert lib is not None
    is_f32 = 1 if X.dtype == np.float32 else 0
    idx = np.ascontiguousarray(idx, np.int64)
    n_rows, f_total = X.shape
    out = np.empty((f_total, len(idx)), np.float64)
    lib.lgbt_sample_transpose(
        X.ctypes.data_as(ctypes.c_void_p), is_f32, f_total,
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_long)), len(idx),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    return out


def find_numeric_bounds(sample_t: np.ndarray, max_bin: int,
                        min_data_in_bin: int, use_missing: bool,
                        zero_as_missing: bool):
    """Whole-matrix numeric boundary search (native FindBin loop over
    features, OpenMP). sample_t: [F, S] contiguous f64 raw sample.
    Returns (bounds_list[F], missing_type[F], minmax[F,2],
    zero_na[F,2])."""
    lib = get_lib()
    assert lib is not None
    sample_t = np.ascontiguousarray(sample_t, np.float64)
    n_feat, s = sample_t.shape
    stride = max_bin + 2
    bounds = np.empty(n_feat * stride, np.float64)
    nb = np.empty(n_feat, np.int32)
    mtype = np.empty(n_feat, np.int32)
    minmax = np.empty((n_feat, 2), np.float64)
    zero_na = np.empty((n_feat, 2), np.int64)
    lib.lgbt_find_numeric_bounds(
        sample_t.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        n_feat, s, max_bin, min_data_in_bin, int(use_missing),
        int(zero_as_missing),
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        nb.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        mtype.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        minmax.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        zero_na.ctypes.data_as(ctypes.POINTER(ctypes.c_long)))
    blist = [bounds[j * stride: j * stride + nb[j]].copy()
             for j in range(n_feat)]
    return blist, mtype, minmax, zero_na


def bin_matrix(X: np.ndarray, feat_idx: np.ndarray, bounds_flat: np.ndarray,
               bounds_off: np.ndarray, num_search: np.ndarray,
               nan_bin: np.ndarray, dtype) -> np.ndarray:
    """Quantize every listed numeric column of row-major X in one OpenMP
    pass (DatasetLoader's parallel bin construction analog)."""
    lib = get_lib()
    assert lib is not None
    # float32 is read natively: no whole-matrix float64 copy on the main
    # dense-ingestion path (a 10M x 100 f32 input would transiently
    # double its footprint otherwise)
    if X.dtype == np.float32:
        X = np.ascontiguousarray(X)
        is_f32 = 1
    else:
        X = np.ascontiguousarray(X, np.float64)
        is_f32 = 0
    n, f_total = X.shape
    feat_idx = np.ascontiguousarray(feat_idx, np.int32)
    bounds_flat = np.ascontiguousarray(bounds_flat, np.float64)
    bounds_off = np.ascontiguousarray(bounds_off, np.int64)
    num_search = np.ascontiguousarray(num_search, np.int32)
    nan_bin = np.ascontiguousarray(nan_bin, np.int32)
    out = np.empty((n, len(feat_idx)), dtype)
    lib.lgbt_bin_matrix(
        X.ctypes.data_as(ctypes.c_void_p), is_f32, n, f_total,
        feat_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        len(feat_idx),
        bounds_flat.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        bounds_off.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        num_search.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        nan_bin.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        out.dtype.itemsize, out.ctypes.data_as(ctypes.c_void_p))
    return out


# ---------------------------------------------------------------------------
# native forest predictor (predict.cpp; reference predictor.hpp:30)
# ---------------------------------------------------------------------------

_PSRC = os.path.join(_DIR, "predict.cpp")
_PLIB_PATH = os.path.join(_DIR, "libpredict.so")
_plib: Optional[ctypes.CDLL] = None
_ptried = False


def get_predict_lib() -> Optional[ctypes.CDLL]:
    global _plib, _ptried
    if _plib is not None or _ptried:
        return _plib
    _ptried = True
    lib = _load_or_build(_PSRC, _PLIB_PATH,
                         flag_sets=(("-fopenmp",), ()))
    if lib is None:
        return None
    c_dp = ctypes.POINTER(ctypes.c_double)
    c_ip = ctypes.POINTER(ctypes.c_int)
    c_lp = ctypes.POINTER(ctypes.c_long)
    c_u8 = ctypes.POINTER(ctypes.c_uint8)
    c_u32 = ctypes.POINTER(ctypes.c_uint32)
    lib.lgbt_predict.restype = None
    lib.lgbt_predict.argtypes = [
        c_dp, ctypes.c_long, ctypes.c_int, ctypes.c_int, c_ip, ctypes.c_int,
        c_lp, c_lp, c_ip, c_dp, c_u8, c_ip, c_ip, c_dp,
        c_lp, c_lp, c_u32, c_lp,
        c_u8, c_dp, c_lp, c_ip, c_dp,
        ctypes.c_int, ctypes.c_int, c_dp]
    lib.lgbt_predict_leaf.restype = None
    lib.lgbt_predict_leaf.argtypes = [
        c_dp, ctypes.c_long, ctypes.c_int, ctypes.c_int,
        c_lp, c_lp, c_ip, c_dp, c_u8, c_ip, c_ip,
        c_lp, c_lp, c_u32, c_lp,
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
    _plib = lib
    return _plib


def predict_available() -> bool:
    return get_predict_lib() is not None


def _ptr(a, ct):
    return a.ctypes.data_as(ctypes.POINTER(ct))


def forest_predict(flat: dict, X: np.ndarray, k: int, start_tree: int,
                   end_tree: int) -> np.ndarray:
    """Run the native predictor over trees [start_tree, end_tree)."""
    lib = get_predict_lib()
    assert lib is not None
    X = np.ascontiguousarray(X, np.float64)
    n, nfeat = X.shape
    out = np.zeros((n, k), np.float64)
    lib.lgbt_predict(
        _ptr(X, ctypes.c_double), n, nfeat, flat["num_trees"],
        _ptr(flat["tree_class"], ctypes.c_int), k,
        _ptr(flat["node_off"], ctypes.c_long),
        _ptr(flat["leaf_off"], ctypes.c_long),
        _ptr(flat["split_feature"], ctypes.c_int),
        _ptr(flat["threshold"], ctypes.c_double),
        _ptr(flat["decision_type"], ctypes.c_uint8),
        _ptr(flat["left"], ctypes.c_int),
        _ptr(flat["right"], ctypes.c_int),
        _ptr(flat["leaf_value"], ctypes.c_double),
        _ptr(flat["catb_off"], ctypes.c_long),
        _ptr(flat["cat_boundaries"], ctypes.c_long),
        _ptr(flat["cat_threshold"], ctypes.c_uint32),
        _ptr(flat["catt_off"], ctypes.c_long),
        _ptr(flat["is_linear"], ctypes.c_uint8),
        _ptr(flat["leaf_const"], ctypes.c_double),
        _ptr(flat["lfeat_off"], ctypes.c_long),
        _ptr(flat["leaf_features"], ctypes.c_int),
        _ptr(flat["leaf_coeff"], ctypes.c_double),
        start_tree, end_tree, _ptr(out, ctypes.c_double))
    return out


def forest_predict_leaf(flat: dict, X: np.ndarray, start_tree: int,
                        end_tree: int) -> np.ndarray:
    lib = get_predict_lib()
    assert lib is not None
    X = np.ascontiguousarray(X, np.float64)
    n, nfeat = X.shape
    out = np.zeros((n, end_tree - start_tree), np.int32)
    lib.lgbt_predict_leaf(
        _ptr(X, ctypes.c_double), n, nfeat, flat["num_trees"],
        _ptr(flat["node_off"], ctypes.c_long),
        _ptr(flat["leaf_off"], ctypes.c_long),
        _ptr(flat["split_feature"], ctypes.c_int),
        _ptr(flat["threshold"], ctypes.c_double),
        _ptr(flat["decision_type"], ctypes.c_uint8),
        _ptr(flat["left"], ctypes.c_int),
        _ptr(flat["right"], ctypes.c_int),
        _ptr(flat["catb_off"], ctypes.c_long),
        _ptr(flat["cat_boundaries"], ctypes.c_long),
        _ptr(flat["cat_threshold"], ctypes.c_uint32),
        _ptr(flat["catt_off"], ctypes.c_long),
        start_tree, end_tree, _ptr(out, ctypes.c_int))
    return out
