"""Native (C++) helpers, compiled on demand with g++ and loaded via ctypes.

The reference keeps its performance-critical host IO in C++
(reference: src/io/parser.cpp, src/io/dataset_loader.cpp); this package is
the equivalent. Compilation is lazy and cached next to the source under a
name keyed on the CONTENT of the tracked ``.cpp`` files and the compile
command, so a binary built from other sources (a stale one copied along
with the tree, say) can never be loaded; if no compiler is available the
callers fall back to Python parsing.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

from ..utils import log

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_HERE = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ("parser.cpp", "treeshap.cpp", "binner.cpp", "fastpred.cpp",
            "capi.cpp")
_COMPILE = ("g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread")


def artefact_path() -> str:
    """``_lg_native.<sha256 of compile command + sources>.so`` beside the
    sources."""
    h = hashlib.sha256(" ".join(_COMPILE).encode())
    for name in _SOURCES:
        with open(os.path.join(_HERE, name), "rb") as f:
            h.update(f.read())
    return os.path.join(_HERE, f"_lg_native.{h.hexdigest()[:16]}.so")


def _build_lib() -> Optional[str]:
    out = artefact_path()
    if os.path.exists(out):
        return out
    tmp = f"{out[:-3]}.tmp{os.getpid()}.so"
    try:
        subprocess.run([*_COMPILE,
                        *(os.path.join(_HERE, n) for n in _SOURCES),
                        "-o", tmp],
                       check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)      # atomic: a concurrent loader sees all or none
    except (subprocess.SubprocessError, OSError) as e:
        log.warning("Native build failed (%s); using Python fallback", e)
        return None
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return out


def get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _LIB is None and not _TRIED:
        _TRIED = True
        path = _build_lib()
        if path is not None:
            lib = ctypes.CDLL(path)
            i64p = ctypes.POINTER(ctypes.c_int64)
            dp = ctypes.POINTER(ctypes.c_double)
            lib.lg_count_libsvm.argtypes = [ctypes.c_char_p, i64p, i64p]
            lib.lg_parse_libsvm.argtypes = [ctypes.c_char_p, dp, dp, i64p,
                                            ctypes.c_int64, ctypes.c_int64]
            lib.lg_count_delim.argtypes = [ctypes.c_char_p, ctypes.c_char,
                                           ctypes.c_int, i64p, i64p]
            lib.lg_parse_delim.argtypes = [ctypes.c_char_p, ctypes.c_char,
                                           ctypes.c_int, dp,
                                           ctypes.c_int64, ctypes.c_int64]
            i32p = ctypes.POINTER(ctypes.c_int32)
            u8p = ctypes.POINTER(ctypes.c_uint8)
            u32p = ctypes.POINTER(ctypes.c_uint32)
            lib.lg_tree_shap.argtypes = [
                ctypes.c_int64, i32p, dp, u8p, i32p, i32p, i32p, u8p,
                u32p, i64p, dp, dp, dp, dp, dp,
                ctypes.c_int64, ctypes.c_int64, dp]
            lib.lg_tree_shap.restype = None
            i8p = ctypes.POINTER(ctypes.c_int8)
            lib.lg_bin_matrix.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64, i64p, dp, i64p, i8p, i32p,
                u8p, ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
            lib.lg_bin_matrix.restype = None
            fp = ctypes.POINTER(ctypes.c_float)
            lib.lg_fast_predict.argtypes = [
                ctypes.c_int64, i64p, i64p, i32p, fp, u8p, u8p, u8p, i64p,
                i32p, u32p, i32p, i32p, dp, i32p, ctypes.c_int64,
                fp, ctypes.c_int64, ctypes.c_int64, dp]
            lib.lg_fast_predict.restype = None
            _LIB = lib
    return _LIB


class FastForest:
    """Flattened read-only forest for the native low-latency predictor
    (reference: src/c_api.cpp:63 SingleRowPredictorInner). Thread-safe:
    prediction touches only these arrays."""

    def __init__(self, trees, tree_class, n_class: int) -> None:
        import numpy as np
        node_off = [0]
        leaf_off = [0]
        feat, thr, dl, mt, ic = [], [], [], [], []
        left, right = [], []
        cat_off, cat_len, cat_bits = [], [], []
        leaf_val = []
        for t in trees:
            n = t.num_internal
            node_off.append(node_off[-1] + n)
            leaf_off.append(leaf_off[-1] + max(t.num_leaves, 1))
            feat.extend(t.split_feature[:n])
            thr.extend(t.threshold_real[:n])
            dl.extend(t.default_left[:n])
            mt.extend(t.missing_type[:n])
            ic.extend(t.is_categorical[:n])
            left.extend(t.left_child[:n])
            right.extend(t.right_child[:n])
            for i in range(n):
                bits = t.cat_bitset_real[i]
                cat_off.append(len(cat_bits))
                cat_len.append(len(bits))
                cat_bits.extend(int(w) for w in bits)
            leaf_val.extend(float(v) for v in
                            t.leaf_value[:max(t.num_leaves, 1)])
        self.n_trees = len(trees)
        self.node_off = np.asarray(node_off, np.int64)
        self.leaf_off = np.asarray(leaf_off, np.int64)
        self.feat = np.asarray(feat, np.int32)
        self.thr = np.asarray(thr, np.float32)
        self.dl = np.asarray(dl, np.uint8)
        self.mt = np.asarray(mt, np.uint8)
        self.ic = np.asarray(ic, np.uint8)
        self.cat_off = np.asarray(cat_off, np.int64)
        self.cat_len = np.asarray(cat_len, np.int32)
        self.cat_bits = np.asarray(cat_bits if cat_bits else [0], np.uint32)
        self.left = np.asarray(left, np.int32)
        self.right = np.asarray(right, np.int32)
        self.leaf_val = np.asarray(leaf_val, np.float64)
        self.tree_class = np.asarray(tree_class, np.int32)
        self.n_class = int(n_class)
        self.max_feat = int(self.feat.max()) if len(self.feat) else 0

    def predict(self, X) -> "np.ndarray":
        """Raw scores [n_rows, n_class]; X is float32 row-major [n, d]."""
        import numpy as np
        lib = get_lib()
        X = np.ascontiguousarray(X, dtype=np.float32)
        n, d = X.shape
        out = np.zeros((n, self.n_class), dtype=np.float64)
        c = ctypes
        lib.lg_fast_predict(
            self.n_trees,
            self.node_off.ctypes.data_as(c.POINTER(c.c_int64)),
            self.leaf_off.ctypes.data_as(c.POINTER(c.c_int64)),
            self.feat.ctypes.data_as(c.POINTER(c.c_int32)),
            self.thr.ctypes.data_as(c.POINTER(c.c_float)),
            self.dl.ctypes.data_as(c.POINTER(c.c_uint8)),
            self.mt.ctypes.data_as(c.POINTER(c.c_uint8)),
            self.ic.ctypes.data_as(c.POINTER(c.c_uint8)),
            self.cat_off.ctypes.data_as(c.POINTER(c.c_int64)),
            self.cat_len.ctypes.data_as(c.POINTER(c.c_int32)),
            self.cat_bits.ctypes.data_as(c.POINTER(c.c_uint32)),
            self.left.ctypes.data_as(c.POINTER(c.c_int32)),
            self.right.ctypes.data_as(c.POINTER(c.c_int32)),
            self.leaf_val.ctypes.data_as(c.POINTER(c.c_double)),
            self.tree_class.ctypes.data_as(c.POINTER(c.c_int32)),
            self.n_class,
            X.ctypes.data_as(c.POINTER(c.c_float)), n, d,
            out.ctypes.data_as(c.POINTER(c.c_double)))
        return out


def bin_matrix_native(data, used_features, mappers, out) -> bool:
    """Bin the numerical columns of ``data`` into ``out`` via the native
    single-pass loop (reference analog: the multi-threaded push at
    src/io/dataset_loader.cpp:203). Returns False when the native lib is
    unavailable or the dtype is unsupported; categorical columns are always
    left for the caller (``skip`` mask)."""
    import numpy as np
    lib = get_lib()
    if lib is None:
        return False
    if data.dtype == np.float64:
        code = 0
    elif data.dtype == np.float32:
        code = 1
    else:
        return False
    data = np.ascontiguousarray(data)
    n, f_total = data.shape
    n_used = len(used_features)
    used_idx = np.asarray(used_features, dtype=np.int64)
    bounds_list, missing, nan_bins, skip = [], [], [], []
    from ..data.binning import BIN_CATEGORICAL, MISSING_NAN
    for j in used_features:
        m = mappers[j]
        if m.bin_type == BIN_CATEGORICAL:
            bounds_list.append(np.empty(0, np.float64))
            missing.append(0)
            nan_bins.append(0)
            skip.append(1)
            continue
        b = np.asarray([x for x in m.bin_upper_bound if not np.isnan(x)],
                       dtype=np.float64)
        bounds_list.append(b)
        missing.append(2 if m.missing_type == MISSING_NAN else 0)
        nan_bins.append(m.num_bin - 1)
        skip.append(0)
    bounds_flat = (np.concatenate(bounds_list) if bounds_list
                   else np.empty(0, np.float64))
    bounds_off = np.zeros(n_used + 1, dtype=np.int64)
    np.cumsum([len(b) for b in bounds_list], out=bounds_off[1:])
    missing = np.asarray(missing, dtype=np.int8)
    nan_bins_a = np.asarray(nan_bins, dtype=np.int32)
    skip_a = np.asarray(skip, dtype=np.uint8)
    out16 = 1 if out.dtype.itemsize == 2 else 0
    c = ctypes
    lib.lg_bin_matrix(
        data.ctypes.data_as(c.c_void_p), code, n, f_total, n_used,
        used_idx.ctypes.data_as(c.POINTER(c.c_int64)),
        bounds_flat.ctypes.data_as(c.POINTER(c.c_double)),
        bounds_off.ctypes.data_as(c.POINTER(c.c_int64)),
        missing.ctypes.data_as(c.POINTER(c.c_int8)),
        nan_bins_a.ctypes.data_as(c.POINTER(c.c_int32)),
        skip_a.ctypes.data_as(c.POINTER(c.c_uint8)),
        out.ctypes.data_as(c.c_void_p), out16, 0)
    return True
