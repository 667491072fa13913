"""Binned dataset resident in TPU HBM.

TPU-native analog of the reference's ``Dataset``/``Metadata``/``FeatureGroup``
(reference: include/LightGBM/dataset.h:48-397,487; src/io/dataset.cpp). Instead
of per-group Bin objects with dense/sparse variants, the TPU layout is a single
dense row-major ``uint8``/``uint16`` matrix ``[num_data, num_used_features]``
padded to lane multiples — the analog of ``CUDARowData``'s row-wise layout
(reference: include/LightGBM/cuda/cuda_row_data.hpp:32). EFB merges
mutually-exclusive sparse features into shared columns in a *second*
bundled matrix consumed by the fused device learner (see
:mod:`lambdagap_tpu.data.bundling`; reference: src/io/dataset.cpp:107
FindGroups, :246 FastFeatureBundling); the public unbundled matrix stays
authoritative for binned tree traversal.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..config import Config
from ..utils import log
from .binning import (BIN_CATEGORICAL, BIN_NUMERICAL, MISSING_NAN, MISSING_NONE,
                      MISSING_ZERO, BinMapper)


@dataclass
class Metadata:
    """Labels, weights, query boundaries, positions, init scores
    (reference: include/LightGBM/dataset.h:48-397)."""

    label: Optional[np.ndarray] = None
    weight: Optional[np.ndarray] = None
    query_boundaries: Optional[np.ndarray] = None   # int32 [num_queries+1]
    query_weights: Optional[np.ndarray] = None
    init_score: Optional[np.ndarray] = None          # [num_data * num_class]
    position: Optional[np.ndarray] = None            # int32 [num_data]
    position_ids: Optional[List[str]] = None

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1

    def set_group(self, group: Optional[np.ndarray]) -> None:
        """Accepts group sizes (LightGBM convention) or per-row query ids."""
        if group is None:
            self.query_boundaries = None
            return
        group = np.asarray(group)
        if self.label is not None and len(group) == len(self.label) and len(group) > 0 \
                and not _looks_like_sizes(group, len(self.label)):
            # per-row query ids -> boundaries
            change = np.nonzero(np.diff(group))[0] + 1
            self.query_boundaries = np.concatenate(
                [[0], change, [len(group)]]).astype(np.int32)
        else:
            sizes = group.astype(np.int64)
            self.query_boundaries = np.concatenate(
                [[0], np.cumsum(sizes)]).astype(np.int32)

    def check(self, num_data: int) -> None:
        if self.label is not None and len(self.label) != num_data:
            log.fatal("Length of label (%d) != num_data (%d)", len(self.label), num_data)
        if self.weight is not None and len(self.weight) != num_data:
            log.fatal("Length of weight (%d) != num_data (%d)", len(self.weight), num_data)
        if self.query_boundaries is not None and self.query_boundaries[-1] != num_data:
            log.fatal("Sum of query counts (%d) != num_data (%d)",
                      int(self.query_boundaries[-1]), num_data)
        if self.position is not None and len(self.position) != num_data:
            log.fatal("Length of position (%d) != num_data (%d)", len(self.position), num_data)


def _looks_like_sizes(group: np.ndarray, num_data: int) -> bool:
    try:
        return int(np.sum(group)) == num_data
    except (TypeError, ValueError):
        return False


def _load_forced_bounds(config: Config) -> Dict[int, List[float]]:
    """forced bin boundaries (reference: DatasetLoader forced_bin_bounds_,
    examples/regression/forced_bins.json)."""
    forced: Dict[int, List[float]] = {}
    if config.forcedbins_filename:
        import json
        with open(config.forcedbins_filename) as f:
            for entry in json.load(f):
                forced[int(entry["feature"])] = \
                    [float(v) for v in entry["bin_upper_bound"]]
    return forced


# columns a bin-finding task takes at once, and the most threads it runs on
_BIN_BLOCK = 64
_MAX_BIN_WORKERS = 16


def _bin_workers() -> int:
    """Threads for bin finding: the cores this process may run on, capped."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    return max(1, min(cores, _MAX_BIN_WORKERS))


def _finish_bins(ds: "BinnedDataset") -> None:
    """used_features / bin offsets from freshly built mappers."""
    ds.used_features = []
    ds.feature_num_bins = []
    for j, mapper in enumerate(ds.mappers):
        if not mapper.is_trivial:
            ds.used_features.append(j)
            ds.feature_num_bins.append(mapper.num_bin)
    if not ds.used_features:
        log.fatal("Cannot construct Dataset: all features are trivial "
                  "(constant); check your input data")
    ds.bin_offsets = list(np.concatenate(
        [[0], np.cumsum(ds.feature_num_bins)[:-1]]).astype(int))
    ds.num_total_bins = int(np.sum(ds.feature_num_bins))


def _mappers_from_sketches(ds: "BinnedDataset", sketches, config: Config,
                           categorical: set) -> None:
    """Build per-feature BinMappers from incremental quantile sketches —
    the streaming-construction analog of ``_find_bins`` (boundaries found
    without ever materializing the raw matrix; data/binning.py
    QuantileSketch has the error story)."""
    forced = _load_forced_bounds(config)
    ds.mappers = []
    for j, sk in enumerate(sketches):
        bin_type = BIN_CATEGORICAL if j in categorical else BIN_NUMERICAL
        ds.mappers.append(sk.to_mapper(
            max_bin=(config.max_bin_by_feature[j]
                     if j < len(config.max_bin_by_feature)
                     else config.max_bin),
            min_data_in_bin=config.min_data_in_bin,
            bin_type=bin_type,
            use_missing=config.use_missing,
            zero_as_missing=config.zero_as_missing,
            forced_bounds=forced.get(j, ())))
    _finish_bins(ds)


class BinnedDataset:
    """The constructed, immutable training matrix
    (reference analog: Dataset after ``Construct``, src/io/dataset.cpp:~350).

    Attributes
    ----------
    binned : np.ndarray uint8/uint16 [num_data, num_used_features]
    mappers : list[BinMapper], one per *original* feature
    used_features : original indices of non-trivial features (column order)
    feature_num_bins : bins per used feature
    bin_offsets : cumulative bin offset per used feature (flattened histograms)
    """

    def __init__(self) -> None:
        self.binned: Optional[np.ndarray] = None
        self._bundle = None            # EFB artifact (data.bundling.Bundle)
        self._bundle_built = False
        self.mappers: List[BinMapper] = []
        self.used_features: List[int] = []
        self.feature_num_bins: List[int] = []
        self.bin_offsets: List[int] = []
        self.num_total_bins: int = 0
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.metadata = Metadata()
        self.feature_names: List[str] = []
        self.max_bin: int = 255
        self.raw: Optional[np.ndarray] = None   # retained when linear_tree
        self._device_cache: Dict[Any, Any] = {}

    # ------------------------------------------------------------------
    @classmethod
    def from_matrix(cls, data: np.ndarray, config: Config,
                    label: Optional[np.ndarray] = None,
                    weight: Optional[np.ndarray] = None,
                    group: Optional[np.ndarray] = None,
                    init_score: Optional[np.ndarray] = None,
                    position: Optional[np.ndarray] = None,
                    categorical_features: Sequence[int] = (),
                    feature_names: Optional[Sequence[str]] = None,
                    reference: Optional["BinnedDataset"] = None) -> "BinnedDataset":
        """Construct from a dense float matrix.

        Mirrors DatasetLoader::ConstructFromSampleData
        (reference: src/io/dataset_loader.cpp:593): sample rows, find bins,
        then push all rows.

        Peak-memory contract: the input matrix is NOT converted or copied
        whole — bin finding samples bounded row subsets and the push runs
        row-blockwise — so the transient footprint on top of the caller's
        matrix is ~1x the packed output (asserted by
        tests/test_stream.py::test_from_matrix_peak_memory), not
        raw-float64 + packed.
        """
        data = np.asarray(data)
        if data.ndim != 2:
            log.fatal("Training data must be 2-dimensional, got shape %s", data.shape)
        ds = cls()
        ds.num_data, ds.num_total_features = data.shape
        ds.max_bin = config.max_bin
        ds.feature_names = (list(feature_names) if feature_names
                            else [f"Column_{i}" for i in range(ds.num_total_features)])

        if reference is not None:
            # validation set aligned to training bins
            # (reference: Dataset::CreateValid, src/io/dataset.cpp)
            ds.mappers = reference.mappers
            ds.used_features = reference.used_features
            ds.feature_num_bins = reference.feature_num_bins
            ds.bin_offsets = reference.bin_offsets
            ds.num_total_bins = reference.num_total_bins
            ds.feature_names = reference.feature_names
            ds.max_bin = reference.max_bin
        else:
            ds._find_bins(data, config, set(categorical_features))
        ds._push_data(data)
        if config.linear_tree:
            # linear leaves re-fit against raw numeric values
            # (reference: Dataset raw_data retention under linear_tree)
            ds.raw = data.astype(np.float32)

        md = ds.metadata
        if label is not None:
            md.label = np.asarray(label, dtype=np.float32).reshape(-1)
        if weight is not None:
            md.weight = np.asarray(weight, dtype=np.float32).reshape(-1)
        if init_score is not None:
            md.init_score = np.asarray(init_score, dtype=np.float64).reshape(-1)
        if position is not None:
            md.position = np.asarray(position, dtype=np.int32).reshape(-1)
        md.set_group(group)
        md.check(ds.num_data)
        return ds

    @classmethod
    def from_sequences(cls, seqs, config: Config,
                       label=None, weight=None, group=None,
                       init_score=None, position=None,
                       categorical_features: Sequence = (),
                       feature_names=None,
                       reference: Optional["BinnedDataset"] = None
                       ) -> "BinnedDataset":
        """Streaming construction from row-batch readers: an incremental
        per-feature quantile sketch (data/binning.py QuantileSketch) finds
        bin boundaries over EVERY row in one bounded-memory pass — no row
        sample matrix, no rng — then batches are pushed straight into the
        uint8 matrix, so the full float matrix never materializes (the
        analog of the C-API streaming push path, reference:
        include/LightGBM/dataset.h:593 PushOneRow / tests/cpp_tests/
        test_stream.cpp; Python lightgbm.Sequence, basic.py:903; sketch
        construction per "Out-of-Core GPU Gradient Boosting",
        arXiv:2005.09148 §3.1)."""
        lens = [len(s) for s in seqs]
        total = int(sum(lens))
        if total == 0:
            log.fatal("Cannot construct Dataset from empty sequences")
        probe = np.asarray(seqs[0][0:1], dtype=np.float64)
        F = probe.shape[1]

        ds = cls()
        ds.num_data = total
        ds.num_total_features = F
        ds.max_bin = config.max_bin
        ds.feature_names = (list(feature_names) if feature_names
                            else [f"Column_{i}" for i in range(F)])

        if reference is not None:
            # validation sequences align to the training bins
            ds.mappers = reference.mappers
            ds.used_features = reference.used_features
            ds.feature_num_bins = reference.feature_num_bins
            ds.bin_offsets = reference.bin_offsets
            ds.num_total_bins = reference.num_total_bins
            ds.feature_names = reference.feature_names
            ds.max_bin = reference.max_bin
        else:
            from .binning import QuantileSketch
            sketches = [QuantileSketch(
                budget=getattr(config, "stream_sketch_budget", 65536))
                for _ in range(F)]
            for s, ln in zip(seqs, lens):
                bs = max(int(getattr(s, "batch_size", 4096)), 1)
                for lo in range(0, ln, bs):
                    blk = np.asarray(s[lo:min(lo + bs, ln)], np.float64)
                    for j in range(F):
                        sketches[j].push(blk[:, j])
            _mappers_from_sketches(ds, sketches, config,
                                   set(categorical_features))

        # push batches straight into the binned matrix
        dtype = np.uint8 if max(ds.feature_num_bins, default=2) <= 256 \
            else np.uint16
        binned = np.empty((total, len(ds.used_features)), dtype=dtype)
        row0 = 0
        for s, ln in zip(seqs, lens):
            bs = max(int(getattr(s, "batch_size", 4096)), 1)
            for lo in range(0, ln, bs):
                hi = min(lo + bs, ln)
                mat = np.asarray(s[lo:hi], dtype=np.float64)
                for k, j in enumerate(ds.used_features):
                    binned[row0 + lo:row0 + hi, k] = \
                        ds.mappers[j].values_to_bins(mat[:, j]).astype(dtype)
            row0 += ln
        ds.binned = binned

        md = ds.metadata
        if label is not None:
            md.label = np.asarray(label, dtype=np.float32).reshape(-1)
        if weight is not None:
            md.weight = np.asarray(weight, dtype=np.float32).reshape(-1)
        if init_score is not None:
            md.init_score = np.asarray(init_score, np.float64).reshape(-1)
        if position is not None:
            md.position = np.asarray(position, np.int32).reshape(-1)
        md.set_group(group)
        md.check(total)
        return ds

    def _find_bins(self, data: np.ndarray, config: Config,
                   categorical: set) -> None:
        """Sample rows and build per-feature BinMappers
        (reference: DatasetLoader::ConstructBinMappersFromTextData,
        src/io/dataset_loader.cpp:1072)."""
        n = self.num_data
        sample_cnt = min(config.bin_construct_sample_cnt, n)
        rng = np.random.RandomState(config.data_random_seed)
        if sample_cnt >= n:
            # whole-data "sample": no fancy-index copy of the matrix (the
            # from_matrix peak-memory contract — the old arange gather
            # silently duplicated the input)
            sample = data
        else:
            sample = data[np.sort(rng.choice(n, sample_cnt,
                                             replace=False))]

        forced = _load_forced_bounds(config)

        def find(j, col):
            bin_type = BIN_CATEGORICAL if j in categorical else BIN_NUMERICAL
            # sparse convention: pass non-zero entries, infer zeros from total
            nz = col[~((col == 0.0) & ~np.isnan(col))]
            return BinMapper.find_bin(
                nz, total_sample_cnt=len(col),
                max_bin=(config.max_bin_by_feature[j]
                         if j < len(config.max_bin_by_feature) else config.max_bin),
                min_data_in_bin=config.min_data_in_bin,
                bin_type=bin_type,
                use_missing=config.use_missing,
                zero_as_missing=config.zero_as_missing,
                forced_bounds=forced.get(j, ()))

        def block(lo):
            hi = min(lo + _BIN_BLOCK, self.num_total_features)
            return [find(j, sample[:, j]) for j in range(lo, hi)]

        # features are binned independently (the reference's OpenMP loop,
        # dataset_loader.cpp ConstructBinMappersFromTextData); numpy's sorts
        # and searches release the interpreter's lock, so blocks of columns
        # share the host's cores, each mapper the same as on one thread.
        # One worker a block: a worker's per-column temporaries (~6 x rows
        # x 8 B) stay a small share of the matrix, and a narrow matrix is
        # binned on one thread, as before
        starts = range(0, self.num_total_features, _BIN_BLOCK)
        with ThreadPoolExecutor(min(_bin_workers(), len(starts))) as pool:
            self.mappers = [m for found in pool.map(block, starts)
                            for m in found]
        _finish_bins(self)

    def _push_data(self, data: np.ndarray) -> None:
        dtype = np.uint8 if max(self.feature_num_bins, default=2) <= 256 else np.uint16
        binned = np.empty((self.num_data, len(self.used_features)), dtype=dtype)
        # one native pass for the numerical columns (reference analog:
        # the multi-threaded push, src/io/dataset_loader.cpp:203) — the
        # numpy per-column route pays ~6 full-size temporaries per feature
        from ..native import bin_matrix_native
        if (data.dtype in (np.float64, np.float32)
                and data.flags["C_CONTIGUOUS"]):
            self._push_block(data, binned, 0)
        else:
            # other dtypes / non-contiguous layouts convert row-blockwise
            # so the float64 temporary stays bounded (the from_matrix
            # peak-memory contract) instead of shadowing the whole matrix
            block = max((1 << 24) // max(data.shape[1], 1), 1024)
            for r0 in range(0, self.num_data, block):
                blk = np.ascontiguousarray(
                    data[r0:r0 + block], dtype=np.float64)
                self._push_block(blk, binned, r0)
                del blk
        self.binned = binned

    def _push_block(self, blk: np.ndarray, binned: np.ndarray,
                    row0: int) -> None:
        """Bin one contiguous float row block into ``binned[row0:...]``."""
        from ..native import bin_matrix_native
        out = binned[row0:row0 + blk.shape[0]]
        dtype = binned.dtype
        if bin_matrix_native(blk, self.used_features, self.mappers, out):
            for k, j in enumerate(self.used_features):
                if self.mappers[j].bin_type == BIN_CATEGORICAL:
                    out[:, k] = self.mappers[j].values_to_bins(
                        blk[:, j]).astype(dtype)
        else:
            for k, j in enumerate(self.used_features):
                out[:, k] = self.mappers[j].values_to_bins(
                    blk[:, j]).astype(dtype)

    # ------------------------------------------------------------------
    def ensure_bundle(self, config: Config):
        """Lazily build the EFB bundled matrix (see data.bundling). Only the
        fused device learner consumes it, so construction is deferred until
        a consumer asks — other learners and validation sets never pay the
        grouping scan or the second matrix."""
        if self._bundle_built:
            return self._bundle
        self._bundle_built = True
        if config.enable_bundle and self.binned is not None:
            from .bundling import build_bundle
            self._bundle = build_bundle(
                self.binned, np.asarray(self.feature_num_bins, np.int32),
                np.asarray([self.mappers[j].default_bin
                            for j in self.used_features], np.int32),
                config.max_conflict_rate)
        return self._bundle

    @property
    def bundle(self):
        return self._bundle

    @property
    def num_features(self) -> int:
        return len(self.used_features)

    @property
    def label(self) -> Optional[np.ndarray]:
        return self.metadata.label

    def feature_arrays(self):
        """Static per-feature metadata arrays used by the jitted split scan."""
        F = self.num_features
        num_bins = np.asarray(self.feature_num_bins, dtype=np.int32)
        offsets = np.asarray(self.bin_offsets, dtype=np.int32)
        default_bins = np.zeros(F, dtype=np.int32)
        missing_types = np.zeros(F, dtype=np.int32)   # 0=None, 1=Zero, 2=NaN
        is_categorical = np.zeros(F, dtype=bool)
        mt_codes = {MISSING_NONE: 0, MISSING_ZERO: 1, MISSING_NAN: 2}
        for k, j in enumerate(self.used_features):
            m = self.mappers[j]
            default_bins[k] = m.default_bin
            missing_types[k] = mt_codes[m.missing_type]
            is_categorical[k] = m.bin_type == BIN_CATEGORICAL
        return dict(num_bins=num_bins, offsets=offsets, default_bins=default_bins,
                    missing_types=missing_types, is_categorical=is_categorical)

    def real_threshold(self, feature_k: int, bin_threshold: int) -> float:
        """Bin threshold -> raw-value threshold for model serialization."""
        j = self.used_features[feature_k]
        return self.mappers[j].bin_to_value(bin_threshold)
