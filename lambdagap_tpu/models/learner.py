"""Serial (single-device) leaf-wise tree learner.

TPU re-design of the reference's canonical leaf-wise loop
(reference: src/treelearner/serial_tree_learner.cpp:179-245 Train, :288
BeforeTrain, :340-384 histogram-pool juggling, :404-476 FindBestSplits,
:766-920 SplitInner). Like the CUDA learner
(reference: src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:158-260) the
host only orchestrates: every step is a jitted device call with shape-stable
padded sizes (power-of-2 buckets bound recompilation), and the
histogram-subtraction trick keeps per-split work at O(min(|left|, |right|)).

Host state per tree: leaf begin/count bookkeeping and fetched best-split
records (one small D2H per step, like the CUDA learner's single SplitInfo
copy at cuda_single_gpu_tree_learner.cpp:246).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data.dataset import BinnedDataset
from ..obs.telemetry import NULL_TELEMETRY
from ..ops.histogram import (full_histogram, leaf_histogram,
                             leaf_histogram_sorted)
from ..ops.partition import split_partition, split_partition_sorted
from ..ops.split import (SplitParams, find_best_split, gather_threshold_split,
                         monotone_split_penalty)
from ..utils import log
from .tree import Tree


import os

# USE_DEBUG analog: heavy self-checks, off unless explicitly requested
_DEBUG_CHECKS = os.environ.get("LAMBDAGAP_DEBUG", "0") not in ("0", "", "false")


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


class _HostSplit:
    """A fetched best-split record (host mirror of SplitInfo)."""
    __slots__ = ("gain", "feature", "threshold", "default_left",
                 "left_sum_g", "left_sum_h", "left_count",
                 "right_sum_g", "right_sum_h", "right_count",
                 "left_output", "right_output", "is_categorical", "cat_bitset")

    def __init__(self, res) -> None:
        (self.gain, self.feature, self.threshold, self.default_left,
         self.left_sum_g, self.left_sum_h, self.left_count,
         self.right_sum_g, self.right_sum_h, self.right_count,
         self.left_output, self.right_output, self.is_categorical,
         self.cat_bitset) = [np.asarray(x) for x in res]

    @property
    def gain_f(self) -> float:
        return float(self.gain)


class SerialTreeLearner:
    # phase-span handle; GBDT._setup_training rebinds it to the booster's
    # TrainTelemetry so histogram/split/partition sub-phases attribute
    # inside the enclosing "tree" span (docs/observability.md)
    telemetry = NULL_TELEMETRY
    """Single-device leaf-wise learner over a BinnedDataset."""

    def __init__(self, dataset: BinnedDataset, config: Config) -> None:
        self.dataset = dataset
        self.config = config
        self.num_data = dataset.num_data
        self.num_features = dataset.num_features

        meta = dataset.feature_arrays()
        self.num_bins_arr = jnp.asarray(meta["num_bins"])
        self.default_bins_arr = jnp.asarray(meta["default_bins"])
        self.missing_types_arr = jnp.asarray(meta["missing_types"])
        self.is_categorical_arr = jnp.asarray(meta["is_categorical"])
        self.has_categorical = bool(meta["is_categorical"].any())
        self.meta_host = meta

        # uniform per-feature bin budget (power of two for clean tiling)
        self.max_num_bins = int(meta["num_bins"].max())
        self.B = max(_next_pow2(self.max_num_bins), 8)

        # data_residency (docs/performance.md "Out-of-core"): hbm keeps the
        # binned matrix device-resident; stream keeps it in host shards and
        # uploads leaf windows on demand (bit-identical trees — the stream
        # hooks feed the same kernels the same values in the same order)
        self.residency = self._resolve_residency(config)
        if self.residency == "stream":
            from ..data.stream import as_sharded
            self.sdata = as_sharded(dataset, config)
            self.x_binned = None
            self._perm_host: Optional[np.ndarray] = None
            self._x_sorted_host: Optional[np.ndarray] = None
        else:
            self.sdata = None
            self.x_binned = jnp.asarray(dataset.binned)
        self.perm0 = jnp.arange(self.num_data, dtype=jnp.int32)

        self.params = SplitParams(
            lambda_l1=config.lambda_l1, lambda_l2=config.lambda_l2,
            max_delta_step=config.max_delta_step, path_smooth=config.path_smooth,
            min_data_in_leaf=config.min_data_in_leaf,
            min_sum_hessian_in_leaf=config.min_sum_hessian_in_leaf,
            min_gain_to_split=config.min_gain_to_split,
            cat_smooth=config.cat_smooth, cat_l2=config.cat_l2,
            max_cat_threshold=config.max_cat_threshold,
            max_cat_to_onehot=config.max_cat_to_onehot,
            min_data_per_group=config.min_data_per_group)

        self.rows_per_block = config.tpu_rows_per_block
        self.hist_precision = config.tpu_hist_precision
        self.hist_impl = self._resolve_hist_impl(config.tpu_hist_impl)
        self.layout = self._resolve_layout(config)
        # physical leaf-ordered copies under tree_layout=sorted (rebuilt per
        # tree in train(); None under the gather layout)
        self._x_sorted: Optional[jax.Array] = None
        self._gh_sorted: Optional[jax.Array] = None
        self._col_rng = np.random.RandomState(config.feature_fraction_seed)

        # monotone constraints, mapped original-feature -> used-feature
        # (reference: monotone_constraints.hpp — 'basic', 'intermediate'
        # and 'advanced' methods)
        mono = np.zeros(self.num_features, dtype=np.int32)
        self.mono_method = config.monotone_constraints_method
        if config.monotone_constraints:
            mc = list(config.monotone_constraints)
            for k, j in enumerate(dataset.used_features):
                if j < len(mc):
                    mono[k] = int(mc[j])
            if (mono != 0)[meta["is_categorical"]].any():
                log.fatal("monotone_constraints cannot be set on "
                          "categorical features")
            if self.mono_method not in ("basic", "intermediate", "advanced"):
                log.fatal("unknown monotone_constraints_method %r",
                          self.mono_method)
        self._nb_np = meta["num_bins"].astype(np.int32)
        self.mono_np = mono
        self.mono_arr = jnp.asarray(mono)
        self.mono_on = bool((mono != 0).any())
        self.mono_penalty = float(config.monotone_penalty)

        # CEGB (reference: src/treelearner/cost_effective_gradient_boosting.hpp)
        c = config
        self.cegb_on = c.cegb_tradeoff > 0 and (
            c.cegb_penalty_split > 0
            or len(c.cegb_penalty_feature_coupled) > 0
            or len(c.cegb_penalty_feature_lazy) > 0)
        coupled = np.zeros(self.num_features, dtype=np.float32)
        for k, j in enumerate(dataset.used_features):
            if j < len(c.cegb_penalty_feature_coupled):
                coupled[k] = c.cegb_penalty_feature_coupled[j]
        self._cegb_coupled = jnp.asarray(c.cegb_tradeoff * coupled)
        self._cegb_split_pen = float(c.cegb_tradeoff * c.cegb_penalty_split)
        self._cegb_used = np.zeros(self.num_features, dtype=bool)
        # lazy per-datum on-demand costs (reference: CalculateOndemandCosts
        # :139-164 + the UpdateLeafBestSplits bitset insert :125-135): a
        # candidate (leaf, feature) pays lazy[f] per in-bag in-leaf row
        # that has not yet been routed through an f-split; applying a
        # split marks the leaf's in-bag rows used for that feature.
        self._cegb_lazy = None
        self._cegb_bag_np = None
        if c.cegb_tradeoff > 0 and c.cegb_penalty_feature_lazy:
            lazy = np.zeros(self.num_features, dtype=np.float64)
            for k, j in enumerate(dataset.used_features):
                if j < len(c.cegb_penalty_feature_lazy):
                    lazy[k] = c.cegb_penalty_feature_lazy[j]
            self._cegb_lazy = c.cegb_tradeoff * lazy
            # host-side bit-packed mask, 1 bit per (feature, row) — the
            # same footprint as the reference's feature_used_in_data
            # bitset (cost_effective_gradient_boosting.hpp); this learner
            # orchestrates splits from the host anyway, and an in-place
            # numpy update beats a functional [F, N] device copy per split
            mask_bytes = (self.num_data + 7) // 8
            if self.num_features * mask_bytes > (1 << 25):   # > 32 MiB
                log.warning("cegb_penalty_feature_lazy keeps a "
                            "[features x rows] used-bitset (%.0f MB here)",
                            self.num_features * mask_bytes / 2**20)
            self._cegb_lazy_used = np.zeros(
                (self.num_features, mask_bytes), dtype=np.uint8)

        # original-feature -> used-feature index map
        self._inner_of = {j: k for k, j in enumerate(dataset.used_features)}

        # interaction constraints (reference: src/treelearner/col_sampler.hpp
        # interaction-set filtering): groups of ORIGINAL feature indices
        self.ic_groups = None
        if c.interaction_constraints:
            self.ic_groups = [frozenset(self._inner_of[j] for j in g
                                        if j in self._inner_of)
                              for g in c.interaction_constraints]

        # extra_trees: each scan considers ONE uniform-random threshold per
        # feature (reference: feature_histogram.hpp:192-205 USE_RAND)
        self.extra_on = bool(config.extra_trees)
        self._extra_rng = np.random.RandomState(config.extra_seed)
        self._nb_minus1 = np.maximum(meta["num_bins"].astype(np.int64) - 1, 1)
        self.nb_minus1_arr = jnp.asarray(self._nb_minus1.astype(np.int32))
        # feature_contri: per-feature multiplier on the post-shift gain
        # (reference: feature_histogram.hpp:174 output->gain *= penalty)
        self.contri_arr = None
        if config.feature_contri:
            fc = list(config.feature_contri)
            contri = np.ones(self.num_features, dtype=np.float32)
            for k, j in enumerate(dataset.used_features):
                if j < len(fc):
                    contri[k] = fc[j]
            self.contri_arr = jnp.asarray(contri)

        # forced splits (reference: serial_tree_learner.cpp:624 ForceSplits;
        # the JSON schema of examples/binary_classification/forced_splits.json)
        self.forced_json = None
        if config.forcedsplits_filename:
            import json
            try:
                with open(config.forcedsplits_filename) as fh:
                    fj = json.load(fh)
            except (OSError, ValueError) as e:
                log.fatal("cannot read forcedsplits_filename=%r: %s",
                          config.forcedsplits_filename, e)
            if fj:
                self.forced_json = fj

        # outputs of the last Train call, used for the O(1)-per-row score update
        self.last_perm: Optional[jax.Array] = None
        self.last_leaf_begin: Optional[np.ndarray] = None
        self.last_leaf_count: Optional[np.ndarray] = None

    #: learners whose histogram/partition passes cannot consume the
    #: physically leaf-ordered layout override this to False and fall back
    #: to the gather layout (the host-loop distributed learners, whose
    #: device matrices are shared per-shard views, and the fused
    #: feature-parallel learner, whose winning split column lives on
    #: another shard)
    supports_sorted_layout = True

    #: learners that can train with the binned matrix in host shards
    #: (``data_residency=stream``); the distributed learners keep their
    #: device matrices resident and override this to False
    supports_stream = True

    def _stream_blockers(self, config: Config) -> List[str]:
        """Config combinations this learner's stream mode does not express
        (checked from config only — subclass __init__ state is not built
        yet when this runs). Non-empty → fall back to hbm residency."""
        return []

    def _estimate_residency_bytes(self) -> int:
        """Approximate device bytes the hbm path would pin for the binned
        matrix (the ``stream_hbm_budget_mb`` auto-residency input)."""
        item = 1 if self.max_num_bins <= 256 else 2
        return self.num_data * self.num_features * item

    def _resolve_residency(self, config: Config) -> str:
        """Resolve ``data_residency``: auto streams for pre-sharded
        datasets (and above ``stream_hbm_budget_mb`` when set), stays
        device-resident otherwise; unsupported learners/options fall back
        to hbm loudly, never silently change semantics."""
        from ..data.stream import ShardedBinnedDataset
        mode = config.data_residency
        sharded = isinstance(self.dataset, ShardedBinnedDataset)
        if mode == "hbm":
            return "hbm"
        if not self.supports_stream:
            if mode == "stream" or sharded:
                # LOUD fallback (warning, not info): silently training a
                # requested-stream distributed run device-resident would
                # hide an OOM footprint the caller sized for streaming.
                # Both axes named (R12b): the demoted knob AND the
                # tree_learner value that forced the demotion. Since
                # ISSUE 15 the stream x distributed cell is SUPPORTED for
                # tree_learner=data on the fused 2-D learner (gbdt routes
                # it there before this resolver runs), so this branch
                # fires only for the learners whose programs genuinely
                # keep the matrix resident: the host-loop distributed
                # trio, fused voting/feature, and pre-partitioned
                # multi-process data.
                log.warning("data_residency=stream is not supported with "
                            "tree_learner=%s (%s keeps its device "
                            "matrices resident); falling back to "
                            "data_residency=hbm — tree_learner=data "
                            "streams through the fused 2-D mesh program",
                            config.tree_learner, type(self).__name__)
            return "hbm"
        blocker_knobs = self._stream_blockers(config)
        if blocker_knobs:
            if mode == "stream" or sharded:
                log.warning("data_residency=stream does not support %s; "
                            "training device-resident",
                            ", ".join(blocker_knobs))
            return "hbm"
        if mode == "stream" or sharded:
            return "stream"
        if config.stream_hbm_budget_mb > 0 and (
                self._estimate_residency_bytes()
                > config.stream_hbm_budget_mb << 20):
            log.info("data_residency=auto: estimated %.0f MB residency "
                     "exceeds stream_hbm_budget_mb=%d; streaming",
                     self._estimate_residency_bytes() / 2**20,
                     config.stream_hbm_budget_mb)
            return "stream"
        return "hbm"

    @staticmethod
    def _resolve_hist_impl(impl: str) -> str:
        """Pick the histogram strategy (the analog of TrainingShareStates'
        col/row-wise probe, reference: src/io/train_share_states.cpp — here
        the choice is XLA one-hot contraction vs the Pallas VMEM kernel;
        'auto' = Pallas on TPU, where Mosaic compiles it; one-hot
        elsewhere. An explicit 'pallas' off-TPU runs the kernel in
        interpret mode — exact but slow, the tier-1 CPU parity path)."""
        if impl == "auto":
            return "pallas" if jax.default_backend() == "tpu" else "onehot"
        if impl not in ("onehot", "pallas"):
            log.fatal("tpu_hist_impl must be auto/onehot/pallas, got %r", impl)
        return impl

    def _resolve_layout(self, config: Config) -> str:
        """Resolve ``tree_layout``: 'auto' picks the physically sorted-leaf
        layout at shapes where a random row gather per window dominates
        the histogram pass and the same bytes stream from a leaf-ordered
        copy (both cells resolve ``sorted``); small data keeps the gather
        layout — the sorted copy's rebuild-per-tree and extra residency
        are not worth it there. The 2^20-row threshold has no cell on
        either side of it (ROADMAP D4)."""
        layout = config.tree_layout
        if not self.supports_sorted_layout:
            if layout == "sorted":
                log.info("tree_layout=sorted is not supported with "
                         "tree_learner=%s (%s); using the gather layout",
                         config.tree_learner, type(self).__name__)
            return "gather"
        if layout == "auto":
            return "sorted" if self.num_data >= (1 << 20) else "gather"
        return layout

    # ------------------------------------------------------------------
    def _pad_size(self, count: int) -> int:
        return min(max(_next_pow2(max(count, 1)), 256), _next_pow2(self.num_data))

    def _feature_mask(self) -> jax.Array:
        """Per-tree column sampling (reference: src/treelearner/col_sampler.hpp)."""
        frac = self.config.feature_fraction
        if frac >= 1.0:
            return jnp.ones(self.num_features, dtype=bool)
        k = max(1, int(np.ceil(frac * self.num_features)))
        chosen = self._col_rng.choice(self.num_features, k, replace=False)
        mask = np.zeros(self.num_features, dtype=bool)
        mask[chosen] = True
        return jnp.asarray(mask)

    def _node_fmask(self, fmask, path_feats):
        """Per-node feature availability: interaction-constraint filtering +
        by-node column sampling (reference: col_sampler.hpp
        GetByNode / interaction sets)."""
        frac = self.config.feature_fraction_bynode
        if self.ic_groups is None and frac >= 1.0:
            return fmask
        m = np.asarray(jax.device_get(fmask)).copy()
        if self.ic_groups is not None:
            allowed = np.zeros(self.num_features, dtype=bool)
            for g in self.ic_groups:
                if path_feats <= g:
                    allowed[list(g)] = True
            m &= allowed
        if frac < 1.0 and m.any():
            avail = np.nonzero(m)[0]
            k = max(1, int(np.ceil(frac * len(avail))))
            keep = self._col_rng.choice(avail, k, replace=False)
            m[:] = False
            m[keep] = True
        return jnp.asarray(m)

    def _draw_extra_thresholds(self) -> jax.Array:
        """One uniform-random threshold bin per feature from the host-side
        extra_trees stream (reference: feature_histogram.hpp:192-205
        USE_RAND) — shared by every host-loop scan (serial, data-parallel,
        voting) so the draw semantics cannot diverge between learners."""
        return jnp.asarray(
            (self._extra_rng.randint(0, 1 << 30, self.num_features)
             % self._nb_minus1).astype(np.int32))

    def _cegb_lazy_rows(self, perm, begin: int, count: int):
        """IN-BAG rows of a leaf spanning perm[begin:begin+count] (the
        partition routes out-of-bag rows too; the reference's bagged
        data_partition_ holds in-bag indices only, so lazy charging and
        marking must filter)."""
        rows = np.asarray(jax.device_get(perm[begin:begin + count]))
        if self._cegb_bag_np is not None:
            rows = rows[self._cegb_bag_np[rows]]
        return rows

    def _cegb_lazy_pen(self, perm, begin: int, count: int):
        """Per-feature lazy on-demand penalty for a leaf (reference:
        CalculateOndemandCosts — lazy[f] * number of in-bag in-leaf rows
        not yet routed through an f-split)."""
        if self._cegb_lazy is None or count <= 0:
            return None
        rows = self._cegb_lazy_rows(perm, begin, count)
        used = ((self._cegb_lazy_used[:, rows >> 3]
                 >> (rows & 7)) & 1).sum(axis=1)
        return jnp.asarray((self._cegb_lazy
                            * (len(rows) - used)).astype(np.float32))

    def _cegb_lazy_mark(self, perm, begin: int, count: int,
                        feat: int) -> None:
        """Applying a split on ``feat`` marks the leaf's in-bag rows as
        having paid its lazy cost (reference: UpdateLeafBestSplits bitset
        insert)."""
        if self._cegb_lazy is not None and count > 0:
            rows = self._cegb_lazy_rows(perm, begin, count)
            np.bitwise_or.at(self._cegb_lazy_used[feat], rows >> 3,
                             (1 << (rows & 7)).astype(np.uint8))

    def _best(self, hist, pg, ph, pc, parent_output, fmask,
              bounds=None, path_feats=frozenset(), depth=0,
              adv=None, lazy_pen=None) -> _HostSplit:
        cons = None
        if self.mono_on:
            if adv is not None:
                # advanced method: dense per-threshold bound arrays
                cons = (self.mono_arr,) + tuple(jnp.asarray(a) for a in adv)
            else:
                lo, hi = bounds if bounds is not None else (-np.inf, np.inf)
                cons = (self.mono_arr, jnp.float32(lo), jnp.float32(hi))
        pen = None
        if self.cegb_on:
            pen = (self._cegb_split_pen * pc
                   + self._cegb_coupled * jnp.asarray(~self._cegb_used))
            if lazy_pen is not None:
                pen = pen + lazy_pen
        rand_t = None
        if self.extra_on:
            rand_t = self._draw_extra_thresholds()
        contri = self.contri_arr
        if self.mono_on and self.mono_penalty > 0:
            # depth-dependent gain penalty on monotone features (reference:
            # serial_tree_learner.cpp:998 + monotone_constraints.hpp:357)
            mp = monotone_split_penalty(int(depth), self.mono_penalty)
            mono_pen = jnp.where(self.mono_arr != 0, mp, 1.0)
            contri = mono_pen if contri is None else contri * mono_pen
        with self.telemetry.phase("split"):
            res = find_best_split(
                hist, pg, ph, pc, parent_output,
                self.num_bins_arr, self.default_bins_arr,
                self.missing_types_arr, self.is_categorical_arr,
                self._node_fmask(fmask, path_feats), self.params,
                has_categorical=self.has_categorical,
                constraints=cons, gain_penalty=pen,
                rand_thresholds=rand_t, gain_contri=contri)
            return _HostSplit(jax.device_get(res))

    # advanced monotone method -------------------------------------------
    # TPU-first re-design of AdvancedLeafConstraints (reference:
    # src/treelearner/monotone_constraints.hpp:858-1176). Instead of the
    # reference's recursive GoUp/GoDownToFindConstrainingLeaves walks
    # building piecewise (threshold, constraint) lists, every leaf carries
    # its bin-space bounding box; the constraining-leaf relation is one
    # vectorized box-adjacency test (m lies across a monotone feature g and
    # overlaps the leaf in every other feature — exactly the set the
    # reference's contiguity pruning converges to), and the per-threshold
    # cumulative extrema (CumulativeFeatureConstraint) become prefix/suffix
    # cummax/cummin over dense [F, B] arrays consumed by the vectorized
    # split scan.

    def _adv_constrainers(self, lo_l, hi_l, los, his):
        """For each monotone feature g: boolean masks over candidate leaves
        that bound this leaf from above/below in g while overlapping it in
        every other feature. Returns {g: (above[M], below[M])}."""
        ov = (los < hi_l[None, :]) & (lo_l[None, :] < his)       # [M, F]
        n_ov = ov.sum(axis=1)
        F = lo_l.shape[0]
        out = {}
        for g in np.nonzero(self.mono_np)[0]:
            others_ok = (n_ov - ov[:, g]) == (F - 1)
            above = (los[:, g] >= hi_l[g]) & others_ok
            below = (his[:, g] <= lo_l[g]) & others_ok
            out[int(g)] = (above, below)
        return out

    def _advanced_bound_arrays(self, leaf, boxes, tree):
        """Dense per-(feature, bin) monotone bounds for ``leaf`` from the
        current tree leaves, already cumulated into the four arrays the
        scan consumes: (min_left, max_left, min_right, max_right), each
        [F, B] f32 where index t carries the bound applicable to the
        left/right child of a split at threshold t."""
        F, B = self.num_features, self.B
        lo_l, hi_l = boxes[leaf]
        live = [m for m in range(tree.num_leaves)
                if m != leaf and m in boxes]
        min_raw = np.full((F, B), -np.inf, np.float32)
        max_raw = np.full((F, B), np.inf, np.float32)
        if live:
            los = np.stack([boxes[m][0] for m in live])
            his = np.stack([boxes[m][1] for m in live])
            outs = np.asarray([tree.leaf_value[m] for m in live], np.float32)
            bins = np.arange(B, dtype=np.int32)
            for g, (above, below) in self._adv_constrainers(
                    lo_l, hi_l, los, his).items():
                sgn = int(self.mono_np[g])
                uppers = above if sgn > 0 else below
                lowers = below if sgn > 0 else above
                pinf = np.float32(np.inf)
                for sel, is_upper in ((uppers, True), (lowers, False)):
                    idx = np.nonzero(sel)[0]
                    # chunk the constrainer axis: the [n, F, B] masks are
                    # transient reductions, so a bounded chunk keeps peak
                    # memory at CH*F*B regardless of leaf count (many-leaf
                    # trees otherwise pay O(leaves*F*B) per refreshed leaf)
                    CH = 64
                    for c0 in range(0, idx.size, CH):
                        ii = idx[c0:c0 + CH]
                        vs = outs[ii]
                        # each constrainer applies over ITS f-range for every
                        # scan feature f != g, and over the full range for
                        # f == g (all of this leaf lies across the boundary)
                        mask = ((bins[None, None, :] >= los[ii][:, :, None])
                                & (bins[None, None, :] < his[ii][:, :, None]))
                        mask[:, g, :] = True
                        if is_upper:
                            v = np.where(mask, vs[:, None, None], pinf)
                            max_raw = np.minimum(max_raw, v.min(axis=0))
                        else:
                            v = np.where(mask, vs[:, None, None], -pinf)
                            min_raw = np.maximum(min_raw, v.max(axis=0))
        # left child at threshold t covers bins [lo, t] -> inclusive prefix;
        # right child covers (t, hi) -> suffix shifted one past t
        min_l = np.maximum.accumulate(min_raw, axis=1)
        max_l = np.minimum.accumulate(max_raw, axis=1)
        sfx_min = np.maximum.accumulate(min_raw[:, ::-1], axis=1)[:, ::-1]
        sfx_max = np.minimum.accumulate(max_raw[:, ::-1], axis=1)[:, ::-1]
        min_r = np.concatenate([sfx_min[:, 1:], sfx_min[:, -1:]], axis=1)
        max_r = np.concatenate([sfx_max[:, 1:], sfx_max[:, -1:]], axis=1)
        return min_l, max_l, min_r, max_r

    def _adv_affected(self, lo_p, hi_p, boxes, leaves):
        """Leaves whose advanced constraints may change when the leaf that
        owned box (lo_p, hi_p) re-splits (its children's outputs are new):
        every leaf the OLD box constrained. The constrainer relation is
        symmetric in adjacency, so this is the union of the above/below
        masks from the shared box test (the reference tracks this as
        leaves_to_update_, monotone_constraints.hpp:560+)."""
        cand = [m for m in leaves if m in boxes]
        if not cand:
            return []
        los = np.stack([boxes[m][0] for m in cand])
        his = np.stack([boxes[m][1] for m in cand])
        hit = np.zeros(len(cand), dtype=bool)
        for above, below in self._adv_constrainers(lo_p, hi_p,
                                                   los, his).values():
            hit |= above | below
        return [m for m, h in zip(cand, hit) if h]

    # histogram hook points (overridden by the distributed learners) --------
    def _root_histogram(self, grad, hess, row_mask):
        if self.residency == "stream":
            return self._root_histogram_stream(grad, hess, row_mask)
        return full_histogram(self.x_binned, grad, hess, row_mask, self.B,
                              self.rows_per_block, self.hist_precision)

    def _root_histogram_stream(self, grad, hess, row_mask):
        """Root histogram over host shards: dataset-order windows pumped
        through the double-buffered H2D ring, accumulated on device in the
        resident scan's exact block order (data/stream.py)."""
        from ..data.stream import stream_windows
        from ..ops.histogram import finish_histogram_acc, histogram_block_acc
        N, F, B = self.num_data, self.num_features, self.B
        block = min(self.rows_per_block, N)
        nch = (N + block - 1) // block
        acc = [jnp.zeros((3, F * B), jnp.float32)]
        dtype = self.sdata.shards[0].dtype

        def fetch(c):
            lo = c * block
            hi = min(lo + block, N)
            buf = np.zeros((block, F), dtype=dtype)
            self.sdata.row_block(lo, hi, out=buf[:hi - lo])
            return (buf,)

        def consume(c, bins_dev):
            acc[0] = histogram_block_acc(
                acc[0], bins_dev, grad, hess, row_mask,
                jnp.int32(c * block), B, self.hist_precision)

        stream_windows(nch, fetch, consume, self.telemetry,
                       self.config.stream_prefetch_depth)
        return finish_histogram_acc(acc[0], F, B)

    def _leaf_histogram(self, perm, grad, hess, begin, count, padded, row_mask):
        if self.residency == "stream":
            return self._leaf_histogram_stream(grad, hess, begin, count,
                                               padded, row_mask)
        if self._x_sorted is not None:
            # sorted layout: the leaf is a contiguous position slice of the
            # physically reordered matrix — consecutive-index read, no
            # row gather (identical rows in identical order, so the
            # histogram is bit-identical to the gather oracle's)
            return leaf_histogram_sorted(
                self._x_sorted, self._gh_sorted, jnp.int32(begin),
                jnp.int32(count), padded, self.B, self.rows_per_block,
                self.hist_precision)
        return leaf_histogram(
            self.x_binned, perm, grad, hess, jnp.int32(begin),
            jnp.int32(count), padded, self.B, self.rows_per_block,
            row_mask, self.hist_precision)

    def _leaf_histogram_stream(self, grad, hess, begin, count, padded,
                               row_mask):
        """One leaf's histogram under stream residency: the host supplies
        the leaf's binned rows (a contiguous payload slice under the
        sorted layout, a shard gather under the gather layout); the
        gradient channels stay device-resident. Same kernels, same padded
        shapes, same values → bit-identical to the resident hooks."""
        from ..ops.histogram import (leaf_histogram_sorted_streamed,
                                     leaf_histogram_streamed)
        N = self.num_data
        if self.layout == "sorted":
            with self.telemetry.phase("h2d_prefetch"):
                buf = np.zeros((padded, self.num_features),
                               dtype=self.sdata.shards[0].dtype)
                hi = min(begin + count, N)
                buf[:hi - begin] = self._x_sorted_host[begin:hi]
                bins = jax.device_put(buf)
            return leaf_histogram_sorted_streamed(
                bins, self._gh_sorted, jnp.int32(begin), jnp.int32(count),
                self.B, self.rows_per_block, self.hist_precision)
        with self.telemetry.phase("h2d_prefetch"):
            idx = np.clip(np.arange(begin, begin + padded), 0, N - 1)
            rows_np = self._perm_host[idx]
            bins = jax.device_put(self.sdata.gather_rows(rows_np))
            rows = jax.device_put(rows_np.astype(np.int32))
        return leaf_histogram_streamed(bins, rows, grad, hess,
                                       jnp.int32(count), self.B,
                                       self.rows_per_block, row_mask,
                                       self.hist_precision)

    def _cat_bitset_real(self, feature_k: int, bitset_bins: np.ndarray) -> np.ndarray:
        """Convert a bin-space bitset to raw-category space for model export.

        The bitset is sized to the largest selected category (the reference
        sizes these dynamically, Common::ConstructBitset /
        src/io/tree.cpp cat_threshold_), so categories >= 256 route
        correctly at predict time."""
        j = self.dataset.used_features[feature_k]
        mapper = self.dataset.mappers[j]
        cats = []
        for b in range(mapper.num_bin):
            if (bitset_bins[b // 32] >> (b % 32)) & 1:
                cat = mapper.bin_2_categorical[b] if b < len(mapper.bin_2_categorical) else -1
                if cat >= 0:
                    cats.append(int(cat))
        words = max(8, (max(cats) + 32) // 32) if cats else 8
        out = np.zeros(words, dtype=np.uint32)
        for cat in cats:
            out[cat // 32] |= np.uint32(1) << np.uint32(cat % 32)
        return out

    def _forced_bin(self, node) -> Optional[tuple]:
        """Map a forced-split JSON node to (inner_feature, threshold_bin).
        Returns None (→ abort forcing) when the feature is unused or the
        threshold maps to no bin (the analog of InnerFeatureIndex +
        BinThreshold in ForceSplits)."""
        try:
            j = int(node["feature"])
            thr = float(node["threshold"])
        except (KeyError, TypeError, ValueError):
            log.warning("Malformed forced-split node %r; aborting forced "
                        "splits", node)
            return None
        k = self._inner_of.get(j)
        if k is None:
            log.warning("Forced split on unused feature %d; aborting forced "
                        "splits", j)
            return None
        mapper = self.dataset.mappers[j]
        if self.meta_host["is_categorical"][k]:
            thr_bin = mapper.categorical_2_bin.get(int(thr))
            if thr_bin is None:
                log.warning("Forced categorical split on unseen category %d "
                            "of feature %d; aborting forced splits",
                            int(thr), j)
                return None
        else:
            thr_bin = mapper._value_to_bin_scalar(thr)
        return k, int(thr_bin)

    def _split_partition_stream(self, perm, begin: int, count: int,
                                feat: int, s, P: int):
        """Stream-residency partition: the host supplies the split
        feature's bin values for the leaf slice (1-2 B/row over the link),
        the device runs the identical stable partition on ``perm`` (and
        the gradient channels under the sorted layout), and the returned
        go_left flags keep the host mirror — permutation or physical
        payload — in lockstep. Returns ``(new_perm, left_count_dev)``."""
        from ..ops.partition import (split_partition_sorted_vals,
                                     split_partition_vals)
        N = self.num_data
        idx = np.clip(np.arange(begin, begin + P), 0, N - 1)
        if self.layout == "sorted":
            with self.telemetry.phase("h2d_prefetch"):
                vals = jax.device_put(self._x_sorted_host[idx, feat])
            perm, self._gh_sorted, left_cnt_dev, gl = \
                split_partition_sorted_vals(
                    vals, self._gh_sorted, perm,
                    jnp.int32(begin), jnp.int32(count),
                    jnp.int32(s.threshold),
                    jnp.asarray(bool(s.default_left)),
                    self.default_bins_arr[feat],
                    self.missing_types_arr[feat],
                    self.num_bins_arr[feat],
                    jnp.asarray(bool(s.is_categorical)),
                    jnp.asarray(s.cat_bitset), P)
            # graftlint: disable=R1 — the go_left fetch IS the stream
            # design: the host must reorder its payload mirror; one small
            # D2H per split on the (already host-orchestrated) learner
            glh = np.asarray(jax.device_get(gl))[:count]
            sl = self._x_sorted_host[begin:begin + count]
            self._x_sorted_host[begin:begin + count] = np.concatenate(
                [sl[glh], sl[~glh]])
        else:
            rows_np = self._perm_host[idx]
            with self.telemetry.phase("h2d_prefetch"):
                vals = jax.device_put(self.sdata.gather_col(feat, rows_np))
            perm, left_cnt_dev, gl = split_partition_vals(
                vals, perm, jnp.int32(begin), jnp.int32(count),
                jnp.int32(s.threshold), jnp.asarray(bool(s.default_left)),
                self.default_bins_arr[feat], self.missing_types_arr[feat],
                self.num_bins_arr[feat], jnp.asarray(bool(s.is_categorical)),
                jnp.asarray(s.cat_bitset), P)
            # graftlint: disable=R1 — see above: the permutation mirror
            # must follow the device partition for the next host gather
            glh = np.asarray(jax.device_get(gl))[:count]
            rs = self._perm_host[begin:begin + count]
            self._perm_host[begin:begin + count] = np.concatenate(
                [rs[glh], rs[~glh]])
        return perm, left_cnt_dev

    # ------------------------------------------------------------------
    def train(self, grad: jax.Array, hess: jax.Array,
              row_mask: Optional[jax.Array] = None) -> Tree:
        """Grow one tree. grad/hess are [N] float32 on device, already
        multiplied by the bagging mask when sampling is active."""
        cfg = self.config
        num_leaves = cfg.num_leaves
        max_depth = cfg.max_depth
        tree = Tree(max_leaves=num_leaves)
        fmask = self._feature_mask()
        if self._cegb_lazy is not None:
            self._cegb_bag_np = (None if row_mask is None
                                 else np.asarray(jax.device_get(row_mask)))

        perm = self.perm0
        if self.layout == "sorted":
            # physical leaf-ordered copies, rebuilt per tree (gradients
            # change every iteration and the permutation restarts at
            # identity); the layout_apply span makes the rebuild cost tile
            # the iteration wall like every other phase
            with self.telemetry.phase("layout_apply"):
                parts = [grad[:, None], hess[:, None]]
                if row_mask is not None:
                    parts.append(row_mask.astype(jnp.float32)[:, None])
                if self.residency == "stream":
                    # the payload copy the host physically reorders lives
                    # in host RAM; only the gradient channels ride HBM
                    self._x_sorted = None
                    self._x_sorted_host = self.sdata.dataset_order_copy()
                else:
                    self._x_sorted = self.x_binned
                self._gh_sorted = jnp.concatenate(parts, axis=1)
        else:
            self._x_sorted = self._gh_sorted = None
        if self.residency == "stream" and self.layout != "sorted":
            # host mirror of the device permutation (kept in lockstep by
            # the partition go_left flags) drives the per-leaf row gathers
            self._perm_host = np.arange(self.num_data, dtype=np.int64)
        leaf_begin = np.zeros(num_leaves, dtype=np.int64)
        leaf_count = np.zeros(num_leaves, dtype=np.int64)
        leaf_count[0] = self.num_data

        # root histogram + totals (BeforeTrain analog)
        with self.telemetry.phase("histogram"):
            hist_root = self._root_histogram(grad, hess, row_mask)
        totals = jnp.sum(hist_root[0], axis=0)   # (g, h, c) — every row hits f0
        root_out = _leaf_output_scalar(totals[0], totals[1], totals[2], self.params)
        hists: Dict[int, jax.Array] = {0: hist_root}
        sums: Dict[int, tuple] = {0: (totals[0], totals[1], totals[2], root_out)}
        bounds: Dict[int, tuple] = {0: (-np.inf, np.inf)}
        paths: Dict[int, frozenset] = {0: frozenset()}
        best: Dict[int, _HostSplit] = {
            0: self._best(hist_root, totals[0], totals[1], totals[2], root_out,
                          fmask, bounds[0], paths[0],
                          lazy_pen=self._cegb_lazy_pen(perm, 0,
                                                       self.num_data))}

        # non-finite gradients poison the histogram count channel; the int
        # conversion must not crash mid-iteration — the guard layer decides
        # what to do with the tree at the iteration boundary
        # (guard_nonfinite policy, docs/robustness.md)
        # graftlint: disable=R1 — root-stat D2H, ONE batched pytree get
        # per tree (value/weight/count ride a single sync instead of three
        # blocking scalar gets); graftir's I2 audit proves every jitted
        # program here is transfer-free, so this explicit boundary read is
        # the whole per-tree host cost on this path
        root_out_h, root_w, root_cnt = (
            float(v) for v in
            jax.device_get((root_out, totals[1], totals[2])))
        tree.leaf_value[0] = root_out_h
        tree.leaf_weight[0] = root_w
        tree.leaf_count[0] = int(root_cnt) if np.isfinite(root_cnt) else 0

        # intermediate monotone method: per-tree node topology + subtree
        # markers (reference: IntermediateLeafConstraints state). The
        # advanced method keeps the intermediate scalar-bound bookkeeping
        # (AdvancedLeafConstraints : IntermediateLeafConstraints) and adds
        # per-leaf bin-space boxes feeding _advanced_bound_arrays.
        adv_on = self.mono_on and self.mono_method == "advanced"
        inter_on = self.mono_on and self.mono_method in ("intermediate",
                                                         "advanced")
        node_parent: List[int] = []
        leaf_mono: Dict[int, bool] = {}
        boxes: Dict[int, tuple] = {}
        if adv_on:
            boxes[0] = (np.zeros(self.num_features, np.int32),
                        self._nb_np.copy())

        def apply_split(leaf: int, s: _HostSplit) -> Optional[int]:
            """Partition + record split ``s`` on ``leaf``, then compute both
            children's histograms and best splits (the loop body shared by
            the forced-splits phase and the gain-driven main loop). Returns
            the right child's leaf id, or None when numerically degenerate."""
            nonlocal perm
            pnode_before = int(tree.leaf_parent[leaf])
            begin, count = int(leaf_begin[leaf]), int(leaf_count[leaf])
            P = self._pad_size(count)
            feat = int(s.feature)
            with self.telemetry.phase("partition"):
                if self.residency == "stream":
                    perm, left_cnt_dev = self._split_partition_stream(
                        perm, begin, count, feat, s, P)
                elif self._x_sorted is not None:
                    # sorted layout: apply the stable partition physically
                    # to the row payload + gradient channels as well
                    (perm, self._x_sorted, self._gh_sorted,
                     left_cnt_dev) = split_partition_sorted(
                        self._x_sorted, self._gh_sorted, perm,
                        jnp.int32(begin), jnp.int32(count),
                        jnp.int32(feat), jnp.int32(s.threshold),
                        jnp.asarray(bool(s.default_left)),
                        self.default_bins_arr[feat],
                        self.missing_types_arr[feat],
                        self.num_bins_arr[feat],
                        jnp.asarray(bool(s.is_categorical)),
                        jnp.asarray(s.cat_bitset), P)
                else:
                    perm, left_cnt_dev = split_partition(
                        self.x_binned, perm,
                        jnp.int32(begin), jnp.int32(count),
                        jnp.int32(feat), jnp.int32(s.threshold),
                        jnp.asarray(bool(s.default_left)),
                        self.default_bins_arr[feat],
                        self.missing_types_arr[feat],
                        self.num_bins_arr[feat],
                        jnp.asarray(bool(s.is_categorical)),
                        jnp.asarray(s.cat_bitset), P)
                left_cnt = int(jax.device_get(left_cnt_dev))
            right_cnt = count - left_cnt
            if _DEBUG_CHECKS and row_mask is None:
                # re-check the partition against the histogram's split
                # counts (the analog of SerialTreeLearner::CheckSplit's
                # partition re-walk under USE_DEBUG,
                # reference: serial_tree_learner.cpp:1071+)
                expect = int(round(float(s.left_count)))
                if left_cnt != expect:
                    log.fatal("CheckSplit failed on leaf %d feature %d: "
                              "partition left=%d but histogram left=%d",
                              leaf, feat, left_cnt, expect)
            if left_cnt == 0 or right_cnt == 0:
                # numerically degenerate split; drop this leaf from candidates
                log.warning("Degenerate split on leaf %d (feature %d): "
                            "left=%d right=%d; skipping", leaf, feat, left_cnt, right_cnt)
                return None

            j = self.dataset.used_features[feat]
            mapper = self.dataset.mappers[j]
            cat_real = (self._cat_bitset_real(feat, s.cat_bitset)
                        if s.is_categorical else None)
            mt_code = {"None": 0, "Zero": 1, "NaN": 2}[mapper.missing_type]
            # recorded counts are the IN-BAG histogram counts (the partition
            # routes out-of-bag rows too, but the reference's bagging counts
            # only used indices — and the fused learner records in-bag)
            right_leaf = tree.split(
                leaf, feature=j, feature_inner=feat,
                threshold_bin=int(s.threshold),
                threshold_real=mapper.bin_to_value(int(s.threshold)),
                default_left=bool(s.default_left), missing_type=mt_code,
                gain=s.gain_f,
                left_value=float(s.left_output), right_value=float(s.right_output),
                left_weight=float(s.left_sum_h), right_weight=float(s.right_sum_h),
                left_count=int(round(float(s.left_count))),
                right_count=int(round(float(s.right_count))),
                is_categorical=bool(s.is_categorical),
                cat_bitset=np.asarray(s.cat_bitset),
                cat_bitset_real=cat_real)

            if inter_on:
                # BeforeSplit analog: record the new node's parent and mark
                # the monotone subtree membership of both children
                node_parent.append(pnode_before)
                if int(self.mono_np[feat]) != 0 or leaf_mono.get(leaf, False):
                    leaf_mono[leaf] = True
                    leaf_mono[right_leaf] = True

            leaf_begin[leaf] = begin
            leaf_count[leaf] = left_cnt
            leaf_begin[right_leaf] = begin + left_cnt
            leaf_count[right_leaf] = right_cnt

            parent_hist = hists.pop(leaf)
            l_sums = (jnp.float32(s.left_sum_g), jnp.float32(s.left_sum_h),
                      jnp.float32(s.left_count), jnp.float32(s.left_output))
            r_sums = (jnp.float32(s.right_sum_g), jnp.float32(s.right_sum_h),
                      jnp.float32(s.right_count), jnp.float32(s.right_output))

            # children's monotone bounds. basic: the mid of the two outputs
            # caps the subtree on the constrained side; intermediate: each
            # child is capped by its SIBLING's output — looser, recovered
            # accuracy is the method's point (reference:
            # UpdateConstraintsWithOutputs, monotone_constraints.hpp:545)
            plo, phi = bounds.pop(leaf, (-np.inf, np.inf))
            m = int(self.mono_np[feat])
            llo, lhi, rlo, rhi = plo, phi, plo, phi
            if m != 0:
                lout_f = float(s.left_output)
                rout_f = float(s.right_output)
                if inter_on:
                    if m > 0:
                        lhi = min(phi, rout_f)
                        rlo = max(plo, lout_f)
                    else:
                        llo = max(plo, rout_f)
                        rhi = min(phi, lout_f)
                else:
                    mid = (lout_f + rout_f) / 2.0
                    if m > 0:
                        lhi = min(phi, mid)
                        rlo = max(plo, mid)
                    else:
                        llo = max(plo, mid)
                        rhi = min(phi, mid)
            bounds[leaf] = (llo, lhi)
            bounds[right_leaf] = (rlo, rhi)
            if adv_on:
                # children inherit the parent's bin-space box narrowed on
                # the split feature (categorical splits scatter bins to
                # both sides; keeping the parent box is conservative)
                lo_p, hi_p = boxes.pop(leaf)
                llo_b, lhi_b = lo_p.copy(), hi_p.copy()
                rlo_b, rhi_b = lo_p.copy(), hi_p.copy()
                if not bool(s.is_categorical):
                    lhi_b[feat] = int(s.threshold) + 1
                    rlo_b[feat] = int(s.threshold) + 1
                boxes[leaf] = (llo_b, lhi_b)
                boxes[right_leaf] = (rlo_b, rhi_b)
            child_path = paths.pop(leaf, frozenset()) | {feat}
            paths[leaf] = child_path
            paths[right_leaf] = child_path
            if self.cegb_on:
                self._cegb_used[feat] = True
                # lazy CEGB: the applied split routes the parent's rows
                # through `feat` even when it is the tree's LAST split —
                # the mark must precede the early return or later trees
                # re-charge first-use costs already paid (reference:
                # UpdateLeafBestSplits runs on every applied split)
                self._cegb_lazy_mark(perm, begin, count, feat)

            if tree.num_leaves >= num_leaves:
                return right_leaf  # no more splits: skip children histograms

            # smaller child gets a fresh histogram; sibling by subtraction
            # (reference: serial_tree_learner.cpp:408-476)
            small_is_left = left_cnt <= right_cnt
            sb, sc = (begin, left_cnt) if small_is_left else (begin + left_cnt, right_cnt)
            Ph = self._pad_size(sc)
            with self.telemetry.phase("histogram"):
                hist_small = self._leaf_histogram(perm, grad, hess, sb, sc,
                                                  Ph, row_mask)
                hist_large = parent_hist - hist_small

            small_leaf = leaf if small_is_left else right_leaf
            large_leaf = right_leaf if small_is_left else leaf
            s_sums = l_sums if small_is_left else r_sums
            g_sums = r_sums if small_is_left else l_sums

            hists[small_leaf] = hist_small
            hists[large_leaf] = hist_large
            child_depth = int(tree.leaf_depth[leaf])
            adv_s = (self._advanced_bound_arrays(small_leaf, boxes, tree)
                     if adv_on else None)
            adv_g = (self._advanced_bound_arrays(large_leaf, boxes, tree)
                     if adv_on else None)
            best[small_leaf] = self._best(hist_small, *s_sums, fmask,
                                          bounds[small_leaf],
                                          paths[small_leaf], child_depth,
                                          adv=adv_s,
                                          lazy_pen=self._cegb_lazy_pen(
                                              perm,
                                              int(leaf_begin[small_leaf]),
                                              int(leaf_count[small_leaf])))
            best[large_leaf] = self._best(hist_large, *g_sums, fmask,
                                          bounds[large_leaf],
                                          paths[large_leaf], child_depth,
                                          adv=adv_g,
                                          lazy_pen=self._cegb_lazy_pen(
                                              perm,
                                              int(leaf_begin[large_leaf]),
                                              int(leaf_count[large_leaf])))
            sums[small_leaf] = s_sums
            sums[large_leaf] = g_sums

            if inter_on and not adv_on and leaf_mono.get(leaf, False):
                # tighten bounds of contiguous leaves in monotone ancestors'
                # opposite subtrees, then refresh their cached best splits
                upd = _intermediate_propagate(
                    tree, node_parent, tree.num_leaves - 2, feat,
                    int(s.threshold), s, bounds, self.mono_np,
                    lambda lf_: lf_ in best and np.isfinite(best[lf_].gain_f))
                for ul in set(upd):
                    if ul in hists:
                        best[ul] = self._best(
                            hists[ul], *sums[ul], fmask, bounds[ul],
                            paths[ul], int(tree.leaf_depth[ul]),
                            lazy_pen=self._cegb_lazy_pen(
                                perm, int(leaf_begin[ul]),
                                int(leaf_count[ul])))
            elif adv_on:
                # the split replaced one output with two new ones: refresh
                # the cached best split of every leaf the OLD box
                # constrained (reference: leaves_to_update_ +
                # RecomputeConstraintsIfNeeded)
                lo_pre, hi_pre = boxes[leaf][0].copy(), boxes[leaf][1].copy()
                if not bool(s.is_categorical):
                    hi_pre[feat] = boxes[right_leaf][1][feat]  # parent range
                for ul in self._adv_affected(
                        lo_pre, hi_pre, boxes,
                        [m for m in hists if m not in (leaf, right_leaf)]):
                    best[ul] = self._best(
                        hists[ul], *sums[ul], fmask, bounds[ul], paths[ul],
                        int(tree.leaf_depth[ul]),
                        adv=self._advanced_bound_arrays(ul, boxes, tree),
                        lazy_pen=self._cegb_lazy_pen(
                            perm, int(leaf_begin[ul]),
                            int(leaf_count[ul])))
            return right_leaf

        # ---- forced-splits phase (reference: serial_tree_learner.cpp:624
        # ForceSplits): BFS over the JSON tree, splitting each named node at
        # its fixed (feature, threshold) before any gain-driven search; a
        # non-positive forced gain aborts the remaining forcing
        if self.forced_json is not None:
            from collections import deque
            q = deque([(self.forced_json, 0)])
            while q and tree.num_leaves < num_leaves:
                node, leaf = q.popleft()
                fb = self._forced_bin(node)
                if fb is None:
                    break
                k, thr_bin = fb
                if max_depth > 0 and tree.leaf_depth[leaf] >= max_depth:
                    break
                pg, ph, pc, pout = sums[leaf]
                fbounds = None
                if self.mono_on:
                    lo, hi = bounds.get(leaf, (-np.inf, np.inf))
                    fbounds = (jnp.float32(lo), jnp.float32(hi))
                res = gather_threshold_split(
                    hists[leaf][k], pg, ph, pc, pout, jnp.int32(k),
                    jnp.int32(thr_bin), self.num_bins_arr[k],
                    self.default_bins_arr[k], self.missing_types_arr[k],
                    self.is_categorical_arr[k], self.params, bounds=fbounds)
                s = _HostSplit(jax.device_get(res))
                if not np.isfinite(s.gain_f) or s.gain_f <= 0:
                    log.warning("Forced split on feature %d ignored (gain "
                                "not positive); aborting remaining forced "
                                "splits", int(node["feature"]))
                    break
                best.pop(leaf, None)
                right_leaf = apply_split(leaf, s)
                if right_leaf is None:
                    break
                for key, child in (("left", leaf), ("right", right_leaf)):
                    ch = node.get(key)
                    if (isinstance(ch, dict) and "feature" in ch
                            and "threshold" in ch):
                        q.append((ch, child))

        # ---- gain-driven main loop: pick the leaf with max gain (ArgMax
        # over best_split_per_leaf_, reference: serial_tree_learner.cpp:225)
        while tree.num_leaves < num_leaves:
            cand = [(s.gain_f, leaf) for leaf, s in best.items()
                    if np.isfinite(s.gain_f) and s.gain_f > 0
                    and (max_depth <= 0 or tree.leaf_depth[leaf] < max_depth)]
            if not cand:
                break
            _, leaf = max(cand)
            apply_split(leaf, best.pop(leaf))

        self.last_perm = perm
        self.last_leaf_begin = leaf_begin[:tree.num_leaves].copy()
        self.last_leaf_count = leaf_count[:tree.num_leaves].copy()
        return tree


def _leaf_output_scalar(g, h, c, params: SplitParams):
    from ..ops.split import calculate_leaf_output
    return calculate_leaf_output(g, h, params, c, 0.0)


def _intermediate_propagate(tree: Tree, node_parent: List[int],
                            start_node: int, split_feat: int, thr_bin: int,
                            s, bounds: Dict[int, tuple], mono_np: np.ndarray,
                            splittable) -> List[int]:
    """Intermediate-method constraint propagation: walk up from the new
    split node; in every monotone ancestor's opposite subtree, tighten the
    min/max bound of each leaf contiguous to the new children using the new
    children's outputs (reference: monotone_constraints.hpp:560-850
    IntermediateLeafConstraints::Update / GoUpToFindLeavesToUpdate /
    GoDownToFindLeavesToUpdate / ShouldKeepGoingLeftRight). Mutates
    ``bounds`` in place; returns the leaves whose bounds tightened (their
    cached best splits must be recomputed)."""
    updated: List[int] = []
    up_feats: List[int] = []
    up_thrs: List[int] = []
    up_was_right: List[bool] = []
    lout, rout = float(s.left_output), float(s.right_output)

    def go_down(nidx: int, update_max: bool, use_left: bool,
                use_right: bool) -> None:
        if nidx < 0:
            leaf = ~nidx
            # unsplittable leaves never split again, so their (already
            # clamped) outputs need no tighter bound
            if not splittable(leaf):
                return
            if use_left and use_right:
                lo_v, hi_v = min(lout, rout), max(lout, rout)
            elif use_right:
                lo_v = hi_v = rout
            else:
                lo_v = hi_v = lout
            plo, phi = bounds.get(leaf, (-np.inf, np.inf))
            if update_max:
                new_hi = min(phi, lo_v)
                if new_hi < phi:
                    bounds[leaf] = (plo, new_hi)
                    updated.append(leaf)
            else:
                new_lo = max(plo, hi_v)
                if new_lo > plo:
                    bounds[leaf] = (new_lo, phi)
                    updated.append(leaf)
            return
        inner_f = tree.split_feature_inner[nidx]
        thr = tree.threshold_bin[nidx]
        is_num = not tree.is_categorical[nidx]
        # contiguity pruning against the recorded up-path splits
        keep_left = keep_right = True
        if is_num:
            for f_i, t_i, r_i in zip(up_feats, up_thrs, up_was_right):
                if f_i == inner_f:
                    if thr >= t_i and not r_i:
                        keep_right = False
                    if thr <= t_i and r_i:
                        keep_left = False
        # same-feature splits below decide which new leaf stays contiguous
        use_l_for_right = use_r_for_left = True
        if is_num and inner_f == split_feat:
            if thr >= thr_bin:
                use_l_for_right = False
            if thr <= thr_bin:
                use_r_for_left = False
        if keep_left:
            go_down(tree.left_child[nidx], update_max,
                    use_left, use_right and use_r_for_left)
        if keep_right:
            go_down(tree.right_child[nidx], update_max,
                    use_left and use_l_for_right, use_right)

    node = start_node
    while True:
        parent = node_parent[node] if 0 <= node < len(node_parent) else -1
        if parent < 0:
            break
        inner_f = tree.split_feature_inner[parent]
        is_right = tree.right_child[parent] == node
        is_num_parent = not tree.is_categorical[parent]
        # only branches contiguous to the original leaf can need updates:
        # for a feature already crossed in the same direction going up,
        # the opposite child cannot be contiguous
        opposite_ok = is_num_parent and all(
            not (f_i == inner_f and r_i == is_right)
            for f_i, r_i in zip(up_feats, up_was_right))
        if opposite_ok:
            if mono_np[inner_f] != 0:
                left_is_curr = tree.left_child[parent] == node
                opposite = (tree.right_child[parent] if left_is_curr
                            else tree.left_child[parent])
                update_max = (left_is_curr if mono_np[inner_f] < 0
                              else not left_is_curr)
                go_down(opposite, update_max, True, True)
            up_was_right.append(is_right)
            up_thrs.append(tree.threshold_bin[parent])
            up_feats.append(inner_f)
        node = parent
    return updated
