"""Exclusive Feature Bundling (EFB).

TPU re-design of the reference's greedy conflict-bounded bundling
(reference: src/io/dataset.cpp:107 FindGroups, :246 FastFeatureBundling,
include/LightGBM/feature_group.h): mutually-exclusive (rarely
simultaneously non-default) features share one stored column, shrinking
the histogram width the device learner sweeps.

Layout differences from the reference are deliberate: the dataset's public
``binned`` matrix stays unbundled (so binned tree traversal — validation
replay, DART renormalize, continued-training replay — needs no decode);
the bundled matrix is a *second* device artifact consumed by the fused
learner, whose histograms are un-bundled back to per-feature space just
before the split scan (``ops.histogram.unbundle_hist``). A bundle's bin 0
means "every member at its default bin"; member ``m`` contributes bins
``offset_m .. offset_m + num_bin_m - 2`` for its non-default bins (rank
encoding skips the default bin). Conflicting rows keep the last member's
value — the same bounded corruption the reference accepts
(``max_conflict_rate``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from ..utils import log

MAX_BUNDLE_BINS = 256            # keep bundled columns uint8-addressable
KIND_ZERO, KIND_COPY, KIND_DEFAULT = 0, 1, 2


@dataclass
class Bundle:
    """Bundled matrix + per-feature decode metadata (inner-feature indexed)."""
    cols: np.ndarray             # [N, C] uint8/uint16 bundled matrix
    num_bins: List[int]          # bins per bundled column
    col_of: np.ndarray           # i32 [F] column holding feature f
    off_of: np.ndarray           # i32 [F] rank offset of f inside its column
    single: np.ndarray           # bool [F] column holds only this feature
    members: List[List[int]]     # per column: inner feature indices

    @property
    def num_cols(self) -> int:
        return self.cols.shape[1]


def find_groups(nz: np.ndarray, feature_bins: np.ndarray,
                max_conflict_rate: float,
                max_scan: int = 64):
    """Greedy conflict-bounded grouping (reference: dataset.cpp:107).

    nz: bool [S, F] sampled non-default mask per feature.
    Returns list of bundles (lists of feature indices).
    """
    S, F = nz.shape
    budget = max_conflict_rate * S
    nz_cnt = nz.sum(axis=0)
    order = np.argsort(-nz_cnt)                # most non-defaults first
    bundle_members: List[List[int]] = []
    bundle_masks: List[np.ndarray] = []
    bundle_cnts: List[int] = []                # popcount of each mask
    bundle_conflicts: List[float] = []
    bundle_bins: List[int] = []
    for f in order:
        placed = False
        cnt_f = int(nz_cnt[f])
        # cap the candidate scan like the reference's random-subset probe
        for bi in range(min(len(bundle_members), max_scan)):
            extra_bins = int(feature_bins[f]) - 1
            if bundle_bins[bi] + extra_bins > MAX_BUNDLE_BINS:
                continue
            # pigeonhole lower bound on the conflict count: two sets of
            # cnt_f and cnt_b rows among S overlap on at least
            # cnt_f + cnt_b - S rows, so a candidate that already fails on
            # the bound fails on the true count — skip the O(S) mask AND.
            # Dense matrices (every feature ~always non-default) used to
            # pay F x max_scan full-sample ANDs here just to bundle
            # nothing, which made max_bin=63 dataset construction
            # slower than max_bin=255 (whose wide bins never pass the
            # bin-budget check above).
            if bundle_conflicts[bi] + max(0, cnt_f + bundle_cnts[bi] - S) \
                    > budget:
                continue
            c = int((bundle_masks[bi] & nz[:, f]).sum())
            if bundle_conflicts[bi] + c <= budget:
                bundle_members[bi].append(int(f))
                bundle_masks[bi] |= nz[:, f]
                bundle_cnts[bi] = int(bundle_masks[bi].sum())
                bundle_conflicts[bi] += c
                bundle_bins[bi] += extra_bins
                placed = True
                break
        if not placed:
            bundle_members.append([int(f)])
            bundle_masks.append(nz[:, f].copy())
            bundle_cnts.append(cnt_f)
            bundle_conflicts.append(0.0)
            bundle_bins.append(1 + int(feature_bins[f]) - 1)
    return bundle_members


def build_bundle(binned: np.ndarray, feature_bins: np.ndarray,
                 default_bins: np.ndarray, max_conflict_rate: float,
                 sample_cnt: int = 100_000) -> Optional[Bundle]:
    """Find groups on a row sample and encode the bundled matrix.

    binned: the UNBUNDLED [N, F] matrix; feature_bins/default_bins are
    per-inner-feature. Returns None when no multi-feature bundle exists
    (bundling would only add decode overhead).
    """
    N, F = binned.shape
    if F < 2:
        return None
    # a bundle of two holds 1 + (b1 - 1) + (b2 - 1) bins: when even the two
    # narrowest features overflow MAX_BUNDLE_BINS no bundle can form, and the
    # conflict search over the sample would only find so (dense 255-bin
    # columns: 1.7 s of 2,000 x 100,000 compares to bundle nothing)
    narrow = np.sort(np.asarray(feature_bins, np.int64))[:2]
    if int(narrow.sum()) - 1 > MAX_BUNDLE_BINS:
        return None
    S = min(N, sample_cnt)
    step = max(N // S, 1)
    sample = binned[::step][:S]
    nz = sample != default_bins[None, :]
    groups = find_groups(nz, feature_bins, max_conflict_rate)
    if all(len(g) == 1 for g in groups):
        return None

    # singles keep raw bins; multi-member bundles use rank encoding
    C = len(groups)
    max_bins = 2
    col_of = np.zeros(F, np.int32)
    off_of = np.zeros(F, np.int32)
    single = np.zeros(F, bool)
    num_bins_out: List[int] = []
    for ci, g in enumerate(groups):
        if len(g) == 1:
            f = g[0]
            col_of[f] = ci
            single[f] = True
            num_bins_out.append(int(feature_bins[f]))
        else:
            off = 1
            for f in g:
                col_of[f] = ci
                off_of[f] = off
                off += int(feature_bins[f]) - 1
            num_bins_out.append(off)
        max_bins = max(max_bins, num_bins_out[-1])

    dtype = np.uint8 if max_bins <= 256 else np.uint16
    cols = np.zeros((N, C), dtype=dtype)
    for ci, g in enumerate(groups):
        if len(g) == 1:
            cols[:, ci] = binned[:, g[0]].astype(dtype)
            continue
        for f in g:
            b = binned[:, f].astype(np.int32)
            d = int(default_bins[f])
            nzm = b != d
            rank = b - (b > d)
            cols[nzm, ci] = (off_of[f] + rank[nzm]).astype(dtype)
    log.info("EFB bundled %d features into %d columns "
             "(max %d bins per column)", F, C, max_bins)
    return Bundle(cols=cols, num_bins=num_bins_out, col_of=col_of,
                  off_of=off_of, single=single, members=groups)


def unbundle_map(bundle: Bundle, feature_bins: np.ndarray,
                 default_bins: np.ndarray, B: int, Bb: int):
    """Precompute the histogram un-bundling gather.

    Returns (src[F, B] i32 into the flattened [C*Bb] bundle histogram,
    kind[F, B] u8 in {ZERO, COPY, DEFAULT}): COPY bins gather straight from
    the bundle histogram; a bundled feature's default bin is the residual
    ``leaf_total - sum(its COPY bins)`` (rows whose winner was another
    member sit in other bins of the shared column).
    """
    F = len(bundle.col_of)
    src = np.zeros((F, B), np.int32)
    kind = np.zeros((F, B), np.uint8)
    for f in range(F):
        nb = int(feature_bins[f])
        ci = int(bundle.col_of[f])
        if bundle.single[f]:
            src[f, :nb] = ci * Bb + np.arange(nb)
            kind[f, :nb] = KIND_COPY
            continue
        d = int(default_bins[f])
        for b in range(nb):
            if b == d:
                kind[f, b] = KIND_DEFAULT
            else:
                rank = b - (1 if b > d else 0)
                src[f, b] = ci * Bb + int(bundle.off_of[f]) + rank
                kind[f, b] = KIND_COPY
    return src, kind
