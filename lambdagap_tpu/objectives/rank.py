"""Ranking objectives: the extended LambdaRank family and RankXENDCG.

This is the fork's namesake delta: ``lambdarank_target`` selects one of 18
pairwise gradient targets — ranknet / bin-ranknet / ndcg / bndcg /
lambdaloss-{ndcg,bndcg}[-plus-plus] / precision / arpk /
lambdaloss-arp{1,2} / lambdagap-{s,x}[-plus[-plus]] — with the
``lambdagap_weight`` hybrid knob
(reference: src/objective/rank_objective.hpp:22-41 target enum, :253-524
pairwise loop with per-target pair windows and delta_pair formulas,
include/LightGBM/config.h:989-1013).

TPU design: queries are bucketed by padded power-of-2 length; per bucket one
jitted, query-vmapped kernel sorts by score, forms the [L, L] pair lattice
with the target's (i_end, start, end) window as masks, and accumulates
lambdas/hessians by row+column reduction — O(ΣL²) dense VPU work instead of
the reference's per-query OMP loops (rank_objective.hpp:82-116) or the CUDA
bitonic-sort kernel (src/objective/cuda/cuda_rank_objective.cu). The sigmoid
lookup table (:526-552) is unnecessary — the VPU computes sigmoids directly.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import Config
from ..obs.telemetry import device_scope
from ..utils import log
from .base import K_EPSILON, ObjectiveFunction, register_objective

K_MIN_SCORE = -1e30

# targets using the binarized pair filter (skip pairs with both labels > 0)
# (reference: rank_objective.hpp:365-380)
_BINARY_TARGETS = frozenset({
    "precision", "bndcg", "lambdaloss-bndcg", "lambdaloss-bndcg-plus-plus",
    "arpk", "bin-ranknet", "lambdagap-s", "lambdagap-x", "lambdagap-s-plus",
    "lambdagap-x-plus", "lambdagap-s-plus-plus", "lambdagap-x-plus-plus"})

# targets whose outer loop stops at the truncation level
# (reference: rank_objective.hpp:306-321)
_TRUNCATED_I_TARGETS = frozenset({
    "ndcg", "lambdaloss-ndcg", "lambdaloss-ndcg-plus-plus", "bndcg",
    "lambdaloss-bndcg", "lambdaloss-bndcg-plus-plus", "precision"})


def _discount(rank):
    """1/log2(2+rank) (reference: dcg_calculator.cpp GetDiscount)."""
    return 1.0 / jnp.log2(2.0 + rank)


def _gain_gap(hi_is_i, gi, gj):
    """``label_gain[high label] - label_gain[low label]`` of each pair, from
    the two documents' own gains: a label's gain belongs to its document, so
    it is looked up once a document (``lattice``) and only oriented here."""
    return jnp.where(hi_is_i, gi, gj) - jnp.where(hi_is_i, gj, gi)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def max_dcg_at_k(labels: np.ndarray, k: int, label_gain: np.ndarray) -> float:
    """(reference: dcg_calculator.cpp CalMaxDCGAtK)"""
    top = np.sort(labels)[::-1][:k]
    disc = 1.0 / np.log2(2.0 + np.arange(len(top)))
    return float(np.sum(label_gain[top.astype(np.int64)] * disc))


def max_bdcg_at_k(labels: np.ndarray, k: int) -> float:
    """Binarized max DCG (fork-added; reference: dcg_calculator.cpp:82
    CalMaxBDCGAtK): sum of top-min(k, #relevant) discounts."""
    relevant = int(np.sum(labels > 0))
    kk = min(k, len(labels), relevant)
    if kk <= 0:
        return 0.0
    return float(np.sum(1.0 / np.log2(2.0 + np.arange(kk))))


class _QueryBuckets:
    """Queries grouped by padded length for shape-stable jitted kernels.

    No length cap: arbitrarily long queries are exact (the reference handles
    any query length, rank_objective.hpp:253-524) — buckets past the dense
    lattice limit route to the row-tiled pairwise kernel, whose memory is
    O(L·T) instead of O(L²)."""

    def __init__(self, query_boundaries: np.ndarray, num_data: int) -> None:
        self.num_data = num_data
        qb = np.asarray(query_boundaries, dtype=np.int64)
        lengths = np.diff(qb)
        self.num_queries = len(lengths)
        buckets: Dict[int, List[int]] = {}
        for qi, ln in enumerate(lengths):
            L = max(_next_pow2(int(ln)), 8)
            buckets.setdefault(L, []).append(qi)
        self.buckets = []
        for L, qids in sorted(buckets.items()):
            nq = len(qids)
            idx = np.full((nq, L), num_data, dtype=np.int32)   # num_data = pad
            for r, qi in enumerate(qids):
                ln = min(int(lengths[qi]), L)
                idx[r, :ln] = np.arange(qb[qi], qb[qi] + ln, dtype=np.int32)
            self.buckets.append((L, np.asarray(qids, np.int32), idx))


_LOOP_CACHE: dict = {}


class RankingBase(ObjectiveFunction):
    """Shared query plumbing (reference: rank_objective.hpp:45-147
    RankingObjective): per-query gradient kernels + position-bias Newton
    updates + effective-pair-rate logging."""

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.position_bias_regularization = \
            config.lambdarank_position_bias_regularization
        self.learning_rate = config.learning_rate
        self.iter_count = 0
        self.last_effective_pair_rate = None

    def init(self, metadata, num_data) -> None:
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Ranking tasks require query information")
        self.query_boundaries = np.asarray(metadata.query_boundaries)
        self.num_queries = metadata.num_queries
        self.bucketing = _QueryBuckets(self.query_boundaries, num_data)
        # constants of the bucketing for the iteration record's ``counts``:
        # docs, docs after padding every query to its bucket's length, and
        # the pair-lattice cells the gradient program evaluates
        self.work_counts = {
            "rank_docs": int(num_data),
            "rank_pad_docs": sum(int(idx.size) for _, _, idx
                                 in self.bucketing.buckets),
            "rank_pair_cells": sum(len(qids) * self._pair_cells(L)
                                   for L, qids, _ in self.bucketing.buckets)}
        # positions for unbiased LTR
        if metadata.position is not None:
            pos = np.asarray(metadata.position, np.int32)
            self.positions = jnp.asarray(pos)
            self.num_position_ids = int(pos.max()) + 1
            self.pos_biases = jnp.zeros(self.num_position_ids, jnp.float32)
        else:
            self.positions = None
            self.num_position_ids = 0

    # per-bucket kernel; subclasses implement
    def _bucket_gradients(self, scores_b, labels_b, valid_b, aux_b):
        raise NotImplementedError

    def _bucket_gradients_k(self, scores_b, labels_b, valid_b, aux_b, key):
        """Keyed variant for randomized objectives (xendcg); the default
        ignores the key."""
        return self._bucket_gradients(scores_b, labels_b, valid_b, aux_b)

    def _bucket_aux(self, qids: np.ndarray) -> tuple:
        return ()

    def _pair_cells(self, L: int) -> int:
        """Pair-lattice cells the bucket kernel evaluates for one query
        padded to ``L`` (no lattice: none)."""
        return 0

    def _next_key(self):
        """Per-iteration PRNG key for randomized subclasses."""
        return jnp.zeros(2, jnp.uint32)

    def _loop_statics(self) -> tuple:
        """Hashable tuple of EVERY self-dependency the jitted loop body
        reads (kernel config + label gains): two objectives with equal
        statics may share one compiled loop."""
        return ()

    def _make_loop(self):
        """Compile the WHOLE bucket loop into one program. The eager loop
        paid ~6 dispatches per bucket per iteration (gathers, the kernel,
        two scatter-adds). Bucket index/aux arrays are passed as pytree
        ARGUMENTS, not closed over: captured device arrays would inline
        into the HLO as N-scale constants."""
        num_data = self.num_data
        has_pos = self.positions is not None

        def loop(s, label, positions, pos_biases, key, idxs, auxs):
            # the four inner scopes (obs.telemetry.GRADIENT_SCOPES) tile
            # the program: the bucket kernels open rank_sort / rank_lattice
            with device_scope("gradients"):
                with device_scope("rank_gather"):
                    if has_pos:
                        s = s + pos_biases[positions]
                    pad_s = jnp.concatenate([s, jnp.asarray([K_MIN_SCORE],
                                                            s.dtype)])
                    pad_l = jnp.concatenate([label,
                                             jnp.asarray([0.0], label.dtype)])
                with device_scope("rank_scatter"):
                    grad = jnp.zeros(num_data + 1, jnp.float32)
                    hess = jnp.zeros(num_data + 1, jnp.float32)
                eff_sum = jnp.float32(0.0)
                for idx_d, aux in zip(idxs, auxs):
                    with device_scope("rank_gather"):
                        sb = pad_s[idx_d]
                        lb = pad_l[idx_d]
                        vb = idx_d < num_data
                    lam, hes, eff = self._bucket_gradients_k(sb, lb, vb, aux,
                                                             key)
                    with device_scope("rank_scatter"):
                        grad = grad.at[idx_d.reshape(-1)].add(
                            lam.reshape(-1), mode="drop")
                        hess = hess.at[idx_d.reshape(-1)].add(
                            hes.reshape(-1), mode="drop")
                    with device_scope("rank_lattice"):
                        eff_sum = eff_sum + jnp.sum(eff)
                with device_scope("rank_scatter"):
                    return grad[:-1], hess[:-1], eff_sum

        idxs = tuple(jnp.asarray(idx) for (_, _, idx)
                     in self.bucketing.buckets)
        auxs = tuple(self._bucket_aux(qids) for (_, qids, _)
                     in self.bucketing.buckets)
        # share compiled loops across instances (cv folds, repeated
        # sweeps): the closure captures `self`, so the cache key must list
        # every self-dependency of the body — num_data, position use, and
        # the kernel statics. The cached closure pins its first objective
        # alive; the cache is small and bounded.
        key = (type(self).__qualname__, num_data, has_pos,
               self._loop_statics())
        fn = _LOOP_CACHE.get(key)
        if fn is None:
            if len(_LOOP_CACHE) > 16:
                _LOOP_CACHE.clear()
            _LOOP_CACHE[key] = fn = jax.jit(loop)
        return fn, idxs, auxs

    def get_gradients(self, scores):
        s = scores[0]
        if getattr(self, "_loop_jit", None) is None:
            self._loop_jit, self._loop_idxs, self._loop_auxs = \
                self._make_loop()
        pos = self.positions if self.positions is not None \
            else jnp.zeros(1, jnp.int32)
        pb = self.pos_biases if self.positions is not None \
            else jnp.zeros(1, jnp.float32)
        g, h, eff_sum = self._loop_jit(s, self.label, pos, pb,
                                       self._next_key(), self._loop_idxs,
                                       self._loop_auxs)
        if self.weight is not None:
            g = g * self.weight
            h = h * self.weight
        if self.positions is not None:
            self._update_position_bias(g, h)
        # the fork's per-iteration effective-pair-rate line
        # (reference: src/objective/rank_objective.hpp:108-116) — the D2H
        # sync is only paid when debug logging is on
        if log.debug_enabled():
            rate = float(eff_sum) / max(self.num_queries, 1)
            self.last_effective_pair_rate = rate
            log.debug("iteration %d: effective pair rate %.4f "
                      "(mean over %d queries)",
                      self.iter_count + 1, rate, self.num_queries)
        self.iter_count += 1
        return g[None, :], h[None, :]

    def _update_position_bias(self, grad, hess) -> None:
        """Newton-Raphson on per-position utility derivatives
        (reference: rank_objective.hpp:554-591 UpdatePositionBiasFactors)."""
        npos = self.num_position_ids
        first = -jax.ops.segment_sum(grad, self.positions, num_segments=npos)
        second = -jax.ops.segment_sum(hess, self.positions, num_segments=npos)
        counts = jax.ops.segment_sum(jnp.ones_like(grad), self.positions,
                                     num_segments=npos)
        first = first - self.pos_biases * self.position_bias_regularization * counts
        second = second - self.position_bias_regularization * counts
        self.pos_biases = self.pos_biases + \
            self.learning_rate * first / (jnp.abs(second) + 0.001)


@register_objective
class LambdarankNDCG(RankingBase):
    """The 18-target LambdaRank
    (reference: rank_objective.hpp:174-648 LambdarankNDCG)."""
    name = "lambdarank"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.sigmoid = config.sigmoid
        self.norm = config.lambdarank_norm
        self.truncation_level = config.lambdarank_truncation_level
        self.target = config.lambdarank_target
        self.lambdagap_weight = config.lambdagap_weight

    def init(self, metadata, num_data) -> None:
        super().init(metadata, num_data)
        max_label = int(self.label_np.max())
        if np.any(self.label_np < 0) or np.any(self.label_np != np.floor(self.label_np)):
            log.fatal("[lambdarank]: labels must be non-negative integers")
        gains = np.asarray(self.config.label_gain_or_default(max_label))
        if max_label >= len(gains):
            log.fatal("Label %d exceeds label_gain size %d", max_label, len(gains))
        self.label_gain = jnp.asarray(gains, jnp.float32)
        # per-query inverse max (B)DCG at the truncation level
        # (reference: rank_objective.hpp:250-266)
        inv_dcg = np.zeros(self.num_queries)
        inv_bdcg = np.zeros(self.num_queries)
        qb = self.query_boundaries
        for qi in range(self.num_queries):
            ql = self.label_np[qb[qi]:qb[qi + 1]]
            d = max_dcg_at_k(ql, self.truncation_level, gains)
            b = max_bdcg_at_k(ql, self.truncation_level)
            inv_dcg[qi] = 1.0 / d if d > 0 else 0.0
            inv_bdcg[qi] = 1.0 / b if b > 0 else 0.0
        self.inv_max_dcg = inv_dcg
        self.inv_max_bdcg = inv_bdcg
        log.info("Using lambdarank objective with target '%s'", self.target)

    def _loop_statics(self) -> tuple:
        import numpy as _np
        return (self.target, self.sigmoid, self.norm,
                self.truncation_level, self.lambdagap_weight,
                tuple(_np.asarray(self.label_gain).tolist()))

    def _bucket_aux(self, qids):
        return (jnp.asarray(self.inv_max_dcg[qids], jnp.float32),
                jnp.asarray(self.inv_max_bdcg[qids], jnp.float32))

    @staticmethod
    def _tile(L: int) -> Optional[int]:
        """Row block of the tiled sweep for a bucket of padded length
        ``L``; None: the dense lattice."""
        return None if L <= _DENSE_PAIR_L else max(
            (_DENSE_PAIR_L * _DENSE_PAIR_L) // L, 64)

    def _pair_cells(self, L: int) -> int:
        tile = self._tile(L)
        if tile is None:
            return L * L
        rows = min(L, self.truncation_level) \
            if self.target in _TRUNCATED_I_TARGETS else L
        return -(-rows // tile) * tile * L     # whole row blocks swept

    def _bucket_gradients(self, scores_b, labels_b, valid_b, aux_b):
        inv_dcg, inv_bdcg = aux_b
        return _lambdarank_bucket(
            scores_b, labels_b, valid_b, inv_dcg, inv_bdcg, self.label_gain,
            target=self.target, sigmoid=self.sigmoid, norm=self.norm,
            truncation_level=self.truncation_level,
            lambdagap_weight=self.lambdagap_weight,
            tile=self._tile(scores_b.shape[1]))


# queries up to this padded length use the dense [L, L] lattice; longer ones
# route to the row-tiled sweep (same math, O(L*tile) memory) — the TPU-shaped
# answer to the reference's arbitrary-length per-query loops
# (rank_objective.hpp:253-524)
_DENSE_PAIR_L = 4096


def _score_order(scores, labels, valid):
    """A ``[nq, L]`` bucket in score order by one stable sort along the
    document axis, the data riding with the key (``jnp.argsort``'s order,
    ties included): order, scores, labels, validity, valid count, best and
    worst valid score."""
    neg = jnp.where(valid, scores, K_MIN_SCORE)
    pos = lax.broadcasted_iota(jnp.int32, neg.shape, 1)
    key, order, ls, vs = lax.sort((-neg, pos, labels, valid), dimension=1,
                                  num_keys=1, is_stable=True)
    ss = -key               # negation is exact: no score operand to carry
    nv = jnp.sum(vs, axis=1)
    # ss[max(nv - 1, 0)] by a select and a max, not a gather a query
    worst = jnp.max(jnp.where(pos == jnp.maximum(nv - 1, 0)[:, None], ss,
                              -jnp.inf), axis=1)
    return order, ss, ls.astype(jnp.float32), vs, nv, ss[:, 0], worst


def _document_order(order, lam_sorted, hes_sorted):
    """Back to document order: ``order`` is a permutation of the positions."""
    _, lam, hes = lax.sort((order, lam_sorted, hes_sorted), dimension=1,
                           num_keys=1)
    return lam, hes


@functools.partial(
    jax.jit,
    static_argnames=("target", "sigmoid", "norm", "truncation_level",
                     "lambdagap_weight", "tile"))
def _lambdarank_bucket(scores, labels, valid, inv_dcg, inv_bdcg, label_gain,
                       *, target: str, sigmoid: float, norm: bool,
                       truncation_level: int, lambdagap_weight: float,
                       tile: Optional[int] = None):
    """Vectorized per-query lambda computation for one padded bucket.

    scores/labels/valid: [nq, L]; inv_dcg/inv_bdcg: [nq].
    Returns (lambdas [nq, L], hessians [nq, L], effective_pair_rate [nq]).

    ``tile=None``: one dense [L, L] pair lattice per query. ``tile=T``:
    the row axis is swept in blocks of T under the same window masks —
    peak memory O(L*T), identical arithmetic per pair — so arbitrarily
    long queries stay exact."""
    tl = truncation_level
    if tile is not None and scores.shape[1] % tile != 0:
        # lax.dynamic_slice clamps out-of-range starts, so a non-divisor
        # tile would silently misalign rank indices against the sliced
        # score/label rows and produce wrong lambdas
        raise ValueError(
            f"tile={tile} must divide the padded bucket length "
            f"{scores.shape[1]}")

    def pair_block(i, j, si, sj, li, lj, gi, gj, vij, imd, imb, best, worst):
        """All pair quantities for one [bi, bj] block of the sorted
        lattice. i/j are rank indices ([bi,1] / [1,bj]); s/l/g are the
        score/label/label-gain slices at those ranks; vij the validity
        product.
        Returns (lam_to_row [bi,bj] signed lambda for the ROW doc,
        p_hessian [bi,bj], sum_p_lambda scalar, pair_count scalar); the
        COLUMN doc's lambda is minus the row's (accumulated by the
        caller), per reference :505-512."""
        pair_valid = vij & (i < j) & (li != lj)
        if target in _BINARY_TARGETS:
            pair_valid &= ~((li > 0) & (lj > 0))

        # outer-loop truncation (i_end) and per-target (start, end) windows
        if target in _TRUNCATED_I_TARGETS:
            pair_valid &= i < tl
        if target == "precision":
            pair_valid &= j >= tl
        elif target in ("arpk", "lambdagap-s-plus", "lambdagap-x-plus",
                        "lambdagap-s-plus-plus", "lambdagap-x-plus-plus"):
            pair_valid &= j >= tl              # j >= max(i+1, tl); i<j holds
        elif target == "lambdagap-s":
            pair_valid &= j == i + tl
        elif target == "lambdagap-x":
            pair_valid &= j >= i + tl

        # orient the pair: high = larger label
        hi_is_i = li > lj
        hs = jnp.where(hi_is_i, si, sj)
        lo_s = jnp.where(hi_is_i, sj, si)
        hr = jnp.where(hi_is_i, i, j)          # rank of the high-label doc
        lr = jnp.where(hi_is_i, j, i)
        delta_score = hs - lo_s

        rank_diff = (j - i).astype(jnp.float32)
        disc_hr = _discount(hr.astype(jnp.float32))
        disc_lr = _discount(lr.astype(jnp.float32))
        paired_lambdarank = jnp.abs(disc_hr - disc_lr)
        paired_lambdaloss = _discount(rank_diff) - _discount(rank_diff + 1.0)
        gain_gap = _gain_gap(hi_is_i, gi, gj)

        # delta_pair per target (reference: rank_objective.hpp:398-489)
        if target == "ndcg":
            delta = gain_gap * paired_lambdarank * imd
        elif target == "lambdaloss-ndcg":
            delta = gain_gap * paired_lambdaloss * imd
        elif target == "lambdaloss-ndcg-plus-plus":
            delta = gain_gap * (paired_lambdarank
                                + lambdagap_weight * paired_lambdaloss) * imd
        elif target == "bndcg":
            delta = paired_lambdarank * imb
        elif target == "lambdaloss-bndcg":
            delta = paired_lambdaloss * imb
        elif target == "lambdaloss-bndcg-plus-plus":
            delta = (paired_lambdarank
                     + lambdagap_weight * paired_lambdaloss) * imb
        elif target in ("precision", "lambdagap-s", "lambdagap-x",
                        "bin-ranknet", "ranknet"):
            delta = jnp.ones_like(delta_score)
        elif target == "lambdagap-s-plus":
            delta = ((j - i == tl) * lambdagap_weight
                     + (i < tl)).astype(jnp.float32)
        elif target == "lambdagap-x-plus":
            delta = ((j - i >= tl) * lambdagap_weight
                     + (i < tl)).astype(jnp.float32)
        elif target == "lambdagap-s-plus-plus":
            delta = ((j - i == tl) * lambdagap_weight + (j + 1 - tl)
                     - (i >= tl) * (i + 1 - tl)).astype(jnp.float32)
        elif target == "lambdagap-x-plus-plus":
            delta = ((j - i >= tl) * lambdagap_weight + (j + 1 - tl)
                     - (i >= tl) * (i + 1 - tl)).astype(jnp.float32)
        elif target == "arpk":
            delta = ((j + 1 - tl)
                     - (i >= tl) * (i + 1 - tl)).astype(jnp.float32)
        elif target == "lambdaloss-arp1":
            delta = jnp.where(hi_is_i, li, lj)
        elif target == "lambdaloss-arp2":
            delta = jnp.where(hi_is_i, li, lj) - jnp.where(hi_is_i, lj, li)
        else:
            raise ValueError(f"unknown lambdarank target {target!r}")

        pair_valid &= delta != 0

        # score-distance normalization (reference: :495-498)
        if norm:
            delta = jnp.where(best != worst,
                              delta / (0.01 + jnp.abs(delta_score)), delta)

        p = 1.0 / (1.0 + jnp.exp(sigmoid * delta_score))
        p_lambda = -sigmoid * delta * p
        p_hessian = sigmoid * sigmoid * delta * p * (1.0 - p)
        p_lambda = jnp.where(pair_valid, p_lambda, 0.0)
        p_hessian = jnp.where(pair_valid, p_hessian, 0.0)
        lam_to_row = jnp.where(hi_is_i, p_lambda, -p_lambda)
        # pair count in f32: int32 would wrap past ~2^31 pairs, reachable
        # now that query length is uncapped (a 66k-doc query alone has 2^31)
        return (lam_to_row, p_hessian, jnp.sum(p_lambda),
                jnp.sum(pair_valid, dtype=jnp.float32))

    def lattice(ss, ls, vs, nv, imd, imb, best, worst):
        """The pair block(s) of one score-sorted query, their row and
        column reductions and the normalisation: (lambdas, hessians) in
        sorted order and the effective pair rate."""
        L = ss.shape[0]
        ranks = jnp.arange(L, dtype=jnp.int32)
        gs = label_gain[ls.astype(jnp.int32)]  # [L]: one lookup a document
        if tile is None:
            lam_to_row, p_hessian, sum_pl, count_lambdas = pair_block(
                ranks[:, None], ranks[None, :], ss[:, None], ss[None, :],
                ls[:, None], ls[None, :], gs[:, None], gs[None, :],
                vs[:, None] & vs[None, :], imd, imb, best, worst)
            lam_sorted = (jnp.sum(lam_to_row, axis=1)
                          - jnp.sum(lam_to_row, axis=0))
            hes_sorted = (jnp.sum(p_hessian, axis=1)
                          + jnp.sum(p_hessian, axis=0))
        else:
            T = tile
            # truncated-i targets zero every row past the truncation level:
            # their row sweep stops at ceil(tl / T) blocks (exact — those
            # rows' pair_valid is identically False)
            i_limit = min(L, tl) if target in _TRUNCATED_I_TARGETS else L
            nb = -(-i_limit // T)
            jr = ranks[None, :]
            sj = ss[None, :]
            lj = ls[None, :]
            gj = gs[None, :]
            vj = vs[None, :]

            def body(b, carry):
                lam_row, col_lam, hes_row, col_hes, spl, cnt = carry
                off = b * T
                ir = (off + jnp.arange(T, dtype=jnp.int32))[:, None]
                si = lax.dynamic_slice(ss, (off,), (T,))[:, None]
                li = lax.dynamic_slice(ls, (off,), (T,))[:, None]
                gi = lax.dynamic_slice(gs, (off,), (T,))[:, None]
                vi = lax.dynamic_slice(vs, (off,), (T,))[:, None]
                ltr, ph, s1, c1 = pair_block(ir, jr, si, sj, li, lj, gi, gj,
                                             vi & vj, imd, imb, best, worst)
                lam_row = lax.dynamic_update_slice(
                    lam_row,
                    lax.dynamic_slice(lam_row, (off,), (T,))
                    + jnp.sum(ltr, axis=1), (off,))
                hes_row = lax.dynamic_update_slice(
                    hes_row,
                    lax.dynamic_slice(hes_row, (off,), (T,))
                    + jnp.sum(ph, axis=1), (off,))
                col_lam = col_lam + jnp.sum(ltr, axis=0)
                col_hes = col_hes + jnp.sum(ph, axis=0)
                return (lam_row, col_lam, hes_row, col_hes,
                        spl + s1, cnt + c1)

            z = jnp.zeros(L, jnp.float32)
            lam_row, col_lam, hes_row, col_hes, sum_pl, count_lambdas = \
                lax.fori_loop(0, nb, body,
                              (z, z, z, z, jnp.float32(0.0),
                               jnp.float32(0.0)))
            lam_sorted = lam_row - col_lam
            hes_sorted = hes_row + col_hes

        sum_lambdas = -2.0 * sum_pl
        if norm:
            norm_factor = jnp.where(
                sum_lambdas > 0,
                jnp.log2(1.0 + sum_lambdas)
                / jnp.maximum(sum_lambdas, K_EPSILON),
                1.0)
            lam_sorted = lam_sorted * norm_factor
            hes_sorted = hes_sorted * norm_factor
        nvf = nv.astype(jnp.float32)           # int32 nv*(nv-1) would wrap
        eff = 2.0 * count_lambdas.astype(jnp.float32) / \
            jnp.maximum(nvf * (nvf - 1.0), 1.0)
        return lam_sorted, hes_sorted, eff

    # the lattice's vmap is opened UNDER its scope: a scope opened inside a
    # vmapped function is named ``vmap(<scope>)`` and no selector finds it
    with device_scope("rank_sort"):
        order, ss, ls, vs, nv, best, worst = _score_order(scores, labels,
                                                          valid)
    with device_scope("rank_lattice"):
        lam_sorted, hes_sorted, eff = jax.vmap(lattice)(
            ss, ls, vs, nv, inv_dcg, inv_bdcg, best, worst)
    with device_scope("rank_sort"):
        lam, hes = _document_order(order, lam_sorted, hes_sorted)
    return lam, hes, eff


@register_objective
class RankXENDCG(RankingBase):
    """Cross-entropy NDCG surrogate
    (reference: rank_objective.hpp:650-724 RankXENDCG): per-query softmax
    with Gumbel-perturbed gains and third-order gradient correction."""
    name = "rank_xendcg"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.seed = config.seed

    def init(self, metadata, num_data) -> None:
        super().init(metadata, num_data)
        self.key = jax.random.PRNGKey(self.seed)

    def _bucket_aux(self, qids):
        return (len(qids),)

    def _next_key(self):
        # fresh per-iteration randomness (reference uses per-query Random
        # streams; a split PRNG key is the JAX analog)
        self.key, sub = jax.random.split(self.key)
        return sub

    def _bucket_gradients_k(self, scores_b, labels_b, valid_b, aux_b, key):
        return _xendcg_bucket(scores_b, labels_b, valid_b,
                              jax.random.fold_in(key, scores_b.shape[1]))


@jax.jit
def _xendcg_bucket(scores, labels, valid, key):
    def one_query(s, l, v, k):
        L = s.shape[0]
        nv = jnp.sum(v)
        sm = jnp.where(v, s, K_MIN_SCORE)
        m = jnp.max(sm)
        e = jnp.where(v, jnp.exp(sm - m), 0.0)
        rho = e / jnp.maximum(jnp.sum(e), K_EPSILON)

        u = jax.random.uniform(k, (L,))
        phi = jnp.where(v, jnp.power(2.0, l.astype(jnp.float32)) - u, 0.0)
        inv_denominator = 1.0 / jnp.maximum(jnp.sum(phi), K_EPSILON)

        # third-order expansion (reference: rank_objective.hpp:695-719)
        term1 = -phi * inv_denominator + rho
        lam = term1
        params = jnp.where(v, term1 / jnp.maximum(1.0 - rho, K_EPSILON), 0.0)
        sum_l1 = jnp.sum(params)
        term2 = rho * (sum_l1 - params)
        lam = lam + term2
        params = jnp.where(v, term2 / jnp.maximum(1.0 - rho, K_EPSILON), 0.0)
        sum_l2 = jnp.sum(params)
        lam = lam + rho * (sum_l2 - params)
        hes = rho * (1.0 - rho)
        lam = jnp.where(v & (nv > 1), lam, 0.0)
        hes = jnp.where(v & (nv > 1), hes, 0.0)
        return lam, hes, jnp.float32(0.0)

    nq = scores.shape[0]
    keys = jax.random.split(key, nq)
    return jax.vmap(one_query)(scores, labels, valid, keys)
