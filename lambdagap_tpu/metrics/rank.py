"""Ranking metrics: NDCG@k, MAP@k, precision@k.

(reference: src/metric/rank_metric.hpp NDCGMetric, src/metric/map_metric.hpp
MapMetric, and the fork-added src/metric/precision_metric.hpp:16
PrecisionMetric with its cumulative-hit bucket formula.)
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import Config
from ..obs.telemetry import device_scope
from .base import Metric, register_metric


def _default_label_gain(max_label: int) -> np.ndarray:
    return np.asarray([(1 << i) - 1 if i < 31 else 2.0 ** 31 - 1
                       for i in range(max(max_label + 1, 32))], dtype=np.float64)


class _RankMetricBase(Metric):
    greater_is_better = True

    def init(self, metadata, num_data) -> None:
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            from ..utils import log
            log.fatal("For %s metric, there should be query information",
                      self.name)
        self.qb = np.asarray(metadata.query_boundaries)
        self.num_queries = metadata.num_queries
        self.query_weights = metadata.query_weights
        self.sum_qw = (float(np.sum(self.query_weights))
                       if self.query_weights is not None
                       else float(self.num_queries))
        self.eval_at = list(self.config.eval_at) or [1, 2, 3, 4, 5]

    def _per_query(self, label: np.ndarray, score: np.ndarray) -> List[float]:
        raise NotImplementedError

    def eval(self, scores, objective=None):
        scores = np.asarray(scores)
        totals = np.zeros(len(self.eval_at))
        for qi in range(self.num_queries):
            lo, hi = self.qb[qi], self.qb[qi + 1]
            vals = np.asarray(self._per_query(self.label[lo:hi], scores[lo:hi]))
            w = self.query_weights[qi] if self.query_weights is not None else 1.0
            totals += vals * w
        totals /= self.sum_qw
        return [(f"{self.name}@{k}", float(v))
                for k, v in zip(self.eval_at, totals)]


@register_metric
class NDCGMetric(_RankMetricBase):
    """(reference: rank_metric.hpp NDCGMetric; empty queries score 1)."""
    name = "ndcg"

    def init(self, metadata, num_data) -> None:
        super().init(metadata, num_data)
        max_label = int(np.max(self.label)) if num_data else 0
        gains = self.config.label_gain
        self.label_gain = (np.asarray(gains, dtype=np.float64) if gains
                           else _default_label_gain(max_label))

    def _per_query(self, label, score):
        order = np.argsort(-score, kind="stable")
        sorted_labels = label[order].astype(np.int64)
        disc = 1.0 / np.log2(2.0 + np.arange(len(label)))
        out = []
        ideal = np.sort(label.astype(np.int64))[::-1]
        for k in self.eval_at:
            kk = min(k, len(label))
            dcg = float(np.sum(self.label_gain[sorted_labels[:kk]] * disc[:kk]))
            max_dcg = float(np.sum(self.label_gain[ideal[:kk]] * disc[:kk]))
            out.append(dcg / max_dcg if max_dcg > 0 else 1.0)
        return out

    # -- the device form ------------------------------------------------
    @functools.cached_property
    def _dev(self):
        """What :func:`_ndcg_at` needs beside the scores, built on the host
        at the first use (a metric that only ever runs :meth:`eval` never
        pays for it): the queries in the padded buckets the ranking objectives use
        (``objectives.rank._QueryBuckets``), per bucket the documents'
        indices, their gains, and per query ``weight / maxDCG@k`` (the
        inverse max DCG precomputed at init as rank_metric.hpp does; 0
        where a query has no relevant document: it counts 1, as a constant
        of the set). Float64 here, float32 on the device."""
        from ..objectives.rank import _QueryBuckets
        ks = tuple(int(k) for k in self.eval_at)
        kmax = max(ks)
        disc = 1.0 / np.log2(2.0 + np.arange(kmax))
        label_pad = np.concatenate([self.label.astype(np.int64), [-1]])
        gain_of = np.concatenate([self.label_gain, [0.0]])    # [-1]: a pad
        weights = (np.ones(self.num_queries) if self.query_weights is None
                   else np.asarray(self.query_weights, np.float64))
        buckets, ones = [], np.zeros(len(ks))
        for L, qids, idx in _QueryBuckets(self.qb, self.num_data).buckets:
            lab = label_pad[idx]                              # [nq, L]
            gain = gain_of[lab]
            top = min(kmax, L)
            ideal = gain_of[-np.sort(-lab, axis=1)[:, :top]] * disc[:top]
            max_dcg = np.stack([ideal[:, :min(k, L)].sum(axis=1)
                                for k in ks], axis=1)         # [nq, nk]
            w = weights[qids][:, None]
            some = max_dcg > 0
            scale = np.where(some, w / np.where(some, max_dcg, 1.0), 0.0)
            ones += np.sum(np.where(some, 0.0, w), axis=0)
            buckets.append((jnp.asarray(idx), jnp.asarray(gain, jnp.float32),
                            jnp.asarray(scale, jnp.float32)))
        return (ks, tuple(buckets), jnp.asarray(disc, jnp.float32),
                jnp.asarray(ones / self.sum_qw, jnp.float32),
                jnp.float32(1.0 / self.sum_qw))

    def eval_device(self, scores):
        if scores.ndim == 2:
            if scores.shape[0] != 1:
                return None
            scores = scores[0]
        ks, buckets, disc, ones, inv_sum_qw = self._dev
        return ([f"{self.name}@{k}" for k in ks],
                _ndcg_at(scores, buckets, disc, ones, inv_sum_qw, ks=ks))


def _pairwise_sum(x: jax.Array) -> jax.Array:
    """Sum over axis 0 by halving: every value passes through log2(n)
    float32 additions, not n (7,000 per-query values summed in a row lose
    the sixth digit of their mean)."""
    n = 1 << max(x.shape[0] - 1, 0).bit_length()
    x = jnp.concatenate([x, jnp.zeros((n - x.shape[0],) + x.shape[1:],
                                      x.dtype)])
    while n > 1:
        n //= 2
        x = x[:n] + x[n:]
    return x[0]


@functools.partial(jax.jit, static_argnames=("ks",))
def _ndcg_at(scores, buckets, disc, ones, inv_sum_qw, ks):
    """NDCG@k of every ``k`` in ``ks`` on device-resident scores ``[N]``:
    per bucket the scores gathered into ``[queries, L]``, ONE stable sort a
    bucket by descending score with the gains riding along (a tie keeps the
    earlier document first: ``std::stable_sort`` in dcg_calculator.cpp,
    ``kind="stable"`` in :meth:`NDCGMetric._per_query`; pad slots sort
    last and gain 0), the top ``k`` gains against ``1 / log2(2 + i)``, each
    query's DCG times its ``weight / maxDCG``. Returns float32 ``[len(ks)]``:
    what leaves the device."""
    with device_scope("valid_metric"):
        # descending by score = ascending by its negative; a NaN sorts with
        # the lowest scores (the host's argsort puts it last)
        key = jnp.where(jnp.isnan(scores), jnp.inf, -scores)
        pad_key = jnp.concatenate([key, jnp.full(1, jnp.inf, key.dtype)])
        per_query = []
        for idx, gain, scale in buckets:
            L = idx.shape[1]
            _, g = lax.sort((pad_key[idx], gain), dimension=1,
                            is_stable=True, num_keys=1)
            top = min(max(ks), L)
            terms = g[:, :top] * disc[:top]
            dcg = jnp.stack([jnp.sum(terms[:, :min(k, L)], axis=1)
                             for k in ks], axis=1)
            per_query.append(dcg * scale)
        total = _pairwise_sum(jnp.concatenate(per_query, axis=0))
        return total * inv_sum_qw + ones


@register_metric
class MapMetric(_RankMetricBase):
    """Mean average precision@k (reference: map_metric.hpp)."""
    name = "map"

    def _per_query(self, label, score):
        order = np.argsort(-score, kind="stable")
        rel = (label[order] > 0).astype(np.float64)
        hits = np.cumsum(rel)
        prec = hits / np.arange(1, len(rel) + 1)
        out = []
        for k in self.eval_at:
            kk = min(k, len(rel))
            num_hit = hits[kk - 1] if kk > 0 else 0.0
            if num_hit > 0:
                out.append(float(np.sum(prec[:kk] * rel[:kk]) / num_hit))
            else:
                out.append(1.0 if np.sum(rel) == 0 else 0.0)
        return out


@register_metric
class PrecisionMetric(_RankMetricBase):
    """Fork-added precision@k (reference: precision_metric.hpp:16
    CalPrecisionAtK — hits accumulate across the eval_at buckets and each
    bucket divides by min(k, remaining docs))."""
    name = "precision"

    def _per_query(self, label, score):
        order = np.argsort(-score, kind="stable")
        rel = label[order] > 0.5
        out = []
        num_hit = 0
        cur_left = 0
        n = len(rel)
        for k in self.eval_at:
            num_hit += int(np.sum(rel[cur_left:min(k, n)]))
            denom = min(k, max(n - cur_left, 0))
            out.append(num_hit / denom if denom > 0 else 0.0)
            cur_left = k
        return out


# graftir IR contract
from ..analysis.ir.contracts import register_program  # noqa: E402

register_program(
    "rank._ndcg_at", collective_free=True,
    notes="NDCG@k on device-resident scores: one stable sort a bucket, "
          "float32[len(eval_at)] out")
