"""MSLR-WEB30K-shaped data from a seed: queries with the dataset's heavy
tail of lengths, 136 dense float32 features, graded labels 0-4 skewed to 0.

The multiset of query lengths is fixed (a lognormal's quantiles: median
~100, mean ~120, longest 1,251 -- the dataset's), so every seed gives the
pair lattice the same sizes in another order, and so is the label's law
(which features carry the relevance, how strongly: a law drawn anew per seed
made another learning problem of every seed and spread ``train_iter_s`` by
3.3 %, my chip run, PR 25); the order, every feature value, the label's noise
and the hold-out's queries come from ``--seed``. Lengths, label skew and
feature law are ``assumed`` in the configuration's file."""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

BLOCK = 1 << 18     # docs drawn at a time: the latent's temporaries stay small
LAW = 30            # stream of the label's law: 25 of 136 features carry it


def query_lengths(n_queries: int, n_docs: int, median: float, longest: int
                  ) -> np.ndarray:
    """The fixed multiset: lognormal quantiles with the given median, sigma
    solved so the mean is n_docs / n_queries, clipped to [1, longest], the
    last one set to ``longest`` and the total trimmed to ``n_docs``."""
    mean = n_docs / n_queries
    sigma = float(np.sqrt(2.0 * np.log(mean / median)))
    inv = NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / n_queries) for i in range(n_queries)])
    sizes = np.clip(np.rint(median * np.exp(sigma * z)), 1, longest)
    sizes = sizes.astype(np.int64)
    sizes[-1] = longest
    # spread the rounding remainder evenly over all but the longest
    diff = int(n_docs - sizes.sum())
    step, rest = divmod(abs(diff), n_queries - 1)
    sign = 1 if diff > 0 else -1
    sizes[:-1] += sign * step
    sizes[n_queries // 4:n_queries // 4 + rest] += sign
    if sizes.sum() != n_docs or sizes.min() < 1:
        raise ValueError("query lengths do not add up to the document count")
    return sizes


def generate(cfg: dict, seed: int, rows_train: int, rows_holdout: int) -> dict:
    """``rows_holdout`` counts QUERIES of the hold-out; training is the whole
    configured dataset (``num_queries`` / ``num_docs`` = ``rows_train``)."""
    f = int(cfg["num_features"])
    a = cfg["assumed"]
    nq = int(cfg["num_queries"])
    rng = np.random.Generator(np.random.PCG64(seed))
    sizes = query_lengths(nq, int(cfg["num_docs"]),
                          float(a["query_length_median"]),
                          int(a["query_length_longest"]))
    sizes = sizes[rng.permutation(nq)]
    hold_sizes = sizes[rng.permutation(nq)[:rows_holdout]].copy()
    law = np.random.Generator(np.random.PCG64(LAW))
    w = (law.standard_normal(f, dtype=np.float32)
         * (law.random(f) < 0.2)).astype(np.float32)
    # graded labels at fixed quantiles of the latent's law (label skew)
    std = float(np.sqrt(0.36 * float(w @ w) + 1.0))
    cum = np.cumsum(a["label_share"])[:-1]
    cuts = np.array([NormalDist().inv_cdf(float(c)) for c in cum]) * std

    n, nh = int(sizes.sum()), int(hold_sizes.sum())
    if n != rows_train:
        raise ValueError(f"{n} docs generated, {rows_train} configured")
    X = np.empty((n + nh, f), np.float32)
    y = np.empty(n + nh, np.float32)
    for lo in range(0, n + nh, BLOCK):
        xb = X[lo:lo + BLOCK]
        rng.standard_normal(out=xb, dtype=np.float32)
        latent = xb @ w * np.float32(0.6) \
            + rng.standard_normal(len(xb), dtype=np.float32)
        y[lo:lo + BLOCK] = np.searchsorted(cuts, latent)
    return {"X": X[:n], "y": y[:n], "group": sizes,
            "X_hold": X[n:], "y_hold": y[n:], "group_hold": hold_sizes}
