"""Epsilon-shaped data from a seed, as LightGBM's GPU experiments feed it
(``docs/GPU-Performance.rst``, the Epsilon row: the PASCAL Large Scale
Learning Challenge's dense set, 2,000 real-valued features, binary): every
row is 2,000 float32 standard normals scaled to unit L2 norm, as the
published rows are, and the label comes from a seeded law on the scaled
features (``s = sqrt(2000) x``, each of unit variance):

- a sparse linear part: 48 features, weights of both signs,
- four pairwise products,
- Gaussian noise,

thresholded at 0, so ~50 % of rows are positive (the law is symmetric).
Every feature is a continuous value, so each fills its 255 bins; no value
is missing. Blocks of 2^12 rows are drawn on a few threads, each block from
its own generator seeded by (``--seed``, block): the same seed gives the
same rows at any thread count. The last ``rows_holdout`` rows are the
hold-out."""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 12
THREADS = 12
N_LINEAR, N_PAIRS = 48, 4
NOISE = 0.5


def _laws(f: int) -> dict:
    """The label's constants, the same for every seed: which features carry
    the linear part and with what weight (the part of unit variance), which
    pairs multiply."""
    r = np.random.Generator(np.random.PCG64(2000))
    cols = r.choice(f, N_LINEAR + 2 * N_PAIRS, replace=False)
    w = r.standard_normal(N_LINEAR).astype(np.float32)
    return {"linear": cols[:N_LINEAR],
            "w": w / np.float32(np.sqrt(np.sum(w * w))),
            "pairs": cols[N_LINEAR:].reshape(N_PAIRS, 2)}


def _block(seed: int, index: int, X: np.ndarray, y: np.ndarray,
           laws: dict) -> None:
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([seed, index])))
    f = X.shape[1]
    rng.standard_normal(dtype=np.float32, out=X)
    norm = np.sqrt(np.einsum("ij,ij->i", X, X, dtype=np.float32))
    X /= norm[:, None]
    root = np.float32(np.sqrt(f))           # s = root * x: unit variance
    score = root * (X[:, laws["linear"]] @ laws["w"])
    for a, b in laws["pairs"]:
        score += (0.5 * root * root) * X[:, a] * X[:, b]
    score += NOISE * rng.standard_normal(len(y), dtype=np.float32)
    np.greater(score, 0.0, out=y, casting="unsafe")


def generate(cfg: dict, seed: int, rows_train: int, rows_holdout: int) -> dict:
    f = int(cfg["num_features"])
    n = rows_train + rows_holdout
    X = np.empty((n, f), np.float32)
    y = np.empty(n, np.float32)
    laws = _laws(f)
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(lambda lo: _block(seed, lo // BLOCK, X[lo:lo + BLOCK],
                                        y[lo:lo + BLOCK], laws),
                      range(0, n, BLOCK)))
    return {"X": X[:rows_train], "y": y[:rows_train], "group": None,
            "X_hold": X[rows_train:], "y_hold": y[rows_train:],
            "group_hold": None}
