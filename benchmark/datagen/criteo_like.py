"""Criteo-shaped data from a seed, as LightGBM's parallel experiment feeds it
(``docs/Experiments.rst``, "Parallel Experiment"): 67 float32 columns,

- 13 integer counts (the log's integer columns),
- 26 click-through rates in [0, 1] (each categorical column replaced by its
  value's CTR) and 26 integer counts (the same value's frequency),
- 2 more integer counts, for the 67 the source reports (assumed),

and a binary label from a seeded logistic law with ~3 % positives (assumed:
the source gives no rate). Counts are heavy-tailed with many zeros
(zero-inflated, floored lognormals whose scale differs by column), so several
columns fill far fewer than 255 bins; the rates are logit-normal. No value is
missing. Blocks of 2^16 rows are drawn from float32 normals and uniforms,
each block from its own generator seeded by (``--seed``, block), on a few
threads: the same seed gives the same rows at any thread count. The last
``rows_holdout`` rows are the hold-out."""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 16
THREADS = 12
N_INT, N_CAT, N_EXTRA = 13, 26, 2
N_FEATURES = N_INT + 2 * N_CAT + N_EXTRA    # 67


def _laws():
    """Per-column constants, the same for every seed: a count column's
    zero share, lognormal location and scale; a rate column's logit location
    and scale; the label's weights on the rates' logits and on the counts'
    normals. The label's intercept puts ~3 % of rows positive."""
    r = np.random.Generator(np.random.PCG64(67))
    n_counts = N_INT + N_CAT + N_EXTRA
    return {
        "zero": r.uniform(0.05, 0.6, n_counts).astype(np.float32),
        "mu": r.uniform(0.0, 4.0, n_counts).astype(np.float32),
        "sigma": r.uniform(0.5, 2.0, n_counts).astype(np.float32),
        "ctr_mu": r.uniform(-4.0, -2.0, N_CAT).astype(np.float32),
        "ctr_sigma": r.uniform(0.3, 1.2, N_CAT).astype(np.float32),
        "w_ctr": (r.standard_normal(N_CAT) * 0.35).astype(np.float32),
        "w_int": (r.standard_normal(N_INT) * 0.2).astype(np.float32),
        "bias": np.float32(-4.85),
    }


LAWS = _laws()


def _counts(z: np.ndarray, u: np.ndarray, cols: slice) -> np.ndarray:
    """floor(exp(mu + sigma z)) - 1, and 0 where u falls under the zero
    share: integer-valued float32 counts, in place in ``z``."""
    z *= LAWS["sigma"][cols]
    z += LAWS["mu"][cols]
    np.exp(z, out=z)
    np.floor(z, out=z)
    z -= 1.0
    np.maximum(z, 0.0, out=z)
    z[u < LAWS["zero"][cols]] = 0.0
    return z


def _block(seed: int, index: int, X: np.ndarray, y: np.ndarray,
           scratch: threading.local) -> None:
    """Rows of block ``index``, drawn into the calling thread's own scratch
    buffers."""
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([seed, index])))
    n = X.shape[0]
    if not hasattr(scratch, "z"):
        scratch.z = np.empty((BLOCK, N_FEATURES), np.float32)
        scratch.u = np.empty((BLOCK, N_INT + N_CAT + N_EXTRA), np.float32)
        scratch.v = np.empty(BLOCK, np.float32)
    z, u, v = scratch.z[:n], scratch.u[:n], scratch.v[:n]
    rng.standard_normal(dtype=np.float32, out=z)
    rng.random(dtype=np.float32, out=u)
    rng.random(dtype=np.float32, out=v)
    ints = slice(0, N_INT)
    rates = slice(N_INT, N_INT + N_CAT)
    freq = slice(N_INT + N_CAT, N_FEATURES)
    logit = LAWS["bias"] + z[:, rates] @ LAWS["w_ctr"] \
        + z[:, ints] @ LAWS["w_int"]
    X[:, ints] = _counts(z[:, ints], u[:, ints], ints)
    r = z[:, rates]
    r *= LAWS["ctr_sigma"]
    r += LAWS["ctr_mu"]
    np.negative(r, out=r)
    np.exp(r, out=r)
    r += 1.0
    np.reciprocal(r, out=X[:, rates])
    X[:, freq] = _counts(z[:, freq], u[:, N_INT:], slice(N_INT, None))
    np.negative(logit, out=logit)
    np.exp(logit, out=logit)
    logit += 1.0
    np.reciprocal(logit, out=logit)
    np.less(v, logit, out=y, casting="unsafe")


def generate(cfg: dict, seed: int, rows_train: int, rows_holdout: int) -> dict:
    assert int(cfg["num_features"]) == N_FEATURES
    n = rows_train + rows_holdout
    X = np.empty((n, N_FEATURES), np.float32)
    y = np.empty(n, np.float32)
    starts = range(0, n, BLOCK)
    scratch = threading.local()
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(lambda lo: _block(seed, lo // BLOCK, X[lo:lo + BLOCK],
                                        y[lo:lo + BLOCK], scratch), starts))
    return {"X": X[:rows_train], "y": y[:rows_train], "group": None,
            "X_hold": X[rows_train:], "y_hold": y[rows_train:],
            "group_hold": None}
