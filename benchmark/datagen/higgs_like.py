"""HIGGS-shaped data from a seed: dense float32 normals, a binary label from
a nonlinear score (the law of ``chip_smoke.higgs_like``; numpy's PCG64
drawing float32 directly, ~5x faster at 294M draws than ``RandomState``).
Every row comes from ``--seed``; the last ``rows_holdout`` are the hold-out."""
from __future__ import annotations

import numpy as np

BLOCK = 1 << 20     # rows drawn at a time: the label's temporaries stay small


def generate(cfg: dict, seed: int, rows_train: int, rows_holdout: int) -> dict:
    f = int(cfg["num_features"])
    n = rows_train + rows_holdout
    rng = np.random.Generator(np.random.PCG64(seed))
    X = np.empty((n, f), np.float32)
    y = np.empty(n, np.float32)
    for lo in range(0, n, BLOCK):
        xb = X[lo:lo + BLOCK]
        rng.standard_normal(out=xb, dtype=np.float32)
        s = (xb[:, 0] + 0.5 * xb[:, 1] * xb[:, 2] + 0.25 * xb[:, 3] ** 2
             - 0.25 + 0.5 * rng.standard_normal(len(xb), dtype=np.float32))
        y[lo:lo + BLOCK] = s > 0
    return {"X": X[:rows_train], "y": y[:rows_train], "group": None,
            "X_hold": X[rows_train:], "y_hold": y[rows_train:],
            "group_hold": None}
