"""Hold-out quality of the trained forest, printed before the result line
and stamped ``synthetic``: the data is generated, so these say the forest
learned something, never how good the system is on HIGGS or MSLR-WEB30K."""
from __future__ import annotations

import numpy as np


def auc(y: np.ndarray, score: np.ndarray) -> float:
    """Rank AUC with midranks (a few trees leave many tied scores)."""
    _, inv, cnt = np.unique(score, return_inverse=True, return_counts=True)
    ranks = (np.cumsum(cnt) - (cnt - 1) / 2.0)[inv]
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


def ndcg_at(y: np.ndarray, score: np.ndarray, group: np.ndarray,
            k: int = 10) -> float:
    """Mean NDCG@k over queries with a relevant document; gain 2^l - 1."""
    out, lo = [], 0
    disc = 1.0 / np.log2(2.0 + np.arange(k))
    for n in group:
        yy, ss = y[lo:lo + n], score[lo:lo + n]
        lo += n
        ideal = np.sort(yy)[::-1][:k]
        best = float(np.sum((2.0 ** ideal - 1.0) * disc[:len(ideal)]))
        if best <= 0:
            continue
        top = yy[np.argsort(-ss, kind="stable")[:k]]
        out.append(float(np.sum((2.0 ** top - 1.0) * disc[:len(top)])) / best)
    return float(np.mean(out)) if out else float("nan")


def _binary(data: dict, raw: np.ndarray) -> dict:
    y = data["y_hold"]
    p = np.clip(1.0 / (1.0 + np.exp(-raw)), 1e-15, 1 - 1e-15)
    return {"data": "synthetic", "holdout_auc": auc(y, raw),
            "holdout_logloss": float(-np.mean(
                y * np.log(p) + (1 - y) * np.log(1 - p)))}


def _rank(data: dict, raw: np.ndarray) -> dict:
    return {"data": "synthetic", "holdout_ndcg_at_10": ndcg_at(
        data["y_hold"], raw, data["group_hold"], 10)}


QUALITY = {"binary": _binary, "lambdarank": _rank}
