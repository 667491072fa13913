"""The plain reference of the watched folds: what a validation and a test
fold had to score after every boosting iteration, and what NDCG@k and early
stopping had to say of it. Float64 numpy; imports nothing of the program and
takes nothing it made except its ANSWERS: the model text, the value every
evaluation returned, the folds' scores read back after the last iteration
and the early-stopping callback's final word.

After every iteration a fold's scores are float64 sums of the PROGRAM's
leaf values at the reference's own leaf of each row (``gbdt_check.leaf_index``:
raw float32 features against the text's real thresholds, no bin table of the
program), and NDCG@k is the published rule: a stable descending sort (a tie
keeps the earlier document first), gains ``2^l - 1``, discounts
``1 / log2(2 + i)``, a query with no relevant document counts 1, the mean
over queries.

- ``valid_score``  widest gap over ALL rows of each fold between the scores
                 the device holds after the last iteration and the
                 reference's, over their standard deviation; the worst fold
                 (binning of the fold's rows against the training set's
                 mappers, the routing, the leaf values, every tree once)
- ``valid_metric_last``  |the program's NDCG at its last evaluation - float64
                 NDCG of the PROGRAM's own read-back scores|, worst fold: the
                 metric alone, no rank can flip
- ``valid_metric``  worst over folds and over EVERY evaluation of the run of
                 |the value the program returned - the reference's from its
                 own float64 scores|: a stale set, a swapped fold, an
                 unstable tie order
- ``valid_evals_missing``  iterations run minus evaluations recorded
- ``early_stop_mismatch``  0 when the callback's best iteration and best
                 score of the watched (first) fold, and whether it stopped,
                 are what the reference's own series gives under the same
                 rule, within ``metric_tol``; else 1
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from .gbdt_check import K_EPS, leaf_index, parse_model


def ndcg_at_k(y: np.ndarray, scores: np.ndarray, group: np.ndarray,
              k: int = 10, label_gain: Optional[np.ndarray] = None) -> float:
    """Mean NDCG@k over the queries of ``group`` (lengths, in row order)."""
    y = np.asarray(y).astype(np.int64)
    scores = np.asarray(scores, np.float64)
    group = np.asarray(group, np.int64)
    gain = (2.0 ** y - 1.0) if label_gain is None \
        else np.asarray(label_gain, np.float64)[y]
    nq = len(group)
    qid = np.repeat(np.arange(nq), group)
    start = np.concatenate([[0], np.cumsum(group)[:-1]])
    disc = 1.0 / np.log2(2.0 + np.arange(k))

    def dcg(order):
        """DCG@k a query, documents taken in ``order`` (grouped by query)."""
        rank = np.arange(len(order)) - start[qid[order]]
        top = rank < k
        return np.bincount(qid[order][top],
                           weights=gain[order][top] * disc[rank[top]],
                           minlength=nq)
    # lexsort is stable: equal (query, score) keep their row order
    got = dcg(np.lexsort((-scores, qid)))
    best = dcg(np.lexsort((-y, qid)))
    some = best > 0
    return float(np.mean(np.where(some, got / np.where(some, best, 1.0),
                                  1.0)))


def best_so_far(series: List[float], patience: int) -> Dict[str, float]:
    """The early-stopping rule on one metric where greater is better: the
    best is the FIRST value no later one strictly beats, and the job stops
    at the first iteration ``patience`` past its best."""
    best_iter, best = -1, -np.inf
    for i, v in enumerate(series):
        if v > best:
            best_iter, best = i, v
        elif i - best_iter >= patience:
            return {"best_iter": best_iter, "best": best, "stopped_at": i}
    return {"best_iter": best_iter, "best": best, "stopped_at": -1}


def check(model_text: str, folds: List[dict], evals: List[dict],
          final_scores: Dict[str, np.ndarray], iterations_run: int,
          early_stop: dict, k: int = 10, metric_tol: float = 0.0
          ) -> Dict[str, float]:
    """The numbers that decide ``correct`` for the watched folds (module
    docstring). ``folds``: ``{"name", "X", "y", "group"}`` in the order they
    were attached; ``evals``: one ``{"trees": trees grown so far, "values":
    {fold: NDCG@k}}`` an evaluation; ``final_scores``: fold -> the device's
    scores after the last iteration; ``early_stop``: the callback's
    ``best_iter`` (index into ``evals``), ``best_score`` and ``stopped_at``
    (-1: it did not stop) for the first fold."""
    trees = parse_model(model_text)
    at = {}                          # trees grown -> indices into evals
    for i, e in enumerate(evals):
        at.setdefault(int(e["trees"]), []).append(i)
    out = {"valid_score": 0.0, "valid_metric_last": 0.0, "valid_metric": 0.0,
           "valid_evals_missing": float(max(iterations_run - len(evals), 0)),
           "early_stop_mismatch": 0.0}
    series = {}
    for fold in folds:
        name, X, y, group = fold["name"], fold["X"], fold["y"], fold["group"]
        scores = np.zeros(X.shape[0], np.float64)
        ref = [np.nan] * len(evals)
        for t, tree in enumerate(trees, start=1):
            scores += tree["leaf_value"][leaf_index(tree, X)]
            if t in at:
                value = ndcg_at_k(y, scores, group, k)
                for i in at[t]:
                    ref[i] = value
        series[name] = ref
        for i, e in enumerate(evals):
            got = e["values"].get(name)
            gap = abs(got - ref[i]) if got is not None \
                and np.isfinite(ref[i]) else float("inf")
            if gap > out["valid_metric"]:
                out["valid_metric"] = gap
                out["valid_metric_worst_eval"] = float(i)
        out["ndcg_ref_" + name] = ref[-1] if ref else float("nan")
        dev = np.asarray(final_scores[name], np.float64).reshape(-1)
        if dev.shape != scores.shape:
            out["valid_score"] = float("inf")
            continue
        sd = max(float(np.std(scores)), K_EPS)
        out["valid_score"] = max(out["valid_score"],
                                 float(np.max(np.abs(dev - scores)) / sd))
        if evals:
            last = evals[-1]["values"].get(name, float("inf"))
            out["valid_metric_last"] = max(
                out["valid_metric_last"],
                abs(last - ndcg_at_k(y, dev, group, k)))
    if evals and folds:
        watched = series[folds[0]["name"]]
        want = best_so_far(watched, int(early_stop["patience"]))
        bi = int(early_stop["best_iter"])
        ok = (0 <= bi < len(watched)
              and abs(watched[bi] - want["best"]) <= metric_tol
              and abs(early_stop["best_score"] - watched[bi]) <= metric_tol
              and (int(early_stop["stopped_at"]) >= 0)
              == (want["stopped_at"] >= 0))
        out["early_stop_mismatch"] = 0.0 if ok else 1.0
        out["early_stop_best_iter"] = float(bi)
        out["early_stop_best_iter_ref"] = float(want["best_iter"])
    return out
