"""The partition pass grows the same forest as the commit it replaced.

``tests/data/partition_golden.json`` was recorded from the PARENT of PR 27
(the pass that routed a window's rows with ``.at[pos].set``); the pass that
compacts the window and writes it contiguously has to place every row where
the scatter placed it, so every tree's split features, threshold bins and
leaf row counts, and the last tree's row -> leaf map, stay what they were.
Integers only: the golden does not depend on how a machine prints a float.

Windows are forced to 1,024 rows over 5,000-row datasets, so a leaf spans
several windows (the rights of later windows land BELOW those of earlier
ones) — which the 900-row shapes of ``tests/test_layout.py`` never reach.
Re-record (only from a commit whose pass is trusted):
``python tests/test_partition_golden.py --record``.
"""
import hashlib
import json
import os

import numpy as np
import pytest

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "partition_golden.json")
N = 5000


def _plain(rng):
    X = rng.randn(N, 8)
    y = X[:, 1] + np.sin(X[:, 2] * 2) + X[:, 3] * 0.5 + 0.1 * rng.randn(N)
    return X, y, "auto", {}


def _cat_efb(rng):
    """One categorical column, four one-hot blocks that EFB bundles, two
    dense columns."""
    cols = [rng.randint(0, 9, N).astype(float)[:, None]]
    for c in (8, 6, 5, 7):
        blk = np.zeros((N, c))
        blk[np.arange(N), rng.randint(0, c, N)] = 1.0
        cols.append(blk)
    X = np.column_stack(cols + [rng.randn(N, 2)])
    y = ((X[:, 0] % 3) * 0.5 + X[:, 1] * 0.5 - X[:, 10] * 0.3 + X[:, -2]
         + 0.05 * rng.randn(N))
    return X, y, [0], {"min_data_in_bin": 1, "enable_bundle": True}


def _quant_bagged(rng):
    X, y, cat, _ = _plain(rng)
    return X, y, cat, {"use_quantized_grad": True, "num_grad_quant_bins": 16,
                       "stochastic_rounding": False,
                       "bagging_fraction": 0.6, "bagging_freq": 1}


DATASETS = {"plain": _plain, "cat_efb": _cat_efb,
            "quant_bagged": _quant_bagged}
CASES = [(d, layout) for d in DATASETS for layout in ("sorted", "gather")]


def _grow(dataset: str, layout: str) -> dict:
    import jax

    import lambdagap_tpu as lgb
    X, y, cat, extra = DATASETS[dataset](np.random.RandomState(7))
    params = {"objective": "regression", "num_leaves": 31,
              "min_data_in_leaf": 5, "learning_rate": 0.1, "verbose": -1,
              "tpu_fused_learner": "1", "tpu_hist_impl": "onehot",
              "tree_layout": layout, **extra}
    ds = lgb.Dataset(X, label=y, categorical_feature=cat, params=params)
    gb = lgb.train(params, ds, num_boost_round=4)._booster
    learner = gb.learner
    assert learner.layout == layout and learner._window(N) == 1024
    if dataset == "cat_efb":
        assert learner.bundled, "EFB bundle did not form"
    row_leaf = np.asarray(jax.device_get(learner.last_row_leaf), np.int32)
    return {
        "split_feature": [list(map(int, t.split_feature_inner))
                          for t in gb.host_models],
        "threshold_bin": [list(map(int, t.threshold_bin))
                          for t in gb.host_models],
        "leaf_count": [list(map(int, t.leaf_count[:t.num_leaves]))
                       for t in gb.host_models],
        "row_leaf_sha256": hashlib.sha256(
            np.ascontiguousarray(row_leaf).tobytes()).hexdigest(),
    }


def _small_window(self) -> int:
    return 1024


@pytest.fixture
def small_windows(monkeypatch):
    from lambdagap_tpu.models.fused_learner import FusedTreeLearner
    monkeypatch.setattr(FusedTreeLearner, "_pick_chunk", _small_window)


@pytest.mark.parametrize("dataset,layout", CASES)
def test_forest_equals_parent_commits(small_windows, dataset, layout):
    with open(GOLDEN) as f:
        golden = json.load(f)[f"{dataset}-{layout}"]
    got = _grow(dataset, layout)
    assert max(len(c) for c in got["leaf_count"]) > 8, "trees did not grow"
    for key in golden:
        assert got[key] == golden[key], key


if __name__ == "__main__":
    import sys
    assert sys.argv[1:] == ["--record"], __doc__
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from lambdagap_tpu.models.fused_learner import FusedTreeLearner
    FusedTreeLearner._pick_chunk = _small_window
    out = {f"{d}-{layout}": _grow(d, layout) for d, layout in CASES}
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    with open(GOLDEN, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print("recorded", GOLDEN, {k: len(v["leaf_count"]) for k, v in out.items()})
