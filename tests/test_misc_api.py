"""Streaming Sequence construction, cv details, plotting.

(reference: basic.py:903 Sequence + test_basic.py:139-234 Sequence cases;
engine.py cv; plotting.py)
"""
import numpy as np
import pytest

import lambdagap_tpu as lgb


def _data(n=900, d=5, seed=2):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d)
    y = X @ rng.randn(d) + 0.1 * rng.randn(n)
    return X, y


class _NpSequence(lgb.Sequence):
    batch_size = 128

    def __init__(self, arr):
        self.arr = arr

    def __getitem__(self, idx):
        return self.arr[idx]

    def __len__(self):
        return len(self.arr)


def test_sequence_matches_matrix():
    X, y = _data()
    params = {"objective": "regression", "num_leaves": 15, "verbose": -1}
    b_mat = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5)
    seqs = [_NpSequence(X[:400]), _NpSequence(X[400:])]
    b_seq = lgb.train(params, lgb.Dataset(seqs, label=y), num_boost_round=5)
    np.testing.assert_allclose(b_seq.predict(X), b_mat.predict(X),
                               rtol=1e-5, atol=1e-6)


def test_cv_sklearn_splitter_and_train_metric():
    pytest.importorskip("sklearn")
    from sklearn.model_selection import KFold
    X, y = _data()
    res = lgb.cv({"objective": "regression", "num_leaves": 7, "verbose": -1,
                  "metric": "l2"},
                 lgb.Dataset(X, label=y, free_raw_data=False),
                 num_boost_round=5, folds=KFold(n_splits=3),
                 eval_train_metric=True)
    assert "valid l2-mean" in res
    assert "train l2-mean" in res
    assert len(res["valid l2-mean"]) == 5
    # train error below valid error on average (sanity)
    assert np.mean(res["train l2-mean"]) <= np.mean(res["valid l2-mean"]) + 1e-9


def test_cv_early_stopping_uses_first_metric():
    X, y = _data()
    res = lgb.cv({"objective": "regression", "num_leaves": 7, "verbose": -1,
                  "metric": ["l2", "l1"], "early_stopping_round": 3},
                 lgb.Dataset(X, label=y, free_raw_data=False),
                 num_boost_round=30, nfold=3)
    # converged training stops early and truncates consistently
    lens = {len(v) for v in res.values()}
    assert len(lens) == 1


def test_plot_importance_without_display():
    mpl = pytest.importorskip("matplotlib")
    mpl.use("Agg")
    X, y = _data()
    b = lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1},
                  lgb.Dataset(X, label=y), num_boost_round=5)
    ax = lgb.plot_importance(b)
    assert len(ax.patches) > 0
    recorded = {}
    b2 = lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1,
                    "metric": "l2"},
                   lgb.Dataset(X, label=y), num_boost_round=5,
                   valid_sets=[lgb.Dataset(X[:200], label=y[:200],
                                           reference=None)],
                   callbacks=[lgb.record_evaluation(recorded)])
    ax2 = lgb.plot_metric(recorded)
    assert ax2.get_lines()


def test_sequence_subsampled_binning_and_reference():
    # total rows > bin_construct_sample_cnt exercises the sampled-binning
    # path; a reference-aligned Sequence valid set must share bins
    X, y = _data(n=3000)
    params = {"objective": "regression", "num_leaves": 15, "verbose": -1,
              "bin_construct_sample_cnt": 500}
    dtrain = lgb.Dataset(_NpSequence(X[:2500]), label=y[:2500], params=params)
    dvalid = lgb.Dataset(_NpSequence(X[2500:]), label=y[2500:],
                         reference=dtrain)
    rec = {}
    lgb.train(params, dtrain, num_boost_round=5, valid_sets=[dvalid],
              callbacks=[lgb.record_evaluation(rec)])
    vals = rec["valid_0"]["l2"]
    assert vals[-1] < vals[0]
    tds, vds = dtrain.construct(), dvalid.construct()
    assert tds.mappers is vds.mappers


def test_predict_shape_check():
    """Fewer predict columns than the model needs must fail loudly, unless
    predict_disable_shape_check pads with NaN (reference:
    predict_disable_shape_check)."""
    import pytest
    rng = np.random.RandomState(0)
    X = rng.randn(1000, 6)
    y = (X[:, 5] > 0).astype(float)     # force use of the last feature
    b = lgb.train({"objective": "binary", "verbose": -1, "num_leaves": 7},
                  lgb.Dataset(X, label=y), num_boost_round=3)
    with pytest.raises(Exception):
        b.predict(X[:10, :3])
    b2 = lgb.train({"objective": "binary", "verbose": -1, "num_leaves": 7,
                    "predict_disable_shape_check": True},
                   lgb.Dataset(X, label=y), num_boost_round=3)
    p = b2.predict(X[:10, :3])          # missing columns ride as NaN
    assert np.all(np.isfinite(p))


def test_auc_mu_weights_matrix():
    """auc_mu_weights reshapes into the KxK cost matrix and changes the
    pairwise separating directions (reference: config.cpp
    auc_mu_weights_matrix)."""
    from sklearn.datasets import make_classification
    X, y = make_classification(1200, 8, n_informative=5, n_classes=3,
                               random_state=0)
    base = {"objective": "multiclass", "num_class": 3, "metric": "auc_mu",
            "verbose": -1}
    res1, res2 = {}, {}
    ds = lgb.Dataset(X, label=y)
    lgb.train(base, ds, num_boost_round=5, valid_sets=[lgb.Dataset(X, label=y)],
              callbacks=[lgb.record_evaluation(res1)])
    w = [0, 1, 5, 1, 0, 1, 5, 1, 0]
    lgb.train({**base, "auc_mu_weights": w}, lgb.Dataset(X, label=y),
              num_boost_round=5, valid_sets=[lgb.Dataset(X, label=y)],
              callbacks=[lgb.record_evaluation(res2)])
    a1 = res1["valid_0"]["auc_mu"][-1]
    a2 = res2["valid_0"]["auc_mu"][-1]
    assert 0.5 < a1 <= 1.0 and 0.5 < a2 <= 1.0
    assert a1 != a2


def test_booster_api_parity():
    """Reference Booster surface: pickling/deepcopy via the text model,
    eval() on arbitrary data matching the training-loop metrics,
    lower/upper_bound, get/set_leaf_output, get_split_value_histogram,
    model_from_string, shuffle_models (reference: python-package basic.py
    Booster methods)."""
    import copy
    import pickle
    from sklearn.datasets import make_classification
    X, y = make_classification(800, 6, random_state=0)
    res = {}
    b = lgb.train({"objective": "binary", "metric": "auc", "verbose": -1,
                   "num_leaves": 7},
                  lgb.Dataset(X, label=y), num_boost_round=4,
                  valid_sets=[lgb.Dataset(X, label=y)],
                  callbacks=[lgb.record_evaluation(res)])
    p0 = b.predict(X[:20])
    b2 = pickle.loads(pickle.dumps(b))
    np.testing.assert_allclose(b2.predict(X[:20]), p0, rtol=1e-6)
    b3 = copy.deepcopy(b)
    np.testing.assert_allclose(b3.predict(X[:20]), p0, rtol=1e-6)
    assert b.lower_bound() < b.upper_bound()
    ev = b3.eval(lgb.Dataset(X, label=y), "extra")
    assert ev[0][0] == "extra"
    assert abs(ev[0][2] - res["valid_0"]["auc"][-1]) < 1e-5
    hist, edges = b3.get_split_value_histogram(0)
    assert hist.sum() >= 0 and len(edges) == len(hist) + 1
    v = b.get_leaf_output(0, 0)
    b.set_leaf_output(0, 0, v + 1.0)
    assert abs(b.get_leaf_output(0, 0) - (v + 1.0)) < 1e-12
    assert not np.allclose(b.predict(X[:20]), p0)
    # model_from_string replaces the model in place
    b.model_from_string(b3.model_to_string())
    np.testing.assert_allclose(b.predict(X[:20]), p0, rtol=1e-6)
    # shuffled tree order leaves gbdt predictions unchanged (order-free sum)
    b3.shuffle_models()
    np.testing.assert_allclose(b3.predict(X[:20]), p0, rtol=1e-6)


def test_parameter_docs_in_sync():
    """docs/Parameters.md is generated from the Config dataclass (the
    config_auto pattern, reference: src/io/config_auto.cpp:6); the
    checked-in artifact must match a fresh generation."""
    import subprocess, sys, os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "gen_params_doc.py"),
         "--check"], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


def test_parameter_docs_cover_all_fields():
    import dataclasses, re, os
    from lambdagap_tpu.config import Config
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = open(os.path.join(root, "docs", "Parameters.md")).read()
    documented = set(re.findall(r"^\| `(\w+)`", doc, re.M))
    missing = {f.name for f in dataclasses.fields(Config)} - documented
    assert not missing, missing
