"""End-to-end training tests (reference analog:
tests/python_package_test/test_engine.py — small synthetic datasets, few
iterations, metric-threshold assertions)."""
import numpy as np
import pytest
from sklearn.datasets import make_classification, make_regression

import lambdagap_tpu as lgb


def _reg_data(n=1500, d=10, seed=0):
    X, y = make_regression(n, d, n_informative=6, noise=5.0, random_state=seed)
    return X, y


def _bin_data(n=2000, d=15, seed=0):
    return make_classification(n, d, n_informative=8, random_state=seed)


def test_regression_decreasing_loss():
    X, y = _reg_data()
    ds = lgb.Dataset(X, label=y)
    res = {}
    booster = lgb.train({"objective": "regression", "metric": "l2",
                         "num_leaves": 15, "verbose": -1},
                        ds, num_boost_round=30,
                        valid_sets=[ds], valid_names=["training"],
                        callbacks=[lgb.record_evaluation(res)])
    l2 = res["training"]["l2"]
    assert l2[-1] < l2[0] * 0.2
    assert all(b <= a + 1e-9 for a, b in zip(l2, l2[1:]))


def test_binary_auc():
    X, y = _bin_data()
    ds = lgb.Dataset(X[:1500], label=y[:1500])
    vs = ds.create_valid(X[1500:], label=y[1500:])
    res = {}
    booster = lgb.train({"objective": "binary", "metric": ["auc", "binary_logloss"],
                         "num_leaves": 31, "verbose": -1},
                        ds, num_boost_round=50, valid_sets=[vs],
                        callbacks=[lgb.record_evaluation(res)])
    assert res["valid_0"]["auc"][-1] > 0.93
    preds = booster.predict(X[1500:])
    assert preds.shape == (500,)
    assert np.all((preds >= 0) & (preds <= 1))


def test_multiclass():
    X, y = make_classification(2000, 20, n_informative=10, n_classes=4,
                               random_state=3)
    ds = lgb.Dataset(X[:1500], label=y[:1500])
    vs = ds.create_valid(X[1500:], label=y[1500:])
    res = {}
    booster = lgb.train({"objective": "multiclass", "num_class": 4,
                         "metric": "multi_logloss", "verbose": -1},
                        ds, num_boost_round=30, valid_sets=[vs],
                        callbacks=[lgb.record_evaluation(res)])
    ml = res["valid_0"]["multi_logloss"]
    assert ml[-1] < ml[0]
    preds = booster.predict(X[1500:])
    assert preds.shape == (500, 4)
    np.testing.assert_allclose(preds.sum(axis=1), 1.0, rtol=1e-4)
    acc = np.mean(np.argmax(preds, axis=1) == y[1500:])
    assert acc > 0.6


def test_early_stopping():
    X, y = _bin_data(seed=5)
    ds = lgb.Dataset(X[:1000], label=y[:1000])
    vs = ds.create_valid(X[1000:], label=y[1000:])
    booster = lgb.train({"objective": "binary", "metric": "binary_logloss",
                         "verbose": -1, "early_stopping_round": 5,
                         "num_leaves": 63, "learning_rate": 0.3},
                        ds, num_boost_round=500, valid_sets=[vs])
    assert 0 < booster.best_iteration < 500


def test_weights_change_model():
    X, y = _reg_data(seed=2)
    w = np.where(y > np.median(y), 10.0, 0.1)
    p = {"objective": "regression", "verbose": -1, "num_leaves": 7}
    b0 = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=10)
    b1 = lgb.train(p, lgb.Dataset(X, label=y, weight=w), num_boost_round=10)
    assert not np.allclose(b0.predict(X), b1.predict(X))


def test_bagging_and_feature_fraction():
    X, y = _bin_data(seed=6)
    booster = lgb.train({"objective": "binary", "verbose": -1,
                         "bagging_fraction": 0.5, "bagging_freq": 1,
                         "feature_fraction": 0.7, "metric": "auc"},
                        lgb.Dataset(X, label=y), num_boost_round=20,
                        valid_sets=[lgb.Dataset(X, label=y, reference=None)])
    # still learns signal
    pred = booster.predict(X)
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, pred) > 0.85


def test_goss():
    X, y = _bin_data(seed=7)
    booster = lgb.train({"objective": "binary", "verbose": -1,
                         "data_sample_strategy": "goss",
                         "learning_rate": 0.1},
                        lgb.Dataset(X, label=y), num_boost_round=30)
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, booster.predict(X)) > 0.9


def test_boosting_goss_alias():
    X, y = _bin_data(seed=8)
    booster = lgb.train({"objective": "binary", "boosting": "goss",
                         "verbose": -1}, lgb.Dataset(X, label=y),
                        num_boost_round=5)
    assert booster.num_trees() == 5


def test_min_data_in_leaf_respected():
    X, y = _reg_data(n=300)
    booster = lgb.train({"objective": "regression", "verbose": -1,
                         "min_data_in_leaf": 50, "num_leaves": 31},
                        lgb.Dataset(X, label=y), num_boost_round=5)
    for tree in booster._booster.models:
        counts = tree.leaf_count[:tree.num_leaves]
        assert counts.min() >= 50


def test_max_depth():
    X, y = _reg_data(n=1000)
    booster = lgb.train({"objective": "regression", "verbose": -1,
                         "max_depth": 3, "num_leaves": 31},
                        lgb.Dataset(X, label=y), num_boost_round=5)
    for tree in booster._booster.models:
        assert tree.max_depth <= 3


def test_categorical_feature_training():
    rng = np.random.RandomState(11)
    n = 2000
    cat = rng.randint(0, 5, n)
    num = rng.randn(n)
    y = (cat == 2) * 3.0 + (cat == 4) * -2.0 + 0.5 * num + 0.05 * rng.randn(n)
    X = np.column_stack([cat.astype(float), num])
    booster = lgb.train({"objective": "regression", "verbose": -1,
                         "num_leaves": 15, "min_data_in_leaf": 5},
                        lgb.Dataset(X, label=y, categorical_feature=[0]),
                        num_boost_round=40)
    pred = booster.predict(X)
    assert np.mean((pred - y) ** 2) < 0.1 * np.var(y)


def test_categorical_high_cardinality_values():
    """Raw category values >= 256 must route correctly at predict time
    (variable-width bitsets; reference sizes them dynamically via
    Common::ConstructBitset)."""
    rng = np.random.RandomState(21)
    n = 2000
    cat = rng.randint(300, 310, n)          # all values above the old 256 cap
    num = rng.randn(n)
    y = (cat == 302) * 3.0 + (cat == 308) * -2.0 + 0.5 * num + 0.05 * rng.randn(n)
    X = np.column_stack([cat.astype(float), num])
    booster = lgb.train({"objective": "regression", "verbose": -1,
                         "num_leaves": 15, "min_data_in_leaf": 5},
                        lgb.Dataset(X, label=y, categorical_feature=[0]),
                        num_boost_round=40)
    pred = booster.predict(X)
    assert np.mean((pred - y) ** 2) < 0.1 * np.var(y)
    # text round-trip keeps the wide bitsets too
    reloaded = lgb.Booster(model_str=booster.model_to_string())
    pred2 = reloaded.predict(X)
    np.testing.assert_allclose(pred, pred2, rtol=1e-5, atol=1e-5)


def test_missing_values_nan():
    rng = np.random.RandomState(12)
    n = 2000
    x0 = rng.randn(n)
    y = np.where(np.isnan(x0), 5.0, x0 * 2.0)
    x0[rng.rand(n) < 0.3] = np.nan
    y = np.where(np.isnan(x0), 5.0, x0 * 2.0)
    X = np.column_stack([x0, rng.randn(n)])
    booster = lgb.train({"objective": "regression", "verbose": -1,
                         "num_leaves": 31}, lgb.Dataset(X, label=y),
                        num_boost_round=40)
    pred = booster.predict(X)
    assert np.mean((pred - y) ** 2) < 0.05 * np.var(y)


def test_dart_training():
    """DART drops + renormalizes via the batched forest path
    (reference: dart.hpp DroppingTrees/Normalize)."""
    X, y = _reg_data(n=800, seed=31)
    booster = lgb.train({"objective": "regression", "boosting": "dart",
                         "drop_rate": 0.4, "verbose": -1, "num_leaves": 15},
                        lgb.Dataset(X, label=y), num_boost_round=20,
                        valid_sets=[lgb.Dataset(X[:200], label=y[:200],
                                                reference=None)])
    pred = booster.predict(X)
    assert np.mean((pred - y) ** 2) < 0.7 * np.var(y)


def test_init_score():
    X, y = _reg_data(seed=13)
    init = np.full(len(y), 100.0)
    booster = lgb.train({"objective": "regression", "verbose": -1},
                        lgb.Dataset(X, label=y + 100.0, init_score=init),
                        num_boost_round=10)
    # model learns residual around init score; prediction excludes init score
    pred = booster.predict(X)
    assert abs(np.mean(pred) - np.mean(y)) < 5.0


def test_cv_runs():
    X, y = _bin_data(seed=14)
    res = lgb.cv({"objective": "binary", "metric": "auc", "verbose": -1},
                 lgb.Dataset(X, label=y, free_raw_data=False),
                 num_boost_round=10, nfold=3)
    assert "valid auc-mean" in res
    assert len(res["valid auc-mean"]) == 10
    assert res["valid auc-mean"][-1] > 0.85


def test_forest_predict_tree_blocks():
    """The device forest scan dispatches in bounded tree blocks with the
    accumulator carried between kernels (no kernel grows with T); results
    are bit-comparable to the single-dispatch scan for plain, early-stop, and
    padding (odd block) configurations."""
    import jax.numpy as jnp
    from lambdagap_tpu.ops.predict import forest_to_arrays, predict_forest
    rng = np.random.RandomState(0)
    X = rng.randn(1200, 8)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)
    b = lgb.train({"objective": "binary", "num_leaves": 15, "verbose": -1,
                   "min_data_in_leaf": 5}, lgb.Dataset(X, label=y),
                  num_boost_round=150)
    forest, depth = forest_to_arrays(b._booster.host_models)
    tc = jnp.zeros(150, jnp.int32)
    xd = jnp.asarray(X[:256])
    single = np.asarray(predict_forest(xd, forest, tc, 1, depth, False,
                                       tree_block=10**9))
    for kw in ({"tree_block": 64}, {"tree_block": 37},
               {"tree_block": 64, "early_stop_freq": 10,
                "early_stop_margin": 3.0}):
        want = single
        if "early_stop_freq" in kw:
            want = np.asarray(predict_forest(
                xd, forest, tc, 1, depth, False, tree_block=10**9,
                early_stop_freq=10, early_stop_margin=3.0))
        got = np.asarray(predict_forest(xd, forest, tc, 1, depth, False,
                                        **kw))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # leaf-index prediction blocks the same way (refit/linear replay path)
    from lambdagap_tpu.ops.predict import predict_forest_leaf
    leaf_single = np.asarray(predict_forest_leaf(xd, forest, depth, False,
                                                 tree_block=10**9))
    leaf_blocked = np.asarray(predict_forest_leaf(xd, forest, depth, False,
                                                  tree_block=37))
    np.testing.assert_array_equal(leaf_single, leaf_blocked)
