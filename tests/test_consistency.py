"""Reference-example consistency suite.

Trains on the reference's shipped example datasets WITH the reference's own
train.conf parameters, asserting metric bars and file-vs-array / CLI-vs-API
agreement (reference model:
tests/python_package_test/test_consistency.py:1-143 + examples/*/train.conf).
These anchor accuracy to real data instead of synthetic draws.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import lambdagap_tpu as lgb

EX = "/root/reference/examples"

pytestmark = pytest.mark.skipif(not os.path.isdir(EX),
                                reason="reference examples not present")

# conf keys that are host/runtime concerns, not model parameters
_SKIP_KEYS = {"task", "data", "valid_data", "output_model", "num_machines",
              "local_listen_port", "is_save_binary_file",
              "use_two_round_loading", "is_enable_sparse", "machine_list_file",
              "tree_learner"}


# LAMBDAGAP_CONSISTENCY_FULL=1 runs every example at its conf's full
# num_trees (the reference confs ship 100) with the full-length metric
# bars; the default caps at 50 to keep the quick suite quick. The full
# mode runs in tools/run_full_suite.sh's slow group.
FULL = os.environ.get("LAMBDAGAP_CONSISTENCY_FULL", "0") not in ("0", "")


def _conf(d, name="train.conf", max_trees=50):
    params = {}
    for line in open(os.path.join(EX, d, name)):
        line = line.strip()
        if line and not line.startswith("#") and "=" in line:
            k, v = [t.strip() for t in line.split("=", 1)]
            if "early_stopping" in k or k in _SKIP_KEYS:
                continue
            params[k] = v
    params["verbose"] = -1
    # keep every conf parameter but (outside FULL mode) cap rounds
    if not FULL and max_trees and int(params.get("num_trees", 100)) > max_trees:
        params["num_trees"] = max_trees
    return params


def _load(d, fname):
    mat = np.loadtxt(os.path.join(EX, d, fname))
    return mat[:, 1:], mat[:, 0]


def _ds_from_file(d, fname, params):
    return lgb.Dataset(os.path.join(EX, d, fname), params=params)


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cli_env():
    """CLI subprocess env: the child runs the same CPU backend as the
    in-process API (cross-backend float noise flips near-ties)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _cli_train_binary(model_path, num_trees):
    d = os.path.join(EX, "binary_classification")
    return subprocess.run(
        [sys.executable, "-m", "lambdagap_tpu",
         "config=" + os.path.join(d, "train.conf"),
         "data=" + os.path.join(d, "binary.train"),
         "valid_data=" + os.path.join(d, "binary.test"),
         f"num_trees={num_trees}", "output_model=" + model_path,
         "verbose=-1"],
        capture_output=True, text=True, env=_cli_env(), cwd=_REPO_ROOT)


def test_binary_example():
    d = "binary_classification"
    p = _conf(d)
    X, y = _load(d, "binary.train")
    Xt, yt = _load(d, "binary.test")
    w = np.loadtxt(os.path.join(EX, d, "binary.train.weight"))
    res = {}
    bst = lgb.train(p, lgb.Dataset(X, label=y, weight=w),
                    valid_sets=[lgb.Dataset(Xt, label=yt, reference=None,
                                            params=p)],
                    callbacks=[lgb.record_evaluation(res)])
    auc = res["valid_0"]["auc"][-1]
    # the reference's own example reaches ~0.98 train / high-0.7s test AUC
    from sklearn.metrics import roc_auc_score
    test_auc = roc_auc_score(yt, bst.predict(Xt))
    assert test_auc > (0.77 if FULL else 0.75), test_auc
    # file-loaded prediction path agrees with the array path
    pred_arr = bst.predict(Xt)
    pred_file = bst.predict(os.path.join(EX, d, "binary.test"))
    np.testing.assert_allclose(pred_arr, pred_file, rtol=1e-6)


def test_binary_file_dataset_matches_array():
    d = "binary_classification"
    p = _conf(d)
    X, y = _load(d, "binary.train")
    w = np.loadtxt(os.path.join(EX, d, "binary.train.weight"))
    ds_a = lgb.Dataset(X, label=y, weight=w, params=p).construct()
    ds_f = _ds_from_file(d, "binary.train", p).construct()
    assert ds_a.num_data == ds_f.num_data
    assert ds_a.num_features == ds_f.num_features
    np.testing.assert_allclose(ds_a.metadata.label, ds_f.metadata.label)
    np.testing.assert_allclose(ds_a.metadata.weight, ds_f.metadata.weight)
    # identical parsing + sampling -> identical bin mappers and binned rows
    assert np.array_equal(ds_a.binned, ds_f.binned)


def test_binary_cli_matches_api(tmp_path):
    """CLI training with the reference's own train.conf produces the same
    predictions as the API on the same file-loaded dataset."""
    d = os.path.join(EX, "binary_classification")
    model = str(tmp_path / "cli_model.txt")
    pred = str(tmp_path / "cli_pred.txt")
    r = _cli_train_binary(model, 20)
    assert r.returncode == 0, r.stderr[-2000:]
    r = subprocess.run(
        [sys.executable, "-m", "lambdagap_tpu", "task=predict",
         "data=" + os.path.join(d, "binary.test"),
         "input_model=" + model, "output_result=" + pred],
        capture_output=True, text=True, env=_cli_env(), cwd=_REPO_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    cli_pred = np.loadtxt(pred)

    p = _conf("binary_classification")
    p["num_trees"] = 20
    bst = lgb.train(p, _ds_from_file("binary_classification", "binary.train",
                                     p))
    api_pred = bst.predict(_load("binary_classification", "binary.test")[0])
    np.testing.assert_allclose(cli_pred, api_pred, rtol=1e-5, atol=1e-6)


def test_regression_example():
    d = "regression"
    p = _conf(d)
    X, y = _load(d, "regression.train")
    Xt, yt = _load(d, "regression.test")
    init = np.loadtxt(os.path.join(EX, d, "regression.train.init"))
    res = {}
    ds = lgb.Dataset(X, label=y, init_score=init, params=p)
    bst = lgb.train(p, ds, valid_sets=[lgb.Dataset(X, label=y,
                                                   init_score=init,
                                                   params=p)],
                    valid_names=["training"],
                    callbacks=[lgb.record_evaluation(res)])
    l2 = res["training"]["l2"]
    assert l2[-1] < l2[0] * 0.9
    # the shipped .init scores exercise the init_score path but do not help
    # generalization; the holdout accuracy bar uses a plain model
    plain = lgb.train(p, lgb.Dataset(X, label=y, params=p))
    mse = np.mean((yt - plain.predict(Xt)) ** 2)
    assert mse < 0.8 * np.var(yt), (mse, np.var(yt))


def test_multiclass_example():
    d = "multiclass_classification"
    p = _conf(d)
    X, y = _load(d, "multiclass.train")
    Xt, yt = _load(d, "multiclass.test")
    res = {}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    valid_sets=[lgb.Dataset(Xt, label=yt, params=p)],
                    callbacks=[lgb.record_evaluation(res)])
    ml = res["valid_0"]["multi_logloss"]
    # at the conf's full 100 trees the 7k-row example overfits and the
    # final valid logloss can drift a hair above the start; the curve must
    # still have improved (the conf ships no early stopping)
    assert min(ml) < ml[0] * 0.99, (min(ml), ml[0])
    if not FULL:
        assert ml[-1] < ml[0]
    acc = np.mean(np.argmax(bst.predict(Xt), axis=1) == yt)
    # 5 classes, chance = 0.2; the example reaches ~0.43 at 50 trees and
    # ~0.46 at the conf's full 100
    assert acc > (0.42 if FULL else 0.38), acc


@pytest.mark.parametrize("d,obj", [("lambdarank", "lambdarank"),
                                   ("xendcg", "rank_xendcg")])
def test_rank_examples(d, obj):
    p = _conf(d)
    p["objective"] = obj
    res = {}
    train = _ds_from_file(d, "rank.train", p)
    valid = _ds_from_file(d, "rank.test", p)
    lgb.train(p, train, valid_sets=[train, valid],
              valid_names=["training", "valid"],
              callbacks=[lgb.record_evaluation(res)])
    key = next((k for k in res["valid"] if "ndcg@5" in k),
               next(k for k in res["valid"] if "ndcg" in k))
    # the 3k-row example overfits: training NDCG must climb hard, the
    # holdout bar is what the tiny validation fold supports
    tr_ndcg = res["training"][key]
    assert tr_ndcg[-1] > tr_ndcg[0] + 0.1, (key, tr_ndcg[0], tr_ndcg[-1])
    assert tr_ndcg[-1] > 0.9, tr_ndcg[-1]
    assert res["valid"][key][-1] > 0.45, res["valid"][key][-1]


def test_parallel_learning_example():
    """The reference's 2-machine example, run data-parallel on a 2-device
    mesh: distributed accuracy must match serial on the same data."""
    d = "parallel_learning"
    p = _conf(d)
    p["num_trees"] = 20
    X, y = _load(d, "binary.train")
    Xt, yt = _load(d, "binary.test")
    from sklearn.metrics import roc_auc_score
    serial = lgb.train(p, lgb.Dataset(X, label=y, params=p))
    dist = lgb.train({**p, "tree_learner": "data", "tpu_num_devices": 2},
                     lgb.Dataset(X, label=y, params=p))
    auc_s = roc_auc_score(yt, serial.predict(Xt))
    auc_d = roc_auc_score(yt, dist.predict(Xt))
    assert auc_d > 0.7, auc_d
    assert abs(auc_s - auc_d) < 0.05, (auc_s, auc_d)


def test_binary_linear_example():
    """The reference's shipped linear-tree config (train_linear.conf) on its
    own data (reference model: test_consistency.py test_binary_linear)."""
    d = "binary_classification"
    p = _conf(d, name="train_linear.conf")
    X, y = _load(d, "binary.train")
    Xt, yt = _load(d, "binary.test")
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p))
    from sklearn.metrics import roc_auc_score
    auc = roc_auc_score(yt, bst.predict(Xt))
    assert auc > 0.75, auc
    # the model really carries linear leaves
    assert "is_linear=1" in bst.model_to_string()


def test_regression_forced_bins_example():
    """The reference's shipped forced_bins.json drives bin boundaries on its
    own regression data (reference: examples/regression/forced_bins.json)."""
    d = "regression"
    p = _conf(d)
    p["forcedbins_filename"] = os.path.join(EX, d, "forced_bins.json")
    X, y = _load(d, "regression.train")
    ds = lgb.Dataset(X, label=y, params=p).construct()
    for feat, bounds in ((0, (0.3, 0.35, 0.4)), (1, (-0.1, -0.15, -0.2))):
        ub = ds.mappers[feat].bin_upper_bound
        for b in bounds:
            assert any(abs(x - b) < 1e-9 for x in ub), (feat, b, ub[:10])


def test_predict_conf_cli(tmp_path):
    """The reference's predict.conf flow: train via CLI, then task=predict
    driven by the shipped conf (with path overrides)."""
    d = os.path.join(EX, "binary_classification")
    model = str(tmp_path / "m.txt")
    out = str(tmp_path / "preds.txt")
    r = _cli_train_binary(model, 5)
    assert r.returncode == 0, r.stderr[-1500:]
    r = subprocess.run(
        [sys.executable, "-m", "lambdagap_tpu",
         "config=" + os.path.join(d, "predict.conf"),
         "data=" + os.path.join(d, "binary.test"),
         "input_model=" + model, "output_result=" + out],
        capture_output=True, text=True, env=_cli_env(), cwd=_REPO_ROOT)
    assert r.returncode == 0, r.stderr[-1500:]
    preds = np.loadtxt(out)
    assert preds.shape == (500,)
    assert np.all((preds >= 0) & (preds <= 1))
