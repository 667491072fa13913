"""Validation without leaving the device (the zero-sync path with watched
sets): each tree's validation scores are routed from the device-resident
record by a program whose shape holds no property of the tree, NDCG@k is
computed where the scores live, early stopping reads the same values, and
nothing but the metric values crosses to the host. Values and counts only:
nothing here reads a time."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lambdagap_tpu as lgb
from lambdagap_tpu.data.dataset import Metadata
from lambdagap_tpu.config import Config
from lambdagap_tpu.metrics.rank import NDCGMetric, _ndcg_at
from lambdagap_tpu.models.gbdt import _LazyTree, _valid_tree_score
from lambdagap_tpu.ops.predict import (_round_depth, predict_tree_binned,
                                       tree_to_arrays)

F = 9


def _rows(rng, n):
    """Numerical features with NaNs (NaN-missing), one with many exact
    zeros, one categorical."""
    X = rng.standard_normal((n, F)).astype(np.float32)
    X[rng.random((n, F)) < 0.06] = np.nan
    X[rng.random(n) < 0.4, 5] = 0.0
    X[:, 3] = rng.integers(0, 7, n)
    return X


def _rank_job(seed=0, nq=70, longest=45):
    rng = np.random.default_rng(seed)

    def fold(nq):
        sizes = rng.integers(1, longest, nq)
        X = _rows(rng, int(sizes.sum()))
        y = np.clip((np.nan_to_num(X[:, 0]) > 0.2).astype(int)
                    + (X[:, 3] == 2) * 2
                    + (rng.random(len(X)) < 0.1), 0, 4).astype(np.float32)
        return X, y, sizes
    params = dict(objective="lambdarank", num_leaves=15, min_data_in_leaf=2,
                  min_sum_hessian_in_leaf=1e-3, verbose=-1, metric="ndcg",
                  eval_at=[1, 5, 10], tpu_fused_learner=1,
                  categorical_feature=[3], learning_rate=0.2)
    return params, fold(nq), [fold(25), fold(18)]


def _binary_job(seed=1):
    rng = np.random.default_rng(seed)

    def fold(n):
        X = _rows(rng, n)
        y = ((np.nan_to_num(X[:, 0]) > 0.3) | (X[:, 3] == 2)).astype(
            np.float32)
        return X, y, None
    params = dict(objective="binary", num_leaves=15, min_data_in_leaf=2,
                  verbose=-1, metric=["auc", "binary_logloss"],
                  tpu_fused_learner=1, categorical_feature=[3])
    return params, fold(2500), [fold(700), fold(433)]


def _booster(params, train, folds):
    X, y, g = train
    ds = lgb.Dataset(X, label=y, group=g, params=params)
    bst = lgb.Booster(params, ds)
    for i, (Xv, yv, gv) in enumerate(folds):
        bst.add_valid(lgb.Dataset(Xv, label=yv, group=gv, reference=ds),
                      f"fold{i}")
    gb = bst._booster
    assert type(gb.learner).__name__ == "FusedTreeLearner"
    return bst, gb


@pytest.mark.parametrize("job", [_rank_job, _binary_job],
                         ids=["lambdarank", "binary"])
def test_device_scores_equal_the_materialising_path_bit_for_bit(job):
    """Beside the device path, every tree is materialised the way the
    parent's zero-sync branch did (a host Tree, ``tree_to_arrays``,
    ``predict_tree_binned`` at the tree's rounded depth) and added to a
    shadow copy: the two agree on every row of both sets, to the bit. (The
    parent ALSO folded the boost-from-average bias into the first
    materialised tree after adding it to the validation scores, so its
    binary validation scores sat one init score too high; the shadow adds
    the bias once, as the training scores take it.)"""
    params, train, folds = job()
    bst, gb = _booster(params, train, folds)
    shadow = [jnp.zeros_like(s) for s in gb.valid_scores]
    depths = set()
    for it in range(6):
        bst.update()
        lazy = gb.models[-1]
        assert isinstance(lazy, _LazyTree)
        tree = gb.learner.materialize(lazy.rec)
        depths.add(tree.max_depth)
        tree.leaf_value[:tree.num_leaves] = (
            tree.leaf_value[:tree.num_leaves].astype(np.float32)
            * np.float32(lazy.shrinkage))
        arrs = tree_to_arrays(tree, feature_meta=gb._meta,
                              use_inner_feature=True)
        for vi in range(len(folds)):
            if it == 0 and lazy.bias:
                shadow[vi] = shadow[vi].at[0].add(lazy.bias)
            shadow[vi] = shadow[vi].at[0].add(predict_tree_binned(
                gb.valid_binned[vi], arrs, _round_depth(tree.max_depth + 1)))
            assert np.array_equal(np.asarray(gb.valid_scores[vi]),
                                  np.asarray(shadow[vi])), (it, vi)
    assert len(depths) > 1          # trees of several depths went through
    for vi, (Xv, _, _) in enumerate(folds):
        # and they are the model's own predictions of the raw rows
        np.testing.assert_allclose(
            np.asarray(gb.valid_scores[vi])[0],
            bst.predict(Xv, raw_score=True), rtol=0, atol=2e-6)


def _metric(label, sizes, eval_at, weights=None, label_gain=None):
    cfg = {"objective": "lambdarank", "eval_at": list(eval_at)}
    if label_gain is not None:
        cfg["label_gain"] = list(label_gain)
    md = Metadata()
    md.label = np.asarray(label, np.float32)
    md.set_group(np.asarray(sizes))
    md.query_weights = weights
    m = NDCGMetric(Config.from_params(cfg))
    m.init(md, len(label))
    return m


@pytest.mark.parametrize("weights", [False, True], ids=["plain", "weights"])
@pytest.mark.parametrize("gain", [None, (0.0, 1.5, 2.0, 7.0, 3.0)],
                         ids=["default_gain", "label_gain"])
@pytest.mark.parametrize("eval_at", [(1,), (5,), (10,), (1, 5, 10)])
def test_device_ndcg_equals_the_float64_metric(eval_at, gain, weights):
    """Seeded scores WITH ties (a few distinct values, as after one or two
    trees), queries shorter than k, queries of one document, queries with
    no relevant document, query weights, a ``label_gain`` that is not
    monotone: the device's float32 NDCG@k within 1e-6 of the host's float64
    statement (``NDCGMetric.eval``), the names alike."""
    rng = np.random.default_rng(7)
    sizes = np.concatenate([rng.integers(1, 9, 40), rng.integers(9, 70, 60),
                            [1, 1, 200]])
    n = int(sizes.sum())
    label = rng.integers(0, 5, n).astype(np.float32)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    for q in rng.choice(len(sizes), 12, replace=False):
        label[starts[q]:starts[q + 1]] = 0            # nothing relevant
    w = rng.uniform(0.2, 3.0, len(sizes)) if weights else None
    m = _metric(label, sizes, eval_at, w, gain)
    for distinct in (2, 7, 1000):
        scores = rng.choice(rng.standard_normal(distinct), n).astype(
            np.float32)
        host = m.eval(scores.astype(np.float64))
        names, dev = m.eval_device(jnp.asarray(scores)[None, :])
        dev = np.asarray(dev)
        assert dev.dtype == np.float32 and dev.shape == (len(eval_at),)
        assert names == [name for name, _ in host]
        for (_, want), got in zip(host, dev):
            assert abs(float(got) - want) < 1e-6, (distinct, want, got)


def test_a_tie_keeps_the_earlier_document_first():
    """One query, every score equal: the ranking is the row order, so
    NDCG@1 is the first document's gain over the best one's."""
    label = np.array([1, 3, 0, 2], np.float32)
    m = _metric(label, [4], (1, 2))
    _, dev = m.eval_device(jnp.zeros(4, jnp.float32))
    assert float(dev[0]) == pytest.approx(1.0 / 7.0, abs=1e-7)
    want = (1.0 + 7.0 / np.log2(3.0)) / (7.0 + 3.0 / np.log2(3.0))
    assert float(dev[1]) == pytest.approx(want, abs=1e-7)


def _train(params, train, folds, rounds, **kw):
    X, y, g = train
    ds = lgb.Dataset(X, label=y, group=g, params=params)
    valid = [lgb.Dataset(Xv, label=yv, group=gv, reference=ds)
             for Xv, yv, gv in folds]
    record = {}
    bst = lgb.train(params, ds, num_boost_round=rounds, valid_sets=valid,
                    valid_names=["valid", "test"],
                    callbacks=[lgb.early_stopping(3, verbose=False),
                               lgb.record_evaluation(record)], **kw)
    return bst, record


def test_early_stopping_stops_where_the_host_metric_stops(monkeypatch):
    """``lgb.train`` with ``early_stopping``: the same stop, the same
    ``best_iteration`` and ``best_score`` whether the metric is computed on
    the device or, as the parent did, on the host from the scores read
    back."""
    params, train, folds = _rank_job(seed=3, nq=40, longest=25)
    params.update(eval_at=[10], learning_rate=0.5)
    dev, dev_rec = _train(params, train, folds, 60)
    monkeypatch.setattr(NDCGMetric, "eval_device", lambda self, s: None)
    host, host_rec = _train(params, train, folds, 60)
    assert 0 < dev.best_iteration < 60             # it fired
    assert dev.best_iteration == host.best_iteration
    assert dev.num_trees() == host.num_trees()
    for fold in ("valid", "test"):
        got, want = dev_rec[fold]["ndcg@10"], host_rec[fold]["ndcg@10"]
        assert len(got) == len(want)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
        assert dev.best_score[fold]["ndcg@10"] == pytest.approx(
            host.best_score[fold]["ndcg@10"], abs=1e-6)


def test_nothing_but_the_metric_values_leaves_the_device(monkeypatch):
    """Through training with two watched sets: no ``_LazyTree`` is
    materialised, every read is of the metric values alone (3 values a set,
    4 bytes each), and trees of other depths compile nothing: one
    ``_valid_tree_score`` and one ``_ndcg_at`` a set's shape."""
    params, train, folds = _rank_job(seed=5)
    bst, gb = _booster(params, train, folds)
    bst.update()
    bst.eval_valid()                         # every compile is behind us
    compiled = (_valid_tree_score._cache_size(), _ndcg_at._cache_size())
    reads, made = [], []
    get = jax.device_get

    def spy(tree):
        out = get(tree)
        reads.append(sum(np.asarray(x).nbytes
                         for x in jax.tree_util.tree_leaves(out)))
        return out
    monkeypatch.setattr(jax, "device_get", spy)
    monkeypatch.setattr(
        _LazyTree, "materialize",
        lambda self: made.append(self) or pytest.fail("materialised"))
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(8):
            bst.update()
            evals = gb.eval_valid()
            assert len(evals) == 6 and all(
                isinstance(v, float) for _, _, v, _ in evals)
    monkeypatch.undo()
    assert made == [] and all(isinstance(m, _LazyTree) for m in gb.models)
    # the guard's finite-scores flag (1 byte an iteration) is the one other
    # read of the zero-sync path
    assert [r for r in reads if r > 1] == [2 * 3 * 4] * 8
    assert (_valid_tree_score._cache_size(),
            _ndcg_at._cache_size()) == compiled
    assert len({t.max_depth for t in gb.host_models}) > 1


def test_the_iteration_record_counts_the_watched_sets():
    params, train, folds = _rank_job(seed=6)
    params["telemetry"] = True
    bst, gb = _booster(params, train, folds)
    for _ in range(2):
        bst.update()
        with gb.telemetry.phase("eval"):
            gb.eval_valid()
    gb.telemetry.close()
    rec = list(gb.telemetry.records)[-1]
    counts = rec["counts"]
    assert counts["valid_sets"] == 2
    assert counts["valid_rows"] == sum(len(f[0]) for f in folds)
    assert counts["eval_d2h_bytes"] == 2 * 3 * 4
    assert rec["phases"]["eval"] > 0


def test_a_metric_without_a_device_form_reads_the_scores_back():
    """``auc`` and ``binary_logloss`` keep the host path, unchanged: the
    set's scores are read back once for both."""
    params, train, folds = _binary_job()
    params["telemetry"] = True
    bst, gb = _booster(params, train, folds)
    bst.update()
    evals = gb.eval_valid()
    assert [(d, m) for d, m, _, _ in evals] == [
        (f"fold{i}", m) for i in range(2)
        for m in ("auc", "binary_logloss")]
    gb.telemetry.close()
    counts = list(gb.telemetry.records)[-1]["counts"]
    assert counts["eval_d2h_bytes"] == 4 * sum(len(f[0]) for f in folds)
    assert counts["valid_sets"] == 2
    assert counts["valid_rows"] == sum(len(f[0]) for f in folds)


def test_a_watched_set_added_to_a_resumed_model_takes_the_same_scores():
    """``add_valid_set``'s replay of an existing model and the per-tree
    device adds feed the same scores: a booster resumed from a model text
    and then trained on scores a fold exactly as its own predictions do."""
    params, train, folds = _rank_job(seed=8)
    first, _ = _booster(params, train, folds[:1])
    for _ in range(3):
        first.update()
    X, y, g = train
    ds = lgb.Dataset(X, label=y, group=g, params=params)
    bst = lgb.train(params, ds, num_boost_round=0,
                    init_model=lgb.Booster(
                        model_str=first.model_to_string()))
    Xv, yv, gv = folds[0]
    bst.add_valid(lgb.Dataset(Xv, label=yv, group=gv, reference=ds), "fold")
    gb = bst._booster
    np.testing.assert_allclose(np.asarray(gb.valid_scores[0])[0],
                               first.predict(Xv, raw_score=True),
                               rtol=0, atol=2e-6)
    for _ in range(3):
        bst.update()
    np.testing.assert_allclose(np.asarray(gb.valid_scores[0])[0],
                               bst.predict(Xv, raw_score=True),
                               rtol=0, atol=3e-6)
    bst.rollback_one_iter()
    np.testing.assert_allclose(np.asarray(gb.valid_scores[0])[0],
                               bst.predict(Xv, raw_score=True),
                               rtol=0, atol=3e-6)
