"""Ranking objective/metric tests (reference analog: test_engine.py
lambdarank tests :736-835 + the fork's 18-target surface)."""
import numpy as np
import pytest

import lambdagap_tpu as lgb
from lambdagap_tpu.config import LAMBDARANK_TARGETS


def _make_ltr(n_queries=60, docs_per_query=25, n_features=10, seed=0):
    """Synthetic LTR data: relevance depends on a few features."""
    rng = np.random.RandomState(seed)
    n = n_queries * docs_per_query
    X = rng.randn(n, n_features)
    util = 2.0 * X[:, 0] + X[:, 1] + 0.5 * rng.randn(n)
    labels = np.zeros(n)
    group = np.full(n_queries, docs_per_query)
    for q in range(n_queries):
        s = slice(q * docs_per_query, (q + 1) * docs_per_query)
        u = util[s]
        ranks = np.argsort(np.argsort(-u))
        lab = np.zeros(docs_per_query)
        lab[ranks < 3] = 2
        lab[(ranks >= 3) & (ranks < 8)] = 1
        labels[s] = lab
    return X, labels, group


def _ndcg_at(booster, X, labels, group, k=5):
    scores = booster.predict(X, raw_score=True)
    qb = np.concatenate([[0], np.cumsum(group)]).astype(int)
    vals = []
    for qi in range(len(group)):
        s, e = qb[qi], qb[qi + 1]
        order = np.argsort(-scores[s:e])
        l = labels[s:e][order].astype(int)
        disc = 1.0 / np.log2(2.0 + np.arange(len(l)))
        dcg = np.sum((2.0 ** l[:k] - 1) * disc[:k])
        li = np.sort(labels[s:e].astype(int))[::-1]
        mdcg = np.sum((2.0 ** li[:k] - 1) * disc[:k])
        vals.append(dcg / mdcg if mdcg > 0 else 1.0)
    return float(np.mean(vals))


def test_lambdarank_learns_ranking():
    X, labels, group = _make_ltr()
    ds = lgb.Dataset(X, label=labels, group=group)
    booster = lgb.train({"objective": "lambdarank", "metric": "ndcg",
                         "eval_at": [5], "num_leaves": 15, "verbose": -1,
                         "min_data_in_leaf": 5},
                        ds, num_boost_round=40)
    ndcg = _ndcg_at(booster, X, labels, group)
    assert ndcg > 0.85


def test_lambdarank_ndcg_metric_reported():
    X, labels, group = _make_ltr(seed=1)
    ds = lgb.Dataset(X, label=labels, group=group)
    vs = ds.create_valid(X, label=labels, group=group)
    res = {}
    lgb.train({"objective": "lambdarank", "metric": "ndcg",
               "eval_at": [1, 3, 5], "verbose": -1, "min_data_in_leaf": 5},
              ds, num_boost_round=15, valid_sets=[vs],
              callbacks=[lgb.record_evaluation(res)])
    assert "ndcg@1" in res["valid_0"]
    assert "ndcg@5" in res["valid_0"]
    assert res["valid_0"]["ndcg@5"][-1] > res["valid_0"]["ndcg@5"][0] - 1e-9


@pytest.mark.parametrize("target", LAMBDARANK_TARGETS)
def test_all_lambdarank_targets_train(target):
    """Every one of the fork's 18 gradient targets produces a learning model
    (reference: rank_objective.hpp:22-41)."""
    X, labels, group = _make_ltr(n_queries=30, docs_per_query=15, seed=2)
    ds = lgb.Dataset(X, label=labels, group=group)
    booster = lgb.train({"objective": "lambdarank",
                         "lambdarank_target": target,
                         "lambdarank_truncation_level": 5,
                         "num_leaves": 7, "verbose": -1,
                         "min_data_in_leaf": 3},
                        ds, num_boost_round=15)
    assert booster.num_trees() > 0
    ndcg = _ndcg_at(booster, X, labels, group)
    assert ndcg > 0.6, f"target {target} failed to learn: ndcg={ndcg}"


def test_lambdagap_weight_changes_gradients():
    X, labels, group = _make_ltr(seed=3)
    preds = []
    for w in (0.1, 5.0):
        booster = lgb.train({"objective": "lambdarank",
                             "lambdarank_target": "lambdaloss-ndcg-plus-plus",
                             "lambdagap_weight": w, "verbose": -1,
                             "min_data_in_leaf": 5},
                            lgb.Dataset(X, label=labels, group=group),
                            num_boost_round=10)
        preds.append(booster.predict(X, raw_score=True))
    assert not np.allclose(preds[0], preds[1])


def test_rank_xendcg():
    X, labels, group = _make_ltr(seed=4)
    booster = lgb.train({"objective": "rank_xendcg", "verbose": -1,
                         "min_data_in_leaf": 5, "num_leaves": 15},
                        lgb.Dataset(X, label=labels, group=group),
                        num_boost_round=40)
    assert _ndcg_at(booster, X, labels, group) > 0.8


def test_query_ids_as_group():
    """Per-row query ids are accepted in place of group sizes."""
    X, labels, group = _make_ltr(n_queries=20, seed=5)
    qid = np.repeat(np.arange(20), 25)
    b1 = lgb.train({"objective": "lambdarank", "verbose": -1,
                    "min_data_in_leaf": 5},
                   lgb.Dataset(X, label=labels, group=group), num_boost_round=5)
    b2 = lgb.train({"objective": "lambdarank", "verbose": -1,
                    "min_data_in_leaf": 5},
                   lgb.Dataset(X, label=labels, group=qid), num_boost_round=5)
    np.testing.assert_allclose(b1.predict(X), b2.predict(X), rtol=1e-5)


def test_position_bias():
    X, labels, group = _make_ltr(seed=6)
    pos = np.tile(np.arange(25), 60)
    booster = lgb.train({"objective": "lambdarank", "verbose": -1,
                         "min_data_in_leaf": 5},
                        lgb.Dataset(X, label=labels, group=group, position=pos),
                        num_boost_round=10)
    obj = booster._booster.objective
    assert obj.pos_biases is not None
    assert obj.pos_biases.shape == (25,)
    # biases moved away from zero
    assert float(np.abs(np.asarray(obj.pos_biases)).sum()) > 0


def test_precision_metric():
    X, labels, group = _make_ltr(seed=7)
    ds = lgb.Dataset(X, label=labels, group=group)
    vs = ds.create_valid(X, label=labels, group=group)
    res = {}
    lgb.train({"objective": "lambdarank", "metric": "precision",
               "eval_at": [3, 5], "verbose": -1, "min_data_in_leaf": 5},
              ds, num_boost_round=10, valid_sets=[vs],
              callbacks=[lgb.record_evaluation(res)])
    assert "precision@3" in res["valid_0"]
    assert 0 <= res["valid_0"]["precision@3"][-1] <= 1


def test_map_metric():
    X, labels, group = _make_ltr(seed=8)
    ds = lgb.Dataset(X, label=(labels > 0).astype(float), group=group)
    vs = ds.create_valid(X, label=(labels > 0).astype(float), group=group)
    res = {}
    lgb.train({"objective": "lambdarank", "metric": "map", "eval_at": [5],
               "verbose": -1, "min_data_in_leaf": 5},
              ds, num_boost_round=10, valid_sets=[vs],
              callbacks=[lgb.record_evaluation(res)])
    assert "map@5" in res["valid_0"]


@pytest.mark.parametrize("target", ["ndcg", "ranknet", "lambdagap-x",
                                    "arpk", "lambdaloss-ndcg-plus-plus"])
def test_tiled_pair_lattice_matches_dense(target):
    """The row-tiled long-query kernel computes EXACTLY the dense lattice's
    math (same pair windows, same normalization) — block sweeps only bound
    memory (reference handles arbitrary query lengths the same way,
    rank_objective.hpp:253-524)."""
    import jax.numpy as jnp
    from lambdagap_tpu.objectives.rank import _lambdarank_bucket
    rng = np.random.RandomState(7)
    nq, L = 3, 256
    scores = jnp.asarray(rng.randn(nq, L).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 4, (nq, L)).astype(np.float32))
    valid = jnp.asarray(np.arange(L)[None, :] < np.asarray([256, 200, 37])[:, None])
    inv_dcg = jnp.asarray(rng.rand(nq).astype(np.float32))
    inv_bdcg = jnp.asarray(rng.rand(nq).astype(np.float32))
    gains = jnp.asarray((2.0 ** np.arange(4) - 1).astype(np.float32))
    kw = dict(target=target, sigmoid=1.0, norm=True, truncation_level=20,
              lambdagap_weight=0.5)
    lam_d, hes_d, eff_d = _lambdarank_bucket(scores, labels, valid, inv_dcg,
                                             inv_bdcg, gains, tile=None, **kw)
    lam_t, hes_t, eff_t = _lambdarank_bucket(scores, labels, valid, inv_dcg,
                                             inv_bdcg, gains, tile=64, **kw)
    np.testing.assert_allclose(np.asarray(lam_d), np.asarray(lam_t),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(hes_d), np.asarray(hes_t),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(eff_d), np.asarray(eff_t),
                               rtol=1e-5)


def test_long_query_trains_without_truncation():
    """A query longer than any dense-lattice bound trains exactly: every
    doc can receive gradient mass (the pre-round-5 16,384-doc truncation is
    gone; click-log datasets routinely exceed it)."""
    rng = np.random.RandomState(3)
    n = 20000                      # ONE query, past the old 1<<14 cap
    X = rng.randn(n, 6)
    util = X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.randn(n)
    ranks = np.argsort(np.argsort(-util))
    y = np.zeros(n)
    y[ranks < 50] = 2
    y[(ranks >= 50) & (ranks < 500)] = 1
    b = lgb.train({"objective": "lambdarank", "num_leaves": 15,
                   "lambdarank_truncation_level": 30, "verbose": -1,
                   "min_data_in_leaf": 20},
                  lgb.Dataset(X, label=y, group=[n]), num_boost_round=5)
    from lambdagap_tpu.objectives.rank import _QueryBuckets
    bk = _QueryBuckets(np.asarray([0, n]), n)
    assert bk.buckets[0][0] == 32768    # padded, not capped
    s = b.predict(X, raw_score=True)
    # the learned order must separate relevant docs (gradient mass reached
    # the whole query, not just a truncated prefix)
    top = np.argsort(-s)[:50]
    assert y[top].mean() > 0.5


def _argsort_and_gather(scores, labels, valid):
    """A bucket into score order as the kernel used to put it: an argsort a
    query and three gathers of ``[nq, L]`` by its indices."""
    import jax
    import jax.numpy as jnp
    from lambdagap_tpu.objectives.rank import K_MIN_SCORE

    def sort_query(s, l, v):
        neg = jnp.where(v, s, K_MIN_SCORE)
        order = jnp.argsort(-neg)
        ss = neg[order]
        vs = v[order]
        nv = jnp.sum(vs)
        return (order, ss, l[order].astype(jnp.float32), vs, nv, ss[0],
                ss[jnp.maximum(nv - 1, 0)])
    return jax.vmap(sort_query)(scores, labels, valid)


def _gather_back(order, lam_sorted, hes_sorted):
    """Its way back: the inverse permutation by a second argsort, and two
    more gathers."""
    import jax
    import jax.numpy as jnp

    def unsort_query(order, lam, hes):
        inv = jnp.argsort(order)
        return lam[inv], hes[inv]
    return jax.vmap(unsort_query)(order, lam_sorted, hes_sorted)


def _tied_bucket(kind, L=128):
    """A ``[nq, L]`` bucket (scores, labels, valid, inv_dcg, inv_bdcg,
    label_gain) with tied scores, ``-0.0`` beside ``+0.0``, invalid pads
    with stray scores; ``queries`` also holds a query of no valid document,
    one of one, one with holes and one whose valid scores all tie."""
    rng = np.random.RandomState(11)
    nq = 1 if kind == "one-query" else 6
    scores = (rng.randint(-3, 4, (nq, L)) * 0.5).astype(np.float32)
    zero = scores == 0
    scores[zero] = np.where(rng.rand(int(zero.sum())) < 0.5, -0.0, 0.0)
    labels = rng.randint(0, 5, (nq, L)).astype(np.float32)
    lengths = [L - 5] if nq == 1 else [L, 100, 0, 1, 77, 50]
    valid = np.arange(L)[None, :] < np.asarray(lengths)[:, None]
    scores[:, 3] = -7.0          # a worst valid score with no tie
    if nq > 1:
        valid[4, rng.rand(L) < 0.3] = False
        scores[5] = 0.25
    return (scores, labels, valid, rng.rand(nq).astype(np.float32),
            rng.rand(nq).astype(np.float32),
            (2.0 ** np.arange(5) - 1).astype(np.float32))


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("bucket", ["queries", "one-query"])
def test_score_order_is_argsort_and_gather_to_the_bit(bucket):
    """The forward sort's seven outputs (the permutation, the score-ordered
    scores, labels and validity, the valid count, the best and the worst
    valid score) are the argsort-and-gather ones, bit for bit: the sort is
    stable on (-score, position), as ``jnp.argsort`` is."""
    from lambdagap_tpu.objectives.rank import _score_order
    args = _tied_bucket(bucket)[:3]
    got, want = _score_order(*args), _argsort_and_gather(*args)
    assert len(got) == len(want) == 7
    for g, w in zip(got, want):
        assert np.array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("bucket", ["queries", "one-query"])
@pytest.mark.parametrize("form", ["dense", "tiled"])
@pytest.mark.parametrize("target", ["ndcg", "lambdagap-s",
                                    "lambdagap-x-plus-plus", "precision",
                                    "lambdaloss-arp2"])
def test_sorting_the_data_with_its_key_keeps_every_lambda_to_the_bit(
        target, form, bucket, monkeypatch):
    """``_lambdarank_bucket``'s lambdas, hessians and effective pair rates
    equal, bit for bit, those of the same kernel with its two sorts swapped
    back for argsort-and-gather: only the op that moves the data changed."""
    import types
    import jax
    from lambdagap_tpu.objectives import rank
    args = _tied_bucket(bucket)
    kw = dict(target=target, sigmoid=1.0, norm=True, truncation_level=20,
              lambdagap_weight=0.5, tile=None if form == "dense" else 32)
    got = rank._lambdarank_bucket(*args, **kw)
    monkeypatch.setattr(rank, "_score_order", _argsort_and_gather)
    monkeypatch.setattr(rank, "_document_order", _gather_back)
    # a new function object: jit keeps a function's traces by its identity,
    # and the kernel's own trace would read the swapped stages from none
    kernel = rank._lambdarank_bucket.__wrapped__
    old = types.FunctionType(kernel.__code__, kernel.__globals__)
    old.__kwdefaults__ = kernel.__kwdefaults__
    want = jax.jit(old, static_argnames=tuple(kw))(*args, **kw)
    assert np.any(np.asarray(got[0]) != 0)
    for g, w in zip(got, want):
        assert np.array_equal(_bits(g), _bits(w))
