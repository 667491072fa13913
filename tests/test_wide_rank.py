"""The wide ranking deployment (Istella-S: 220 features, lambdarank) against
the benchmark's plain reference at small sizes: the feature-tiled histogram
kernel on both sides of ``_pick_blocks``' switch, the lambdarank gradients on
every bucket length of the deployment, one end-to-end ranking fit at 220
features through the fused learner, and the ``rank_*`` work counts.
``benchmark.reference.gbdt_check`` is float64 numpy and imports nothing of
the program. Counts and values only: nothing here reads a time."""
import jax.numpy as jnp
import numpy as np
import pytest

import lambdagap_tpu as lgb
from benchmark import manifest
from benchmark.reference import gbdt_check
from lambdagap_tpu.ops import hist_pallas as hp


# -- (a) the histogram kernel past one feature tile ----------------------
B = 256
ROWS, COUNT = 1280, 1100     # two row blocks of 1,024; count inside the 2nd


def _hist64(bins, g, h, count):
    """[F, B, 3] float64 histogram of the first ``count`` rows."""
    F = bins.shape[1]
    out = np.zeros((F, B, 3))
    for f in range(F):
        b = bins[:count, f].astype(np.int64)
        out[f, :, 0] = np.bincount(b, weights=g[:count], minlength=B)
        out[f, :, 1] = np.bincount(b, weights=h[:count], minlength=B)
        out[f, :, 2] = np.bincount(b, minlength=B)
    return out


@pytest.mark.parametrize("F", [192, 193, 220, 256, 257])
def test_tiled_histogram_kernel_matches_float64(F):
    """F <= 192 is one whole-width tile; past it the kernel takes 128-column
    tiles over a feature axis padded to the next multiple (220 -> 256: 36 pad
    columns whose histograms are sliced off). Rows past ``count`` hold junk
    bins and junk channels and must not be counted."""
    blk, fblk = hp._pick_blocks(F, B, ROWS)
    assert (blk, fblk) == (1024, F if F <= 192 else 128)
    rng = np.random.default_rng(F)
    bins = rng.integers(0, B, size=(ROWS, F), dtype=np.uint8)
    g = rng.normal(size=ROWS).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=ROWS).astype(np.float32)
    g[COUNT:], h[COUNT:] = 1e6, 1e6                  # junk past the count
    want = _hist64(bins, g.astype(np.float64), h.astype(np.float64), COUNT)
    valid = jnp.ones(ROWS, bool)

    got = np.asarray(hp.hist_pallas(
        jnp.asarray(bins), hp.pack_gh8(jnp.asarray(g), jnp.asarray(h), valid),
        B, count=jnp.int32(COUNT)), np.float64)
    assert got.shape == (F, B, 3)
    np.testing.assert_array_equal(got[..., 2], want[..., 2])
    # split precision: each value is hi + lo of two bf16 (16 mantissa bits,
    # 2^-17 relative a row), summed in float32 over ~4 rows a bin
    absum = _hist64(bins, np.abs(g).astype(np.float64),
                    np.abs(h).astype(np.float64), COUNT)
    for c in (0, 1):
        assert np.all(np.abs(got[..., c] - want[..., c])
                      <= 2.0 ** -15 * absum[..., c] + 1e-30)

    gq = rng.integers(-127, 128, size=ROWS).astype(np.int8)
    hq = rng.integers(0, 128, size=ROWS).astype(np.int8)
    gq[COUNT:], hq[COUNT:] = 127, 127
    want_q = _hist64(bins, gq.astype(np.float64), hq.astype(np.float64),
                     COUNT).astype(np.int64)
    got_q = np.asarray(hp.hist_pallas_q(
        jnp.asarray(bins),
        hp.pack_ghq8(jnp.asarray(gq), jnp.asarray(hq), valid),
        B, count=jnp.int32(COUNT)))
    assert got_q.dtype == np.int32 and got_q.shape == (F, B, 3)
    np.testing.assert_array_equal(got_q, want_q)          # int32: exact


# -- (b) lambdarank gradients on the deployment's bucket set -------------
OBJ = {"target": "ndcg", "truncation_level": 30, "sigmoid": 1.0, "norm": True}


def _query_set():
    """Lengths that pad to every bucket from 8 to 2,048 (the deployment's
    nine), one of them exactly the truncation level; then by hand: ties in
    score, one query of a single label, one of a single document."""
    rng = np.random.default_rng(28)
    sizes = [5, 8, 13, 16, 30, 31, 64, 100, 128, 200, 400, 512, 1000, 1077]
    n = sum(sizes)
    y = rng.choice(5, size=n, p=[0.6, 0.2, 0.1, 0.06, 0.04]).astype(
        np.float32)
    s = rng.normal(size=n).astype(np.float32)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    s[starts[3]:starts[4]] = np.round(s[starts[3]:starts[4]])   # ties
    s[starts[6]:starts[7]] = 0.25               # every score equal
    y[starts[2]:starts[3]] = 2.0                # one label only: no pairs
    sizes.append(1)
    return (np.append(s, np.float32(0.5)), np.append(y, np.float32(3.0)),
            np.asarray(sizes))


def test_lambdarank_gradients_match_the_plain_reference():
    s, y, sizes = _query_set()
    X = np.random.default_rng(0).normal(size=(len(s), 2)).astype(np.float32)
    params = {"objective": "lambdarank", "verbose": -1,
              "lambdarank_truncation_level": OBJ["truncation_level"]}
    ds = lgb.Dataset(X, label=y, group=sizes, params=params)
    gb = lgb.Booster(params, ds)._booster
    obj = gb.objective
    assert [L for L, _, _ in obj.bucketing.buckets] == [
        8, 16, 32, 64, 128, 256, 512, 1024, 2048]
    assert (obj.target, obj.sigmoid, obj.norm) == ("ndcg", 1.0, True)
    g, h = obj.get_gradients(jnp.asarray(s)[None, :])
    g, h = np.asarray(g[0], np.float64), np.asarray(h[0], np.float64)
    g64, h64 = gbdt_check.lambdarank_grad(s.astype(np.float64),
                                          y.astype(np.float64), sizes, OBJ)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    # a float32 lattice against float64: a document's lambda is a sum of up
    # to ~1,000 pair terms, each off by a few float32 roundings (the
    # discount's log2, the sigmoid's exp, the division by 0.01 + |score
    # gap|), and terms of both signs cancel; so the error is held against
    # the QUERY's largest lambda, at 2^-17 (64 float32 ulps)
    for q in range(len(sizes)):
        lo, hi = starts[q], starts[q + 1]
        for got, ref in ((g[lo:hi], g64[lo:hi]), (h[lo:hi], h64[lo:hi])):
            scale = np.max(np.abs(ref))
            assert np.max(np.abs(got - ref)) <= 2.0 ** -17 * scale + 1e-30, q
    # no pairs: a single label, a single document
    for q in (2, len(sizes) - 1):
        assert not g64[starts[q]:starts[q + 1]].any()
        assert not g[starts[q]:starts[q + 1]].any()
    assert np.abs(g64).max() > 0.1 and h64.min() >= 0.0


# -- (c) a 220-feature ranking fit through the fused learner --------------
def test_wide_ranking_fit_follows_the_plain_reference():
    """The cell's ``correct``, small: 220 features (two kernel tiles, a
    57-word sorted payload), Pallas interpreted, sorted layout; every tree
    followed teacher-forced by the float64 reference, held to the cell's
    own limits."""
    cfg = manifest.load_json("configs", "istella-s.json")
    limits = manifest.load_json("limits", "istella-s-train.json")["limits"]
    from benchmark.datagen import mslr_like
    small = dict(cfg, num_queries=170, num_docs=20000, num_rows=20000)
    data = mslr_like.generate(small, 7, 20000, 4)
    assert data["X"].shape == (20000, 220)
    params = dict(cfg["params"], num_leaves=15, min_sum_hessian_in_leaf=1e-3,
                  tpu_fused_learner=1, tpu_hist_impl="pallas",
                  tree_layout="sorted")
    ds = lgb.Dataset(data["X"], label=data["y"], group=data["group"],
                     params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(3):
        bst.update()
    gb = bst._booster
    learner = gb.learner
    assert {"class": type(learner).__name__, "hist_impl": learner.hist_impl,
            "layout": learner.layout,
            "residency": learner.residency} == cfg["learner"]
    numbers = gbdt_check.check(
        bst.model_to_string(), data,
        dict(params, objective_params=cfg["objective_params"]),
        np.asarray(gb.scores), 3, seed=7)
    assert numbers["leaf_rows"] == 0 and numbers["trees_missing"] == 0
    for name in ("leaf_value", "leaf_value_median", "leaf_hess",
                 "leaf_hess_median", "split_gap", "train_score"):
        assert numbers[name] <= limits[name], (name, numbers[name])


# -- (e) the rank_* work counts ------------------------------------------
def _counts(objective, group, n):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(n, 3)).astype(np.float32)
    y = rng.integers(0, 2, n).astype(np.float32)
    params = {"objective": objective, "num_leaves": 4, "verbose": -1,
              "min_data_in_leaf": 1, "telemetry": True}
    bst = lgb.train(params, lgb.Dataset(X, label=y, group=group),
                    num_boost_round=2)
    tel = bst._booster.telemetry
    return [r.get("counts", {}) for r in tel.records], tel.summary()


def test_rank_counts_equal_a_hand_count_and_only_ranking_writes_them():
    # three queries of 5, 9 and 40 documents pad to 8, 16 and 64
    records, summary = _counts("lambdarank", [5, 9, 40], 54)
    want = {"rank_docs": 54, "rank_pad_docs": 8 + 16 + 64,
            "rank_pair_cells": 8 * 8 + 16 * 16 + 64 * 64}
    assert len(records) == 2
    for counts in records:
        assert {k: counts[k] for k in want} == want
    assert summary["counts_total"]["rank_pair_cells"] == 2 * 4416
    # no lattice in the cross-entropy surrogate: its cells count 0
    records, _ = _counts("rank_xendcg", [5, 9, 40], 54)
    assert records[0]["rank_pair_cells"] == 0 \
        and records[0]["rank_pad_docs"] == 88
    records, _ = _counts("binary", None, 54)
    assert len(records) == 2
    assert not any(k.startswith("rank_") for c in records for k in c)


def test_rank_pair_cells_count_the_tiled_sweep():
    """Past 4,096 padded documents the lattice is swept in row blocks, and
    a truncated target stops after the blocks that hold the truncation
    level: the count is what the program evaluates, not L x L."""
    from lambdagap_tpu.objectives.rank import LambdarankNDCG
    L = 8192
    tile = LambdarankNDCG._tile(L)
    assert tile == 2048 and LambdarankNDCG._tile(4096) is None

    class Obj(LambdarankNDCG):
        truncation_level = 30
        target = "ndcg"

        def __init__(self):
            pass
    assert Obj()._pair_cells(L) == tile * L
    Obj.target = "ranknet"
    assert Obj()._pair_cells(L) == L * L
    assert Obj()._pair_cells(2048) == 2048 * 2048
