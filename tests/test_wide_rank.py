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


# -- (b2) the gain of a label, looked up once a document (PR 34) ----------
USER_GAIN = [0.0, 0.7, 1.9, 3.3, 1e-3, 12345.678, 2.0 ** -20, 3e7]
TABLES = {"default": np.asarray(
    lgb.config.Config.from_params({}).label_gain_or_default(4), np.float32),
    "user": np.asarray(USER_GAIN, np.float32)}


@pytest.mark.parametrize("table", sorted(TABLES))
def test_gain_gap_is_the_per_cell_lookup_to_the_bit(table):
    """Every ordered pair of labels: the pair block's ``gain_gap``, oriented
    from the two DOCUMENTS' gains, is the float32 ``label_gain[high] -
    label_gain[low]`` that a lookup per pair cell gave, bit for bit."""
    from lambdagap_tpu.objectives.rank import _gain_gap
    gain = TABLES[table]
    assert len(gain) == {"default": 32, "user": 8}[table]
    lab = np.arange(len(gain), dtype=np.float32)
    li, lj = lab[:, None], lab[None, :]
    hi_is_i = li > lj
    # per cell, as the lattice did before: orient the LABELS, then look up
    hl = np.where(hi_is_i, li, lj).astype(np.int32)
    ll = np.where(hi_is_i, lj, li).astype(np.int32)
    want = gain[hl] - gain[ll]
    assert want.dtype == np.float32
    # per document: look up, then orient the GAINS
    gs = jnp.asarray(gain)[jnp.asarray(lab).astype(jnp.int32)]
    got = np.asarray(_gain_gap(jnp.asarray(hi_is_i), gs[:, None],
                               gs[None, :]))
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    # the tables' entries are distinct: only the diagonal reads zero
    assert np.count_nonzero(got) == len(gain) * (len(gain) - 1)


def _discount32(rank):
    return np.float32(1.0) / np.log2(np.float32(2.0) + rank.astype(np.float32))


def _per_cell_lattice32(s, l, v, gain, imd, target, weight, tl=30):
    """One query's lambdas and hessians by the per-cell form, numpy float32
    throughout: sort by score (stable), orient each pair on its LABELS and
    look ``gain[hl] - gain[ll]`` up per cell; sigmoid 1, norm on."""
    f32 = np.float32
    neg = np.where(v, s, f32(-1e30)).astype(f32)
    order = np.argsort(-neg, kind="stable")
    ss, ls, vs = neg[order], l[order].astype(f32), v[order]
    nv = int(vs.sum())
    best, worst = ss[0], ss[max(nv - 1, 0)]
    L = len(s)
    i, j = np.arange(L)[:, None], np.arange(L)[None, :]
    li, lj, si, sj = ls[:, None], ls[None, :], ss[:, None], ss[None, :]
    ok = vs[:, None] & vs[None, :] & (i < j) & (li != lj) & (i < tl)
    hi_is_i = li > lj
    delta_score = (np.where(hi_is_i, si, sj) - np.where(hi_is_i, sj, si)
                   ).astype(f32)
    hl = np.where(hi_is_i, li, lj).astype(np.int32)
    ll = np.where(hi_is_i, lj, li).astype(np.int32)
    gain_gap = gain[hl] - gain[ll]                       # per pair cell
    rank_diff = np.maximum(j - i, 1).astype(f32)         # i >= j: masked
    lambdarank = np.abs(_discount32(np.where(hi_is_i, i, j))
                        - _discount32(np.where(hi_is_i, j, i))).astype(f32)
    lambdaloss = (_discount32(rank_diff)
                  - _discount32(rank_diff + f32(1.0))).astype(f32)
    weigh = {"ndcg": lambdarank, "lambdaloss-ndcg": lambdaloss,
             "lambdaloss-ndcg-plus-plus":
                 (lambdarank + f32(weight) * lambdaloss).astype(f32)}[target]
    delta = (gain_gap * weigh * f32(imd)).astype(f32)
    ok &= delta != 0
    if best != worst:
        delta = (delta / (f32(0.01) + np.abs(delta_score))).astype(f32)
    with np.errstate(over="ignore"):
        p = (f32(1.0) / (f32(1.0) + np.exp(delta_score))).astype(f32)
    p_lambda = np.where(ok, -delta * p, f32(0.0)).astype(f32)
    p_hess = np.where(ok, delta * p * (f32(1.0) - p), f32(0.0)).astype(f32)
    row = np.where(hi_is_i, p_lambda, -p_lambda)
    # the sums in float64: what is held to the bit is gain_gap (above); here
    # the lattice's float32 sums are held to a few ulps of the largest
    lam = row.sum(axis=1, dtype=np.float64) - row.sum(axis=0,
                                                       dtype=np.float64)
    hes = p_hess.sum(axis=1, dtype=np.float64) \
        + p_hess.sum(axis=0, dtype=np.float64)
    sum_lambdas = -2.0 * p_lambda.sum(dtype=np.float64)
    if sum_lambdas > 0:
        factor = np.log2(1.0 + sum_lambdas) / max(sum_lambdas, 1e-15)
        lam, hes = lam * factor, hes * factor
    inv = np.argsort(order, kind="stable")
    return lam[inv], hes[inv]


@pytest.mark.parametrize("table", sorted(TABLES))
@pytest.mark.parametrize("target", ["ndcg", "lambdaloss-ndcg",
                                    "lambdaloss-ndcg-plus-plus"])
@pytest.mark.parametrize("L", [8, 128, 1024])
def test_lattice_matches_the_per_cell_form_in_float32(L, target, table):
    """Seeded buckets of padded length 8, 128 (queries of 100) and 1,024:
    ties in score, padded tails, a query of one label. The bucket kernel
    against the per-cell lookup written out in numpy float32 above."""
    from lambdagap_tpu.objectives.rank import _lambdarank_bucket
    gain = TABLES[table]
    rng = np.random.default_rng(L)
    nq = 4
    lens = {8: [8, 5, 3, 1], 128: [100, 100, 128, 65],
            1024: [1024, 1000, 700, 513]}[L]
    top = 5 if table == "default" else len(gain)
    s = rng.normal(size=(nq, L)).astype(np.float32)
    l = rng.integers(0, top, size=(nq, L)).astype(np.float32)
    s[1] = np.round(s[1] * 2) / 2                        # ties in score
    l[3, :] = 2.0                                        # one label: no pairs
    v = np.arange(L)[None, :] < np.asarray(lens)[:, None]
    imd = rng.uniform(0.05, 1.0, size=nq).astype(np.float32)
    lam, hes, _ = _lambdarank_bucket(
        jnp.asarray(s), jnp.asarray(l), jnp.asarray(v), jnp.asarray(imd),
        jnp.asarray(imd), jnp.asarray(gain), target=target, sigmoid=1.0,
        norm=True, truncation_level=30, lambdagap_weight=0.5, tile=None)
    lam, hes = np.asarray(lam, np.float64), np.asarray(hes, np.float64)
    for q in range(nq):
        want_l, want_h = _per_cell_lattice32(s[q], l[q], v[q], gain, imd[q],
                                             target, 0.5)
        for got, want in ((lam[q], want_l), (hes[q], want_h)):
            # float32 against float32: each pair term within a few ulps
            # (exp and log2 of two libraries), a document's sum of up to
            # 1,024 of them in float32 against float64; the 36 cases read
            # 3.9 ulps of the largest at most, the limit is 16
            assert np.max(np.abs(got - want)) \
                <= 2.0 ** -19 * np.max(np.abs(want)) + 1e-30, (q, target)
        assert not lam[q][~v[q]].any() and not hes[q][~v[q]].any()
    assert not lam[3].any() and np.abs(lam[:3]).max() > 1e-3


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
