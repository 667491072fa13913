"""The plain reference's own pieces on hand-made inputs."""
import numpy as np
import pytest

from benchmark import manifest
from benchmark.reference import gbdt_check as G


def stump(threshold, feature=0):
    return {"num_leaves": 2, "split_feature": np.array([feature]),
            "threshold": np.array([threshold]),
            "left_child": np.array([-1]), "right_child": np.array([-2]),
            "leaf_value": np.array([-1.0, 1.0])}


def test_a_row_on_the_threshold_goes_left_and_the_next_float_right():
    # the model text's float64 threshold decides, not its float32 rounding:
    # 0.3 rounds UP to float32, and the row that equals the rounded value
    # lies above the threshold
    up = np.float32(0.3)
    assert float(up) > 0.3
    X = np.array([[np.nextafter(up, np.float32(-1))], [up], [0.0]],
                 np.float32)
    assert list(G.leaf_index(stump(0.3), X)) == [0, 1, 0]
    assert list(G.leaf_index(stump(float(up)), X)) == [0, 0, 0]
    assert list(G.tree_scores([stump(0.3)] * 2, X)) == [-2.0, 2.0, -2.0]
    # whatever the block of rows that goes through at once
    many = np.linspace(-1, 1, 1000, dtype=np.float32)[:, None]
    assert np.array_equal(G.leaf_index(stump(0.3), many, block=7),
                          G.leaf_index(stump(0.3), many))


MODEL = """tree
version=v4

Tree=0
num_leaves=2
split_feature=0
threshold=0.5
left_child=-1
right_child=-2
leaf_value={v0} {v1}
leaf_weight={w0} {w1}
leaf_count=2 2
shrinkage=1

end of trees
"""


def test_check_holds_the_leaves_and_the_training_scores_to_the_reference():
    # one stump on four rows, binary objective at init score 0 (two of four
    # positive): g = 0.5 - y, h = 0.25; left holds y = 0, 0 and right 1, 1
    data = {"X": np.array([[0.0], [0.2], [0.7], [0.9]], np.float32),
            "y": np.array([0, 0, 1, 1], np.float32), "group": None}
    params = {"objective": "binary", "learning_rate": 0.1}
    text = MODEL.format(v0=-0.2, v1=0.2, w0=0.5, w1=0.5)
    scores = np.array([[-0.2, -0.2, 0.2, 0.2]], np.float32)
    got = G.check(text, data, params, scores, 1, seed=3)
    assert got["leaf_rows"] == 0 and got["trees_missing"] == 0
    assert got["leaf_value"] < 1e-12 and got["leaf_hess"] < 1e-12
    assert got["train_score"] < 1e-7
    assert got["train_score_last_step"] == pytest.approx(1.0)
    # scores the last step did not reach read what the last step was worth
    stale = G.check(text, data, params, np.zeros((1, 4), np.float32), 1, 3)
    assert stale["train_score"] == pytest.approx(1.0)
    # a leaf that says something else, a count that is off, a step too few
    off = G.check(MODEL.format(v0=-0.21, v1=0.2, w0=0.5, w1=0.6)
                  .replace("leaf_count=2 2", "leaf_count=3 1"),
                  data, params, scores, 2, seed=3)
    assert off["leaf_value"] == pytest.approx(0.05)
    assert off["leaf_hess"] == pytest.approx(0.2)
    assert off["leaf_rows"] == 2 and off["trees_missing"] == 1
    assert G.check(text, data, params, scores[:, :3], 1, 3)["train_score"] \
        == float("inf")


def test_lambdarank_chunking_does_not_change_the_gradients():
    rng = np.random.default_rng(5)
    sizes = rng.integers(1, 60, 40)
    n = int(sizes.sum())
    y = rng.integers(0, 5, n).astype(np.float64)
    s = rng.standard_normal(n)
    obj = {"truncation_level": 30, "sigmoid": 1.0, "norm": True}
    g1, h1 = G.lambdarank_grad(s, y, sizes, obj, budget=10 ** 7)
    g2, h2 = G.lambdarank_grad(s, y, sizes, obj, budget=2000)
    np.testing.assert_allclose(g1, g2, rtol=0, atol=1e-13)
    np.testing.assert_allclose(h1, h2, rtol=0, atol=1e-13)
    # lambdas of a query sum to nought; hessians are positive
    lo = 0
    for k in sizes:
        assert abs(g1[lo:lo + k].sum()) < 1e-12
        lo += k
    assert (h1 >= 0).all()


def test_judge_fails_a_missing_or_non_finite_number():
    ok, rows = G.judge({"a": 0.5, "b": float("nan")}, {"a": 1, "b": 1, "c": 0})
    assert not ok
    assert [r["ok"] for r in rows] == [True, False, False]
    assert G.judge({"a": 0.5, "x": 9.0}, {"a": 1})[0]


# -- split_gap: a node is judged as far as its gain stands over the noise --
CELL_LIMITS = [manifest.load_json("limits", name)["limits"]["split_gap"]
               for name in ("higgs-train.json", "istella-s-train.json",
                            "mslr-train.json")]
STUMP = """Tree={k}
num_leaves=2
split_feature={f}
threshold={thr!r}
left_child=-1
right_child=-2
leaf_value={v[0]!r} {v[1]!r}
leaf_weight={w[0]!r} {w[1]!r}
leaf_count={c[0]} {c[1]}
shrinkage={lr}

"""


def best_split_of_all_rows(X, g, h, grid=512):
    """The argmax over ALL rows on the reference's own kind of grid: the
    split a sound scan takes, by construction."""
    G, H, best = g.sum(), h.sum(), (0.0, 0, 0.0)
    for f in range(X.shape[1]):
        x = X[:, f].astype(np.float64)
        lo, hi = x.min(), x.max()
        b = np.minimum(((x - lo) * (grid / (hi - lo))).astype(np.int64),
                       grid - 1)
        gl = np.cumsum(np.bincount(b, weights=g, minlength=grid))[:-1]
        hl = np.cumsum(np.bincount(b, weights=h, minlength=grid))[:-1]
        ok = (hl > 0) & (H - hl > 0)
        gain = np.where(ok, gl ** 2 / np.where(ok, hl, 1.0) + (G - gl) ** 2
                        / np.where(ok, H - hl, 1.0) - G * G / H, 0.0)
        c = int(np.argmax(gain))
        if gain[c] > best[0]:
            best = (float(gain[c]), f, float(lo + (c + 1) * (hi - lo) / grid))
    return best


def boosted_stumps(seed, trees, lr, poor=()):
    """Rows whose label turns on one feature, and a sound model of stumps:
    every root is the best split of ALL rows, every leaf -lr G / H, so each
    tree takes a share of what is left and the root's gain shrinks as
    boosting shrinks it. Trees in ``poor`` split the same feature at a
    threshold moved to a poor place (their leaves still -lr G / H)."""
    rng = np.random.default_rng(seed)
    n = 1 << 18
    X = rng.standard_normal((n, 6)).astype(np.float32)
    y = (rng.random(n) < np.where(X[:, 0] > 0.1, 0.8, 0.2)).astype(np.float32)
    init = G.binary_init(y)
    scores = np.full(n, init)
    text, gains = "tree\nversion=v4\n\n", []
    for k in range(trees):
        g, h = G.binary_grad(scores, y.astype(np.float64), None, {})
        gain, f, thr = best_split_of_all_rows(X, g, h)
        if k in poor:
            thr = thr + 1.5
        left = X[:, f].astype(np.float64) <= thr
        sides = (left, ~left)
        w = [float(h[s].sum()) for s in sides]
        v = [float(-lr * g[s].sum() / h[s].sum()) for s in sides]
        for s, step in zip(sides, v):
            scores[s] += step
        text += STUMP.format(k=k, f=f, thr=thr, lr=lr, w=w,
                             v=[x + (init if k == 0 else 0.0) for x in v],
                             c=[int(s.sum()) for s in sides])
        gains.append(gain)
    data = {"X": X, "y": y, "group": None}
    params = {"objective": "binary", "learning_rate": lr}
    return text + "end of trees\n", data, params, scores, gains


def worst_gap(tree, cols, g, h, **kw):
    """The worst reading of a tree's judged nodes and the node it sits at."""
    node, gap, _, _ = max(G._split_nodes(tree, cols, g, h, 0.0, **kw),
                          key=lambda r: r[1])
    return gap, node


def cut(text, k):
    return "\nTree=".join(text.split("\nTree=")[:k + 1]) + "\nend of trees\n"


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_sound_split_stays_sound_while_boosting_shrinks_its_gain(seed):
    # every tree halves the root's gain; a 1/16 sample holds a sixteenth of
    # it, against a chance level that stays: 2 ln(6 x 511) = 16 units of
    # sum g^2 / sum h, about 1 a unit under a logistic loss
    trees, share = 24, 16
    text, data, params, scores, gains = boosted_stumps(seed, trees, lr=0.3)
    n = len(scores)
    assert gains[0] / share > 300 * 16 and gains[-1] / share < 16
    got = G.check(text, data, params, scores, trees, seed,
                  sample_rows=n // share)
    # on the parent's reference the trees from gain 100 down read 0.3 .. 1.0
    assert got["split_gap"] <= 0.5 * min(CELL_LIMITS), got
    assert got["leaf_rows"] == 0 and got["leaf_value"] < 1e-9
    assert got["trees_followed"] == trees


@pytest.mark.parametrize("poor", [0, 29])
def test_a_poor_split_far_over_chance_reads_over_the_limit(poor):
    trees = 30
    sound = boosted_stumps(5, trees, lr=0.02)
    text, data, params, scores, gains = boosted_stumps(5, trees, lr=0.02,
                                                       poor=(poor,))
    n = len(scores)
    assert gains[poor] / 4 > 300 * 16          # far over chance on the sample
    assert G.check(*sound[:4], trees, 5, sample_rows=n // 4)["split_gap"] \
        <= 0.5 * min(CELL_LIMITS)
    got = G.check(text, data, params, scores, trees, 5, sample_rows=n // 4)
    assert got["split_gap"] > 0.5 > 1.5 * max(CELL_LIMITS), got
    assert (got["split_gap_tree"], got["split_gap_node"]) == (poor, 0)
    # the worst of the first K trees never reads over the worst of them all
    readings = [G.check(cut(text, k), data, params, scores, k, 5,
                        sample_rows=n // 4)["split_gap"]
                for k in (1, 10, 29, 30)]
    assert readings == sorted(readings)
    assert (readings[0] > max(CELL_LIMITS)) == (poor == 0)
    assert readings[-1] == got["split_gap"]


def test_a_sample_that_is_the_whole_set_is_judged_by_the_plain_share():
    # no sampling, no noise to credit: (best - got) / best as it stands
    rng = np.random.default_rng(9)
    n = 4096
    cols = rng.standard_normal((3, n)).astype(np.float32)
    g = np.where(cols[0] > 0, 0.4, -0.4) + 0.3 * rng.standard_normal(n)
    h = np.full(n, 0.25)
    tree = stump(0.8)
    rows = np.arange(n)
    best, searched = G._best_gain(cols, rows, g, h, 0.0)
    left = cols[0] <= 0.8
    got = (g[left].sum() ** 2 / h[left].sum() + g[~left].sum() ** 2
           / h[~left].sum() - g.sum() ** 2 / h.sum())
    assert searched == 3 * 511
    gap, node = worst_gap(tree, cols, g, h, share=1.0)
    assert node == 0 and gap == pytest.approx((best - got) / best, rel=1e-9)
    # on a sample of a larger set the same split is credited with the noise
    assert 0 < worst_gap(tree, cols, g, h, share=0.05)[0] < gap


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_a_node_whose_best_gain_is_what_chance_gives_is_not_judged(seed):
    # gradients that no feature explains: the best of 6 x 511 candidates on a
    # 1/16 sample is chance's, the split judged (a median cut) gains nothing
    rng = np.random.default_rng(seed)
    n = 1 << 14
    cols = rng.standard_normal((6, n)).astype(np.float32)
    g = rng.choice([-0.5, 0.5], n) + 0.0
    h = np.full(n, 0.25)
    tree = stump(0.0)
    best, searched = G._best_gain(cols, np.arange(n), g, h, 0.0)
    unit = (g * g).sum() / h.sum()
    assert 0.3 * unit * 2 * np.log(searched) < best \
        < G.SPLIT_NOISE ** 2 * unit * 2 * np.log(searched)
    plain, _ = worst_gap(tree, cols, g, h, share=1.0)
    assert plain > 0.5                    # the parent's reading of this node
    assert worst_gap(tree, cols, g, h, share=1 / 16)[0] == 0.0


def test_the_best_split_keeps_the_minima_it_is_given():
    # one row with a large gradient and next to no hessian at the end of a
    # feature: the free search splits it off, a search under a minimum on a
    # child's hessian or rows may not
    rng = np.random.default_rng(8)
    n = 2048
    cols = rng.standard_normal((2, n)).astype(np.float32)
    cols[0, 0] = 9.0
    g = 0.1 * rng.standard_normal(n)
    h = np.full(n, 0.25)
    g[0], h[0] = 0.9, 0.01
    rows = np.arange(n)
    free, searched = G._best_gain(cols, rows, g, h, 0.0)
    assert free == pytest.approx(g[0] ** 2 / h[0] + (g.sum() - g[0]) ** 2
                                 / (h.sum() - h[0]) - g.sum() ** 2 / h.sum())
    by_hess, fewer = G._best_gain(cols, rows, g, h, 0.0, min_hess=1.0)
    by_rows, _ = G._best_gain(cols, rows, g, h, 0.0, min_rows=2)
    assert max(by_hess, by_rows) < 0.5 * free and fewer < searched
    # and the split judged against it reads the nearer to the best
    tree = stump(0.0, feature=1)
    assert worst_gap(tree, cols, g, h, share=1.0)[0] \
        > worst_gap(tree, cols, g, h, share=1.0, min_hess=1.0)[0]
