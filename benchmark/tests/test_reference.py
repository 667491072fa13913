"""The plain reference's own pieces on hand-made inputs."""
import numpy as np
import pytest

from benchmark.reference import gbdt_check as G


def stump(threshold):
    return {"num_leaves": 2, "split_feature": np.array([0]),
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
