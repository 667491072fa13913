"""The watched folds' plain reference on hand-made inputs, the new cell's
rehearsal, and ``correct`` seen to come out false, each time by the number
meant for it: a validation score left one tree stale, the two folds swapped,
an evaluation skipped, a best iteration off by one. The faults are planted
through the driver's hooks (``update``, ``evaluate``, ``early_stopping``)."""
import argparse

import numpy as np
import pytest

from benchmark import run
from benchmark.reference import valid_check

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "istella-s-valid-train"
NEW = ("valid_score", "valid_metric_last", "valid_metric",
       "valid_evals_missing", "early_stop_mismatch")


# -- the reference's own pieces ------------------------------------------
def test_ndcg_by_hand():
    # query 0: two documents tie on top, the EARLIER one (label 0) ranks
    # first; query 1 is shorter than k; query 2 has no relevant document
    y = np.array([0, 2, 1, 3, 1, 0, 0])
    s = np.array([0.5, 0.5, 0.1, 0.9, 0.2, 0.3, 0.1])
    group = [3, 2, 2]
    d = 1.0 / np.log2(2.0 + np.arange(3))
    q0 = (0 * d[0] + 3 * d[1] + 1 * d[2]) / (3 * d[0] + 1 * d[1])
    q1 = 1.0                                  # already in the best order
    assert valid_check.ndcg_at_k(y, s, group, 10) == pytest.approx(
        (q0 + q1 + 1.0) / 3, abs=1e-15)
    # at k = 1 the tie decides alone: the earlier document's gain is 0
    assert valid_check.ndcg_at_k(y, s, group, 1) == pytest.approx(
        (0.0 + 1.0 + 1.0) / 3, abs=1e-15)
    # the later document first would read 1: the tie rule is the number
    assert valid_check.ndcg_at_k(y[[1, 0, 2]], s[[1, 0, 2]], [3], 1) == 1.0
    # a custom gain, not monotone in the label
    assert valid_check.ndcg_at_k([0, 1, 2], [3.0, 2.0, 1.0], [3], 2,
                                 label_gain=[5.0, 1.0, 2.0]) \
        == pytest.approx((5 * d[0] + 1 * d[1]) / (2 * d[0] + 1 * d[1]))


def test_the_early_stopping_rule():
    rule = valid_check.best_so_far
    assert rule([0.1, 0.3, 0.3, 0.2], 50) == {
        "best_iter": 1, "best": 0.3, "stopped_at": -1}
    assert rule([0.1, 0.3, 0.3, 0.2, 0.25], 2) == {
        "best_iter": 1, "best": 0.3, "stopped_at": 3}


MODEL = """tree
Tree=0
num_leaves=3
split_feature=0 1
threshold=0.5 -1
left_child=1 -1
right_child=-3 -2
leaf_value=0.1 0.2 -0.3
leaf_weight=1 1 1
leaf_count=1 1 1
shrinkage=1

Tree=1
num_leaves=2
split_feature=1
threshold=0
left_child=-1
right_child=-2
leaf_value=-0.25 0.25
leaf_weight=1 1
leaf_count=1 1
shrinkage=1

end of trees
"""


def _hand_made():
    X = np.array([[0.0, -2.0], [0.0, 3.0], [1.0, 0.0], [0.2, -1.0],
                  [0.9, 5.0]], np.float32)
    fold = {"name": "valid", "X": X, "y": np.array([0, 2, 1, 0, 3]),
            "group": np.array([3, 2])}
    one = np.array([0.1, 0.2, -0.3, 0.1, -0.3])
    two = one + np.array([-0.25, 0.25, -0.25, -0.25, 0.25])
    evals = [{"trees": t, "values": {"valid": valid_check.ndcg_at_k(
        fold["y"], s, fold["group"], 10)}} for t, s in ((1, one), (2, two))]
    early = {"patience": 50, "best_iter": 1,
             "best_score": evals[1]["values"]["valid"], "stopped_at": -1}
    return fold, two, evals, early


def test_check_on_hand_made_trees():
    fold, scores, evals, early = _hand_made()
    sound = valid_check.check(MODEL, [fold], evals, {"valid": scores}, 2,
                              early, metric_tol=1e-9)
    assert all(sound[name] < 1e-12 for name in NEW), sound
    # a score left one tree stale
    stale = valid_check.check(MODEL, [fold], evals,
                              {"valid": scores - [0, 0, 0, 0, 0.25]}, 2,
                              early, metric_tol=1e-9)
    assert stale["valid_score"] > 0.1
    # an evaluation that saw one tree fewer than it says
    late = [dict(evals[1], trees=2, values=evals[0]["values"])]
    assert valid_check.check(MODEL, [fold], [evals[0]] + late,
                             {"valid": scores}, 2, early,
                             metric_tol=1e-9)["valid_metric"] > 1e-3
    assert valid_check.check(MODEL, [fold], evals[:1], {"valid": scores}, 2,
                             dict(early, best_iter=0,
                                  best_score=evals[0]["values"]["valid"]),
                             metric_tol=1e-9)["valid_evals_missing"] == 1
    assert valid_check.check(MODEL, [fold], evals, {"valid": scores}, 2,
                             dict(early, best_iter=0),
                             metric_tol=1e-9)["early_stop_mismatch"] == 1


# -- the cell, rehearsed -------------------------------------------------
def drive(hooks=None, seed=21, seconds=4.0):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=0, rehearse_cpu=True, control="")
    return run.run_cell(args, DEVICE, hooks)


def reading(result, name):
    return result["compared"][name][0]


def failed(result):
    return {name for name, (value, limit) in result["compared"].items()
            if limit is not None and not value <= limit}


@pytest.fixture(scope="module")
def sound():
    return drive()


def test_the_rehearsal_is_correct_and_judges_the_folds(sound):
    assert sound["correct"] is True and sound["attempted"] >= 2
    for name in NEW + ("leaf_rows", "leaf_value_median", "train_score"):
        value, limit = sound["compared"][name]
        assert limit is not None and value <= limit, name
    assert reading(sound, "valid_score") < 1e-6
    assert reading(sound, "valid_metric") < 1e-6
    assert 0.0 < reading(sound, "ndcg_ref_valid") < 1.0


def test_a_validation_score_left_one_tree_stale_is_not_correct(sound):
    calls = {"n": 0}

    def update(bst):
        gb = bst._booster
        before = gb.valid_scores[0]
        bst.update()
        calls["n"] += 1
        if calls["n"] == 3:                  # the window's first tree
            gb.valid_scores[0] = before
    res = drive({"update": update})
    assert res["correct"] is False
    assert {"valid_score", "valid_metric"} <= failed(res) \
        <= {"valid_score", "valid_metric", "early_stop_mismatch"}
    assert reading(res, "valid_score") > 100 * reading(sound, "valid_score")


def test_the_two_folds_swapped_is_not_correct():
    def evaluate(bst):
        got = bst._booster.eval_valid()
        swap = {"valid": "test", "test": "valid"}
        return [(swap[d], m, v, g) for d, m, v, g in got]
    res = drive({"evaluate": evaluate})
    assert res["correct"] is False
    assert {"valid_metric", "valid_metric_last"} <= failed(res)
    assert "valid_score" not in failed(res)


def test_an_evaluation_skipped_is_not_correct():
    calls = {"n": 0}

    def evaluate(bst):
        calls["n"] += 1
        return [] if calls["n"] == 2 else bst._booster.eval_valid()
    res = drive({"evaluate": evaluate})
    assert res["correct"] is False
    assert reading(res, "valid_evals_missing") == 1
    assert failed(res) == {"valid_evals_missing"}


def test_a_best_iteration_off_by_one_is_not_correct():
    def early_stopping(rounds, verbose=False):
        import lambdagap_tpu as lgb
        inner = lgb.early_stopping(rounds, verbose=verbose)
        told = {"best_iter": {}}

        def callback(env):
            inner(env)
            told["best_score"] = inner.state["best_score"]
            told["best_iter"] = {
                key: at - 1 if at > 0 else at + 1
                for key, at in inner.state["best_iter"].items()}
        callback.state = told
        return callback
    res = drive({"early_stopping": early_stopping})
    assert res["correct"] is False
    assert failed(res) == {"early_stop_mismatch"}
