"""``work/hist.py`` on a hand-counted three-leaf tree."""
import numpy as np

from benchmark.work import hist


def three_leaf_tree():
    # 10 rows; node 0 splits into leaf 0 (4 rows) and node 1 (6 rows);
    # node 1 splits into leaf 1 (1 row) and leaf 2 (5 rows)
    return {"num_leaves": 3,
            "left_child": np.array([-1, -2]), "right_child": np.array([1, -3]),
            "leaf_count": np.array([4, 1, 5])}


def test_row_visits_are_root_plus_smaller_children():
    t = three_leaf_tree()
    assert list(hist.node_counts(t)) == [10, 6]
    assert hist.row_visits(t) == 10 + 4 + 1


def test_bytes_and_ops_by_hand():
    w = hist.work([three_leaf_tree()], num_features=28, max_bin=255)
    assert w["row_visits"] == 15
    # 15 visits x (28 one-byte bins + 8 B of gradient and hessian)
    # + 3 histograms built x 28 x 256 x 3 x 4 B
    assert w["bytes"] == 15 * 36 + 3 * 28 * 256 * 12
    assert w["ops"] == 3 * 15 * 28


def test_a_stump_visits_its_rows_once():
    stump = {"num_leaves": 1, "left_child": np.zeros(0, int),
             "right_child": np.zeros(0, int), "leaf_count": np.array([7])}
    assert hist.row_visits(stump) == 7
