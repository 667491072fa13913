"""The histogram kernel's work, from the algorithm and the trees it grew --
the same whatever implements the kernel.

Leaf-wise growth with the subtraction trick has to accumulate, per tree, the
root's rows and then, per split, the rows of the SMALLER child (the larger
child's histogram is parent minus smaller). Each row-visit reads the row's
bin of every feature and its gradient and hessian (two float32), and makes 3
accumulations (gradient, hessian, count) per feature; each histogram built
that way (the root's and one per split) is written once: features x bins x
3 x 4 bytes."""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def node_counts(tree: dict) -> np.ndarray:
    """Rows under every internal node, from the leaves' counts."""
    n_int = tree["num_leaves"] - 1
    counts = np.zeros(n_int, np.int64)

    def rows(child: int) -> int:
        return int(tree["leaf_count"][~child]) if child < 0 \
            else int(counts[child])
    # LightGBM numbers internal nodes in the order they were split, so a
    # child's index is above its parent's: fill from the back
    for nd in range(n_int - 1, -1, -1):
        counts[nd] = rows(int(tree["left_child"][nd])) \
            + rows(int(tree["right_child"][nd]))
    return counts


def row_visits(tree: dict) -> int:
    if tree["num_leaves"] <= 1:
        return int(np.sum(tree["leaf_count"]))
    counts = node_counts(tree)
    visits = int(counts[0])
    for nd in range(tree["num_leaves"] - 1):
        kids = []
        for c in (int(tree["left_child"][nd]), int(tree["right_child"][nd])):
            kids.append(int(tree["leaf_count"][~c]) if c < 0
                        else int(counts[c]))
        visits += min(kids)
    return visits


def work(trees: List[dict], num_features: int, max_bin: int) -> Dict[str, float]:
    """Least bytes moved and accumulations made to build these trees'
    histograms."""
    bin_bytes = 1 if max_bin <= 256 else 2
    bins = max_bin + 1
    visits = sum(row_visits(t) for t in trees)
    built = sum(max(t["num_leaves"], 1) for t in trees)
    return {"row_visits": float(visits),
            "bytes": float(visits * (num_features * bin_bytes + 8)
                           + built * num_features * bins * 3 * 4),
            "ops": float(3 * visits * num_features)}
