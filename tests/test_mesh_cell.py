"""The four-chip cell ``criteo-dp4-train`` at its rehearsal size on a 4-device
CPU mesh, its reference copy (``benchmark/reference/mesh_check.py``), and the
data-parallel program's exact leaf counts past 2^24 rows.

Also re-exports ``benchmark/tests/test_old_against_new.py`` (PERF.md section
7, 21a): the reference's loops held to the parent's, in tier-1."""
import argparse

import numpy as np
import pytest

from benchmark import run
from benchmark.reference import gbdt_check, mesh_check
from benchmark.tests.test_old_against_new import *  # noqa: F401,F403
from benchmark.tests.test_old_against_new import grown, model_text

CELL = "criteo-dp4-train"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 8}


def drive(seed=21, control="", hooks=None):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.5, trace=0,
                              rehearse_cpu=True, control=control)
    return run.run_cell(args, DEVICE, hooks)


def test_the_rehearsal_runs_the_mesh_learner_on_four_devices():
    seen = {}

    def update(bst):
        bst.update()
        learner = bst._booster.learner
        seen["mesh"] = dict(learner.mesh.shape)
        seen["scores"] = bst._booster.scores.sharding.spec
    res = drive(hooks={"update": update})
    assert res["correct"] is True
    assert res["compared"]["learner_mismatch"][0] == 0
    assert res["compared"]["leaf_rows"][0] == 0
    assert seen["mesh"] == {"data": 4, "feature": 1}
    # the boosting state stays row-sharded between trees
    assert tuple(seen["scores"]) == (None, "data")


def _forest(params, X, y, rounds=4):
    import lambdagap_tpu as lgb
    booster = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                        num_boost_round=rounds)
    return booster._booster.learner, gbdt_check.parse_model(
        booster.model_to_string())


def test_the_mesh_grows_the_one_device_trees():
    from benchmark.datagen import criteo_like
    data = criteo_like.generate({"num_features": 67}, 2147600101, 20000, 0)
    X, y = data["X"], data["y"]
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 0,
              "min_sum_hessian_in_leaf": 5, "tree_layout": "sorted",
              "tpu_fused_learner": 1, "tpu_hist_impl": "onehot",
              "verbose": -1}
    one, serial = _forest(params, X, y)
    mesh, dp = _forest(dict(params, tree_learner="data", tpu_num_devices=4),
                       X, y)
    assert type(one).__name__ == "FusedTreeLearner"
    assert type(mesh).__name__ == "FusedDataParallelTreeLearner"
    for a, b in zip(serial, dp):
        for key in ("split_feature", "threshold", "left_child",
                    "right_child", "leaf_count"):
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
        # the same rows summed in another grouping (four shards' partial
        # histograms, then the psum): 2.3e-6 apart at most here
        np.testing.assert_allclose(a["leaf_value"], b["leaf_value"],
                                   rtol=2e-5, atol=1e-7)


class _NoPsum:
    """``jax.lax`` for the tree program with every psum dropped."""

    def __getattr__(self, name):
        import jax
        return getattr(jax.lax, name)

    @staticmethod
    def psum(x, axis_name):
        return x


def test_a_dropped_psum_is_not_correct(monkeypatch):
    from lambdagap_tpu.models import fused_learner
    monkeypatch.setattr(fused_learner, "lax", _NoPsum())
    res = drive(seed=22)
    assert res["correct"] is False
    assert res["compared"]["leaf_rows"][0] > 0 \
        or res["compared"]["leaf_value_median"][0] \
        > res["compared"]["leaf_value_median"][1]


@pytest.mark.parametrize("shards", [1, 4])
def test_the_shard_processes_route_every_row_as_leaf_index(shards):
    rng = np.random.default_rng(7)
    X = rng.standard_normal((5003, 5)).astype(np.float32)
    cols = gbdt_check.feature_major(X)
    trees = [grown(rng, X, 31), grown(rng, X, 17, chain=True),
             grown(rng, X, 1)]
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(2) as threads:
        pool = mesh_check._ShardPool(threads, shards)
        try:
            # two trees in flight at once, as _follow asks for them
            for a, b in zip(trees, trees[1:] + trees[:1]):
                first = pool.submit(gbdt_check.leaf_index, a, cols)
                got = first.result().copy()
                second = pool.submit(gbdt_check.leaf_index, b, cols)
                np.testing.assert_array_equal(
                    got, gbdt_check.leaf_index(a, cols))
                np.testing.assert_array_equal(
                    second.result(), gbdt_check.leaf_index(b, cols))
        finally:
            pool.close()


@pytest.mark.parametrize("shards", [1, 4])
def test_every_number_of_the_check_is_gbdt_checks(shards):
    rng = np.random.default_rng(9)
    n = 6007
    X = rng.standard_normal((n, 6)).astype(np.float32)
    data = {"X": X, "group": None,
            "y": (rng.random(n) < 0.1).astype(np.float32)}
    text = model_text([grown(rng, X, 15) for _ in range(4)])
    params = {"objective": "binary", "learning_rate": 0.1,
              "min_sum_hessian_in_leaf": 1.0}
    scores = rng.standard_normal(n)
    want = gbdt_check.check(text, data, params, scores, 4, seed=3)
    got = mesh_check.check(text, data, params, scores, 4, seed=3,
                           shards=shards)
    assert got == want and got["trees_followed"] == 4


def test_leaf_counts_are_exact_past_two_to_the_24_rows():
    """20M rows over 4 shards: a psum-ed float32 count channel rounds past
    2^24, and the larger child inherits its parent's rounding (the parent's
    program read 996,173 for a leaf of 996,177). The program's leaf counts
    are integer sums of the shards' own."""
    n = 20_000_000
    rng = np.random.default_rng(1)
    X = np.empty((n, 2), np.float32)
    X[:, 0] = np.floor(rng.exponential(2.0, n))
    X[:, 1] = rng.random(n, dtype=np.float32)
    y = (rng.random(n, dtype=np.float32)
         < 1 / (1 + np.exp(-(-3 + 0.5 * X[:, 0] + 2 * X[:, 1])))
         ).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "tree_learner": "data", "tpu_num_devices": 4,
              "min_data_in_leaf": 0, "min_sum_hessian_in_leaf": 1,
              "tpu_hist_impl": "onehot", "enable_bundle": False,
              "verbose": -1}
    _, trees = _forest(params, X, y, rounds=1)
    leaf = gbdt_check.leaf_index(trees[0], gbdt_check.feature_major(X))
    np.testing.assert_array_equal(
        trees[0]["leaf_count"], np.bincount(leaf, minlength=8))


def test_the_row_count_psum_is_not_a_split_collective():
    """``hist_allreduce_device_ms`` and ``allreduce_us_per_split`` read scope
    ``hist_allreduce`` as the histogram psums of the root and of every
    split; the tree's one integer psum of its leaf and node row counts sits
    under ``row_leaf`` (read by ``tree_fixed_device_ms``)."""
    import re

    import lambdagap_tpu as lgb
    rng = np.random.default_rng(0)
    X = rng.normal(size=(2000, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 15,
              "tree_learner": "data", "tpu_num_devices": 4,
              "tree_layout": "sorted", "tpu_fused_learner": 1,
              "tpu_hist_impl": "onehot", "min_data_in_leaf": 1,
              "verbose": -1}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    learner = bst._booster.learner
    jitted, seen = learner._train_jit_dp, {}

    def keep(*args):
        seen["args"] = args
        return jitted(*args)
    learner._train_jit_dp = keep
    bst.update()
    found = []

    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for child in block.operations:
                    if child.operation.name == "stablehlo.all_reduce":
                        m = re.match(r'loc\("([^"]*)"', str(child.location))
                        path = (m.group(1) if m else "").split("/")
                        for r in child.results:
                            found.append((path, len(r.type.shape),
                                          str(r.type.element_type)))
                    walk(child)
    walk(jitted.lower(*seen["args"]).compiler_ir(
        dialect="stablehlo").operation)
    hists = [f for f in found if f[1] == 3]
    counts = [f for f in found if f[1] == 1 and f[2] == "i32"]
    assert len(hists) == 2 and len(counts) == 2, found
    assert all("hist_allreduce" in p for p, _, _ in hists)
    assert all("hist_allreduce" not in p and "row_leaf" in p
               for p, _, _ in counts)
