"""Seeded differential sweep: the host-driven serial learner and the fused
whole-tree program must agree across random config combinations (the
cross-backend analog of the reference's CPU-vs-GPU test_dual.py, run here
as host-loop vs fused on one backend so float noise stays bounded), and the
fused data-parallel program must agree with itself across mesh sizes
(1 device vs 8) — the sweep that catches a fused-path regression in any
major feature (bagging, GOSS, DART, EFB, monotone, forced splits,
linear trees, quantized gradients)."""
import json
import os

import numpy as np
import pytest

from conftest import skip_unless_multiprocess

import lambdagap_tpu as lgb


def _random_case(rng, tmp_path=None, for_dp=False):
    n = int(rng.randint(600, 1500))
    d = int(rng.randint(4, 10))
    X = rng.randn(n, d)
    cat_col = None
    if rng.rand() < 0.4:                       # a categorical column
        cat_col = int(rng.randint(d))
        X[:, cat_col] = rng.randint(0, int(rng.randint(3, 20)), n)
    # labels derive from the PRE-corruption features (NaN labels are
    # invalid input, not a differential case)
    obj = rng.choice(["binary", "regression"])
    if obj == "binary":
        y = (X[:, 0] + 0.5 * rng.randn(n) > 0).astype(float)
    else:
        y = X[:, 0] * 2 + rng.randn(n)
    if rng.rand() < 0.5:                       # missing values
        X[rng.rand(n) < 0.1, int(rng.randint(d))] = np.nan
    if rng.rand() < 0.3:                       # exact zeros (Zero missing)
        X[rng.rand(n) < 0.3, int(rng.randint(d))] = 0.0
    if rng.rand() < 0.3:                       # near-exclusive one-hot block
        k = min(d - 1, 3)
        hot = rng.randint(0, k, n)
        for j in range(k):
            X[:, d - 1 - j] = (hot == j) * np.abs(rng.randn(n))
    w = np.abs(rng.randn(n)) + 0.1 if rng.rand() < 0.4 else None
    params = {
        "objective": obj,
        "num_leaves": int(rng.choice([4, 15, 31])),
        # 1-row leaves make f32 gain ties ubiquitous and flip near-tie
        # splits between any two summation orders; 3 is still adversarial
        "min_data_in_leaf": int(rng.choice([3, 5, 20])),
        "max_bin": int(rng.choice([15, 63, 255])),
        "learning_rate": float(rng.choice([0.05, 0.1, 0.3])),
        "lambda_l1": float(rng.choice([0.0, 0.0, 1.0])),
        "lambda_l2": float(rng.choice([0.0, 1.0])),
        "min_gain_to_split": float(rng.choice([0.0, 0.0, 0.1])),
        "enable_bundle": bool(rng.rand() < 0.7),
        "verbose": -1,
    }
    # feature-level draws ------------------------------------------------
    r = rng.rand()
    if r < 0.25:
        params.update(bagging_fraction=float(rng.choice([0.5, 0.8])),
                      bagging_freq=1)
    elif r < 0.45:
        params.update(data_sample_strategy="goss",
                      top_rate=0.3, other_rate=0.2)
    if rng.rand() < 0.2:
        params.update(boosting="dart", drop_rate=0.3)
    if cat_col is None and rng.rand() < 0.3:
        mono = [0] * d
        mono[0] = 1
        params.update(monotone_constraints=mono,
                      monotone_constraints_method=str(
                          rng.choice(["basic", "intermediate", "advanced"])))
    if cat_col is None and not for_dp and rng.rand() < 0.15 \
            and params.get("boosting") != "dart":
        # linear trees now train on the fused learner too; the combo with
        # dart is a config-validation error (ISSUE 11 satellite), so the
        # draw skips it
        params.update(linear_tree=True)
    if tmp_path is not None and rng.rand() < 0.2 and cat_col != 0:
        forced = {"feature": 0, "threshold": float(np.nanmedian(X[:, 0]))}
        fp = os.path.join(str(tmp_path), "forced.json")
        with open(fp, "w") as f:
            json.dump(forced, f)
        params["forcedsplits_filename"] = fp
    if for_dp and rng.rand() < 0.25:
        params.update(use_quantized_grad=True, stochastic_rounding=False)
    if cat_col is not None:
        params["categorical_feature"] = [cat_col]
    return X, y, w, params


# tier-1 hygiene (the 870s window, ROADMAP caveat): the differential fuzz
# sweeps dominate the alphabetical window — keep a fast slice of each
# sweep in tier-1 and push the long tail behind -m slow (the full sweeps
# still run wherever slow marks do; seeds are stable so the split is too)
@pytest.mark.parametrize(
    "seed", list(range(8)) + [pytest.param(s, marks=pytest.mark.slow)
                              for s in range(8, 20)])
def test_host_vs_fused_random_config(seed, tmp_path):
    rng = np.random.RandomState(1000 + seed)
    X, y, w, params = _random_case(rng, tmp_path)
    rounds = 5
    b_host = lgb.train({**params, "tpu_fused_learner": "0"},
                       lgb.Dataset(X, label=y, weight=w),
                       num_boost_round=rounds)
    b_fused = lgb.train({**params, "tpu_fused_learner": "1"},
                        lgb.Dataset(X, label=y, weight=w),
                        num_boost_round=rounds)
    p_host = b_host.predict(X)
    p_fused = b_fused.predict(X)
    # identical algorithms; differences are float reduction order only.
    # near-tie splits can diverge structurally, so compare predictions,
    # not model text, at a tolerance covering one flipped minor split
    close = np.isclose(p_host, p_fused, rtol=5e-3, atol=5e-3)
    assert close.mean() > 0.99, (params, float(close.mean()))
    np.testing.assert_allclose(np.mean(p_host), np.mean(p_fused),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize(
    "seed", list(range(4)) + [pytest.param(s, marks=pytest.mark.slow)
                              for s in range(4, 10)])
def test_dp_1dev_vs_8dev_random_config(seed, tmp_path):
    """The fused data-parallel shard_map program must produce the same
    model on a 1-device and an 8-device mesh (per-split psum + replicated
    argmax — any missing collective shows up as divergence here)."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device mesh")
    rng = np.random.RandomState(7000 + seed)
    X, y, w, params = _random_case(rng, tmp_path, for_dp=True)
    params.update(tree_learner="data", tpu_fused_learner="1")
    rounds = 4
    b1 = lgb.train({**params, "tpu_num_devices": 1},
                   lgb.Dataset(X, label=y, weight=w), num_boost_round=rounds)
    b8 = lgb.train({**params, "tpu_num_devices": 8},
                   lgb.Dataset(X, label=y, weight=w), num_boost_round=rounds)
    p1 = b1.predict(X)
    p8 = b8.predict(X)
    close = np.isclose(p1, p8, rtol=5e-3, atol=5e-3)
    assert close.mean() > 0.99, (params, float(close.mean()))
    np.testing.assert_allclose(np.mean(p1), np.mean(p8),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize(
    "seed", list(range(3)) + [pytest.param(s, marks=pytest.mark.slow)
                              for s in range(3, 6)])
def test_feature_parallel_vs_serial_random_config(seed):
    """Random-config differential for the fused FEATURE-parallel program:
    rows are replicated so the column-sharded scan must reproduce the
    fused serial learner exactly (same arithmetic, same global-feature
    tie-break) across quantized/monotone/bagging/GOSS/EFB draws."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device mesh")
    rng = np.random.RandomState(3000 + seed)
    X, y, w, params = _random_case(rng, None, for_dp=True)
    if params.get("monotone_constraints_method") == "advanced":
        # advanced demotes to intermediate on distributed learners; pin
        # both sides to the same method
        params["monotone_constraints_method"] = "intermediate"
    rounds = 4
    b_s = lgb.train({**params, "tpu_fused_learner": "1"},
                    lgb.Dataset(X, label=y, weight=w),
                    num_boost_round=rounds)
    b_f = lgb.train({**params, "tree_learner": "feature",
                     "tpu_num_devices": 8},
                    lgb.Dataset(X, label=y, weight=w),
                    num_boost_round=rounds)
    from lambdagap_tpu.parallel.fused_parallel import \
        FusedFeatureParallelTreeLearner
    assert isinstance(b_f._booster.learner, FusedFeatureParallelTreeLearner)
    close = np.isclose(b_s.predict(X), b_f.predict(X), rtol=5e-3, atol=5e-3)
    assert close.mean() > 0.99, (params, float(close.mean()))


@pytest.mark.parametrize(
    "seed", list(range(3)) + [pytest.param(s, marks=pytest.mark.slow)
                              for s in range(3, 6)])
def test_voting_fused_vs_host_loop_random_config(seed):
    """Random-config differential for the fused VOTING program against the
    host-loop voting learner — same algorithm (local top-k vote, voted
    column psum), fused vs per-split-host-sync execution."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device mesh")
    rng = np.random.RandomState(4000 + seed)
    X, y, w, params = _random_case(rng, None)
    # host-loop voting applies monotone per-split only, no quantized path,
    # and linear trees route to host on both sides — keep the comparison
    # on the shared algorithm space
    for k in ("monotone_constraints", "monotone_constraints_method",
              "linear_tree", "use_quantized_grad"):
        params.pop(k, None)
    params.update(tree_learner="voting", tpu_num_devices=8,
                  top_k=int(rng.choice([3, 8])))
    rounds = 4
    b_f = lgb.train({**params, "tpu_fused_learner": "1"},
                    lgb.Dataset(X, label=y, weight=w),
                    num_boost_round=rounds)
    b_h = lgb.train({**params, "tpu_fused_learner": "0"},
                    lgb.Dataset(X, label=y, weight=w),
                    num_boost_round=rounds)
    close = np.isclose(b_f.predict(X), b_h.predict(X), rtol=5e-3, atol=5e-3)
    assert close.mean() > 0.99, (params, float(close.mean()))


_CHILD_FUZZ = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, os.getcwd())
import jax

rank = int(sys.argv[1]); port = sys.argv[2]; workdir = sys.argv[3]
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=rank)
from lambdagap_tpu.config import Config
from lambdagap_tpu.parallel.multiprocess import load_pre_partitioned
from lambdagap_tpu.models.dart import create_boosting

params = json.load(open(os.path.join(workdir, "params.json")))
cfg = Config.from_params({**params, "pre_partition": True,
                          "num_machines": 2,
                          "bin_construct_sample_cnt": 4000})
ds = load_pre_partitioned(os.path.join(workdir, f"part{rank}.tsv"), cfg)
g = create_boosting(cfg, ds)
for _ in range(4):
    g.train_one_iter()
with open(os.path.join(workdir, f"model{rank}.txt"), "w") as f:
    f.write(g.save_model_to_string())
print(f"RANK{rank}_OK")
"""


@pytest.mark.parametrize("seed", range(3))
def test_pre_partitioned_random_config(seed, tmp_path):
    """Random-config differential for the 2-process pre-partitioned path:
    both ranks must build byte-identical models under random bagging/GOSS/
    quantized/num_leaves draws (any rank-divergent reduction shows up as a
    model mismatch)."""
    skip_unless_multiprocess()
    import socket
    import subprocess
    import sys as _sys
    rng = np.random.RandomState(5000 + seed)
    n = 1600
    X = rng.randn(n, 6)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    params = {"objective": "binary", "tree_learner": "data",
              "num_leaves": int(rng.choice([7, 15, 31])),
              "min_data_in_leaf": int(rng.choice([3, 20])),
              "verbose": -1}
    r = rng.rand()
    if r < 0.33:
        params.update(bagging_fraction=0.7, bagging_freq=1)
    elif r < 0.66:
        params.update(data_sample_strategy="goss", top_rate=0.3,
                      other_rate=0.2)
    if rng.rand() < 0.5:
        params.update(use_quantized_grad=True, stochastic_rounding=False)
    full = np.column_stack([y, X])
    np.savetxt(tmp_path / "part0.tsv", full[:800], delimiter="\t")
    np.savetxt(tmp_path / "part1.tsv", full[800:], delimiter="\t")
    with open(tmp_path / "params.json", "w") as f:
        json.dump(params, f)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    script = tmp_path / "child_fuzz.py"
    script.write_text(_CHILD_FUZZ)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    procs = [subprocess.Popen(
        [_sys.executable, str(script), str(r2), port, str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=os.getcwd(), env=env) for r2 in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("pre-partitioned fuzz timed out")
        outs.append(out)
    for r2, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (params, f"rank {r2}:\n{out[-3000:]}")
        assert f"RANK{r2}_OK" in out
    m0 = (tmp_path / "model0.txt").read_text()
    m1 = (tmp_path / "model1.txt").read_text()
    assert m0 == m1, params
