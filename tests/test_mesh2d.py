"""The fused 2-D data x feature program + its stream composition
(ISSUE 15).

The acceptance surface, all runnable on the conftest's 8-virtual-device
CPU mesh:

- ``make_mesh`` accepts ``dd>1 && ff>1`` and the fused 2-D learner
  trains on it: quantized-path trees BIT-IDENTICAL across the
  1x8 / 2x4 / 4x2 / 8x1 grids AND to the 1-device fused serial learner;
- ``data_residency=stream`` composes with the mesh: streamed 2-D trees
  are bit-identical to resident 2-D trees on the same grid, including
  under GOSS window compaction, with the h2d_prefetch/chunk_wait ring
  phases live and zero steady-state recompiles;
- ``mesh_shape`` validation: wildcard forms ("0x4"/"2x0") resolve
  against the device count with a clear error naming ``mesh_shape``
  when it does not divide;
- elastic resume across grid shapes: train on 4x2, SIGKILL, resume=auto
  on 2x4 and on 8x1 — final trees byte-identical to an uninterrupted
  run (quantized path; the sidecar ``mesh`` block carries the grid).
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import lambdagap_tpu as lgb
from lambdagap_tpu.parallel.fused_parallel import Fused2DTreeLearner
from lambdagap_tpu.parallel.sharding import (make_mesh, parse_mesh_shape,
                                             resolve_mesh_shape)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trees(booster) -> str:
    return booster.model_to_string().split("end of trees")[0]


def _data(n=4001, d=6, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d)
    y = (X[:, 0] - 0.4 * X[:, 1] + np.sin(X[:, 2]) + 0.2 * rng.randn(n)
         > 0).astype(np.float32)
    return X, y


def _train(X, y, extra, rounds=4):
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 10, "tpu_fused_learner": "1"}
    params.update(extra)
    return lgb.train(params, lgb.Dataset(X, label=y, params=params),
                     num_boost_round=rounds)


# -- mesh_shape resolution ----------------------------------------------
def test_make_mesh_accepts_2d_grids():
    for shape, want in (("4x2", (4, 2)), ("2x4", (2, 4)), ("1x8", (1, 8)),
                        ("8x1", (8, 1)), ("0x4", (2, 4)), ("4x0", (4, 2)),
                        ("0x8", (1, 8))):
        m = make_mesh(mesh_shape=shape)
        assert dict(m.shape) == {"data": want[0], "feature": want[1]}, shape
        assert tuple(m.axis_names) == ("data", "feature")


def test_make_mesh_raises_on_more_devices_than_visible():
    """tpu_num_devices beyond what is visible used to be truncated by a
    slice — a 4-chip job would run on 1 and say nothing."""
    n = len(jax.devices())
    assert make_mesh(n).devices.size == n
    with pytest.raises(ValueError, match=f"tpu_num_devices={n + 1}"):
        make_mesh(n + 1)


def test_mesh_shape_wildcards_and_rejections_name_the_knob():
    # wildcards resolve against the device count
    assert resolve_mesh_shape("0x4", 8) == (2, 4)
    assert resolve_mesh_shape("2x0", 8) == (2, 4)
    assert resolve_mesh_shape("", 8) is None
    # non-divisible wildcard, capacity overflow, 0x0, bad syntax — every
    # rejection names mesh_shape (the num_grad_quant_bins precedent)
    for shape, ndev in (("0x3", 8), ("3x0", 8), ("4x4", 8), ("0x16", 8),
                        ("0x0", 8)):
        with pytest.raises(ValueError, match="mesh_shape"):
            resolve_mesh_shape(shape, ndev)
    with pytest.raises(ValueError, match="mesh_shape"):
        parse_mesh_shape("axb")
    with pytest.raises(ValueError, match="mesh_shape"):
        parse_mesh_shape("2x2x2")


# -- the fused 2-D program (hbm) ----------------------------------------
_FLOAT_STATS = ("split_gain", "leaf_value", "leaf_weight", "internal_value",
                "internal_weight")


def _stats_apart(text: str):
    """(the model text without its float statistics' lines, those values):
    what is left holds structure, thresholds and counts."""
    rest, stats = [], []
    for ln in text.split("\n"):
        key, _, vals = ln.partition("=")
        if key in _FLOAT_STATS:
            stats += [float(v) for v in vals.split()]
        else:
            rest.append(ln)
    return "\n".join(rest), np.asarray(stats, np.float64)


def test_quantized_trees_bit_identical_across_grids():
    """The tentpole contract: one program for every dd x ff grid, and on
    the quantized path the integer data-psum + feature-blocked argmax
    make the trees grid-invariant — bit-identical across 1x8 / 2x4 /
    4x2 / 8x1, every byte of the model text.

    Against the 1-device fused serial learner the four grids agree in
    structure, split features, thresholds and counts to bytes, and in
    the float statistics (``_FLOAT_STATS``) to 1e-5 relative: the serial
    program scans 6 feature columns where the mesh programs scan blocks
    of the 8 they pad to, XLA's CPU backend sums a column's dequantized
    float32 bins in another order for the other block shape, and the
    hessian sums of two nodes of the first tree come out 4 ulps apart
    (93.74869 for 93.74872), with the gains and values derived from
    them. 1e-5 is two orders over float32's 1.2e-7 and under any wrong
    sum. Four real chips agreed with one byte for byte (chip_smoke
    ``--require-multichip``, PR 21)."""
    X, y = _data()
    quant = {"use_quantized_grad": True, "stochastic_rounding": False}
    ref = _trees(_train(X, y, {"tree_learner": "serial", **quant}))
    ref_rest, ref_stats = _stats_apart(ref.split("Tree=0")[1])
    first = None
    for grid in ("1x8", "2x4", "4x2", "8x1"):
        b = _train(X, y, {"tree_learner": "data", "mesh_shape": grid,
                          **quant})
        lr = b._booster.learner
        assert isinstance(lr, Fused2DTreeLearner), type(lr).__name__
        assert (lr.dd, lr.ff) == tuple(int(v) for v in grid.split("x"))
        got_t = _trees(b).split("Tree=0")[1]
        if first is None:
            first = got_t
            rest, stats = _stats_apart(got_t)
            assert rest == ref_rest
            np.testing.assert_allclose(stats, ref_stats, rtol=1e-5, atol=0)
        assert got_t == first, grid


def test_2d_grid_zero_steady_recompiles_and_telemetry():
    X, y = _data(n=3000)
    b = _train(X, y, {"tree_learner": "data", "mesh_shape": "2x2",
                      "use_quantized_grad": True,
                      "stochastic_rounding": False,
                      "telemetry": True, "telemetry_warmup": 3},
               rounds=6)
    tel = b._booster.telemetry
    steady = [(r["iter"], r["compiles"]["total"]) for r in tel.records
              if r.get("iter", 0) >= 3
              and (r.get("compiles") or {}).get("total", 0)]
    assert steady == [], steady


def test_2d_bagging_and_feature_fraction_match_serial_quant():
    """Sampling masks ride the row shards and the feature mask is drawn
    at the REAL feature count then padded — neither may perturb the
    grid-invariance of the quantized path."""
    X, y = _data(seed=11)
    extra = {"bagging_fraction": 0.6, "bagging_freq": 1,
             "feature_fraction": 0.8, "use_quantized_grad": True,
             "stochastic_rounding": False}
    ref = _trees(_train(X, y, {"tree_learner": "serial", **extra}))
    got = _trees(_train(X, y, {"tree_learner": "data", "mesh_shape": "2x3",
                               **extra}))
    assert got.split("Tree=0")[1] == ref.split("Tree=0")[1]


def test_2d_requires_fused_learner():
    X, y = _data(n=1200)
    with pytest.raises(Exception, match="2-D data x feature"):
        _train(X, y, {"tree_learner": "data", "mesh_shape": "2x2",
                      "tpu_fused_learner": "0"})


# -- stream x 2-D composition -------------------------------------------
@pytest.mark.parametrize("grid", ["2x4", "4x2"])
def test_stream_matches_resident_on_2d_grid(grid):
    """The composed out-of-core path: host shards pumped through the
    mesh-sharded ring build trees bit-identical to the resident 2-D
    program on the same grid (the same-grid mirror contract)."""
    X, y = _data()
    base = {"tree_learner": "data", "mesh_shape": grid,
            "stream_shard_rows": 1024, "enable_bundle": False}
    a = _train(X, y, {**base, "data_residency": "hbm"})
    b = _train(X, y, {**base, "data_residency": "stream"})
    lr = b._booster.learner
    assert isinstance(lr, Fused2DTreeLearner) and lr.residency == "stream"
    assert lr.sdata.num_shards == 4      # 4001 rows -> ragged tail shard
    assert _trees(a) == _trees(b)


def test_stream_2d_goss_compaction_identical():
    """GOSS drives per-block window compaction: only in-bag rows cross
    the link per data shard; re-expansion keeps bit-identity with and
    without compaction."""
    X, y = _data(seed=13)
    base = {"tree_learner": "data", "mesh_shape": "2x2",
            "stream_shard_rows": 1024, "enable_bundle": False,
            "data_sample_strategy": "goss", "top_rate": 0.2,
            "other_rate": 0.1, "learning_rate": 0.5}
    a = _train(X, y, {**base, "data_residency": "hbm"}, rounds=5)
    b = _train(X, y, {**base, "data_residency": "stream"}, rounds=5)
    c = _train(X, y, {**base, "data_residency": "stream",
                      "stream_goss_compact": False}, rounds=5)
    assert _trees(a) == _trees(b)
    assert _trees(a) == _trees(c)


def test_stream_2d_ring_phases_and_zero_recompiles():
    X, y = _data(n=3000)
    b = _train(X, y, {"tree_learner": "data", "mesh_shape": "2x2",
                      "data_residency": "stream",
                      "stream_shard_rows": 1024, "enable_bundle": False,
                      "telemetry": True, "telemetry_warmup": 4},
               rounds=8)
    tel = b._booster.telemetry
    steady = [(r["iter"], r["compiles"]["total"]) for r in tel.records
              if r.get("iter", 0) >= 4
              and (r.get("compiles") or {}).get("total", 0)]
    assert steady == [], steady
    phases = set()
    for r in tel.records:
        phases.update((r.get("phases") or {}).keys())
    assert {"h2d_prefetch", "chunk_wait"} <= phases, sorted(phases)


def test_stream_2d_blocker_falls_back_to_hbm():
    """Options the composed stream subset does not replicate (quantized
    gradients here) fall back to resident 2-D training loudly — the
    demotion keeps the grid, not the residency."""
    X, y = _data(n=1500)
    b = _train(X, y, {"tree_learner": "data", "mesh_shape": "2x2",
                      "data_residency": "stream",
                      "use_quantized_grad": True,
                      "stochastic_rounding": False})
    lr = b._booster.learner
    assert isinstance(lr, Fused2DTreeLearner)
    assert lr.residency == "hbm"
    assert b.num_trees() > 0


# -- elastic resume across grid shapes ----------------------------------
def _cli(args, tmp_path, faults=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    if faults:
        env["LAMBDAGAP_FAULTS"] = faults
    else:
        env.pop("LAMBDAGAP_FAULTS", None)
    return subprocess.run([sys.executable, "-m", "lambdagap_tpu", *args],
                          cwd=str(tmp_path), env=env, capture_output=True,
                          text=True, timeout=600)


def test_elastic_resume_across_grid_shapes(tmp_path):
    """Train on 4x2, SIGKILL mid-run, resume=auto on 2x4 and (from a
    fresh crash) on 8x1: final trees byte-identical to an uninterrupted
    4x2 run on the quantized path, and the resume logs the grid change
    read from the sidecar's mesh block."""
    rng = np.random.RandomState(3)
    X = rng.randn(2200, 6)
    y = X[:, 0] - 0.4 * X[:, 1] + 0.2 * rng.randn(2200)
    np.savetxt(str(tmp_path / "train.csv"),
               np.column_stack([y, X]), delimiter=",", fmt="%.8g")
    base = ["task=train", "data=train.csv", "label_column=0",
            "objective=regression", "boost_from_average=false",
            "num_iterations=6", "snapshot_freq=1", "min_data_in_leaf=5",
            "verbose=1", "resume=auto", "tpu_fused_learner=1",
            "tree_learner=data", "use_quantized_grad=true",
            "stochastic_rounding=false"]

    def crash_then_resume(resume_grid):
        for f in os.listdir(str(tmp_path)):
            if ".snapshot_iter_" in f:
                os.remove(str(tmp_path / f))
        r = _cli(base + ["mesh_shape=4x2", "output_model=m_crash.txt"],
                 tmp_path, faults="crash_at_iter=3")
        assert r.returncode == -9, f"expected SIGKILL, got " \
            f"{r.returncode}: {r.stdout}\n{r.stderr}"
        r = _cli(base + [f"mesh_shape={resume_grid}",
                         "output_model=m_crash.txt"], tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr
        out = r.stdout + r.stderr
        assert "Resumed from snapshot" in out
        assert "elastic resume across grid shapes" in out
        return (tmp_path / "m_crash.txt").read_text() \
            .split("end of trees")[0]

    m24 = crash_then_resume("2x4")
    m81 = crash_then_resume("8x1")
    r = _cli(base + ["mesh_shape=4x2", "output_model=m_ref.txt"], tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    ref = (tmp_path / "m_ref.txt").read_text().split("end of trees")[0]
    assert m24 == ref
    assert m81 == ref


def test_sidecar_mesh_block_carries_grid_shape():
    from lambdagap_tpu.guard.snapshot import capture_state
    X, y = _data(n=1500)
    b = _train(X, y, {"tree_learner": "data", "mesh_shape": "2x4"})
    state = capture_state(b._booster)
    assert state["mesh"]["axes"] == ["data", "feature"]
    assert state["mesh"]["shape"] == [2, 4]
    assert state["mesh"]["n_devices"] == 8
    assert state["mesh"]["n_loc"] * 2 == state["mesh"]["n_pad"]
