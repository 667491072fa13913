"""Two-process distributed smoke test (DistributedMockup analog).

The reference tests distributed training by launching CLI subprocesses on
localhost (reference: tests/distributed/_test_distributed.py:53-120
DistributedMockup). Here two JAX processes join one runtime over a local
coordinator and run the core distributed primitive — a cross-process
histogram psum over a global mesh — verifying the DCN communication
backend end to end. (Full multi-device training parity is covered on the
virtual 8-device mesh in test_distributed.py.)
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import skip_unless_multiprocess

_CHILD = r"""
import os, sys
import numpy as np
sys.path.insert(0, os.getcwd())
# distributed init MUST precede any backend initialization (so before the
# package import, whose module-level jnp constants touch the backend)
import jax

rank = int(sys.argv[1])
port = sys.argv[2]
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=rank)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4          # 2 processes x 2 local cpu devices
from lambdagap_tpu.parallel.multiprocess import global_array_from_local

import jax.numpy as jnp
from lambdagap_tpu.parallel.sharding import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from lambdagap_tpu.ops.histogram import histogram_from_rows

mesh = Mesh(np.asarray(jax.devices()).reshape(-1), ("data",))
# every process holds its own 8-row block of the 16-row dataset
rng = np.random.RandomState(0)
full_bins = rng.randint(0, 8, (16, 3)).astype(np.uint8)
full_g = rng.randn(16).astype(np.float32)
lo, hi = rank * 8, rank * 8 + 8
x = global_array_from_local(full_bins[lo:hi], mesh, P("data", None))
g = global_array_from_local(full_g[lo:hi], mesh, P("data"))
h = global_array_from_local(np.ones(8, np.float32), mesh, P("data"))
m = global_array_from_local(np.ones(8, bool), mesh, P("data"))

def hist(x_l, g_l, h_l, m_l):
    local = histogram_from_rows(x_l, g_l, h_l, m_l, 8, 4096, "f32")
    return jax.lax.psum(local, "data")

op = jax.jit(shard_map(hist, mesh=mesh,
                       in_specs=(P("data", None), P("data"), P("data"),
                                 P("data")),
                       out_specs=P()))
out = np.asarray(op(x, g, h, m))
# verify against the full-data histogram computed locally
expect = np.zeros((3, 8, 3), np.float32)
for f in range(3):
    for r in range(16):
        b = full_bins[r, f]
        expect[f, b, 0] += full_g[r]
        expect[f, b, 1] += 1.0
        expect[f, b, 2] += 1.0
np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)
print(f"RANK{rank}_OK")
"""


def test_two_process_histogram_psum(tmp_path):
    skip_unless_multiprocess()
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    # the children run multi-process CPU jax
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    procs = [subprocess.Popen([sys.executable, str(script), str(r), port],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              cwd=os.getcwd(), env=env)
             for r in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process smoke test timed out")
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-2000:]}"
        assert f"RANK{r}_OK" in out


_CHILD_TRAIN = r"""
import os, sys
import numpy as np
sys.path.insert(0, os.getcwd())
import jax

rank = int(sys.argv[1])
port = sys.argv[2]
workdir = sys.argv[3]
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=rank)

import lambdagap_tpu as lgb
from lambdagap_tpu.config import Config
from lambdagap_tpu.parallel.multiprocess import load_pre_partitioned

quant = len(sys.argv) > 4 and sys.argv[4] == "quant"
cfg = Config.from_params({
    "objective": "binary", "tree_learner": "data", "num_leaves": 15,
    "min_data_in_leaf": 5, "verbose": -1, "pre_partition": True,
    "num_machines": 2, "bin_construct_sample_cnt": 2000,
    # quantized path: global |grad|/hess maxima are psum-agreed before
    # scale computation, so ranks histogram in identical integer units
    "use_quantized_grad": quant, "stochastic_rounding": False})
ds = load_pre_partitioned(os.path.join(workdir, f"part{rank}.tsv"), cfg)
assert ds.process_sharded and ds.global_num_data == 1600, ds.global_num_data

# drive the GBDT directly on the pre-partitioned dataset
from lambdagap_tpu.models.dart import create_boosting
g = create_boosting(cfg, ds)
for _ in range(5):
    g.train_one_iter()
model = g.save_model_to_string()
with open(os.path.join(workdir, f"model{rank}.txt"), "w") as f:
    f.write(model)
Xt = np.loadtxt(os.path.join(workdir, "test.tsv"))[:, 1:]
np.savetxt(os.path.join(workdir, f"pred{rank}.txt"), g.predict(Xt))
print(f"RANK{rank}_OK")
"""


@pytest.mark.parametrize("quant", [False, True])
def test_two_process_pre_partitioned_training(tmp_path, quant):
    """pre_partition=true end to end: two processes load DISJOINT files,
    sync bin mappers from allgathered samples, and train identical models
    over the multi-process mesh that match a single-process run
    (reference: dataset_loader.cpp:1072 + tests/distributed mockup).
    The quantized variant checks the global-scale agreement: int8
    gradient histograms psum only when every rank quantizes with the
    same (globally-maxed) scales."""
    skip_unless_multiprocess()
    import socket
    rng = np.random.RandomState(3)
    X = rng.randn(1600, 6)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    Xt = rng.randn(400, 6)
    yt = (Xt[:, 0] + 0.5 * Xt[:, 1] > 0).astype(float)
    full = np.column_stack([y, X])
    np.savetxt(tmp_path / "part0.tsv", full[:800], delimiter="\t")
    np.savetxt(tmp_path / "part1.tsv", full[800:], delimiter="\t")
    np.savetxt(tmp_path / "full.tsv", full, delimiter="\t")
    np.savetxt(tmp_path / "test.tsv", np.column_stack([yt, Xt]),
               delimiter="\t")

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    script = tmp_path / "child_train.py"
    script.write_text(_CHILD_TRAIN)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), port, str(tmp_path)]
        + (["quant"] if quant else []),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=os.getcwd(), env=env) for r in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("pre-partitioned training timed out")
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"
        assert f"RANK{r}_OK" in out

    # both ranks must build the IDENTICAL model (identical mappers +
    # psum-reduced histograms)
    m0 = (tmp_path / "model0.txt").read_text()
    m1 = (tmp_path / "model1.txt").read_text()
    assert m0 == m1
    p0 = np.loadtxt(tmp_path / "pred0.txt")
    p1 = np.loadtxt(tmp_path / "pred1.txt")
    np.testing.assert_allclose(p0, p1, rtol=1e-6)

    # and it matches a single-process model on the same data (bin mappers
    # come from different samples, so exact equality is not expected)
    import lambdagap_tpu as lgb
    from sklearn.metrics import roc_auc_score
    single = lgb.train({"objective": "binary", "num_leaves": 15,
                        "min_data_in_leaf": 5, "verbose": -1},
                       lgb.Dataset(X, label=y), num_boost_round=5)
    auc_s = roc_auc_score(yt, single.predict(Xt))
    auc_d = roc_auc_score(yt, p0)
    assert auc_d > 0.9, auc_d
    # int8 quantization shifts individual splits; compare quality only
    assert abs(auc_s - auc_d) < (0.05 if quant else 0.03), (auc_s, auc_d)


def test_cli_pre_partitioned_training(tmp_path):
    """The full CLI flow: `python -m lambdagap_tpu pre_partition=true
    num_machines=2 machine_rank=R machines=...` — the distributed runtime
    joins BEFORE the package import touches the backend (__main__ early
    init), mappers sync, both ranks save identical models (reference: the
    distributed CLI mockup, tests/distributed/_test_distributed.py)."""
    skip_unless_multiprocess()
    import socket
    rng = np.random.RandomState(4)
    X = rng.randn(1200, 5)
    y = (X[:, 0] - 0.5 * X[:, 2] > 0).astype(float)
    full = np.column_stack([y, X])
    np.savetxt(tmp_path / "part0.tsv", full[:600], delimiter="\t")
    np.savetxt(tmp_path / "part1.tsv", full[600:], delimiter="\t")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.getcwd()
    procs = []
    for r in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "lambdagap_tpu",
             f"data={tmp_path}/part{r}.tsv", "task=train",
             "objective=binary", "num_leaves=15", "min_data_in_leaf=5",
             "num_iterations=4", "verbose=-1", "pre_partition=true",
             "num_machines=2", f"machine_rank={r}",
             f"machines=127.0.0.1:{port}", "tree_learner=data",
             f"output_model={tmp_path}/model{r}.txt"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=os.getcwd(), env=env))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("CLI pre-partitioned training timed out")
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"
    m0 = (tmp_path / "model0.txt").read_text()
    m1 = (tmp_path / "model1.txt").read_text()
    assert m0.split("\nparameters")[0] == m1.split("\nparameters")[0]


def test_train_cluster_single_call():
    """The Dask-module analog (reference: python-package/lightgbm/dask.py
    _train — machine list, ports, per-worker training driven
    automatically): one library call partitions the matrix, launches the
    workers, and returns the (rank-identical) model."""
    skip_unless_multiprocess()
    import lambdagap_tpu as lgb
    from sklearn.metrics import roc_auc_score
    rng = np.random.RandomState(8)
    X = rng.randn(1600, 6)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    booster = lgb.train_cluster(
        {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "bin_construct_sample_cnt": 2000},
        X, y, num_workers=2, num_boost_round=5,
        worker_env={**os.environ, "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                    "PYTHONPATH": ""})
    pred = booster.predict(X)
    assert roc_auc_score(y, pred) > 0.95
    # the multi-host recipe is exposed for operators
    assert len(booster.cluster_commands) == 2
    assert "machine_rank=1" in booster.cluster_commands[1]


def test_train_cluster_rank_groups():
    """Query-aligned partitioning: lambdarank over a cluster keeps every
    query on one rank."""
    skip_unless_multiprocess()
    import lambdagap_tpu as lgb
    rng = np.random.RandomState(9)
    n_q, per = 40, 30
    X = rng.randn(n_q * per, 5)
    y = rng.randint(0, 3, n_q * per).astype(float)
    booster = lgb.train_cluster(
        {"objective": "lambdarank", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1, "bin_construct_sample_cnt": 1000},
        X, y, group=np.full(n_q, per), num_workers=2, num_boost_round=3,
        worker_env={**os.environ, "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                    "PYTHONPATH": ""})
    assert booster.num_trees() == 3


def test_train_cluster_multihost_recipe(tmp_path):
    """The multi-host configuration the recipe documents: 2 coordinated
    processes EACH holding 4 virtual devices — an 8-device global mesh
    where the histogram psum crosses both the intra-process (ICI analog)
    and inter-process (DCN analog) boundaries (reference: the dask
    multi-worker tests, python-package/lightgbm/dask.py:375-415). Rank
    models must be identical, and with full-data bin samples the model
    must match single-process training."""
    skip_unless_multiprocess()
    import lambdagap_tpu as lgb
    from sklearn.metrics import roc_auc_score
    rng = np.random.RandomState(11)
    X = rng.randn(1600, 6)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    booster = lgb.train_cluster(
        {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "bin_construct_sample_cnt": 1600},
        X, y, num_workers=2, num_boost_round=5,
        workdir=str(tmp_path), keep_files=True,
        worker_env={**os.environ, "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                    "PYTHONPATH": ""})
    # every rank built the identical model over the 2x4 global mesh
    m0 = (tmp_path / "model0.txt").read_text()
    m1 = (tmp_path / "model1.txt").read_text()
    assert m0.split("\nparameters")[0] == m1.split("\nparameters")[0]
    # with sample_cnt == n each rank samples its full block without
    # replacement, so the allgathered sample is a permutation of the full
    # data and the equal-count mappers match single-process exactly
    single = lgb.train({"objective": "binary", "num_leaves": 15,
                        "min_data_in_leaf": 5, "verbose": -1,
                        "bin_construct_sample_cnt": 1600},
                       lgb.Dataset(X, label=y), num_boost_round=5)
    p_c, p_s = booster.predict(X), single.predict(X)
    assert roc_auc_score(y, p_c) > 0.95
    close = np.isclose(p_c, p_s, rtol=5e-3, atol=5e-3)
    assert close.mean() > 0.99, float(close.mean())
