"""Out-of-core / sparse ingestion (reference: the sparse-bin memory story,
src/io/sparse_bin.hpp:73, and two-round loading,
src/io/dataset_loader.cpp:203 use_two_round_loading): scipy CSR input bins
chunk-wise through the streaming-sequence path, and ``two_round=true`` text
loading re-reads the file in bounded chunks — neither materializes the full
dense float matrix."""
import os
import subprocess
import sys

import numpy as np
import pytest

import lambdagap_tpu as lgb

sp = pytest.importorskip("scipy.sparse")


def _sparse_problem(n=4000, d=40, density=0.05, seed=0):
    rng = np.random.RandomState(seed)
    X = sp.random(n, d, density=density, format="csr", random_state=rng,
                  data_rvs=lambda k: rng.randn(k) * 2)
    dense = X.toarray()
    y = (dense[:, 0] + dense[:, 1] - 0.2 * dense[:, 2] > 0).astype(float)
    return X, dense, y


def test_csr_matches_dense():
    X, dense, y = _sparse_problem()
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}
    b_dense = lgb.train(params, lgb.Dataset(dense, label=y),
                        num_boost_round=8)
    b_csr = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    # identical rows -> identical sample -> identical mappers and bins
    np.testing.assert_allclose(b_dense.predict(dense), b_csr.predict(dense),
                               rtol=1e-6, atol=1e-8)
    # chunked CSR prediction agrees with dense prediction
    np.testing.assert_allclose(b_csr.predict(X), b_csr.predict(dense),
                               rtol=1e-6, atol=1e-8)


def test_csr_construct_memory_envelope():
    """Constructing from CSR must peak WELL below the dense float
    footprint. 400k x 500 f64 dense = 1.6 GB; the binned matrix is 200 MB.
    The check runs in a subprocess so other tests' allocations don't
    pollute the peak, and reads ``VmHWM`` of ``/proc/self/status`` (the
    high-water mark of the child's OWN address space): ``ru_maxrss``
    starts at the forking parent's resident size, so under a tier-1
    worker that has run other files it read over 1,000 MB before the
    child had imported anything. What is bounded is the construct's own
    growth over what was resident when it started."""
    code = r"""
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import scipy.sparse as sp
import lambdagap_tpu as lgb

def status_mb(key):
    for ln in open("/proc/self/status"):
        if ln.startswith(key + ":"):
            return int(ln.split()[1]) / 1024
    raise KeyError(key)

rng = np.random.RandomState(0)
n, d = 400_000, 500
nnz_per_row = 5                      # density 0.01
indptr = np.arange(0, n * nnz_per_row + 1, nnz_per_row, dtype=np.int64)
indices = rng.randint(0, d, n * nnz_per_row).astype(np.int32)
data = rng.randn(n * nnz_per_row).astype(np.float64)
X = sp.csr_matrix((data, indices, indptr), shape=(n, d))
y = rng.randint(0, 2, n).astype(float)
before_mb = status_mb("VmRSS")
ds = lgb.Dataset(X, label=y, params={"max_bin": 63,
                                     "bin_construct_sample_cnt": 20000})
b = ds.construct()
assert b.num_data == n and b.binned.shape[0] == n
grew_mb = status_mb("VmHWM") - before_mb
print("GREW_MB", grew_mb)
# dense f64 would be 1600 MB; the construct's growth is bounded by the
# binned matrix (200 MB) + the bin-finding sample (20k x 500 f64 = 80 MB)
# + one 64k-row chunk (256 MB) = 536 MB (372 measured alone, PR 33)
assert 100 < grew_mb < 600, grew_mb
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.getcwd(), env=env, timeout=540)
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-2000:])
    assert "GREW_MB" in r.stdout


@pytest.mark.parametrize("fmt", ["tsv", "libsvm"])
def test_two_round_matches_one_shot(tmp_path, fmt):
    rng = np.random.RandomState(3)
    n = 3000
    X = rng.randn(n, 6)
    X[rng.rand(n) < 0.1, 2] = 0.0
    y = (X[:, 0] - X[:, 1] > 0).astype(float)
    path = str(tmp_path / f"data.{fmt}")
    if fmt == "tsv":
        np.savetxt(path, np.column_stack([y, X]), delimiter="\t")
    else:
        with open(path, "w") as f:
            for i in range(n):
                toks = [f"{int(y[i])}"] + [
                    f"{j}:{X[i, j]:.6g}" for j in range(6) if X[i, j] != 0]
                f.write(" ".join(toks) + "\n")
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}
    ds1 = lgb.Dataset(path, params=params).construct()
    ds2 = lgb.Dataset(path, params={**params, "two_round": True}).construct()
    assert ds1.num_data == ds2.num_data
    np.testing.assert_allclose(ds1.metadata.label, ds2.metadata.label,
                               rtol=1e-6)
    # identical sample seed -> identical mappers -> identical binned rows
    assert np.array_equal(ds1.binned, ds2.binned)

    b1 = lgb.train(params, lgb.Dataset(path, params=params),
                   num_boost_round=6)
    b2 = lgb.train({**params, "two_round": True},
                   lgb.Dataset(path, params={**params, "two_round": True}),
                   num_boost_round=6)
    np.testing.assert_allclose(b1.predict(X), b2.predict(X), rtol=1e-6)


def test_two_round_rank_with_groups(tmp_path):
    rng = np.random.RandomState(4)
    n_q, per = 60, 25
    n = n_q * per
    X = rng.randn(n, 5)
    y = rng.randint(0, 3, n).astype(float)
    path = str(tmp_path / "rank.libsvm")
    with open(path, "w") as f:
        for i in range(n):
            toks = [f"{int(y[i])}", f"qid:{i // per + 1}"] + [
                f"{j}:{X[i, j]:.6g}" for j in range(5)]
            f.write(" ".join(toks) + "\n")
    ds = lgb.Dataset(path, params={"two_round": True,
                                   "objective": "lambdarank"}).construct()
    assert ds.metadata.query_boundaries is not None
    sizes = np.diff(ds.metadata.query_boundaries)
    assert (sizes == per).all()
    b = lgb.train({"objective": "lambdarank", "num_leaves": 7,
                   "verbose": -1, "two_round": True, "min_data_in_leaf": 5},
                  lgb.Dataset(path, params={"two_round": True}),
                  num_boost_round=4)
    assert len(b._booster.models) == 4


def test_csc_and_coo_inputs():
    """CSC/COO inputs ride the same CSR adapter (reference: the CSC path
    of LGBM_DatasetCreateFromCSC, src/c_api.cpp)."""
    rng = np.random.RandomState(5)
    dense = np.where(rng.rand(1500, 8) < 0.2, rng.randn(1500, 8), 0.0)
    y = (dense[:, 0] + dense[:, 1] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1}
    ref = lgb.train(params, lgb.Dataset(dense, label=y),
                    num_boost_round=5).predict(dense)
    for maker in (sp.csc_matrix, sp.coo_matrix):
        b = lgb.train(params, lgb.Dataset(maker(dense), label=y),
                      num_boost_round=5)
        np.testing.assert_allclose(b.predict(dense), ref, rtol=1e-6)
