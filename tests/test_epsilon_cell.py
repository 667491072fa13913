"""The wide dense job of ``epsilon-train`` (2,000 real-valued features under
2^20 rows, so ``tree_layout=auto`` resolves ``gather``) at CPU sizes: the
Pallas kernel past two feature tiles (interpreted), the gather layout
against the sorted one, the reference judging a gather run, the cell's
data generator, and the set-up repairs this width needed (bin finding on a
pool of threads, no conflict search where no two features fit a bundle)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lambdagap_tpu as lgb
from benchmark import manifest
from benchmark.datagen import epsilon_like
from benchmark.reference import gbdt_check, wide_check
from lambdagap_tpu.config import Config
from lambdagap_tpu.data import dataset as dataset_mod
from lambdagap_tpu.data.bundling import build_bundle
from lambdagap_tpu.ops import hist_pallas

CFG = manifest.load_json("configs", "epsilon.json")
LIMITS = manifest.load_json("limits", "epsilon-train.json")["limits"]


def _params(layout, **extra):
    return dict({"objective": "binary", "num_leaves": 7, "max_bin": 255,
                 "learning_rate": 0.1, "min_data_in_leaf": 1,
                 "min_sum_hessian_in_leaf": 5, "tpu_fused_learner": 1,
                 "tpu_hist_impl": "pallas", "tree_layout": layout,
                 "verbose": -1}, **extra)


@pytest.fixture(scope="module")
def wide():
    """600 features (Fp 640: five 128-lane kernel tiles, past
    ``_pick_blocks``' switch from one tile) x 6,000 rows of the cell's law,
    and three trees grown on them under each layout."""
    data = epsilon_like.generate({"num_features": 600}, 2147843001, 6000, 0)
    out = {"data": data}
    for layout in ("gather", "sorted"):
        params = _params(layout)
        bst = lgb.Booster(params, lgb.Dataset(data["X"], label=data["y"],
                                              params=params))
        for _ in range(3):
            bst.update()
        gb = bst._booster
        out[layout] = {"learner": gb.learner, "params": params,
                       "text": bst.model_to_string(),
                       "scores": np.asarray(gb.scores)}
    return out


def test_the_kernel_runs_five_tiles_at_600_features():
    assert hist_pallas._pick_blocks(600, 256, 4096) == (1024, 128)
    assert hist_pallas.row_tiles(4096, 600, 256) == 4096 * 5
    # the cell's own shape: 16 tiles over Fp 2,048
    assert hist_pallas.row_tiles(4096, 2000, 256) == 4096 * 16
    # one tile where the whole feature axis fits (higgs, istella-s)
    assert hist_pallas.row_tiles(32768, 28, 256) == 32768
    assert hist_pallas.row_tiles(8192, 220, 256) == 8192 * 2


def test_gather_and_sorted_grow_the_same_trees_at_600_features(wide):
    g, s = wide["gather"], wide["sorted"]
    assert (type(g["learner"]).__name__, g["learner"].layout,
            g["learner"].hist_impl) == ("FusedTreeLearner", "gather",
                                        "pallas")
    assert s["learner"].layout == "sorted"
    assert g["text"].split("end of trees")[0] \
        == s["text"].split("end of trees")[0]
    np.testing.assert_array_equal(g["scores"], s["scores"])
    trees = gbdt_check.parse_model(g["text"])
    assert len(trees) == 3 and all(t["num_leaves"] == 7 for t in trees)


def test_the_reference_judges_the_gather_run_within_the_cells_limits(wide):
    g = wide["gather"]
    numbers = gbdt_check.check(g["text"], wide["data"], dict(
        g["params"], objective_params={}), g["scores"], 3, seed=43)
    numbers["learner_mismatch"] = 0.0
    correct, rows = gbdt_check.judge(numbers, LIMITS)
    assert correct, [r for r in rows if not r["ok"]]
    assert numbers["leaf_rows"] == 0 and numbers["trees_followed"] == 3


def test_the_auto_layout_of_the_cell_is_gather():
    """Under 2^20 rows ``auto`` resolves ``gather``: the configuration
    forces no layout, and the learner it names is what the default gives."""
    assert CFG["num_rows"] < 1 << 20
    assert "tree_layout" not in CFG["params"]
    assert CFG["learner"]["layout"] == "gather"
    data = epsilon_like.generate({"num_features": 64}, 7, 512, 0)
    params = _params("auto", tpu_hist_impl="onehot")
    bst = lgb.Booster(params, lgb.Dataset(data["X"], label=data["y"],
                                          params=params))
    assert bst._booster.learner.layout == "gather"


def test_one_kernel_window_at_2000_features_is_the_bincount():
    """One ``lg_hist`` call over a 1,024-row window of 2,000 columns (16
    tiles, the last padded from 2,000 to 2,048), interpreted, against
    numpy's ``bincount`` per feature in float64."""
    rng = np.random.default_rng(3)
    rows, feats, bins = 1024, 2000, 256
    x = rng.integers(0, 255, size=(rows, feats), dtype=np.uint8)
    g = rng.standard_normal(rows).astype(np.float32)
    h = rng.uniform(0.05, 0.25, rows).astype(np.float32)
    live = 1000                       # a ragged tail, masked in the kernel
    valid = np.arange(rows) < live
    gh8 = hist_pallas.pack_gh8(jnp.asarray(g), jnp.asarray(h),
                               jnp.asarray(valid))
    got = np.asarray(hist_pallas.hist_pallas(jnp.asarray(x), gh8, bins,
                                             jnp.int32(live)))
    assert got.shape == (feats, bins, 3)
    want = np.zeros((feats, bins, 3))
    mass = np.zeros((feats, bins, 2))
    for f in range(feats):
        col = x[:live, f]
        for k, v in enumerate((g[:live], h[:live])):
            want[f, :, k] = np.bincount(col, v.astype(np.float64), bins)
            mass[f, :, k] = np.bincount(col, np.abs(v.astype(np.float64)),
                                        bins)
        want[f, :, 2] = np.bincount(col, minlength=bins)
    np.testing.assert_array_equal(got[:, :, 2], want[:, :, 2])
    # the channels are (hi, lo) bf16 pairs: a value keeps 16 bits of its
    # mantissa, so a bin is off by at most ~2^-16 of its rows' |values|
    assert np.all(np.abs(got[:, :, :2] - want[:, :, :2]) <= 2.0 ** -15 * mass)
    assert np.abs(got[:, :, :2] - want[:, :, :2]).max() > 0   # not exact


def test_the_generator_is_the_cells_law():
    cfg = {"num_features": CFG["num_features"]}
    a = epsilon_like.generate(cfg, 2147843002, 3000, 1000)
    b = epsilon_like.generate(cfg, 2147843002, 3000, 1000)
    c = epsilon_like.generate(cfg, 2147843003, 3000, 1000)
    for key in ("X", "y", "X_hold", "y_hold"):
        np.testing.assert_array_equal(a[key], b[key])
    assert not np.array_equal(a["X"], c["X"])
    assert a["X"].shape == (3000, 2000) and a["X"].dtype == np.float32
    assert a["group"] is None
    norms = np.linalg.norm(np.concatenate([a["X"], a["X_hold"]]), axis=1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-5)
    assert not np.isnan(a["X"]).any()
    for y in (a["y"], a["y_hold"], c["y"]):
        assert 0.45 <= float(np.mean(y)) <= 0.55
    # the label is learnable: the law's linear part alone ranks it
    laws = epsilon_like._laws(2000)
    score = a["X"][:, laws["linear"]] @ laws["w"]
    from benchmark.reference.quality import auc
    assert auc(a["y"], score) > 0.7


def test_rows_drawn_on_any_thread_count_are_the_same(monkeypatch):
    cfg = {"num_features": 64}
    a = epsilon_like.generate(cfg, 11, 9000, 0)
    monkeypatch.setattr(epsilon_like, "THREADS", 1)
    b = epsilon_like.generate(cfg, 11, 9000, 0)
    np.testing.assert_array_equal(a["X"], b["X"])
    np.testing.assert_array_equal(a["y"], b["y"])


def _mappers(X, workers, monkeypatch, **params):
    monkeypatch.setattr(dataset_mod, "_bin_workers", lambda: workers)
    ds = dataset_mod.BinnedDataset()
    ds.num_data, ds.num_total_features = X.shape
    ds._find_bins(X, Config.from_params(dict({"verbose": -1}, **params)),
                  {3})
    return [json.dumps({k: np.asarray(v).tolist() if k == "bin_upper_bound"
                        else v for k, v in vars(m).items()}, default=str)
            for m in ds.mappers]


@pytest.mark.parametrize("max_bin", [15, 255])
def test_bin_finding_on_a_pool_is_one_thread_s(monkeypatch, max_bin):
    """Mappers found on 4 threads over column blocks are the single
    thread's, for a matrix wider than a block (continuous, integer,
    mostly-zero, NaN-holding and categorical columns)."""
    rng = np.random.default_rng(5)
    n, f = 3000, 150
    X = rng.standard_normal((n, f))
    X[:, 1::5] = rng.integers(0, 7, (n, len(range(1, f, 5))))
    X[:, 2::5] *= rng.random((n, len(range(2, f, 5)))) < 0.1
    X[rng.random((n, f)) < 0.02] = np.nan
    X[:, 3] = rng.integers(0, 40, n)
    one = _mappers(X, 1, monkeypatch, max_bin=max_bin)
    four = _mappers(X, 4, monkeypatch, max_bin=max_bin)
    assert len(one) == f and one == four


def test_no_conflict_search_where_no_two_features_fit_a_bundle():
    """Two dense 255-bin columns need 509 bins: past a bundle's 256 no
    bundle can form, and ``build_bundle`` says so before it samples. A
    narrow pair still bundles."""
    rng = np.random.default_rng(9)
    binned = rng.integers(0, 255, (5000, 6), dtype=np.uint8)
    assert build_bundle(binned, np.full(6, 255, np.int32),
                        np.zeros(6, np.int32), 0.0) is None
    sparse = np.zeros((5000, 3), np.uint8)
    sparse[:2000, 0] = rng.integers(1, 4, 2000)
    sparse[2000:4000, 1] = rng.integers(1, 4, 2000)
    sparse[4000:, 2] = rng.integers(1, 4, 1000)
    bun = build_bundle(sparse, np.full(3, 4, np.int32),
                       np.zeros(3, np.int32), 0.0)
    assert bun is not None and bun.num_cols == 1


def _lowered_tree(layout):
    data = epsilon_like.generate({"num_features": 64}, 5, 1024, 0)
    params = _params(layout, tpu_hist_impl="onehot")
    learner = lgb.Booster(params, lgb.Dataset(
        data["X"], label=data["y"], params=params))._booster.learner
    n, c = learner.hx_rows.shape
    q = jnp.zeros(1, jnp.int8)
    grad = jnp.zeros(n, jnp.float32)
    mask = jnp.ones(1, bool)
    srows = learner._layout_jit(grad, grad, mask, learner.hx_rows, q, q,
                                has_mask=False) if layout == "sorted" \
        else jnp.zeros((1, 1), jnp.uint32)
    return learner._train_jit.lower(
        grad, grad, mask, learner._feature_mask(), learner.hx_rows,
        learner.x_cols, srows, q, q, jnp.float32(1), jnp.float32(1),
        jnp.zeros((2, 2), jnp.uint32), has_mask=False).as_text(
            debug_info=True)


def test_the_gather_layout_names_its_repack_and_its_row_gather():
    """``pack_rows`` opens inside ``tree_init`` and ``hist_gather`` inside
    ``histogram``, so the accepted selectors on the outer scopes still read
    them; the sorted layout's tree program opens neither."""
    import re
    gather = set(re.findall(r'loc\("([^"]*)"', _lowered_tree("gather")))
    assert any(re.search(r"(^|/)tree_init/pack_rows(/|$)", p)
               for p in gather)
    assert any(re.search(r"(^|/)histogram/.*hist_gather(/|$)", p)
               for p in gather)
    assert not any(re.search(r"(^|/)(pack_rows|hist_gather)(/|$)", p)
                   and not re.search(r"(^|/)(tree_init|histogram)/", p)
                   for p in gather)
    sorted_ = _lowered_tree("sorted")
    assert "pack_rows" not in sorted_ and "hist_gather" not in sorted_


@pytest.mark.parametrize("layout", ["gather", "sorted"])
def test_the_record_counts_the_kernels_row_tiles(layout):
    """``hist_row_tiles``: every histogram window's kernel call, its padded
    rows times its feature tiles; here 64 columns, one tile, so the
    windows' rows."""
    data = epsilon_like.generate({"num_features": 64}, 8, 700, 0)
    params = _params(layout, telemetry=True, num_leaves=4)
    bst = lgb.Booster(params, lgb.Dataset(data["X"], label=data["y"],
                                          params=params))
    bst.update()
    gb = bst._booster
    tel = gb.telemetry
    tel.close()
    counts = list(tel.records)[-1]["counts"]
    w = gb.learner._window(gb.learner.num_data)
    assert counts["hist_row_tiles"] \
        == counts["hist_trips"] * hist_pallas.row_tiles(w, 64, 256)
    assert counts["hist_row_tiles"] == counts["hist_trips"] * max(w, 256)


def test_the_wide_traffic_judges_split_gap_on_the_configurations_sample(
        monkeypatch):
    """``train_window_wide`` runs ``train_window.run`` as it is, with the
    ``gbdt_check`` it reads swapped for one whose ``check`` is
    ``wide_check.check`` at the configuration's ``reference.sample_rows``,
    and puts the module back; ``wide_check`` puts back the search it
    swaps."""
    from benchmark.drivers import train_window, train_window_wide
    cell = manifest.cell("epsilon-train")
    assert cell["traffic"]["driver"] == "train_window_wide"
    seen = {}

    def check(*args, **kw):
        seen.update(kw)
        assert gbdt_check._best_gain is wide_check.best_gain
        return {}

    def body(ctx):
        assert train_window.gbdt_check is not gbdt_check
        assert train_window.gbdt_check.parse_model is gbdt_check.parse_model
        return train_window.gbdt_check.check(None, seconds={})
    monkeypatch.setattr(gbdt_check, "check", check)
    monkeypatch.setattr(train_window, "run", body)
    assert train_window_wide.run({"cell": cell, "log": lambda m: None}) == {}
    assert seen == {"sample_rows": CFG["reference"]["sample_rows"],
                    "seconds": {}}
    assert train_window.gbdt_check is gbdt_check
    assert gbdt_check._best_gain is not wide_check.best_gain


@pytest.mark.parametrize("rows", ["all", "sample", "small", "tiny"])
@pytest.mark.parametrize("minima", [(0.0, 0.0), (5.0, 0.0), (0.5, 20.0),
                                    (1e9, 0.0)])
@pytest.mark.parametrize("threads", [0, 4])
def test_the_block_search_is_gbdt_checks_to_the_bit(rows, minima, threads):
    """``wide_check.best_gain`` against ``gbdt_check._best_gain`` on 150
    features (a constant one, a three-valued one, ten blocks with a ragged
    last), with and without the child minima, on a pool and without."""
    from concurrent.futures import ThreadPoolExecutor
    rng = np.random.default_rng(1)
    n, f = 40000, 150
    cols = rng.standard_normal((f, n)).astype(np.float32)
    cols[3] = 1.5
    cols[7] = rng.integers(0, 3, n)
    g, h = rng.standard_normal(n), rng.uniform(0.01, 0.25, n)
    idx = {"all": np.arange(n),
           "sample": np.sort(rng.choice(n, 30000, replace=False)),
           "small": np.arange(100), "tiny": np.arange(40)}[rows]
    pool = ThreadPoolExecutor(threads) if threads else None
    try:
        args = (cols, idx, g[idx], h[idx], 1.0) + minima
        want = gbdt_check._best_gain(*args, pool=pool)
        assert wide_check.best_gain(*args, pool=pool) == want
    finally:
        if pool is not None:
            pool.shutdown()
