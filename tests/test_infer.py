"""lambdagap_tpu.infer — compiled forest artifacts + traversal engine.

The ISSUE-16 acceptance surface: ``predict_engine=compiled`` is
bit-identical (``array_equal``, never closeness) to the sequential scan
oracle across the full parity matrix — ragged row tiles, NaN/default-left
routing, zero-as-missing, multi-word categorical bitsets, multiclass
routing, linear leaves, early-stop margins, mixed constant/linear
forests — plus the artifact contract: content-addressed round-trip,
hash-mismatch rejection (loud local-compile fallback, never a wrong-model
serve), exact dead-branch pruning, same-structure tree merging, and
cross-model padding buckets (ModelPack) matching each member cache
bit-for-bit through every serve path.
"""
import numpy as np
import pytest

import lambdagap_tpu as lgb
from lambdagap_tpu.infer import (ArtifactMismatch, ArtifactStore,
                                 ForestArtifact, compile_forest,
                                 source_key_of)

# tpu_fast_predict_rows=0 drops the native small-batch shortcut to its
# 512-row floor; all parity predicts use >512 rows so the engine under
# test (not the host reference) answers
DEVICE_PARAMS = {"verbose": -1, "tpu_fast_predict_rows": 0,
                 "predict_engine": "compiled"}


def _flip(b, engine):
    gb = b._booster
    gb.config.predict_engine = engine
    gb.invalidate_predict_cache()
    return gb


def _assert_engine_parity(b, X, **predict_kw):
    """compiled vs the sequential scan oracle: exact equality."""
    _flip(b, "compiled")
    got = b.predict(X, **predict_kw)
    _flip(b, "scan")
    ref = b.predict(X, **predict_kw)
    _flip(b, "compiled")
    assert got.shape == ref.shape
    assert np.array_equal(got, ref), \
        f"compiled != scan (max diff {np.nanmax(np.abs(got - ref))})"
    return got


def _train(params, X, y, rounds=8, cats="auto"):
    return lgb.train({**DEVICE_PARAMS, **params},
                     lgb.Dataset(X, label=y, categorical_feature=cats),
                     num_boost_round=rounds)


def _data(rows=700, feats=10, seed=0, nan_col=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, feats).astype(np.float32)
    if nan_col is not None:
        X[::7, nan_col] = np.nan          # exercises default-left routing
    y = (X[:, 0] + 0.5 * X[:, 1] * np.nan_to_num(X[:, 2]) > 0)
    return X, y.astype(np.float32)


# -- engine parity matrix ------------------------------------------------
def test_parity_binary_nan_default_left():
    X, y = _data()
    b = _train({"objective": "binary", "num_leaves": 15}, X, y)
    _assert_engine_parity(b, X)
    _assert_engine_parity(b, X, raw_score=True)


def test_parity_ragged_row_counts():
    """Odd row counts (no power-of-two or tile multiple) walk the node
    blocks exactly like the scan oracle."""
    X, y = _data(rows=601)
    b = _train({"objective": "binary", "num_leaves": 15}, X, y)
    _assert_engine_parity(b, X)
    _assert_engine_parity(b, X[:599])


def test_parity_zero_as_missing():
    X, y = _data(nan_col=None)
    X[::5, 1] = 0.0
    X[::3, 0] = 0.0
    b = _train({"objective": "binary", "num_leaves": 15,
                "zero_as_missing": True}, X, y)
    _assert_engine_parity(b, X)


def test_parity_categorical_multiword_bitsets():
    """A 70-category feature needs a 3-word (u32) bitset per node — the
    artifact's deduped cat_table and the kernel's word/bit gather must
    route identically to the scan oracle."""
    rng = np.random.RandomState(3)
    X, y = _data(seed=3)
    X[:, 0] = rng.randint(0, 70, size=X.shape[0]).astype(np.float32)
    y = ((X[:, 0].astype(int) % 5 < 2) ^ (X[:, 1] > 0)).astype(np.float32)
    b = _train({"objective": "binary", "num_leaves": 31,
                "min_data_per_group": 5}, X, y, rounds=10, cats=[0])
    art = compile_forest(b._booster)
    assert art.meta["cat_words"] >= 3     # the multi-word case, really
    _assert_engine_parity(b, X)


def test_parity_multiclass_routing():
    rng = np.random.RandomState(4)
    X = rng.randn(700, 8).astype(np.float32)
    X[::9, 2] = np.nan
    y = (X[:, 0] > 0).astype(int) + (X[:, 1] > 0.5)
    b = _train({"objective": "multiclass", "num_class": 3,
                "num_leaves": 15}, X, y, rounds=9)
    out = _assert_engine_parity(b, X)
    assert out.shape == (700, 3)
    _assert_engine_parity(b, X, raw_score=True)


def test_parity_linear_leaves():
    X, y = _data()
    yr = X[:, 0] * 2.0 + np.nan_to_num(X[:, 3]) + 0.1 * y
    b = _train({"objective": "regression", "num_leaves": 7,
                "linear_tree": True}, X, yr)
    assert compile_forest(b._booster).meta["has_linear"]
    _assert_engine_parity(b, X)


def test_parity_mixed_constant_linear_forest():
    """A forest mixing linear-leaf trees and constant trees (the shape a
    linear_tree continuation of a constant model produces)."""
    X, y = _data()
    yr = X[:, 0] - 0.5 * X[:, 1]
    b_lin = _train({"objective": "regression", "num_leaves": 7,
                    "linear_tree": True}, X, yr, rounds=4)
    b_const = _train({"objective": "regression", "num_leaves": 7}, X, yr,
                     rounds=4)
    gb = b_lin._booster
    gb.models = list(gb.host_models) + list(b_const._booster.host_models)
    gb.iter_ = len(gb.models)
    gb.invalidate_predict_cache()
    assert compile_forest(gb).meta["has_linear"]
    _assert_engine_parity(b_lin, X)


def test_parity_early_stop_margins():
    """pred_early_stop replays at the exact same tree boundaries as the
    scan engine — margins checked at (i % freq) == 0, same top-2 rule."""
    X, y = _data()
    b = _train({"objective": "binary", "num_leaves": 15,
                "pred_early_stop": True, "pred_early_stop_freq": 3,
                "pred_early_stop_margin": 0.5}, X, y, rounds=12)
    _assert_engine_parity(b, X)
    rng = np.random.RandomState(5)
    X3 = rng.randn(700, 8).astype(np.float32)
    y3 = (X3[:, 0] > 0).astype(int) + (X3[:, 1] > 0.5)
    b3 = _train({"objective": "multiclass", "num_class": 3,
                 "num_leaves": 15, "pred_early_stop": True,
                 "pred_early_stop_freq": 2,
                 "pred_early_stop_margin": 1.5}, X3, y3, rounds=9)
    _assert_engine_parity(b3, X3)


def test_leaf_index_engine_invariant():
    """predict(pred_leaf=True) under the compiled engine routes through
    the tensor leaf path — leaf ids are engine-invariant by contract."""
    X, y = _data()
    b = _train({"objective": "binary", "num_leaves": 15}, X, y)
    _flip(b, "compiled")
    got = b.predict(X, pred_leaf=True)
    _flip(b, "scan")
    ref = b.predict(X, pred_leaf=True)
    assert np.array_equal(got, ref)


# -- the artifact: compile, round-trip, hash admission -------------------
def test_artifact_roundtrip_and_content_hash():
    X, y = _data()
    b = _train({"objective": "binary", "num_leaves": 15}, X, y)
    art = compile_forest(b._booster)
    payload = art.to_bytes()
    back = ForestArtifact.from_bytes(payload, expect_hash=art.hash)
    assert back.hash == art.hash
    assert back.meta == art.meta
    assert sorted(back.buffers) == sorted(art.buffers)
    for k in art.buffers:
        assert np.array_equal(back.buffers[k], art.buffers[k])
        assert back.buffers[k].dtype == art.buffers[k].dtype
    # deterministic: re-serialization is byte-identical
    assert back.to_bytes() == payload
    # same source, fresh compile -> same source key AND same content hash
    art2 = compile_forest(b._booster)
    assert art2.source_key == art.source_key
    assert art2.hash == art.hash


def test_artifact_mismatch_is_loud():
    X, y = _data()
    b = _train({"objective": "binary", "num_leaves": 15}, X, y)
    payload = compile_forest(b._booster).to_bytes()
    with pytest.raises(ArtifactMismatch):
        ForestArtifact.from_bytes(payload, expect_hash="0" * 64)
    torn = payload[: len(payload) - 8]
    with pytest.raises(ArtifactMismatch):
        ForestArtifact.from_bytes(torn)
    flipped = bytearray(payload)
    flipped[-3] ^= 0x40
    with pytest.raises(ArtifactMismatch):
        ForestArtifact.from_bytes(bytes(flipped))
    with pytest.raises(ArtifactMismatch):
        ForestArtifact.from_bytes(b"NOTANARTIFACT" + payload)


def test_artifact_store_admission():
    X, y = _data()
    b = _train({"objective": "binary", "num_leaves": 15}, X, y)
    gb = b._booster
    art = compile_forest(gb)
    store = ArtifactStore()
    # a corrupt admission must not mutate the store
    bad = bytearray(art.to_bytes())
    bad[-1] ^= 0xFF
    with pytest.raises(ArtifactMismatch):
        store.admit_bytes(bytes(bad))
    assert len(store) == 0
    got = store.admit_bytes(art.to_bytes(), expect_hash=art.hash)
    assert got.hash == art.hash
    assert store.get(source_key_of(gb, 0, -1)).hash == art.hash
    assert store.get_by_hash(art.hash) is not None
    assert store.get("no-such-source-key") is None


# -- pruning and merging -------------------------------------------------
def test_prune_dominated_branch_exact():
    """A split dominated by an ancestor on the same feature (x <= t1 then
    x <= t2 with t2 > t1) has an unreachable arm; the compiler bypasses
    the decided node WITHOUT renumbering leaves, and routing stays
    bit-identical to the unpruned scan oracle."""
    X, y = _data(feats=4, nan_col=None)
    b = _train({"objective": "binary", "num_leaves": 8,
                "num_trees": 2}, X, y, rounds=2)
    gb = b._booster
    base = compile_forest(gb)
    # force domination: put every split on feature 0 and raise every
    # non-root threshold ABOVE the max, so each inner node's right arm is
    # reachable only through a root split that already decided
    # x0 <= threshold_root < new threshold
    text = gb.save_model_to_string()
    out_lines = []
    for line in text.split("\n"):
        if line.startswith("threshold="):
            vals = [float(v) for v in line.split("=", 1)[1].split()]
            vals = [vals[0]] + [abs(v) + 1e6 for v in vals[1:]]
            line = "threshold=" + " ".join(repr(v) for v in vals)
        elif line.startswith("split_feature="):
            n = len(line.split("=", 1)[1].split())
            line = "split_feature=" + " ".join(["0"] * n)
        out_lines.append(line)
    b2 = lgb.Booster(model_str="\n".join(out_lines),
                     params=dict(DEVICE_PARAMS))
    gb2 = b2._booster
    art = compile_forest(gb2)
    assert art.meta["nodes_pruned"] > 0
    assert base.meta["nodes_pruned"] == 0   # the real model had no dead arm
    _assert_engine_parity(b2, X)
    # pruning off: same outputs, zero pruned
    gb2.config.infer_prune = False
    gb2.invalidate_predict_cache()
    assert compile_forest(gb2).meta["nodes_pruned"] == 0
    _assert_engine_parity(b2, X)


def test_merge_tiled_trees_shares_traversal():
    """An iteration-tiled forest (every iteration the same structures) collapses to the
    base structure count: merged trees share one traversal group while
    keeping their own leaf values — outputs stay exact."""
    X, y = _data()
    b = _train({"objective": "regression", "num_leaves": 15}, X,
               X[:, 0] - X[:, 1], rounds=5)
    gb = b._booster
    gb.models = list(gb.host_models) * 6          # 30 trees, 5 structures
    gb.iter_ = len(gb.models)
    gb.invalidate_predict_cache()
    art = compile_forest(gb)
    assert art.meta["num_trees"] == 30
    assert art.meta["num_groups"] == 30 - art.meta["trees_merged"]
    assert art.meta["trees_merged"] >= 25         # 5 unique structures
    _assert_engine_parity(b, X)
    gb.config.infer_merge_trees = False
    gb.invalidate_predict_cache()
    assert compile_forest(gb).meta["num_groups"] == 30
    _assert_engine_parity(b, X)


def test_quant_u8_overflow_errors_instead_of_widening():
    X, y = _data(rows=1500)
    b = _train({"objective": "regression", "num_leaves": 31}, X,
               np.sin(np.nan_to_num(X).sum(axis=1)), rounds=30)
    gb = b._booster
    assert compile_forest(gb).meta["thr_bits"] == 16   # auto widened
    gb.config.infer_quant = "u8"
    gb.invalidate_predict_cache()
    with pytest.raises(ValueError):
        compile_forest(gb)


# -- palette edges (ISSUE 17) -------------------------------------------
def test_palette_u16_widened_forest_exact_and_admitted():
    """>256 unique thresholds: the palette auto-widens u8 -> u16 and the
    widened codes must still route bit-identically to the scan oracle —
    AND the widened artifact must survive the hash-verified store
    admission round-trip (the fleet path serves the u16 palette too)."""
    X, y = _data(rows=1500)
    b = _train({"objective": "regression", "num_leaves": 31}, X,
               np.sin(np.nan_to_num(X).sum(axis=1)), rounds=30)
    gb = b._booster
    art = compile_forest(gb)
    assert art.meta["thr_bits"] == 16
    assert art.buffers["node_thr"].dtype == np.uint16
    assert len(art.buffers["thr_table"]) > 256     # the widening reason
    _assert_engine_parity(b, X)
    store = ArtifactStore()
    got = store.admit_bytes(art.to_bytes(), expect_hash=art.hash)
    assert got.hash == art.hash
    assert np.array_equal(got.buffers["node_thr"],
                          art.buffers["node_thr"])


def test_palette_constant_only_forest_exact_and_admitted():
    """Every tree a single constant leaf (min_data_in_leaf > rows kills
    all splits): zero internal nodes, an empty threshold palette — the
    degenerate artifact must compile, round-trip the store, and predict
    bit-identically to the scan oracle."""
    X, y = _data()
    b = _train({"objective": "regression", "num_leaves": 7,
                "min_data_in_leaf": 10_000}, X, X[:, 0], rounds=3)
    gb = b._booster
    assert all(t.num_internal == 0 for t in gb.host_models)
    art = compile_forest(gb)
    # splitless rounds may stop boosting early; whatever trained, every
    # tree is a stump and the artifact must carry them all
    assert art.meta["num_trees"] == len(gb.host_models) >= 1
    got = _assert_engine_parity(b, X)
    assert np.ptp(got) == 0                       # constant forest output
    store = ArtifactStore()
    assert store.admit_bytes(art.to_bytes(),
                             expect_hash=art.hash).hash == art.hash


def test_palette_all_dead_branches_prune_to_root():
    """Every non-root split shares the root's feature AND threshold, so
    every one of them is decided by the root: the compiler bypasses ALL
    of them (nodes_pruned == num_internal - 1 per tree) and the pruned
    skeleton still routes bit-identically to the UNpruned scan oracle."""
    X, y = _data(feats=4, nan_col=None)
    b = _train({"objective": "binary", "num_leaves": 8}, X, y, rounds=2)
    gb = b._booster
    text = gb.save_model_to_string()
    out_lines = []
    for line in text.split("\n"):
        if line.startswith("threshold="):
            vals = line.split("=", 1)[1].split()
            line = "threshold=" + " ".join([vals[0]] * len(vals))
        elif line.startswith("split_feature="):
            n = len(line.split("=", 1)[1].split())
            line = "split_feature=" + " ".join(["0"] * n)
        elif line.startswith("decision_type="):
            # uniform numerical/default-left: a default-direction mismatch
            # with the ancestor keeps a same-threshold node LIVE (the
            # missing path is this node's to decide), which is not the
            # edge under test
            n = len(line.split("=", 1)[1].split())
            line = "decision_type=" + " ".join(["2"] * n)
        out_lines.append(line)
    b2 = lgb.Booster(model_str="\n".join(out_lines),
                     params=dict(DEVICE_PARAMS))
    gb2 = b2._booster
    art = compile_forest(gb2)
    expect = sum(t.num_internal - 1 for t in gb2.host_models
                 if t.num_internal > 0)
    assert art.meta["nodes_pruned"] == expect > 0
    _assert_engine_parity(b2, X)
    store = ArtifactStore()
    assert store.admit_bytes(art.to_bytes(),
                             expect_hash=art.hash).hash == art.hash


# -- cross-model packing (ModelPack) ------------------------------------
def _cache(b, **kw):
    from lambdagap_tpu.serve.cache import CompiledForestCache
    return CompiledForestCache(b._booster, **kw)


def test_pack_cross_model_bit_identity():
    """Mixed per-tenant batches through ONE packed executable match each
    member cache serving its rows alone — exactly, including mixed
    num_class and mixed feature widths across members."""
    from lambdagap_tpu.serve.cache import ModelPack
    X, y = _data()
    b1 = _train({"objective": "binary", "num_leaves": 15}, X, y)
    b2 = _train({"objective": "regression", "num_leaves": 7}, X[:, :6],
                X[:, 0] * 2.0, rounds=5)
    rng = np.random.RandomState(9)
    X3 = rng.randn(700, 8).astype(np.float32)
    y3 = (X3[:, 0] > 0).astype(int) + (X3[:, 1] > 0.5)
    b3 = _train({"objective": "multiclass", "num_class": 3,
                 "num_leaves": 15}, X3, y3, rounds=6)
    caches = {"a": _cache(b1), "b": _cache(b2), "c": _cache(b3)}
    pack = ModelPack(caches, buckets=(8, 64, 512))
    parts = [("a", X[:37], False), ("b", X[37:60, :6], False),
             ("c", X3[:25], False), ("a", X[60:61], True)]
    outs = pack.predict_mixed(parts)
    for (name, Xp, raw), got in zip(parts, outs):
        ref = caches[name].predict(Xp, raw_score=raw)
        assert np.array_equal(got, ref), f"pack != solo for {name!r}"


def test_pack_rejects_early_stop_members():
    from lambdagap_tpu.serve.cache import ModelPack
    X, y = _data()
    b = _train({"objective": "binary", "num_leaves": 15,
                "pred_early_stop": True, "pred_early_stop_freq": 2}, X, y)
    with pytest.raises(ValueError):
        ModelPack({"es": _cache(b)})


# -- serve paths: registry / router / TCP frontend ----------------------
def test_compiled_engine_through_every_serve_path():
    """The same rows through the server, the registry route, the router,
    and the socket frontend — all bit-identical to the compiled cache
    (which test_pack/... pins to the scan oracle)."""
    from lambdagap_tpu.serve import (FrontendClient, LocalReplica, Router,
                                     ServeFrontend)
    X, y = _data()
    b = _train({"objective": "binary", "num_leaves": 15}, X, y)
    ref = _cache(b).predict(X[:111])
    with b.as_server(buckets=(8, 64), warmup=False) as s:
        assert s.registry.entry("default").engine == "compiled"
        got = np.concatenate([s.predict(X[i:i + 37])
                              for i in range(0, 111, 37)])
        assert np.array_equal(got, ref)
        r = Router([LocalReplica("r0", s)])
        got_r = np.concatenate([r.predict(X[i:i + 37])
                                for i in range(0, 111, 37)])
        assert np.array_equal(got_r, ref)
        with ServeFrontend(s) as fe:
            with FrontendClient("127.0.0.1", fe.port) as cli:
                got_f = np.concatenate([cli.predict(X[i:i + 37])
                                        for i in range(0, 111, 37)])
                assert np.array_equal(got_f, ref)
                # the artifact plane over the wire round-trips exactly
                payload = cli.fetch_artifact()
                h = s.registry.get("default").artifact_hash
                assert cli.push_artifact(payload, expect_hash=h) == h
                with pytest.raises(ArtifactMismatch):
                    cli.push_artifact(payload[:-4])


def test_fleet_shares_one_compile_by_hash():
    """Replica B admits A's artifact, then places the model: B's build is
    a shared admission, not a second compile — and serves bit-identically
    to A. A corrupt admission raises and the subsequent build falls back
    to a loud LOCAL compile (never a wrong-model serve)."""
    from lambdagap_tpu.serve import ForestServer
    X, y = _data()
    b_model = _train({"objective": "binary", "num_leaves": 15}, X, y)
    b_boot = _train({"objective": "binary", "num_leaves": 7}, X, y,
                    rounds=2)
    A = ForestServer(b_model, warmup=False)
    try:
        payload = A.artifact_bytes()
        h = A.registry.get("default").artifact_hash
        assert A.stats.snapshot()["cache"]["compiles_local"] == 1
        assert h in A.registry.snapshot()["models"]["default"][
            "artifact_hash"]
        B = ForestServer(b_boot, warmup=False)
        try:
            with pytest.raises(ArtifactMismatch):
                B.admit_artifact(payload, expect_hash="f" * 64)
            assert B.admit_artifact(payload, expect_hash=h) == h
            B.add_model("m1", b_model._booster)
            snap = B.stats.snapshot()["cache"]
            assert snap["compiles_shared"] == 1     # the admitted one
            assert snap["compiles_local"] == 1      # only B's boot model
            assert B.registry.get("m1").artifact_hash == h
            assert np.array_equal(B.predict(X[:64], model="m1"),
                                  A.predict(X[:64]))
        finally:
            B.close()
    finally:
        A.close()


def test_packed_serve_dispatches_once_per_mixed_batch():
    """serve_pack_models: a mixed 3-tenant batch runs ONE packed dispatch
    and every tenant's rows match its solo cache exactly."""
    from lambdagap_tpu.serve import ForestServer
    X, y = _data()
    b1 = _train({"objective": "binary", "num_leaves": 15,
                 "serve_pack_models": True}, X, y)
    b2 = _train({"objective": "binary", "num_leaves": 7}, X, 1.0 - y,
                rounds=4)
    b3 = _train({"objective": "regression", "num_leaves": 7}, X,
                X[:, 0], rounds=4)
    s = ForestServer(b1, warmup=False, max_delay_ms=30.0, workers=1)
    try:
        s.add_model("t2", b2._booster)
        s.add_model("t3", b3._booster)
        futs = [s.submit(X[:13]), s.submit(X[13:20], model="t2"),
                s.submit(X[20:31], model="t3")]
        outs = [f.result(30.0) for f in futs]
        snap = s.stats_snapshot()
        assert snap["cache"]["packed_dispatches"] >= 1
        assert np.array_equal(outs[0].values,
                              s.registry.get("default").predict(X[:13]))
        assert np.array_equal(outs[1].values,
                              s.registry.get("t2").predict(X[13:20]))
        assert np.array_equal(outs[2].values,
                              s.registry.get("t3").predict(X[20:31]))
    finally:
        s.close()
