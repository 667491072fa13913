"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

One process, the entry points a user calls (``lgb.Dataset`` / ``lgb.train`` /
``Booster.predict`` / ``booster.as_server()``), data generated from a seed.
Legs run in this order, so the first failure is the most important one; any
failing leg raises, the exit code is non-zero and no result line is printed.

  device     platform / device_kind / count as JAX reports them; anything
             but ``tpu`` exits 2 before any training
  kernels    every ``pallas_call`` in the package (``ops/hist_pallas.py``:
             the bf16 and the int8 histogram kernel) compiled by Mosaic
             standalone and compared on the device with the XLA one-hot
             reference
  train      the main leg, HIGGS width: 2^20 x 28, 255 leaves, 255 bins,
             every placement knob at its default, 8 iterations
  parity     fused + Pallas vs the host-driven reference on 65,536 rows
  predict    device predict on every engine, then ``as_server()``
  rank       lambdarank at MSLR width: 136 features, 2,000 queries
  multichip  4x1 and 2x2 vs one device, byte-identical quantized trees;
             only where the process sees >= 4 devices

This is not a benchmark: the seconds it prints are information, under no
metric's name. The report and the telemetry JSONL land in
``chiprun_out/chip_smoke/``. The second-to-last line of stdout is the
summary (per-leg pass, resolved learner, ``"claim":null``); the last line is
the driver's contract and holds exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py                      # on the chip
    python chip_smoke.py --require-multichip  # on the four-chip host
    python chip_smoke.py --rehearse-cpu       # tiny CPU rehearsal (tier-1)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
LEGS = ("kernels", "train", "parity", "predict", "rank", "multichip")
SEED = 21

# Full width: 255 leaves, 255 bins, the published feature counts. The
# rehearsal cuts rows AND widths (Pallas runs in the interpreter on CPU,
# ~100x slow) — it rehearses control flow, nothing else.
FULL = dict(leaves=255, max_bin=255, train_rows=1 << 20, holdout=1 << 16,
            iters=8, parity_rows=1 << 16, parity_iters=4, kernel_rows=8192,
            kernel_shapes=((28, 256, np.uint8), (136, 256, np.uint8),
                           (28, 64, np.uint8), (28, 512, np.uint16)),
            rank_queries=2000, rank_feats=136, rank_iters=5,
            mc_rows=1 << 18, mc_iters=3, serve_sizes=(1, 37, 512))
REHEARSAL = dict(leaves=7, max_bin=255, train_rows=640, holdout=10240,
                 iters=3, parity_rows=640, parity_iters=2, kernel_rows=256,
                 kernel_shapes=((28, 64, np.uint8), (28, 512, np.uint16)),
                 rank_queries=8, rank_feats=16, rank_iters=3,
                 mc_rows=1024, mc_iters=2, serve_sizes=(1, 37, 512))
# holdout AUC the seeded main leg must clear at full size (0.9303 after 8
# iterations on a v5e, my chip run, PR 21; the floor leaves room for f32
# reduction-order noise, not for a broken learner)
AUC_FLOOR = 0.92


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def higgs_like(n: int, f: int, seed: int):
    """Dense f32 features, binary label from a nonlinear score."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    s = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.25 * X[:, 3] ** 2
         - 0.25 + 0.5 * rng.randn(n).astype(np.float32))
    return X, (s > 0).astype(np.float32)


def mslr_like(n_queries: int, f: int, seed: int):
    """~120 docs per query, graded relevance 0..4 skewed toward 0."""
    rng = np.random.RandomState(seed)
    sizes = rng.randint(40, 201, n_queries)
    n = int(sizes.sum())
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f).astype(np.float32) * (rng.rand(f) < 0.2)
    latent = X @ w * 0.6 + rng.randn(n).astype(np.float32)
    y = np.clip(np.floor(latent - latent.mean() + 0.8), 0, 4)
    return X, y.astype(np.float32), sizes


def auc(y: np.ndarray, score: np.ndarray) -> float:
    """Rank AUC with midranks (8 trees leave many tied scores)."""
    _, inv, cnt = np.unique(score, return_inverse=True, return_counts=True)
    ranks = (np.cumsum(cnt) - (cnt - 1) / 2.0)[inv]
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


class Smoke:
    def __init__(self, args) -> None:
        self.rehearsal = args.rehearse_cpu
        self.require_multichip = args.require_multichip
        self.sz = REHEARSAL if self.rehearsal else FULL
        self.report = {"rehearsal": self.rehearsal, "legs": {}}
        # what the CPU rehearsal must FORCE to walk the path the TPU takes
        # by default (fused learner, Pallas in interpret mode, sorted
        # layout); on the chip nothing is forced — the defaults are the
        # thing under test
        self.force = ({"tpu_fused_learner": 1, "tpu_hist_impl": "pallas",
                       "tree_layout": "sorted"} if self.rehearsal else {})

    # -- leg 1 ----------------------------------------------------------
    def device(self) -> None:
        import jax
        d = jax.devices()[0]
        self.report["device"] = {"platform": d.platform,
                                 "kind": d.device_kind,
                                 "count": len(jax.devices())}
        log(f"jax {jax.__version__}  platform={d.platform}  "
            f"device_kind={d.device_kind}  count={len(jax.devices())}")
        if d.platform != "tpu" and not self.rehearsal:
            print(f"chip_smoke: no TPU — JAX found platform={d.platform!r} "
                  f"({d.device_kind}); refusing to run (a CPU rehearsal "
                  "needs --rehearse-cpu and proves nothing about the chip)",
                  file=sys.stderr)
            raise SystemExit(2)
        from lambdagap_tpu import native
        from lambdagap_tpu.utils.compile_cache import configure_compile_cache
        cache_dir = configure_compile_cache()
        reused = os.path.exists(native.artefact_path())
        lib = native.get_lib()
        self.report["jax"] = jax.__version__
        self.report["compile_cache_dir"] = cache_dir
        self.report["native"] = ("unavailable" if lib is None
                                 else "reused" if reused else "built")
        log(f"compile cache: {cache_dir}   native library: "
            f"{self.report['native']}")

    # -- leg 2 ----------------------------------------------------------
    def kernels(self) -> dict:
        """hist_pallas / hist_pallas_q vs the XLA one-hot path, with a
        ragged count and junk past it. Counts exact; grad/hess sums to
        the split-precision tolerance (both sides contract the same
        bf16 hi/lo pair, so only f32 summation order differs); the int8
        kernel exact in every channel."""
        import jax
        import jax.numpy as jnp
        from lambdagap_tpu.ops.hist_pallas import (hist_pallas,
                                                   hist_pallas_q, pack_gh8,
                                                   pack_ghq8)
        from lambdagap_tpu.ops.histogram import histogram_from_rows
        P = self.sz["kernel_rows"]
        count = P - P // 8                   # ragged: the tail is junk
        rng = np.random.RandomState(SEED)
        out = {}
        for F, B, dt in self.sz["kernel_shapes"]:
            bins = jnp.asarray(rng.randint(0, B, (P, F)).astype(dt))
            g = jnp.asarray(rng.randn(P).astype(np.float32))
            h = jnp.asarray(np.abs(rng.randn(P)).astype(np.float32))
            gq = jnp.asarray(rng.randint(-63, 64, P).astype(np.int8))
            hq = jnp.asarray(rng.randint(0, 64, P).astype(np.int8))
            valid = jnp.arange(P) < count
            every = jnp.ones(P, bool)        # junk rows keep live channels
            t0 = time.perf_counter()
            got = jax.block_until_ready(
                hist_pallas(bins, pack_gh8(g, h, every), B, count))
            got_q = jax.block_until_ready(
                hist_pallas_q(bins, pack_ghq8(gq, hq, every), B, count))
            secs = time.perf_counter() - t0
            ref = histogram_from_rows(bins, g, h, valid, B, 4096, "split")
            ref_q = histogram_from_rows(bins, gq.astype(jnp.float32),
                                        hq.astype(jnp.float32), valid, B,
                                        4096, "split")
            got, got_q, ref, ref_q = (np.asarray(a) for a in
                                      (got, got_q, ref, ref_q))
            assert got.shape == ref.shape == (F, B, 3), got.shape
            assert np.isfinite(got).all()
            assert np.array_equal(got[..., 2], ref[..., 2]), \
                f"hist_pallas counts differ at F={F} B={B}"
            assert float(ref[..., 2].sum()) == float(count * F)
            rel = float(np.abs(got[..., :2] - ref[..., :2]).max()
                        / np.abs(ref[..., :2]).max())
            assert rel <= 1e-5, f"hist_pallas rel_err {rel} at F={F} B={B}"
            assert np.array_equal(got_q, ref_q.astype(np.int32)), \
                f"hist_pallas_q differs at F={F} B={B}"
            key = f"F{F}_B{B}_{np.dtype(dt).name}"
            out[key] = {"rel_err": rel, "int8_exact": True,
                        "first_call_s": round(secs, 2)}
            log(f"kernels {key}: rel_err {rel:.1e}, int8 exact, "
                f"compile+run {secs:.1f}s")
        return out

    # -- leg 3 ----------------------------------------------------------
    def train(self) -> dict:
        import lambdagap_tpu as lgb
        from lambdagap_tpu.models.fused_learner import FusedTreeLearner
        from lambdagap_tpu.obs.events import validate_file
        sz = self.sz
        n, hold = sz["train_rows"], sz["holdout"]
        X, y = higgs_like(n + hold, 28, SEED)
        self.X_hold, self.y_hold = X[n:], y[n:]
        tel_path = os.path.join(OUT_DIR, "telemetry_train.jsonl")
        params = {"objective": "binary", "metric": "binary_logloss",
                  "num_leaves": sz["leaves"], "max_bin": sz["max_bin"],
                  "telemetry": True, "telemetry_out": tel_path,
                  "verbose": -1, **self.force}
        t0 = time.perf_counter()
        ds = lgb.Dataset(X[:n], label=y[:n])
        evals: dict = {}
        bst = lgb.train(params, ds, num_boost_round=sz["iters"],
                        valid_sets=[ds], valid_names=["train"],
                        callbacks=[lgb.record_evaluation(evals)])
        wall = time.perf_counter() - t0
        self.booster = bst
        gb = bst._booster
        learner = gb.learner
        resolved = {"learner": type(learner).__name__,
                    "hist_impl": learner.hist_impl,
                    "layout": learner.layout,
                    "residency": learner.residency}
        log(f"train resolved: {resolved}")
        # from the live objects, not from config
        assert type(learner) is FusedTreeLearner, resolved
        assert learner.hist_impl == "pallas", resolved
        assert learner.residency == "hbm", resolved
        assert learner.layout == "sorted", resolved
        tel = gb.telemetry
        summ = tel.summary()
        recs = list(tel.records)
        assert len(recs) == sz["iters"], len(recs)
        assert "layout_apply" in summ["phase_seconds_total"], \
            sorted(summ["phase_seconds_total"])
        warm = gb.config.telemetry_warmup
        steady = sum(r["compiles"]["total"] for r in recs[warm:])
        assert summ["steady_compiles"] == 0 and steady == 0, \
            f"{steady} compiles after warm-up"
        assert validate_file(tel_path) == [], validate_file(tel_path)
        loss = evals["train"]["binary_logloss"]
        assert np.isfinite(loss).all() and loss[-1] < loss[0], loss
        pred = bst.predict(self.X_hold)          # > tpu_fast_predict_rows
        assert pred.shape == (hold,) and np.isfinite(pred).all()
        a = auc(self.y_hold, pred)
        if not self.rehearsal:
            assert a > AUC_FLOOR, f"holdout AUC {a} <= {AUC_FLOOR}"
        steady_walls = [r["wall_s"] for r in recs[warm:]]
        out = {**resolved, "rows": n, "iters": sz["iters"],
               "compiles": summ["compiles"],
               "steady_compiles": summ["steady_compiles"],
               "compile_s": round(summ["compile_secs"], 2),
               "wall_incl_construct_s": round(wall, 2),
               "s_per_iter_info": round(float(np.median(steady_walls)), 4),
               "logloss_first_last": [round(loss[0], 5),
                                      round(loss[-1], 5)],
               "holdout_auc": round(a, 5),
               "phases_s": {k: round(v, 3) for k, v in
                            summ["phase_seconds_total"].items()}}
        log(f"train: compile {out['compile_s']}s over {summ['compiles']} "
            f"compiles (0 steady-state), {out['s_per_iter_info']} s/iter "
            f"(information only), logloss {loss[0]:.4f} -> {loss[-1]:.4f}, "
            f"holdout AUC {a:.4f}")
        return out

    # -- leg 4 ----------------------------------------------------------
    def parity(self) -> dict:
        """Fused + Pallas (the TPU default) vs the host-driven reference
        learner on the XLA one-hot histogram: predictions within 1e-4
        (the standing fused-vs-serial contract)."""
        import lambdagap_tpu as lgb
        from lambdagap_tpu.models.learner import SerialTreeLearner
        sz = self.sz
        X, y = higgs_like(sz["parity_rows"], 28, SEED + 1)
        base = {"objective": "binary", "num_leaves": sz["leaves"],
                "max_bin": sz["max_bin"], "verbose": -1,
                "tpu_fast_predict_rows": 0}
        fused = lgb.train({**base, **self.force}, lgb.Dataset(X, label=y),
                          num_boost_round=sz["parity_iters"])
        ref = lgb.train({**base, "tpu_fused_learner": 0,
                         "tpu_hist_impl": "onehot"},
                        lgb.Dataset(X, label=y),
                        num_boost_round=sz["parity_iters"])
        assert type(ref._booster.learner) is SerialTreeLearner
        assert ref._booster.learner.hist_impl == "onehot"
        assert fused._booster.learner.hist_impl == "pallas"
        d = float(np.abs(fused.predict(X) - ref.predict(X)).max())
        assert d <= 1e-4, f"fused vs host-driven reference differ by {d}"
        log(f"parity: max |fused - reference| = {d:.2e}")
        return {"rows": sz["parity_rows"], "max_abs_diff": d}

    # -- leg 5 ----------------------------------------------------------
    def predict(self) -> dict:
        import lambdagap_tpu as lgb
        bst, X = self.booster, self.X_hold
        assert len(X) > bst.config.tpu_fast_predict_rows   # device answers
        text = bst.model_to_string()
        raw = {e: lgb.Booster(params={"predict_engine": e, "verbose": -1},
                              model_str=text).predict(X, raw_score=True)
               for e in ("scan", "tensor", "compiled")}
        assert np.isfinite(raw["scan"]).all()
        for e in ("tensor", "compiled"):
            assert np.array_equal(raw[e], raw["scan"]), \
                f"predict_engine={e} differs from scan"
        ref = bst.predict(X)
        with bst.as_server() as server:
            lo, got = 0, []
            for n in self.sz["serve_sizes"]:
                got.append(server.predict(X[lo:lo + n]))
                lo += n
            stats = json.loads(server.stats_json())
        assert np.array_equal(np.concatenate(got), ref[:lo]), \
            "served answers differ from the device predict"
        assert stats["requests"] == len(got) and stats["errors"] == 0, stats
        assert stats["batches"]["count"] >= len(got), stats["batches"]
        log(f"predict: tensor == compiled == scan on {len(X)} rows; served "
            f"{stats['requests']} requests in {stats['batches']['count']} "
            "device dispatches, bit-identical")
        return {"rows": len(X), "engines_equal": True,
                "serve_requests": stats["requests"],
                "serve_dispatches": stats["batches"]["count"],
                "serve_engine": stats.get("engine")}

    # -- leg 6 ----------------------------------------------------------
    def rank(self) -> dict:
        import lambdagap_tpu as lgb
        sz = self.sz
        X, y, sizes = mslr_like(sz["rank_queries"], sz["rank_feats"],
                                SEED + 2)
        params = {"objective": "lambdarank", "metric": "ndcg",
                  "eval_at": [10], "num_leaves": sz["leaves"],
                  "max_bin": sz["max_bin"], "min_data_in_leaf": 50,
                  "telemetry": True, "verbose": -1, **self.force}
        if self.rehearsal:
            params["min_data_in_leaf"] = 5
        ds = lgb.Dataset(X, label=y, group=sizes)
        evals: dict = {}
        bst = lgb.train(params, ds, num_boost_round=sz["rank_iters"],
                        valid_sets=[ds], valid_names=["train"],
                        callbacks=[lgb.record_evaluation(evals)])
        ndcg = evals["train"]["ndcg@10"]
        assert np.isfinite(ndcg).all() and ndcg[-1] > ndcg[0], ndcg
        summ = bst._booster.telemetry.summary()
        learner = bst._booster.learner
        log(f"rank: {len(X)} docs x {X.shape[1]}, NDCG@10 {ndcg[0]:.4f} -> "
            f"{ndcg[-1]:.4f}, {type(learner).__name__}/"
            f"{learner.hist_impl}/{learner.layout}, compile "
            f"{summ['compile_secs']:.1f}s")
        return {"docs": len(X), "queries": len(sizes),
                "ndcg10_first_last": [round(ndcg[0], 5),
                                      round(ndcg[-1], 5)],
                "learner": type(learner).__name__,
                "hist_impl": learner.hist_impl, "layout": learner.layout,
                "compile_s": round(summ["compile_secs"], 2),
                "steady_compiles": summ["steady_compiles"]}

    # -- leg 7 ----------------------------------------------------------
    def multichip(self) -> dict:
        """4x1 (fused data-parallel) and 2x2 (fused 2-D) against the
        one-device fused learner on the quantized path, whose integer
        histograms make the trees byte-identical at any width."""
        import jax

        import lambdagap_tpu as lgb
        from lambdagap_tpu.parallel.fused_parallel import (
            Fused2DTreeLearner, FusedDataParallelTreeLearner)
        n_dev = len(jax.devices())
        if n_dev < 4:
            if self.require_multichip:
                raise RuntimeError(f"--require-multichip: this process "
                                   f"sees {n_dev} device(s), needs 4")
            log(f"multichip: not run ({n_dev} device)")
            return {"ran": False, "devices": n_dev}
        sz = self.sz
        X, y = higgs_like(sz["mc_rows"], 28, SEED + 3)
        base = {"objective": "binary", "num_leaves": sz["leaves"],
                "max_bin": sz["max_bin"], "use_quantized_grad": True,
                "stochastic_rounding": False, "verbose": -1,
                **{k: v for k, v in self.force.items()
                   if k != "tree_layout"}}
        arms = {"1 device": ({}, None),
                "4x1": ({"tree_learner": "data", "tpu_num_devices": 4},
                        FusedDataParallelTreeLearner),
                "2x2": ({"tree_learner": "data", "mesh_shape": "2x2"},
                        Fused2DTreeLearner)}
        trees = {}
        for name, (extra, cls) in arms.items():
            bst = lgb.train({**base, **extra}, lgb.Dataset(X, label=y),
                            num_boost_round=sz["mc_iters"])
            learner = bst._booster.learner
            if cls is not None:
                assert type(learner) is cls, type(learner).__name__
                on = {s.device for s in learner.hx_rows.addressable_shards}
                assert len(on) == 4, \
                    f"{name}: binned matrix on {len(on)} device(s)"
            assert learner.hist_impl == "pallas", learner.hist_impl
            trees[name] = bst.model_to_string().split("end of trees")[0]
            log(f"multichip {name}: {type(learner).__name__} trained "
                f"{sz['mc_iters']} iterations")
        for name in ("4x1", "2x2"):
            assert trees[name] == trees["1 device"], \
                f"{name} trees differ from the one-device quantized run"
        log("multichip: 4x1 and 2x2 trees byte-identical to one device; "
            "shards on 4 distinct devices")
        return {"ran": True, "devices": n_dev, "byte_identical": True}

    # ------------------------------------------------------------------
    def run(self) -> dict:
        os.makedirs(OUT_DIR, exist_ok=True)
        t0 = time.perf_counter()
        try:
            self.device()
            for leg in LEGS:
                t = time.perf_counter()
                res = getattr(self, leg)()
                res["seconds"] = round(time.perf_counter() - t, 1)
                self.report["legs"][leg] = res
        finally:
            self.report["seconds"] = round(time.perf_counter() - t0, 1)
            with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
                json.dump(self.report, f, indent=1)
        return self.report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU rehearsal of the control flow; the "
                         "result is stamped rehearsal and proves nothing "
                         "about the chip")
    ap.add_argument("--require-multichip", action="store_true",
                    help="fewer than 4 devices is an error, not a skip")
    rep = Smoke(ap.parse_args(argv)).run()
    legs = rep["legs"]
    main_leg = legs["train"]
    # the summary: one compact JSON object a reader can grep for
    log("summary " + json.dumps({
        "rehearsal": rep["rehearsal"],
        "legs": {k: ("pass" if v.get("ran", True) else "not run")
                 for k, v in legs.items()},
        "learner": main_leg["learner"],
        "hist_impl": main_leg["hist_impl"],
        "layout": main_leg["layout"],
        "compile_s": main_leg["compile_s"],
        "seconds": rep["seconds"],
        "claim": None,
    }, separators=(",", ":")))
    # the contract's last line: exactly these keys, nothing else — the
    # driver refuses a result line that carries more
    print(json.dumps({"ok": True, "device": rep["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
