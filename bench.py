"""Benchmark: HIGGS-shaped GBDT training wall-clock on TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.

Baseline: the reference's published HIGGS train time — 500 iterations,
num_leaves=255, max_bin=255, 10.5M rows x 28 features — 130.094 s on a
28-thread dual-Xeon (reference: docs/Experiments.rst:111-124; BASELINE.md).
The fork ships no CUDA numbers, so the published CPU number is the bar.

To keep the bench bounded we train a slice of the full 500 iterations and
project: steady-state time/iteration x 500 (+ measured dataset construction).

Robustness: every attempt runs in its own subprocess so one attempt's crash
cannot take down the bench; the parent never touches JAX, so each child
can take the chip (a chip belongs to one process at a time). The ladder
tries the fused whole-tree-on-device learner first (with one retry), then
the host-driven SerialTreeLearner, then ramps the row count down. The first
success is reported, with the attempt path in "detail".

Self-normalizing: a device microbench (HBM copy bandwidth + bf16 MXU GEMM
throughput) runs in the SAME session as the training attempts, and the JSON
carries ``roofline_per_iter_s`` (the traffic model's floor on this chip) and
``roofline_fraction`` — so a reader can attribute the wall-clock to the
program or to the chip without any prose. A full 500-iteration run (no
projection) at BENCH_FULL_ROWS validates the projection methodology.

Env knobs: BENCH_ROWS (default 10.5M), BENCH_ITERS (measured steady-state
iterations, default 30), BENCH_MAX_BIN (default 255), BENCH_ATTEMPT_TIMEOUT
(seconds per attempt, default 2400), BENCH_HOLDOUT (AUC holdout rows,
default 200k), BENCH_FULL_ROWS (full-500-run size, default 1M; 0 skips),
BENCH_MICRO=0 skips the microbench.

Real data: BENCH_DATA_HIGGS=<path to HIGGS csv> / BENCH_DATA_MSLR=<path to
a LETOR qid LibSVM file> train on the real datasets (parsed by the native
loader) so the accuracy fields compare against the published bars
(AUC 0.845724, NDCG@10 0.5278). Without them every accuracy field is
stamped "synthetic": true — synthetic AUC/NDCG are NOT comparable to the
bars.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

ROWS = int(os.environ.get("BENCH_ROWS", 10_500_000))
FEATURES = 28
ITERS_MEASURED = int(os.environ.get("BENCH_ITERS", 30))
ITERS_TOTAL = 500
MAX_BIN = int(os.environ.get("BENCH_MAX_BIN", 255))
HOLDOUT = int(os.environ.get("BENCH_HOLDOUT", 200_000))
ATTEMPT_TIMEOUT = float(os.environ.get("BENCH_ATTEMPT_TIMEOUT", 2400))
FULL_ROWS = int(os.environ.get("BENCH_FULL_ROWS", 1_000_000))
BASELINE_S = 130.094
NUM_LEAVES = 255

# Traffic model for one boosting iteration of the fused learner (measured
# accounting, BENCH_NOTES.md): with the smaller-child + subtraction trick a
# row is touched ~log2(L) times; each histogram touch reads the permutation
# entry (4 B), the row's binned features (C B) and the packed grad/hess
# (8 B); the partition pass re-reads perm + one feature column and writes
# perm + copy-back (~17 B) over the same visit count. Chunk-window padding
# adds ~35% at leaf-sized windows.
HIST_BYTES_PER_VISIT = 4 + FEATURES + 8
PART_BYTES_PER_VISIT = 17
PAD_FACTOR = 1.35


def model_bytes_per_iter(rows: int):
    """(gather_bytes, stream_bytes) for one iteration: the histogram pass
    is permutation-gather shaped, the partition pass is mostly sequential
    scans + scatter."""
    visits = rows * math.log2(NUM_LEAVES)
    return (visits * HIST_BYTES_PER_VISIT * PAD_FACTOR,
            visits * PART_BYTES_PER_VISIT * PAD_FACTOR)


def make_higgs_like(n: int, d: int, seed: int = 7):
    """Synthetic stand-in with HIGGS-like marginals (no network egress)."""
    rng = np.random.RandomState(seed)
    X = np.empty((n, d), dtype=np.float32)
    block = 1 << 20
    w = rng.randn(d).astype(np.float32)
    y = np.empty(n, dtype=np.float32)
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        xb = rng.randn(hi - lo, d).astype(np.float32)
        # heavy-tailed positive features like HIGGS' kinematics
        xb[:, d // 2:] = np.abs(xb[:, d // 2:]) ** 1.3
        X[lo:hi] = xb
        logits = xb @ w * 0.7 + 0.5 * np.sin(xb[:, 0] * 2) + rng.randn(hi - lo)
        y[lo:hi] = (logits > 0).astype(np.float32)
    return X, y


def _data_cache_path(rows: int) -> str:
    d = os.path.join(tempfile.gettempdir(), "lambdagap_bench")
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"higgs_like_{rows}x{FEATURES}_h{HOLDOUT}.npz")


def _ensure_data(rows: int) -> str:
    path = _data_cache_path(rows)
    if not os.path.exists(path):
        X, y = make_higgs_like(rows + HOLDOUT, FEATURES)
        np.savez(path, X=X, y=y)
    return path


def auc_score(y_true: np.ndarray, score: np.ndarray) -> float:
    order = np.argsort(score, kind="stable")
    ranks = np.empty(len(score), dtype=np.float64)
    ranks[order] = np.arange(1, len(score) + 1)
    # midranks for ties
    s_sorted = score[order]
    i = 0
    while i < len(s_sorted):
        j = i
        while j + 1 < len(s_sorted) and s_sorted[j + 1] == s_sorted[i]:
            j += 1
        if j > i:
            ranks[order[i:j + 1]] = 0.5 * (i + 1 + j + 1)
        i = j + 1
    pos = y_true > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if n_pos == 0 or n_neg == 0:
        return 0.5
    return (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


def _configure_jax_cache() -> None:
    from lambdagap_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()


def _load_higgs_real(path: str):
    """BENCH_DATA_HIGGS hook: parse the real HIGGS CSV (label first,
    28 features, no header; reference setup docs/Experiments.rst:111-124
    holds out the last 500k rows) through the native parser."""
    from lambdagap_tpu.config import Config
    from lambdagap_tpu.data.loader import _parse_text_file
    X, y, _, _, _ = _parse_text_file(path, Config.from_params(
        {"header": False, "label_column": 0, "verbose": -1}))
    holdout = min(500_000, len(X) // 10)
    n = len(X) - holdout
    return (np.ascontiguousarray(X[:n], np.float32), y[:n].astype(np.float32),
            np.ascontiguousarray(X[n:], np.float32), y[n:].astype(np.float32))


def _predict_crossover(booster, Xv_np, n_big, t_dev_big, native_per_row):
    """Two-point linear model of the warm device predict: measure a second
    (quarter-size) batch, split t = overhead + slope*rows, and solve for
    where the native line crosses. A single-point t/rate estimate answers
    the wrong question (it sets the threshold where native equals the
    FULL-batch device time) and can overstate the crossover ~10x.

    ``crossover_rows_est`` is ALWAYS diagnosable (ISSUE 3 satellite): a
    finite row count when the lines cross, the sentinel string
    ``"never_at_measured_slopes"`` when the native per-row cost is below
    the device slope (native wins at any size on this chip), or
    ``"unmeasurable_single_point"`` when the shape leaves no second
    device point to fit — never a silent null."""
    import time as _t
    n_small = max(n_big // 4, 1)
    thresh = getattr(booster._booster.config, "tpu_fast_predict_rows", 10000)
    if n_big == n_small or n_small <= thresh:
        # the small point would route native (or equal the big one):
        # no second device point, no fit
        return {"crossover_rows_est": "unmeasurable_single_point"}
    booster.predict(Xv_np[:n_small])     # WARM the new shape: the first
    t0 = _t.time()                       # call compiles, and compile time
    booster.predict(Xv_np[:n_small])     # in the fit would swamp the slope
    t_small = _t.time() - t0
    slope = max((t_dev_big - t_small) / (n_big - n_small), 0.0)
    overhead = max(t_small - slope * n_small, 0.0)
    if native_per_row <= slope:
        return {"crossover_rows_est": "never_at_measured_slopes",
                "device_overhead_s": round(overhead, 4),
                "device_slope_us_per_row": round(slope * 1e6, 2)}
    return {"crossover_rows_est": int(overhead
                                      / (native_per_row - slope)),
            "device_overhead_s": round(overhead, 4),
            "device_slope_us_per_row": round(slope * 1e6, 2)}


def _predict_engine_ab(booster, X, hbm_gbps: float = None) -> dict:
    """Same-session A/B of the device traversal engines on identical
    rows (ISSUE 3 acceptance, compiled arm added by ISSUE 17): warm
    us/row for the tensorized [rows x trees] engine vs the sequential
    per-tree scan vs the compiled-forest artifact engine (palette gather
    lattice, ISSUE 16), plus a predict
    roofline from the node-table traffic model — an upper bound assuming
    every per-level node gather misses (26 B node record + 4 B feature
    value per row/tree/level) and a lower bound assuming the node tables
    stay resident (stream the tables once + the row matrix). Measured
    us/row between the two bounds is traversal-issue cost; above the
    gather bound means dispatch overhead dominates."""
    import time as _t
    gb = booster._booster
    fast = gb.config.tpu_fast_predict_rows
    engine0 = gb.config.predict_engine
    gb.config.tpu_fast_predict_rows = 0       # force the device path
    res = {"rows": len(X)}
    try:
        for eng in ("tensor", "scan", "compiled"):
            gb.config.predict_engine = eng
            gb.invalidate_predict_cache()
            booster.predict(X)                # compile + warm this shape
            t0 = _t.time()
            booster.predict(X)
            res[f"{eng}_us_per_row_warm"] = round(
                (_t.time() - t0) / max(len(X), 1) * 1e6, 2)
    finally:
        gb.config.predict_engine = engine0
        gb.config.tpu_fast_predict_rows = fast
        gb.invalidate_predict_cache()
    res["tensor_speedup_vs_scan"] = round(
        res["scan_us_per_row_warm"]
        / max(res["tensor_us_per_row_warm"], 1e-9), 3)
    res["compiled_speedup_vs_scan"] = round(
        res["scan_us_per_row_warm"]
        / max(res["compiled_us_per_row_warm"], 1e-9), 3)

    # node-table traffic model (forest dims off the host trees, padded the
    # way forest_to_arrays pads them)
    from lambdagap_tpu.ops.predict import _round_depth

    def _round32(v):
        return max(32, ((v + 31) // 32) * 32)

    trees = gb.host_models
    T = len(trees)
    M = _round32(max(max(t.num_internal, 1) for t in trees))
    L = _round32(max(max(t.num_leaves, 1) for t in trees))
    depth = _round_depth(max(t.max_depth for t in trees) + 1)
    node_rec_b = 26                  # feat+thr+children+missing meta
    gather_bytes_row = depth * T * (node_rec_b + 4) + T * 4
    table_bytes = T * M * (9 * 4 + 2 + 8 * 4 + 8 * 4) + T * L * 4
    stream_bytes = table_bytes + len(X) * X.shape[1] * 4
    roofline = {
        "trees": T, "padded_nodes": M, "padded_depth": depth,
        "node_gather_bytes_per_row": int(gather_bytes_row),
        "node_table_bytes": int(table_bytes),
        "resident_stream_bytes_per_row": round(
            stream_bytes / max(len(X), 1), 1),
    }
    if hbm_gbps:
        bw = hbm_gbps * 1e9
        roofline["gather_bound_us_per_row"] = round(
            gather_bytes_row / bw * 1e6, 3)
        roofline["resident_bound_us_per_row"] = round(
            stream_bytes / max(len(X), 1) / bw * 1e6, 4)
        roofline["measured_vs_gather_bound"] = round(
            res["tensor_us_per_row_warm"]
            / max(gather_bytes_row / bw * 1e6, 1e-9), 3)
    res["roofline"] = roofline
    return res


def run_predict_ab(n_trees: int, rows: int) -> None:
    """Child-process entry (ISSUE 3 acceptance shape): a ``n_trees``-tree
    forest (trained base tiled out, structure-realistic — predict cost
    depends on tree count/shape, not training history) predicted over
    ``rows`` rows by both device engines + the native baseline. Prints one
    JSON line."""
    _configure_jax_cache()
    import lambdagap_tpu as lgb

    rng = np.random.RandomState(0)
    Xt = rng.randn(8000, FEATURES).astype(np.float32)
    yt = (Xt[:, 0] - 0.5 * Xt[:, 1] + np.sin(Xt[:, 2])
          + 0.1 * rng.randn(8000)).astype(np.float32)
    base = min(n_trees, 50)
    booster = lgb.train({"objective": "regression",
                         "num_leaves": NUM_LEAVES, "verbose": -1},
                        lgb.Dataset(Xt, label=yt), num_boost_round=base)
    gb = booster._booster
    host = gb.host_models
    gb.models = (host * (-(-n_trees // len(host))))[:n_trees]
    gb.iter_ = len(gb.models)
    gb.invalidate_predict_cache()
    X = rng.randn(rows, FEATURES).astype(np.float32)

    out = _predict_engine_ab(booster, X)
    tn = time.time()
    booster.predict(X[:8192])                # native route (< threshold)
    out["native_us_per_row"] = round((time.time() - tn) / 8192 * 1e6, 2)
    out["trees"] = n_trees
    print(json.dumps(out))


def _visit_counts(booster, rows: int, n_trees: int = 10):
    """EXACT per-iteration work counts from the trained trees (the round-4
    roofline modeled rows*log2(L)*1.35 row-visits; the smaller-child +
    subtraction trick makes the real count much lower and tree-shape
    dependent, so the model must read it off the trees):
      hist visits  = N (root) + sum over splits of min(child rows)
      part visits  = sum over splits of parent rows
    Window padding rounds each pass up to the learner's chunk W.
    Returns None for learners without a chunk window (host serial path —
    a different cost model)."""
    if not hasattr(booster._booster.learner, "chunk"):
        return None
    W = booster._booster.learner.chunk
    trees = booster._booster.host_models[-n_trees:]
    vh = vp = vhp = vpp = 0.0
    for t in trees:
        vh_t = float(rows)
        vhp_t = float(-(-rows // W) * W)
        vp_t = vpp_t = 0.0
        for k in range(t.num_internal):
            lc, rc = t.left_child[k], t.right_child[k]
            lcnt = (t.internal_count[lc] if lc >= 0
                    else int(t.leaf_count[~lc]))
            rcnt = (t.internal_count[rc] if rc >= 0
                    else int(t.leaf_count[~rc]))
            small = min(lcnt, rcnt)
            parent = t.internal_count[k]
            vh_t += small
            vp_t += parent
            vhp_t += -(-small // W) * W
            vpp_t += -(-parent // W) * W
        vh += vh_t; vp += vp_t; vhp += vhp_t; vpp += vpp_t
    nt = max(len(trees), 1)
    return {
        "hist_rows_per_iter": int(vh / nt),
        "hist_rows_padded_per_iter": int(vhp / nt),
        "part_rows_per_iter": int(vp / nt),
        "part_rows_padded_per_iter": int(vpp / nt),
        "chunk_window": int(W),
        "trees_sampled": nt,
    }


def _telemetry_section(booster, last_n: int) -> dict:
    """BENCH JSON ``telemetry`` section (ISSUE 4): the per-phase breakdown
    from the booster's TrainTelemetry — aggregate summary plus steady-state
    per-iteration phase means over the last ``last_n`` recorded iterations
    (the measured window), and the recompile-watchdog verdict. This is the
    evidence channel every perf attempt now carries: a regression shows up
    as WHICH phase grew, not just a bigger total."""
    tel = booster._booster.telemetry
    if not tel.enabled:
        return {"enabled": False}
    recs = list(tel.records)[-last_n:]
    steady = {}
    for rec in recs:
        for k, v in rec["phases"].items():
            steady[k] = steady.get(k, 0.0) + v
    n = max(len(recs), 1)
    return {
        "enabled": True,
        "iterations": tel.iterations,
        "steady_phase_s_per_iter": {k: round(v / n, 5)
                                    for k, v in sorted(steady.items())},
        "steady_window_iters": len(recs),
        "steady_compiles": sum(r["compiles"]["steady"] for r in recs),
        "compiles_total": tel.watchdog.totals()["compiles"],
        "transfers_total": tel.watchdog.totals()["transfers"],
        "iter_wall_s": tel.wall_res.percentiles(),
    }


def _costplane_section(iterations: int):
    """Measured train-side traffic from the analytic ledger: total
    bytes/flops of the train-phase entries scaled by observed dispatch
    counts, per iteration (warmup included — the executables are
    identical). None when no train program was captured."""
    from lambdagap_tpu.obs.costplane import PLANE
    return PLANE.train_traffic(iterations)


def run_attempt(rows: int, fused: bool, max_bin: int = None) -> None:
    """Child-process entry: train + measure, print one JSON line."""
    _configure_jax_cache()

    import lambdagap_tpu as lgb

    t_gen0 = time.time()
    higgs_path = os.environ.get("BENCH_DATA_HIGGS")
    if higgs_path:
        X, y, Xv, yv = _load_higgs_real(higgs_path)
        rows = len(X)
        synthetic = False
    else:
        z = np.load(_data_cache_path(rows))
        X_all, y_all = z["X"], z["y"]      # one read each (npz ignores mmap)
        X, y = X_all[:rows], y_all[:rows]
        Xv, yv = X_all[rows:], y_all[rows:]
        synthetic = True
    t_gen = time.time() - t_gen0

    if max_bin is None:
        max_bin = MAX_BIN
    params = {
        "objective": "binary",
        "num_leaves": 255,
        "learning_rate": 0.1,
        "max_bin": max_bin,
        "min_data_in_leaf": 100,
        "verbose": -1,
        "tpu_fused_learner": "1" if fused else "0",
        # phase-span telemetry rides every attempt (measured overhead < 2%,
        # BENCH_NOTES.md) so the JSON carries its own attribution
        "telemetry": True,
        # analytic per-executable ledger (obs/costplane.py): the parent's
        # roofline prefers XLA's own bytes/flops over the hand-derived
        # traffic model where a ledger entry exists
        "cost_plane": True,
    }

    t0 = time.time()
    ds = lgb.Dataset(X, label=y)
    booster = lgb.Booster(params=params, train_set=ds)
    t_construct = time.time() - t0

    # warmup (compilation) iterations, excluded from steady-state timing
    t1 = time.time()
    booster.update()
    booster.update()
    np.asarray(booster._booster.scores[0][:1])   # device-complete warmup
    t_warm = time.time() - t1

    t2 = time.time()
    for _ in range(ITERS_MEASURED):
        booster.update()
    # block on the device scores so async dispatch doesn't flatter the timing
    np.asarray(booster._booster.scores[0][:1])
    t_meas = time.time() - t2
    per_iter = t_meas / ITERS_MEASURED

    t3 = time.time()
    pred = booster.predict(np.asarray(Xv))
    auc = auc_score(np.asarray(yv), pred)
    t_pred = time.time() - t3

    # EXACT per-iteration work counts, read off the trained trees
    # (_visit_counts). Fused program only — the serial-fallback attempts
    # run a different cost model, so modeling them with these counts
    # would mislead.
    visit_counts = _visit_counts(booster, rows,
                                 min(10, ITERS_MEASURED)) if fused else None

    # predict path A/B: the threaded native traverser (fastpred.cpp, the
    # route for batches <= tpu_fast_predict_rows) vs the jitted device
    # forest, measured on the SAME rows — cold (with compile) and warm.
    # The crossover tells which side any batch belongs on, on this chip.
    Xv_np = np.asarray(Xv)
    tn = time.time()
    booster.predict(Xv_np[:512])
    t_native_512 = time.time() - tn
    tn = time.time()
    booster.predict(Xv_np[:8192])
    t_native_8k = time.time() - tn
    tw = time.time()
    booster.predict(Xv_np)               # second big call: warm device path
    t_dev_warm = time.time() - tw
    native_per_row = t_native_8k / 8192
    predict_ab = {
        "native_512rows_s": round(t_native_512, 4),
        "native_8192rows_s": round(t_native_8k, 4),
        "device_%drows_cold_s" % len(yv): round(t_pred, 4),
        "device_%drows_warm_s" % len(yv): round(t_dev_warm, 4),
        "native_us_per_row": round(native_per_row * 1e6, 2),
        "device_us_per_row_warm": round(t_dev_warm / max(len(yv), 1) * 1e6,
                                        2),
        **_predict_crossover(booster, Xv_np, len(yv), t_dev_warm,
                             native_per_row),
        # tensorized vs sequential engine on identical rows (capped at 50k
        # so a throttled chip doesn't eat the session budget)
        "engine_ab": _predict_engine_ab(booster, Xv_np[:50_000]),
    }

    projected = t_construct + t_warm + per_iter * (ITERS_TOTAL - 2)
    print(json.dumps({
        "rows": rows,
        "fused": fused,
        "max_bin": max_bin,
        "tree_layout": getattr(booster._booster.learner, "layout", None),
        "construct_s": round(t_construct, 3),
        "warmup_2iter_s": round(t_warm, 3),
        "per_iter_s": round(per_iter, 4),
        "iters_measured": ITERS_MEASURED,
        "projected_500iter_s": round(projected, 3),
        "holdout_auc": round(float(auc), 5),
        # a synthetic holdout AUC is NOT comparable to the published HIGGS
        # bar 0.845724 (docs/Experiments.rst:134) — only a real-data run
        # (BENCH_DATA_HIGGS) is
        "synthetic": synthetic,
        "data": higgs_path or "higgs_like synthetic",
        "holdout_rows": len(yv),
        "predict_s": round(t_pred, 3),
        "predict_ab": predict_ab,
        "visit_counts": visit_counts,
        "telemetry": _telemetry_section(booster, ITERS_MEASURED),
        "costplane": _costplane_section(ITERS_MEASURED + 2),
        "dataload_s": round(t_gen, 3),
    }))


def run_layout_ab(rows: int, max_bin: int, iters: int) -> None:
    """Child-process entry (ISSUE 6 satellite): ABAB same-session A/B of
    ``tree_layout=sorted`` vs ``gather`` on the fused learner — the two
    boosters share one binned dataset and alternate measured segments, so
    chip drift hits both arms equally (the same methodology as the
    telemetry/guard overhead A/Bs in BENCH_NOTES). Reports per-iter for
    each arm, the sorted arm's permutation-apply (layout_apply) phase cost
    from telemetry, and the effective histogram-read bandwidth against the
    ~20 GB/s contiguous-stream bound the sorted layout exists to reach.

    Env: BENCH_LAYOUT_LEAVES overrides num_leaves (the acceptance shape
    uses 255; CPU-budget validation runs use smaller trees)."""
    _configure_jax_cache()
    import jax

    import lambdagap_tpu as lgb

    leaves = int(os.environ.get("BENCH_LAYOUT_LEAVES", NUM_LEAVES))
    higgs_path = os.environ.get("BENCH_DATA_HIGGS")
    if higgs_path:
        X, y, _, _ = _load_higgs_real(higgs_path)
        rows, synthetic = len(X), False
    else:
        z = np.load(_data_cache_path(rows))
        X, y = z["X"][:rows], z["y"][:rows]
        synthetic = True
    params = {"objective": "binary", "num_leaves": leaves,
              "learning_rate": 0.1, "max_bin": max_bin,
              "min_data_in_leaf": max(min(100, rows // (leaves * 2)), 2),
              "verbose": -1, "tpu_fused_learner": "1", "telemetry": True}
    t0 = time.time()
    ds = lgb.Dataset(X, label=y)
    boosters = {
        layout: lgb.Booster(params={**params, "tree_layout": layout},
                            train_set=ds)
        for layout in ("sorted", "gather")
    }
    construct_s = time.time() - t0

    for b in boosters.values():          # compile + warm both arms
        b.update()
        b.update()
        np.asarray(b._booster.scores[0][:1])   # device-complete warmup

    seg = max(iters // 4, 3)
    segs = {"sorted": [], "gather": []}
    for _rep in range(4):                # A B A B A B A B
        for layout in ("sorted", "gather"):
            b = boosters[layout]
            t0 = time.time()
            for _ in range(seg):
                b.update()
            # device-complete before the clock read (graftlint R7)
            np.asarray(b._booster.scores[0][:1])
            segs[layout].append((time.time() - t0) / seg)
    per_iter = {k: float(np.median(v)) for k, v in segs.items()}

    lr = boosters["sorted"]._booster.learner
    vc = _visit_counts(boosters["sorted"], rows)
    # bytes per packed row in the sorted buffer: C binned columns + the
    # 8 B grad/hess pair, padded to the u32 lane multiple (pack32)
    gh_cols, q_cols, mask_col = lr._packed_meta(False)
    itemsize = np.dtype(np.asarray(lr.hx_rows).dtype).itemsize
    cols = lr.hx_rows.shape[1] + gh_cols + q_cols + int(mask_col)
    row_bytes = -(-cols * itemsize // 4) * 4
    hist_bytes = (vc["hist_rows_padded_per_iter"] * row_bytes) if vc else None
    tel_sorted = _telemetry_section(boosters["sorted"], seg * 4)
    tel_gather = _telemetry_section(boosters["gather"], seg * 4)
    hist_read = None
    if hist_bytes:
        hist_read = {
            "packed_row_bytes": int(row_bytes),
            "hist_rows_padded_per_iter": vc["hist_rows_padded_per_iter"],
            "hist_stream_bytes_per_iter": int(hist_bytes),
            "stream_bound_s_at_20gbps": round(hist_bytes / 20e9, 4),
            # a LOWER bound: the denominator is the whole iteration
            # (partition, scans, fixed costs included), so the true
            # hist-pass bandwidth is at least this
            "effective_hist_gbps_lower_bound": round(
                hist_bytes / per_iter["sorted"] / 1e9, 3),
        }
    print(json.dumps({
        "rows": rows, "max_bin": max_bin, "num_leaves": leaves,
        "synthetic": synthetic, "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "method": f"ABAB same-session: shared dataset, alternating "
                  f"{seg}-iter segments x4 per arm, per-iter = median of "
                  f"segment means, device-complete at every boundary",
        "construct_s": round(construct_s, 3),
        "per_iter_s": {k: round(v, 4) for k, v in per_iter.items()},
        "segments_s_per_iter": {k: [round(s, 4) for s in v]
                                for k, v in segs.items()},
        "speedup_sorted_vs_gather": round(
            per_iter["gather"] / max(per_iter["sorted"], 1e-9), 4),
        "layout_apply_s_per_iter": tel_sorted.get(
            "steady_phase_s_per_iter", {}).get("layout_apply"),
        "visit_counts": vc,
        "hist_read": hist_read,
        "telemetry_sorted": tel_sorted.get("steady_phase_s_per_iter"),
        "telemetry_gather": tel_gather.get("steady_phase_s_per_iter"),
    }))


def _wall_metric_curve(booster, iters: int, metric_fn):
    """Train ``iters`` rounds, recording (cumulative wall seconds, metric)
    after every round, device-complete at each boundary (graftlint R7)."""
    import numpy as np
    walls, metrics = [], []
    t0 = time.time()
    for _ in range(iters):
        booster.update()
        np.asarray(booster._booster.scores[0][:1])
        walls.append(time.time() - t0)
        metrics.append(metric_fn(booster))
    return walls, metrics


def _first_crossing(walls, metrics, target: float, higher_better: bool):
    """(wall_s, iteration) of the first round meeting ``target``."""
    for i, m in enumerate(metrics):
        if (m >= target) if higher_better else (m <= target):
            return round(walls[i], 4), i + 1
    return None, None


def run_linear_ab(rows: int, max_bin: int, iters: int) -> None:
    """Child-process entry (ISSUE 11): constant-leaf vs piece-wise LINEAR
    leaves at HIGGS- and MSLR-shaped configs, scored by
    WALL-CLOCK-TO-TARGET-METRIC — not per-iteration cost. arXiv:1802.05640's
    claim is that linear leaves reach equal accuracy in 2-5x fewer
    iterations; per-iter comparisons would hide exactly that, so each
    shape's target is the CONSTANT arm's final valid metric after ``iters``
    rounds and both arms report the wall/iterations to first reach it.

    Env: BENCH_LINEAR_LEAVES overrides num_leaves; BENCH_LINEAR_RANK_Q the
    MSLR-shaped query count. The CPU container validates the machinery
    (and the iteration-count ratio, which is hardware-independent); the
    wall-clock ratio is a bench-chip number."""
    _configure_jax_cache()
    import jax

    import lambdagap_tpu as lgb

    leaves = int(os.environ.get("BENCH_LINEAR_LEAVES", 63))
    out = {"rows": rows, "max_bin": max_bin, "iters": iters,
           "num_leaves": leaves, "backend": jax.default_backend(),
           "device": str(jax.devices()[0]),
           "method": ("per-iteration wall+metric curves, device-complete "
                      "each boundary; target = constant arm's FINAL valid "
                      "metric; wall_to_target = first crossing")}

    # -- HIGGS-shaped: binary, dense numeric features -------------------
    z = np.load(_ensure_data(rows))
    X, y = z["X"], z["y"]
    n_tr = int(len(X) * 0.85)
    higgs = {}
    for arm, extra in (("constant", {}),
                       ("linear", {"linear_tree": True,
                                   "linear_lambda": 0.01})):
        params = {"objective": "binary", "num_leaves": leaves,
                  "learning_rate": 0.1, "max_bin": max_bin,
                  "min_data_in_leaf": 50, "verbose": -1,
                  "tpu_fused_learner": "1", **extra}
        t0 = time.time()
        dtrain = lgb.Dataset(X[:n_tr], label=y[:n_tr], params=params)
        booster = lgb.Booster(params=params, train_set=dtrain)
        dvalid = lgb.Dataset(X[n_tr:], label=y[n_tr:], reference=dtrain)
        booster.add_valid(dvalid, "valid")
        construct_s = time.time() - t0
        booster.update()                      # compile outside the clock
        np.asarray(booster._booster.scores[0][:1])
        yv = y[n_tr:]

        def val_auc(b, yv=yv):
            return auc_score(yv, np.asarray(b._booster.valid_scores[0][0]))

        walls, aucs = _wall_metric_curve(booster, iters, val_auc)
        higgs[arm] = {"construct_s": round(construct_s, 3),
                      "per_iter_s": round(walls[-1] / iters, 4),
                      "final_auc": round(aucs[-1], 5),
                      "auc_curve": [round(a, 5) for a in aucs],
                      "wall_curve_s": [round(w, 3) for w in walls]}
    target = higgs["constant"]["final_auc"]
    for arm in higgs:
        w, it = _first_crossing(higgs[arm]["wall_curve_s"],
                                higgs[arm]["auc_curve"], target, True)
        higgs[arm]["wall_to_target_s"] = w
        higgs[arm]["iters_to_target"] = it
    wc, wl = (higgs["constant"]["wall_to_target_s"],
              higgs["linear"]["wall_to_target_s"])
    higgs["target_auc"] = target
    higgs["speedup_wall_to_target"] = (round(wc / wl, 3)
                                       if wc and wl else None)
    ic, il = (higgs["constant"]["iters_to_target"],
              higgs["linear"]["iters_to_target"])
    higgs["iter_ratio_to_target"] = (round(ic / il, 3)
                                     if ic and il else None)
    out["higgs_shaped"] = higgs

    # -- MSLR-shaped: lambdarank over graded-relevance queries ----------
    rng = np.random.RandomState(11)
    n_q = int(os.environ.get("BENCH_LINEAR_RANK_Q", 400))
    F = 136
    sizes = rng.randint(40, 201, n_q)
    N = int(sizes.sum())
    Xr = rng.randn(N, F).astype(np.float32)
    w = rng.randn(F).astype(np.float32) * (rng.rand(F) < 0.2)
    latent = Xr @ w * 0.6 + rng.randn(N).astype(np.float32)
    yr = np.clip(np.floor(latent - latent.mean() + 0.8), 0,
                 4).astype(np.float32)
    n_train_q = int(n_q * 0.9)
    train_docs = int(sizes[:n_train_q].sum())
    mslr = {}
    for arm, extra in (("constant", {}),
                       ("linear", {"linear_tree": True,
                                   "linear_lambda": 0.01})):
        params = {"objective": "lambdarank", "metric": "ndcg",
                  "eval_at": [10], "num_leaves": leaves,
                  "learning_rate": 0.1, "max_bin": max_bin,
                  "min_data_in_leaf": 50, "verbose": -1,
                  "tpu_fused_learner": "1", **extra}
        dtrain = lgb.Dataset(Xr[:train_docs], label=yr[:train_docs],
                             group=sizes[:n_train_q], params=params)
        booster = lgb.Booster(params=params, train_set=dtrain)
        dvalid = lgb.Dataset(Xr[train_docs:], label=yr[train_docs:],
                             group=sizes[n_train_q:], reference=dtrain)
        booster.add_valid(dvalid, "valid")
        booster.update()
        np.asarray(booster._booster.scores[0][:1])

        def val_ndcg(b):
            return next(v for (_, m, v, _) in b._booster.eval_valid()
                        if "ndcg" in m)

        walls, ndcgs = _wall_metric_curve(booster, iters, val_ndcg)
        mslr[arm] = {"per_iter_s": round(walls[-1] / iters, 4),
                     "final_ndcg10": round(ndcgs[-1], 5),
                     "ndcg_curve": [round(v, 5) for v in ndcgs],
                     "wall_curve_s": [round(v, 3) for v in walls]}
    target = mslr["constant"]["final_ndcg10"]
    for arm in mslr:
        w, it = _first_crossing(mslr[arm]["wall_curve_s"],
                                mslr[arm]["ndcg_curve"], target, True)
        mslr[arm]["wall_to_target_s"] = w
        mslr[arm]["iters_to_target"] = it
    wc, wl = (mslr["constant"]["wall_to_target_s"],
              mslr["linear"]["wall_to_target_s"])
    mslr["target_ndcg10"] = target
    mslr["speedup_wall_to_target"] = (round(wc / wl, 3)
                                      if wc and wl else None)
    ic, il = (mslr["constant"]["iters_to_target"],
              mslr["linear"]["iters_to_target"])
    mslr["iter_ratio_to_target"] = (round(ic / il, 3)
                                    if ic and il else None)
    out["mslr_shaped"] = mslr
    print(json.dumps(out))


def run_stream_ab(rows: int, max_bin: int, iters: int) -> None:
    """Child-process entry (ISSUE 7): ABAB same-session A/B of
    ``data_residency=stream`` (host-sharded binned matrix + async
    double-buffered H2D window prefetch) vs the resident path at a
    resident-capable shape — the acceptance ratio is per-iter stream <=
    1.5x hbm WITH bit-identical trees, and the telemetry phase breakdown
    must show the transfer time absorbed by ``h2d_prefetch`` overlap
    (issue work that runs concurrently with device compute) rather than
    ``chunk_wait`` (the ring-slot completion block = the un-overlapped
    remainder).

    Env: BENCH_STREAM_LEAVES overrides num_leaves; BENCH_STREAM_SHARDS
    sets the forced shard count (default 4)."""
    _configure_jax_cache()
    import jax

    import lambdagap_tpu as lgb

    leaves = int(os.environ.get("BENCH_STREAM_LEAVES", NUM_LEAVES))
    n_shards = max(int(os.environ.get("BENCH_STREAM_SHARDS", "4")), 2)
    higgs_path = os.environ.get("BENCH_DATA_HIGGS")
    if higgs_path:
        X, y, _, _ = _load_higgs_real(higgs_path)
        rows, synthetic = len(X), False
    else:
        z = np.load(_ensure_data(rows))
        X, y = z["X"][:rows], z["y"][:rows]
        synthetic = True
    shard_rows = max(-(-rows // n_shards), 1 << 10)
    params = {"objective": "binary", "num_leaves": leaves,
              "learning_rate": 0.1, "max_bin": max_bin,
              "min_data_in_leaf": max(min(100, rows // (leaves * 2)), 2),
              "verbose": -1, "tpu_fused_learner": "1", "telemetry": True,
              # EFB bundling is a resident-only optimization; keep the
              # arms on the same (unbundled) histogram math so the ratio
              # isolates residency, and the parity check is apples/apples
              "enable_bundle": False,
              "stream_shard_rows": shard_rows}
    t0 = time.time()
    ds = lgb.Dataset(X, label=y)
    boosters = {
        res: lgb.Booster(params={**params, "data_residency": res},
                         train_set=ds)
        for res in ("stream", "hbm")
    }
    construct_s = time.time() - t0

    for b in boosters.values():          # compile + warm both arms
        b.update()
        b.update()
        np.asarray(b._booster.scores[0][:1])   # device-complete warmup

    # parity first: the warmup trees must already be bit-identical
    trees = {k: b.model_to_string().split("end of trees")[0]
             for k, b in boosters.items()}
    bit_identical = trees["stream"] == trees["hbm"]

    seg = max(iters // 4, 3)
    segs = {"stream": [], "hbm": []}
    for _rep in range(4):                # A B A B A B A B
        for res in ("stream", "hbm"):
            b = boosters[res]
            t0 = time.time()
            for _ in range(seg):
                b.update()
            # device-complete before the clock read (graftlint R7)
            np.asarray(b._booster.scores[0][:1])
            segs[res].append((time.time() - t0) / seg)
    per_iter = {k: float(np.median(v)) for k, v in segs.items()}

    tel_stream = _telemetry_section(boosters["stream"], seg * 4)
    tel_hbm = _telemetry_section(boosters["hbm"], seg * 4)
    phases = tel_stream.get("steady_phase_s_per_iter", {}) or {}
    prefetch_s = phases.get("h2d_prefetch")
    wait_s = phases.get("chunk_wait")
    overlap = None
    if prefetch_s is not None and wait_s is not None \
            and (prefetch_s + wait_s) > 0:
        # fraction of the streaming overhead hidden behind compute:
        # chunk_wait is the part that surfaced as stall
        overlap = round(prefetch_s / (prefetch_s + wait_s), 4)
    lr = boosters["stream"]._booster.learner
    print(json.dumps({
        "rows": rows, "max_bin": max_bin, "num_leaves": leaves,
        "synthetic": synthetic, "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "method": f"ABAB same-session: shared dataset, alternating "
                  f"{seg}-iter segments x4 per arm, per-iter = median of "
                  f"segment means, device-complete at every boundary",
        "construct_s": round(construct_s, 3),
        "num_shards": int(getattr(lr.sdata, "num_shards", 0)),
        "shard_rows": int(getattr(lr.sdata, "shard_rows", 0)),
        "per_iter_s": {k: round(v, 4) for k, v in per_iter.items()},
        "segments_s_per_iter": {k: [round(s, 4) for s in v]
                                for k, v in segs.items()},
        "stream_over_hbm": round(
            per_iter["stream"] / max(per_iter["hbm"], 1e-9), 4),
        "acceptance_1p5x": per_iter["stream"]
        <= 1.5 * per_iter["hbm"],
        "bit_identical_trees": bit_identical,
        "h2d_prefetch_s_per_iter": prefetch_s,
        "chunk_wait_s_per_iter": wait_s,
        "prefetch_overlap_fraction": overlap,
        "telemetry_stream": tel_stream.get("steady_phase_s_per_iter"),
        "telemetry_hbm": tel_hbm.get("steady_phase_s_per_iter"),
    }))


def run_batch_ab(rows: int, trees: int, window: int) -> None:
    """Child-process entry (ISSUE 18): warehouse batch scoring A/B —
    ``predict_stream`` (windowed out-of-core driver: WindowPump H2D ring
    in, ScoreRing D2H ring out, compiled-forest engine per window) vs the
    resident ``predict_raw`` on the SAME model and rows. Reports:

    * rows/s both arms + bit-identity (the streamed scores must be
      ``array_equal`` to resident — the driver's contract);
    * prefetch-overlap fraction from the ring telemetry (h2d_prefetch
      issue time vs chunk_wait stall, same decomposition as
      ``--stream-ab``) plus the ``d2h_scores`` phase, so BOTH link
      directions are measured;
    * the warehouse extrapolation: wall at 2^31 rows from the measured
      streamed rows/s vs the 20 GB/s host-link stream bound on the
      feature bytes (the number the driver exists for — a fraction near
      1.0 means the pump keeps the link busy; on CPU the traversal
      itself is the floor, so the fraction is chip-pending);
    * the interactive-p99-protected arm: a co-tenant prober (its OWN
      small model) issues 256-row resident predicts on a fixed cadence
      while the backfill runs — unthrottled vs throttled, where the
      :class:`CoTenantThrottle`'s signal source reports
      ``good_fraction`` = share of recent probe latencies within 2x the
      idle median (a stand-in for the SignalPlane's goodput block with
      identical schema). Protected p99 must not exceed unthrottled p99.

    Env: BENCH_BATCH_REPS (timed reps per arm, default 5),
    BENCH_BATCH_PROBE_S (per-arm prober soak seconds, default 6)."""
    _configure_jax_cache()
    import threading

    import jax

    import lambdagap_tpu as lgb
    from lambdagap_tpu.guard.backoff import Backoff
    from lambdagap_tpu.infer.stream import CoTenantThrottle

    reps = max(int(os.environ.get("BENCH_BATCH_REPS", "5")), 2)
    probe_soak_s = float(os.environ.get("BENCH_BATCH_PROBE_S", "6"))
    rng = np.random.RandomState(18)
    X = rng.randn(rows, FEATURES).astype(np.float32)
    X[rng.rand(rows, FEATURES) < 0.02] = np.nan
    y = (np.nan_to_num(X[:, 0]) - 0.5 * np.nan_to_num(X[:, 1])
         + 0.3 * rng.randn(rows) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 63, "verbose": -1,
              "max_bin": 63, "min_data_in_leaf": 50,
              "tpu_fast_predict_rows": 0, "predict_engine": "compiled"}
    t0 = time.time()
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=trees)
    train_s = time.time() - t0
    gb = bst._booster

    # resident arm: predict_raw returns a host array (device-complete by
    # construction), so the clock brackets full device work
    ref = gb.predict_raw(X)                       # warm the resident path
    res_s = []
    for _ in range(reps):
        t0 = time.time()
        ref = gb.predict_raw(X)
        res_s.append(time.time() - t0)
    resident_s = float(np.median(res_s))

    # streamed arm: same model, same rows, windowed through the rings
    stats = {}
    got = gb.predict_stream(X, raw_score=True, window_rows=window,
                            stats_out=stats)      # warm every row bucket
    stream_s = []
    for _ in range(reps):
        stats = {}
        t0 = time.time()
        got = gb.predict_stream(X, raw_score=True, window_rows=window,
                                stats_out=stats)
        stream_s.append(time.time() - t0)
    streamed_s = float(np.median(stream_s))
    bit_identical = bool(np.array_equal(ref, got))
    phases = stats.get("phases", {}) or {}
    prefetch_s = phases.get("h2d_prefetch")
    wait_s = phases.get("chunk_wait")
    overlap = None
    if prefetch_s is not None and wait_s is not None \
            and (prefetch_s + wait_s) > 0:
        # fraction of the H2D streaming overhead hidden behind compute:
        # chunk_wait is the part that surfaced as stall
        overlap = round(prefetch_s / (prefetch_s + wait_s), 4)

    # warehouse extrapolation: 2^31 rows at the measured streamed rate
    # vs the 20 GB/s host-link stream bound on the f32 feature bytes
    rows31 = 1 << 31
    stream_rps = rows / max(streamed_s, 1e-9)
    link_gbps = 20.0
    feature_bytes = rows31 * FEATURES * 4
    bound_wall_s = feature_bytes / (link_gbps * 1e9)
    extrapolated_wall_s = rows31 / stream_rps
    warehouse = {
        "rows": rows31,
        "feature_bytes": feature_bytes,
        "link_stream_bound_gbps": link_gbps,
        "link_stream_bound_wall_s": round(bound_wall_s, 1),
        "extrapolated_wall_s": round(extrapolated_wall_s, 1),
        "fraction_of_stream_bound": round(
            min(bound_wall_s / extrapolated_wall_s, 1.0), 4),
        "note": "bound = f32 feature bytes / 20 GB/s host link; the "
                "fraction is how close the pump runs to a saturated "
                "link — on CPU the per-row traversal is the floor, so "
                "the fraction certifies plumbing, not TPU wall",
    }

    # interactive-p99-protected arm: a second tenant (its own small
    # model) probes 256-row resident predicts on a fixed cadence; the
    # throttle's signal source scores recent probe latencies against
    # the idle baseline using the SignalPlane goodput schema
    params_i = {**params, "num_leaves": 31}
    bst_i = lgb.train(params_i,
                      lgb.Dataset(X[:16384], label=y[:16384],
                                  params=params_i),
                      num_boost_round=50)
    Xq = np.ascontiguousarray(X[:256])
    bst_i._booster.predict_raw(Xq)                # warm the probe path

    lat_lock = threading.Lock()
    recent: list = []                             # rolling probe window

    def _probe_loop(stop, out):
        while not stop.is_set():
            t0 = time.time()
            bst_i._booster.predict_raw(Xq)        # host-complete result
            dt = time.time() - t0
            out.append(dt)
            with lat_lock:
                recent.append(dt)
                del recent[:-32]
            stop.wait(0.015)

    def _soak(lat, fn):
        stop = threading.Event()
        th = threading.Thread(target=_probe_loop, args=(stop, lat),
                              daemon=True)
        th.start()
        t_end = time.time() + probe_soak_s
        while time.time() < t_end:
            fn()
        stop.set()
        th.join()

    def _pcts_ms(lat):
        if not lat:
            return None
        return {f"p{p}": round(float(np.percentile(lat, p)) * 1e3, 3)
                for p in (50, 90, 99)}

    lat_idle: list = []
    _soak(lat_idle, lambda: time.sleep(0.05))     # idle baseline
    idle_med = float(np.median(lat_idle)) if lat_idle else 1e-3

    lat_unthrottled: list = []
    _soak(lat_unthrottled,
          lambda: gb.predict_stream(X, raw_score=True, window_rows=window))

    def _signals():
        with lat_lock:
            win = list(recent)
        frac = (float(np.mean([d <= 2.0 * idle_med for d in win]))
                if win else 1.0)
        # the prober's SLO: 98% of recent probes within 2x idle median —
        # a burst of slow probes trips the ratio and arms the backoff
        return {"goodput": {"knee_rps": 0.0, "knee_margin": 1.0,
                            "good_fraction": frac, "good_ratio": 0.98}}

    throttle = CoTenantThrottle(
        _signals, backoff=Backoff(base_s=0.02, factor=2.0, max_s=0.25,
                                  jitter=0.0, seed=9))
    recent.clear()
    lat_protected: list = []
    _soak(lat_protected,
          lambda: gb.predict_stream(X, raw_score=True, window_rows=window,
                                    throttle=throttle))

    interactive = {
        "probe": "256-row resident predict on its own 50-tree model, "
                 "~15 ms cadence",
        "soak_s_per_arm": probe_soak_s,
        "idle_ms": _pcts_ms(lat_idle),
        "unthrottled_ms": _pcts_ms(lat_unthrottled),
        "protected_ms": _pcts_ms(lat_protected),
        "p99_protected": (_pcts_ms(lat_protected) or {}).get("p99", 0.0)
        <= (_pcts_ms(lat_unthrottled) or {}).get("p99", 0.0),
        "throttle": throttle.snapshot(),
    }

    print(json.dumps({
        "rows": rows, "trees": trees, "window_rows": window,
        "features": FEATURES, "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "method": f"median of {reps} timed full-matrix passes per arm, "
                  "warm buckets, host arrays close every bracket",
        "train_s": round(train_s, 2),
        "windows": stats.get("windows"),
        "buckets": stats.get("buckets"),
        "resident_s": round(resident_s, 4),
        "streamed_s": round(streamed_s, 4),
        "resident_rows_per_s": round(rows / max(resident_s, 1e-9)),
        "streamed_rows_per_s": round(stream_rps),
        "stream_over_resident": round(streamed_s / max(resident_s, 1e-9),
                                      4),
        "bit_identical": bit_identical,
        "h2d_prefetch_s": prefetch_s,
        "chunk_wait_s": wait_s,
        "d2h_scores_s": phases.get("d2h_scores"),
        "prefetch_overlap_fraction": overlap,
        "warehouse_2p31": warehouse,
        "interactive": interactive,
    }))


def run_multichip_attempt(grid: str, rows: int, max_bin: int,
                          iters: int, residency: str = "hbm") -> None:
    """Child-process entry (ISSUE 8, grid-swept in ISSUE 15): one fused
    training run at a fixed ``dd x ff`` grid. The parent
    (``--multichip-scaling``) launches one child per grid with the device
    topology in the environment
    (``--xla_force_host_platform_device_count=D`` on CPU; the real mesh
    as-is on TPU), so every grid gets a cold, honest program.

    ``grid`` is a ``mesh_shape`` string ("2x4"); a bare integer is the
    legacy width form ("8" == "8x1"). Both route through the fused 2-D
    data x feature learner — ONE program for every grid, which is what
    makes the sweep comparable. ``residency=stream`` runs the composed
    out-of-core path (ISSUE 15) instead of the resident one.

    Emits per-iter steady wall (device-complete via telemetry iteration
    boundaries), the sha of the built trees (grids must be BIT-identical
    on the quantized path — integer data-psum + feature-blocked argmax
    are grid-invariant), steady-state compile count, and the analytic
    per-iteration wire traffic of all three collectives: the histogram
    psum over ``data``, the best-tuple all_gather over ``feature``, and
    the winning-column psum broadcast over ``feature``.
    """
    import hashlib

    _configure_jax_cache()
    import jax

    import lambdagap_tpu as lgb
    from lambdagap_tpu.parallel.sharding import resolve_mesh_shape

    shape = resolve_mesh_shape(grid if "x" in grid else f"{grid}x1",
                               len(jax.devices()))
    dd, ff = shape
    n_devices = dd * ff
    assert len(jax.devices()) >= n_devices, (
        f"grid {grid} needs {n_devices} devices, have {len(jax.devices())}")
    leaves = int(os.environ.get("BENCH_MULTICHIP_LEAVES", "15"))
    # default QUANTIZED: integer histogram reduction is grid-invariant,
    # which is what makes the cross-grid bit-identity check meaningful
    # (f32 is reduction-order-equal only; near-ties may flip per grid).
    # The stream arm is f32 by construction (quant is a stream blocker)
    # and its contract is same-grid stream==hbm instead.
    quant = (os.environ.get("BENCH_MULTICHIP_QUANT", "1") == "1"
             and residency != "stream")
    higgs = os.environ.get("BENCH_DATA_HIGGS", "")
    if higgs:
        X, y, _, _ = _load_higgs_real(higgs)
        X, y = X[:rows], y[:rows]
    else:
        with np.load(_ensure_data(rows)) as d:
            X, y = d["X"][:rows], d["y"][:rows]
    params = {"objective": "binary", "tree_learner": "data",
              "tpu_fused_learner": "1", "mesh_shape": f"{dd}x{ff}",
              "num_leaves": leaves, "max_bin": max_bin,
              "min_data_in_leaf": 20, "verbose": -1,
              "use_quantized_grad": quant, "stochastic_rounding": False,
              "data_residency": residency, "enable_bundle": False,
              "telemetry": True, "telemetry_warmup": 2}
    if residency == "stream":
        params["stream_shard_rows"] = int(os.environ.get(
            "BENCH_MULTICHIP_SHARD_ROWS", str(max(rows // 7, 1 << 10))))
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params)
    booster = lgb.Booster(params=params, train_set=ds)
    t_construct = time.perf_counter() - t0
    from lambdagap_tpu.parallel.fused_parallel import Fused2DTreeLearner
    lr = booster._booster.learner
    assert isinstance(lr, Fused2DTreeLearner), type(lr)
    assert (lr.dd, lr.ff) == (dd, ff)
    assert lr.residency == residency, (lr.residency, residency)
    warmup = 2
    for _ in range(warmup + iters):
        booster.update()
    tel = booster._booster.telemetry
    recs = list(tel.records)
    steady = recs[warmup:]
    walls = sorted(r["wall_s"] for r in steady)
    s_per_iter = walls[len(walls) // 2] if walls else float("nan")
    compiles_steady = sum((r.get("compiles") or {}).get("total", 0)
                          for r in steady)
    trees_sha = hashlib.sha256(
        booster.model_to_string().split("end of trees")[0]
        .encode()).hexdigest()

    # analytic per-split wire traffic of the 2-D program's collectives
    # (ring-allreduce: 2(D-1)/D of the payload crosses each link;
    # ring-allgather: (D-1)/D)
    C_loc = int(lr.num_features) // ff
    Bb = int(lr.Bb)
    item = 4                              # f32 (quant_exact int32: same)
    splits = leaves - 1
    hist_payload = C_loc * Bb * 3 * item
    ring_d = 2 * (dd - 1) / max(dd, 1)
    # best-split tuple: 11 gathered fields, the 8-word cat bitset widest
    tuple_bytes = 10 * 4 + 8 * 4
    gather_f = (ff - 1) / max(ff, 1)
    n_loc = int(lr.n_loc)
    col_item = 1 if max_bin <= 255 else 2
    ring_f = 2 * (ff - 1) / max(ff, 1)
    wire_per_split = int(hist_payload * ring_d
                         + tuple_bytes * ff * gather_f
                         + n_loc * col_item * ring_f)
    extra = {}
    if residency == "stream":
        phases = {}
        for r in steady:
            for k, v in (r.get("phases") or {}).items():
                phases[k] = phases.get(k, 0.0) + v
        n = max(len(steady), 1)
        pre = phases.get("h2d_prefetch", 0.0) / n
        wait = phases.get("chunk_wait", 0.0) / n
        extra = {
            "h2d_prefetch_s_per_iter": round(pre, 5),
            "chunk_wait_s_per_iter": round(wait, 5),
            "prefetch_overlap_fraction": round(
                1.0 - wait / max(pre + wait, 1e-12), 4),
            "num_host_shards": int(lr.sdata.num_shards),
        }
    print(json.dumps({
        "grid": f"{dd}x{ff}",
        "n_devices": n_devices,
        "residency": residency,
        "rows": rows,
        "max_bin": max_bin,
        "num_leaves": leaves,
        "iters_measured": len(steady),
        "s_per_iter": round(s_per_iter, 5),
        "construct_s": round(t_construct, 3),
        "compiles_steady": compiles_steady,
        "trees_sha": trees_sha,
        "hist_psum_payload_bytes_per_split": hist_payload,
        "wire_bytes_per_split": wire_per_split,
        "wire_bytes_per_iter": wire_per_split * splits,
        "wire_split": {
            "hist_psum_data": int(hist_payload * ring_d),
            "best_tuple_allgather_feature": int(tuple_bytes * ff
                                                * gather_f),
            "column_bcast_feature": int(n_loc * col_item * ring_f),
        },
        "mesh": {"axes": ["data", "feature"], "shape": [dd, ff],
                 "platform": jax.devices()[0].platform},
        **extra,
    }))


def run_multichip_scaling(rows: int, max_bin: int, iters: int) -> None:
    """Parent entry (ISSUE 15 acceptance): measured dd x ff GRID sweep of
    the fused 2-D data x feature program — 1x8 / 2x4 / 4x2 / 8x1 by
    default (BENCH_MULTICHIP_GRIDS overrides), plus a serial 1-device
    anchor and one composed stream x distributed arm on the middle grid.

    Uses the real mesh when this host exposes enough accelerator devices;
    elsewhere each grid runs on a virtual
    ``--xla_force_host_platform_device_count=D`` CPU mesh — which measures
    the *distribution overhead* (padding, collective emulation, per-shard
    program shape), not parallel speedup, since every virtual device
    shares the same cores. Efficiency is therefore defined per mode:

    - real mesh:    efficiency = t_serial / (D * t_grid)   (ideal 1.0)
    - virtual mesh: efficiency = t_serial / t_grid         (ideal 1.0 —
      total work is constant, so any slowdown is pure distribution
      overhead)

    Emits the analytic per-grid wire traffic of all three collectives
    (hist psum over data, best-tuple all_gather over feature, column
    psum broadcast over feature) against the ICI bound (v5e ~45 GB/s,
    BENCH_MULTICHIP_ICI_GBPS), asserts trees are bit-identical across
    grids on the quantized path, asserts the stream arm is bit-identical
    to its same-grid resident arm, and sizes the TARGET out-of-core
    shape (BENCH_MULTICHIP_TARGET_ROWS, default 2^27) against a nominal
    16 GB chip to document where neither pure axis fits. Result JSON
    lands on stdout AND in MULTICHIP_r07.json (BENCH_MULTICHIP_OUT
    overrides).
    """
    grids = [g.strip() for g in os.environ.get(
        "BENCH_MULTICHIP_GRIDS", "1x1,1x8,2x4,4x2,8x1").split(",")]
    stream_grid = os.environ.get("BENCH_MULTICHIP_STREAM_GRID", "2x4")
    need = max(int(g.split("x")[0]) * int(g.split("x")[1]) for g in grids)
    # the parent must not touch JAX: a process that has initialized the
    # backend holds the chip, and the per-grid children below could not
    # take it. A short-lived probe child looks instead, and releases the
    # chip when it exits.
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.default_backend(), len(jax.devices()))"],
        capture_output=True, text=True, timeout=300, check=True)
    backend, n_dev = probe.stdout.split()[-2:]
    real = backend != "cpu" and int(n_dev) >= need
    env = dict(os.environ)

    def attempt(grid, residency, extra_env=None):
        dd, ff = (int(v) for v in grid.split("x"))
        child_env = dict(env, **(extra_env or {}))
        if not real:
            child_env["JAX_PLATFORMS"] = "cpu"
            flags = " ".join(
                f for f in child_env.get("XLA_FLAGS", "").split()
                if not f.startswith("--xla_force_host_platform"))
            child_env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={dd * ff}"
            ).strip()
        cmd = [sys.executable, os.path.abspath(__file__),
               "--multichip-attempt", grid, str(rows), str(max_bin),
               str(iters), residency]
        print(f"[bench] multichip grid {grid} ({residency}, "
              f"{'real mesh' if real else 'virtual CPU'})",
              file=sys.stderr, flush=True)
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=3600, env=child_env)
            if proc.returncode == 0 and proc.stdout.strip():
                return json.loads(proc.stdout.strip().splitlines()[-1])
            return {"error": f"rc={proc.returncode}: "
                             f"{(proc.stderr or '')[-400:]}"}
        except (subprocess.TimeoutExpired, json.JSONDecodeError) as e:
            return {"error": str(e)[:200]}

    results = {g: attempt(g, "hbm") for g in grids}
    stream_res = attempt(stream_grid, "stream")

    ok = [g for g in grids if "error" not in results.get(g, {})]
    t1 = results["1x1"]["s_per_iter"] if "1x1" in ok else None
    scaling = {}
    for g in ok:
        tg = results[g]["s_per_iter"]
        if t1 is None or not tg or g == "1x1":
            continue
        d = results[g]["n_devices"]
        speedup = t1 / tg
        scaling[g] = {
            "s_per_iter": tg,
            "speedup_vs_serial": round(speedup, 4),
            "efficiency": round(speedup / d if real else speedup, 4),
        }
    shas = {g: results[g].get("trees_sha") for g in ok}
    bit_identical = len(set(shas.values())) == 1 if shas else False
    # the stream arm is f32 (quant is a stream blocker); its identity
    # peer is a same-grid f32 RESIDENT run — the same-grid mirror
    # contract (f32 cross-grid identity is shape-lucky, ISSUE-8 finding)
    stream_bit_identical = False
    if "error" not in stream_res:
        stream_ref = attempt(stream_grid, "hbm",
                             {"BENCH_MULTICHIP_QUANT": "0"})
        stream_bit_identical = (
            "error" not in stream_ref
            and stream_res.get("trees_sha") == stream_ref.get("trees_sha"))
    ici_gbps = float(os.environ.get("BENCH_MULTICHIP_ICI_GBPS", "45"))
    wire_bounds = {
        g: round(results[g]["wire_bytes_per_iter"] / (ici_gbps * 1e9), 6)
        for g in ok if results[g].get("wire_bytes_per_iter")}

    # "neither pure axis fits": size the TARGET shape against a nominal
    # chip. The fused hbm path pins ~2x the packed matrix (packed rows +
    # column copy); the histogram state adds (L+1)*C*Bb*3*4 per device.
    # Default target: the pod-scale out-of-core corner — 2^31 rows x 136
    # MSLR-shaped columns, where (1,D) blows the replicated row block,
    # (D,1) blows the per-chip packed rows, and only stream x dd>=2
    # grids fit (O(rows/dd) scalar state + column-sharded histograms).
    target_rows = int(os.environ.get("BENCH_MULTICHIP_TARGET_ROWS",
                                     str(1 << 31)))
    target_cols = int(os.environ.get("BENCH_MULTICHIP_TARGET_COLS", "136"))
    hbm_bytes = 16 << 30
    leaves = int(os.environ.get("BENCH_MULTICHIP_LEAVES", "15"))
    Bb = max(1 << max_bin.bit_length(), 8)   # next_pow2(max_bin+1)
    item = 1 if max_bin <= 255 else 2
    fits = {}
    for g in grids:
        dd, ff = (int(v) for v in g.split("x"))
        rows_dev = -(-target_rows // dd)
        cols_dev = -(-target_cols // ff)
        resident = 2 * rows_dev * (cols_dev * item + 9)
        hist = (leaves + 1) * cols_dev * Bb * 3 * 4
        fits[g] = {
            "resident_bytes_per_dev": resident,
            "hist_state_bytes_per_dev": hist,
            "fits_16gb_hbm": bool(resident + hist < hbm_bytes),
            "fits_16gb_stream": bool(
                # stream keeps only O(rows) scalar state + hist on device
                rows_dev * 24 + hist < hbm_bytes),
        }
    out = {
        "bench": "multichip_scaling",
        "mode": "real_mesh" if real else "virtual_cpu",
        "efficiency_definition": ("t_serial/(D*t_grid) on a real mesh; "
                                  "t_serial/t_grid on a virtual "
                                  "single-host mesh (constant total work "
                                  "-> measures distribution overhead)"),
        "rows": rows,
        "max_bin": max_bin,
        "iters": iters,
        "grids": grids,
        "per_grid": {g: results[g] for g in grids},
        "scaling": scaling,
        "trees_bit_identical_across_grids": bit_identical,
        "stream_arm": stream_res,
        "stream_grid": stream_grid,
        "stream_bit_identical_to_resident_same_grid":
            bool(stream_bit_identical),
        "ici_bound_gbps": ici_gbps,
        "wire_s_lower_bound_per_iter": wire_bounds,
        "target_shape_fit_16gb": {
            "target_rows": target_rows, "target_cols": target_cols,
            "per_grid": fits,
            "note": ("neither pure axis fits resident at the target "
                     "shape when fits_16gb_hbm is false for 1xD and "
                     "Dx1 alike; the composed stream x 2-D mode is the "
                     "remaining path (fits_16gb_stream)"),
        },
        "compiles_steady_total": sum(
            int(results[g].get("compiles_steady", 0)) for g in ok),
    }
    line = json.dumps(out)
    out_path = os.environ.get(
        "BENCH_MULTICHIP_OUT",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "MULTICHIP_r07.json"))
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(line)


def run_microbench() -> None:
    """Child-process entry: measure THIS session's chip ceiling — HBM copy
    bandwidth (GB/s) and bf16 MXU GEMM throughput (TFLOP/s) — so the bench
    JSON can report how close the training program sits to the hardware
    roofline without relying on prose claims about chip health."""
    _configure_jax_cache()
    import jax
    import jax.numpy as jnp

    out = {"device": str(jax.devices()[0])}
    from jax import lax

    # every timed call reads a scalar out of the result (float(...)), which
    # ends the timed region device-complete at the cost of one small D2H.
    # The scalar is a jnp.sum so every element is live, and
    # lax.optimization_barrier separates the passes so XLA cannot fuse the
    # chain into one read+write.
    # HBM bandwidth: K chained out-of-place scaled adds per dispatch (each
    # reads + writes 256 MB) amortize the dispatch round-trip
    n = 1 << 26
    reps = 4
    x = jnp.arange(n, dtype=jnp.float32)

    def sweep(a):
        for _ in range(reps):
            a = lax.optimization_barrier(a * 1.0000001 + 1.0)
        return jnp.sum(a)

    copy = jax.jit(sweep)
    float(copy(x))                          # compile + first run
    best_bw = 0.0
    for _ in range(5):
        t0 = time.time()
        float(copy(x))
        best_bw = max(best_bw,
                      (reps * 2.0 * 4 * n) / (time.time() - t0) / 1e9)
    out["hbm_copy_gbps"] = round(best_bw, 3)

    # random-gather bandwidth: the training program's histogram pass
    # gathers ~30-40 contiguous bytes per random row index (binned row +
    # packed grad/hess), not a stream — on TPU these differ by an order of
    # magnitude, so the roofline needs both numbers. The microbench
    # matches that pattern: random 32 B rows from a 64 MB table.
    mg = 1 << 21
    xg = jnp.arange(mg * 8, dtype=jnp.float32).reshape(mg, 8)
    perm = jnp.asarray(np.random.RandomState(0).permutation(mg)
                       .astype(np.int32))

    def gath(a, p):
        for _ in range(2):
            a = lax.optimization_barrier(a[p])
        return jnp.sum(a)

    gather = jax.jit(gath)
    float(gather(xg, perm))
    best_g = 0.0
    # 68 B per visit: 4 index read + 32 random row read + 32 write
    for _ in range(5):
        t0 = time.time()
        float(gather(xg, perm))
        best_g = max(best_g, (2 * 68.0 * mg) / (time.time() - t0) / 1e9)
    out["hbm_gather_gbps"] = round(best_g, 3)

    # granule-matched gather profiles: random-row gather RATE (million
    # rows/s) for each payload the training program actually fetches —
    # 1 B partition column reads, 4 B u32 lanes, 8 B grad/hess pairs,
    # 32 B reference rows, and the two row-matrix layouts the histogram
    # pass can use (40 x u8 unpacked vs 10 x u32 packed). These feed a
    # floor with NO granule mismatch (the round-4 model read 32 B rows
    # for everything and conceded optimism).
    profiles = {
        "u8x1": (jnp.uint8, 1),
        "u32x1": (jnp.uint32, 1),
        "f32x2": (jnp.float32, 2),
        "f32x8": (jnp.float32, 8),
        "u8x40": (jnp.uint8, 40),
        "u32x10": (jnp.uint32, 10),
    }
    rates = {}
    for name, (dt, cols) in profiles.items():
        shape = (mg,) if cols == 1 else (mg, cols)
        tab = jnp.ones(shape, dt)

        def gat2(a, p):
            for _ in range(2):
                a = lax.optimization_barrier(a[p])
            return jnp.sum(a.astype(jnp.float32))

        # graftlint: disable=R2 — one jit per payload profile (6 total),
        # each compiled+run to completion before the next; not a hot loop
        g2 = jax.jit(gat2)
        float(g2(tab, perm))
        best = 0.0
        for _ in range(4):
            t0 = time.time()
            float(g2(tab, perm))
            best = max(best, 2.0 * mg / (time.time() - t0))
        rates[name] = round(best / 1e6, 2)          # million rows/s
    out["gather_mrows_per_s"] = rates

    # MXU: chained bf16 4096^3 GEMMs (4 per dispatch amortize the dispatch
    # latency); ones * 2^-12 scaling keeps values exactly 1.0 each step
    m = 4096
    a = jnp.ones((m, m), jnp.bfloat16)
    scale = jnp.bfloat16(2.0 ** -12)

    def chain(b):
        for _ in range(4):
            b = lax.optimization_barrier(
                jnp.dot(b, a, preferred_element_type=jnp.bfloat16) * scale)
        return jnp.sum(b.astype(jnp.float32))

    gemm = jax.jit(chain)
    float(gemm(a))
    best_t = float("inf")
    for _ in range(5):
        t0 = time.time()
        float(gemm(a))
        best_t = min(best_t, time.time() - t0)
    out["mxu_bf16_tflops"] = round(4 * 2 * m ** 3 / best_t / 1e12, 3)
    print(json.dumps(out))


def run_fixed_probe(rows: int, max_bin: int) -> None:
    """Child-process entry: per-iteration time at a row count small enough
    that byte traffic is negligible (~0.5% of full size) but with the SAME
    tree shape (num_leaves, min_data scaled down) — this measures the
    fused program's per-split FIXED cost (dispatch, collectives, scan
    latency), the component the bytes-only roofline model cannot see.
    roofline_per_iter_s = this + bytes/bandwidth."""
    _configure_jax_cache()
    import lambdagap_tpu as lgb

    rng = np.random.RandomState(13)
    X = rng.randn(rows, FEATURES).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.randn(rows) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": NUM_LEAVES,
              "learning_rate": 0.1, "max_bin": max_bin,
              # scaled so the tree still reaches ~NUM_LEAVES leaves
              "min_data_in_leaf": max(rows // (NUM_LEAVES * 2), 2),
              "verbose": -1, "tpu_fused_learner": "1"}
    ds = lgb.Dataset(X, label=y)
    booster = lgb.Booster(params=params, train_set=ds)
    booster.update()
    booster.update()
    # best-of-3 segments: single runs on the shared chip are meaningless
    seg = max(ITERS_MEASURED // 3, 5)
    per_iter = float("inf")
    for _ in range(3):
        t0 = time.time()
        for _ in range(seg):
            booster.update()
        np.asarray(booster._booster.scores[0][:1])
        per_iter = min(per_iter, (time.time() - t0) / seg)
    leaves = booster._booster._tree(len(booster._booster.models) - 1).num_leaves
    print(json.dumps({"rows": rows, "per_iter_s": round(per_iter, 4),
                      "iters_per_segment": seg, "segments": 3,
                      "last_tree_leaves": int(leaves)}))


def run_full_attempt(rows: int, max_bin: int) -> None:
    """Child-process entry: ONE full 500-iteration run, wall-clock measured
    end to end (no projection), plus the projection the sliced methodology
    would have produced from the same session — their ratio audits the
    extrapolation the headline relies on."""
    _configure_jax_cache()
    import lambdagap_tpu as lgb

    z = np.load(_data_cache_path(rows))
    X_all, y_all = z["X"], z["y"]
    X, y = X_all[:rows], y_all[:rows]
    Xv, yv = X_all[rows:], y_all[rows:]

    params = {"objective": "binary", "num_leaves": NUM_LEAVES,
              "learning_rate": 0.1, "max_bin": max_bin,
              "min_data_in_leaf": 100, "verbose": -1,
              "tpu_fused_learner": "1", "telemetry": True}
    t0 = time.time()
    ds = lgb.Dataset(X, label=y)
    booster = lgb.Booster(params=params, train_set=ds)
    t_construct = time.time() - t0
    t1 = time.time()
    booster.update()
    booster.update()
    np.asarray(booster._booster.scores[0][:1])   # device-complete warmup
    t_warm = time.time() - t1
    t2 = time.time()
    split_at = min(ITERS_MEASURED, 30)
    t_slice = None
    for i in range(ITERS_TOTAL - 2):
        booster.update()
        if i + 1 == split_at:
            np.asarray(booster._booster.scores[0][:1])
            t_slice = time.time() - t2
    np.asarray(booster._booster.scores[0][:1])
    t_train = time.time() - t2
    wall = t_construct + t_warm + t_train
    projected = (t_construct + t_warm
                 + (t_slice / split_at) * (ITERS_TOTAL - 2))
    # full-forest predict A/B at the REAL forest size: the DEVICE path now
    # dispatches in bounded 64-tree blocks (ops/predict.py), so the
    # 500-tree forest runs on device and gets a measured number — the
    # native/device routing threshold comes from this, not from an 8k-row
    # extrapolation. Measured at <= 50k rows: per-row device cost is
    # linear in rows.
    Xv_np = np.asarray(Xv)[:50_000]
    tp = time.time()
    pred = booster.predict(Xv_np)              # device path (cold compile)
    t_dev_cold = time.time() - tp
    auc = auc_score(np.asarray(yv)[:len(Xv_np)], pred)
    tp = time.time()
    booster.predict(Xv_np)
    t_dev_warm = time.time() - tp
    tn = time.time()
    booster.predict(Xv_np[:8192])              # native route (< threshold)
    t_native_8k = time.time() - tn
    native_us = t_native_8k / 8192 * 1e6
    device_us = t_dev_warm / len(Xv_np) * 1e6
    predict_full = {
        "trees": booster.num_trees(),
        "device_%drows_cold_s" % len(Xv_np): round(t_dev_cold, 3),
        "device_%drows_warm_s" % len(Xv_np): round(t_dev_warm, 3),
        "native_8192rows_s": round(t_native_8k, 4),
        "native_us_per_row": round(native_us, 2),
        "device_us_per_row_warm": round(device_us, 2),
        **_predict_crossover(booster, Xv_np, len(Xv_np), t_dev_warm,
                             native_us / 1e6),
        "device_faulted": False,
        # the ISSUE 3 acceptance A/B: tensorized vs sequential engine at
        # the REAL 500-tree/50k-row shape, same session, warm both sides
        "engine_ab": _predict_engine_ab(booster, Xv_np),
    }
    print(json.dumps({
        "rows": rows, "max_bin": max_bin, "iters": ITERS_TOTAL,
        "full_500iter_wall_s": round(wall, 3),
        "construct_s": round(t_construct, 3),
        "projected_from_first_%d" % split_at: round(projected, 3),
        "projection_error": round(wall / projected, 4),
        "holdout_auc": round(float(auc), 5),
        "synthetic": True,     # the projection audit always runs synthetic
        "predict_full_forest": predict_full,
        "telemetry": _telemetry_section(booster, ITERS_TOTAL - 2),
    }))


def run_rank_attempt(n_queries: int, max_bin: int = None) -> None:
    """MSLR-WEB30K-shaped lambdarank benchmark (second north star:
    NDCG@10 ~= 0.527 bar at full size, reference docs/GPU-Performance.rst:156).
    Child-process entry; prints one JSON line. BENCH_DATA_MSLR (a LETOR
    qid LibSVM file) swaps the synthetic queries for real data."""
    _configure_jax_cache()
    import lambdagap_tpu as lgb

    mslr_path = os.environ.get("BENCH_DATA_MSLR")
    if mslr_path:
        from lambdagap_tpu.config import Config
        from lambdagap_tpu.data.loader import _parse_text_file
        X, y, _, sizes, _ = _parse_text_file(mslr_path, Config.from_params(
            {"verbose": -1}))
        if sizes is None:
            raise SystemExit("BENCH_DATA_MSLR file carries no qid: groups")
        X = np.ascontiguousarray(X, np.float32)
        y = y.astype(np.float32)
        sizes = np.asarray(sizes, np.int64)
        n_queries = len(sizes)
        F = X.shape[1]
        N = len(X)
        synthetic = False
    else:
        rng = np.random.RandomState(11)
        F = 136                   # MSLR feature count
        sizes = rng.randint(40, 201, n_queries)       # ~120 docs/query
        N = int(sizes.sum())
        X = rng.randn(N, F).astype(np.float32)
        w = rng.randn(F).astype(np.float32) * (rng.rand(F) < 0.2)
        latent = X @ w * 0.6 + rng.randn(N).astype(np.float32)
        # graded relevance 0..4, MSLR-like skew toward 0
        y = np.clip(np.floor(latent - latent.mean() + 0.8), 0,
                    4).astype(np.float32)
        synthetic = True

    n_train_q = int(n_queries * 0.9)
    train_docs = int(sizes[:n_train_q].sum())
    params = {"objective": "lambdarank", "metric": "ndcg",
              "eval_at": [10], "num_leaves": 255, "learning_rate": 0.1,
              "max_bin": (max_bin if max_bin is not None else
                          int(os.environ.get("BENCH_RANK_MAX_BIN", 255))),
              "min_data_in_leaf": 50, "verbose": -1, "telemetry": True}
    t0 = time.time()
    dtrain = lgb.Dataset(X[:train_docs], label=y[:train_docs],
                         group=sizes[:n_train_q])
    booster = lgb.Booster(params=params, train_set=dtrain)
    dvalid = lgb.Dataset(X[train_docs:], label=y[train_docs:],
                         group=sizes[n_train_q:], reference=dtrain)
    booster.add_valid(dvalid, "valid")
    t_construct = time.time() - t0
    t1 = time.time()
    booster.update()
    booster.update()
    np.asarray(booster._booster.scores[0][:1])   # device-complete warmup
    t_warm = time.time() - t1
    iters = max(ITERS_MEASURED // 2, 5)
    t2 = time.time()
    for _ in range(iters):
        booster.update()
    np.asarray(booster._booster.scores[0][:1])
    per_iter = (time.time() - t2) / iters
    ndcg = {m: v for (_, m, v, _) in booster.eval_valid()}

    # per-iteration attribution: pairwise-lambda pass vs tree build (the
    # HIGGS-path rigor the rank section lacked). The gradient call is the
    # full bucketed pair-lattice program; tree time is the remainder.
    import jax.numpy as jnp
    obj = booster._booster.objective
    scores = booster._booster.scores
    float(jnp.sum(obj.get_gradients(scores)[0]))      # warm
    grad_s = float("inf")
    for _ in range(3):
        tg = time.time()
        for _ in range(3):
            g, _h = obj.get_gradients(scores)
        float(jnp.sum(g))
        grad_s = min(grad_s, (time.time() - tg) / 3)
    # dense pair-lattice work: sum over buckets of nq * L^2 (the tiled
    # long-query path does identical arithmetic in blocks)
    pairs = int(sum(len(qids) * (L ** 2)
                    for (L, qids, _) in obj.bucketing.buckets))
    projected = t_construct + t_warm + per_iter * (ITERS_TOTAL - 2)
    print(json.dumps({
        "queries": n_queries, "docs": N, "features": F,
        "max_bin": params["max_bin"],
        "construct_s": round(t_construct, 3),
        "per_iter_s": round(per_iter, 4),
        "grad_per_iter_s": round(grad_s, 4),
        "tree_per_iter_s": round(max(per_iter - grad_s, 0.0), 4),
        "lattice_pairs_per_iter": pairs,
        "lattice_gpairs_per_s": round(pairs / grad_s / 1e9, 3),
        "projected_500iter_s": round(projected, 3),
        "valid_ndcg": {k: round(float(v), 5) for k, v in ndcg.items()},
        "synthetic": synthetic,
        "data": mslr_path or "mslr-shaped synthetic",
        "iters_trained": iters + 2,
        "telemetry": _telemetry_section(booster, iters),
    }))


def _run_child(args, timeout, tag):
    """Run a child entry, return parsed JSON or {'error': ...}."""
    cmd = [sys.executable, os.path.abspath(__file__)] + args
    print(f"[bench] {tag}", file=sys.stderr, flush=True)
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout)
        if proc.returncode == 0 and proc.stdout.strip():
            return json.loads(proc.stdout.strip().splitlines()[-1])
        return {"error": f"rc={proc.returncode}: "
                         f"{(proc.stderr or '')[-300:]}"}
    except (subprocess.TimeoutExpired, json.JSONDecodeError) as e:
        return {"error": str(e)[:200]}


def main() -> None:
    # chip ceiling BEFORE the attempts (and again after — the shared chip's
    # minute-to-minute variance is part of the evidence)
    micro_pre = (None if os.environ.get("BENCH_MICRO", "1") == "0"
                 else _run_child(["--micro"], 900, "microbench (pre)"))

    # attempt ladder: (rows, fused, is_retry). With BENCH_DATA_HIGGS the
    # child trains the full real file regardless of the rows argument, so
    # row-ramping rungs would just repeat the same job — one rung (with a
    # retry + the serial fallback), and no synthetic caches get written.
    real_data = os.environ.get("BENCH_DATA_HIGGS") is not None
    ladder = []
    row_rungs = ((ROWS,) if real_data
                 else (ROWS, min(ROWS, 4_000_000), min(ROWS, 1_000_000)))
    for rows in row_rungs:
        if not ladder or rows != ladder[-1][0]:
            ladder.append((rows, True, False))
            ladder.append((rows, True, True))    # one retry
            ladder.append((rows, False, False))  # host-driven serial learner

    seen = set()
    attempts_log = []
    result = None
    for rows, fused, is_retry in ladder:
        key = (rows, fused, is_retry)
        if key in seen:
            continue
        seen.add(key)
        if not real_data:
            _ensure_data(rows)
        name = f"{'fused' if fused else 'serial'}@{rows}" + \
               ("(retry)" if is_retry else "")
        print(f"[bench] attempt {name}", file=sys.stderr, flush=True)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--attempt", str(rows), "1" if fused else "0", str(MAX_BIN)]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=ATTEMPT_TIMEOUT)
        except subprocess.TimeoutExpired:
            attempts_log.append({"attempt": name, "error": "timeout"})
            continue
        if proc.returncode == 0 and proc.stdout.strip():
            try:
                result = json.loads(proc.stdout.strip().splitlines()[-1])
                attempts_log.append({"attempt": name, "ok": True})
                break
            except json.JSONDecodeError:
                attempts_log.append({"attempt": name,
                                     "error": "bad json: " + proc.stdout[-200:]})
        else:
            tail = (proc.stderr or "")[-400:]
            attempts_log.append({"attempt": name,
                                 "error": f"rc={proc.returncode}: {tail}"})
        print(f"[bench] attempt {name} failed", file=sys.stderr, flush=True)

    if result is None:
        print(json.dumps({
            "metric": "higgs_500iter_train_wall_clock_projected",
            "value": None, "unit": "seconds", "vs_baseline": None,
            "detail": {"error": "all attempts failed",
                       "attempts": attempts_log},
        }))
        sys.exit(1)

    # secondary north star: MSLR-shaped lambdarank (reference bar
    # NDCG@10 ~= 0.527 at full size, docs/GPU-Performance.rst:156)
    ranking = None
    if os.environ.get("BENCH_RANK", "1") != "0":
        # like the HIGGS attempts: run the CPU-matched 255-bin setting AND
        # the 63-bin TPU mode (docs/GPU-Performance.rst:43-47), report
        # both, headline the better one (round-5 ABAB: 63-bin ~12% faster
        # per iter at equal NDCG; the round-4 artifact's 7.6x-slower
        # 63-bin run did NOT reproduce — a corrupted session, hence the
        # anomaly flag below)
        nq = int(os.environ.get("BENCH_RANK_QUERIES", 2000))
        rank_runs = {}
        for mb in (255, 63):
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--rank-attempt", str(nq), str(mb)]
            print(f"[bench] rank attempt max_bin={mb}", file=sys.stderr,
                  flush=True)
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=min(ATTEMPT_TIMEOUT, 1200))
                if proc.returncode == 0 and proc.stdout.strip():
                    rank_runs[mb] = json.loads(
                        proc.stdout.strip().splitlines()[-1])
                else:
                    rank_runs[mb] = {"error": f"rc={proc.returncode}: "
                                             f"{(proc.stderr or '')[-200:]}"}
            except (subprocess.TimeoutExpired, json.JSONDecodeError) as e:
                rank_runs[mb] = {"error": str(e)[:200]}
        ok = [r for r in rank_runs.values() if "error" not in r]
        best = (min(ok, key=lambda r: r["projected_500iter_s"])
                if ok else next(iter(rank_runs.values())))
        ranking = {**best,
                   "max_bin_255": rank_runs.get(255),
                   "max_bin_63": rank_runs.get(63)}
        if len(ok) == 2:
            per = [r["per_iter_s"] for r in ok]
            ratio = max(per) / max(min(per), 1e-9)
            # an intra-session A/B spread beyond 2x cannot be a real
            # program property of these two modes (round-5 ABAB measured
            # ~1.15x) — flag the artifact instead of shipping it silently
            ranking["anomaly"] = bool(ratio > 2.0)
            ranking["ab_per_iter_ratio"] = round(ratio, 3)
        if "grad_per_iter_s" in best and micro_pre \
                and "hbm_copy_gbps" in (micro_pre or {}):
            bw = micro_pre["hbm_copy_gbps"] * 1e9
            ranking["rank_roofline"] = {
                "grad_per_iter_s": best["grad_per_iter_s"],
                "tree_per_iter_s": best["tree_per_iter_s"],
                # ~12 B/pair: the fused lattice reads/writes a few f32
                # planes per pair — a bytes floor for the pairwise pass;
                # the pass is VPU/fusion bound well before it is byte
                # bound, so this floor is loose by design
                "lattice_bytes_floor_s": round(
                    best["lattice_pairs_per_iter"] * 12 / bw, 4),
                "note": "tree build shares the HIGGS-path issue model "
                        "(visit_counts roofline); the pairwise pass is "
                        "attributed by direct measurement",
            }

    # 63-bin TPU variant (reference: docs/GPU-Performance.rst:43-47 —
    # the GPU docs' own recommendation; one-hot histogram width drops 4x).
    # Both numbers are reported; the headline is the better one.
    result63 = None
    if (os.environ.get("BENCH_63", "1") != "0" and MAX_BIN == 255
            and result.get("fused")):
        name = f"fused@{result['rows']}/max_bin=63"
        print(f"[bench] attempt {name}", file=sys.stderr, flush=True)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--attempt", str(result["rows"]), "1", "63"]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=ATTEMPT_TIMEOUT)
            if proc.returncode == 0 and proc.stdout.strip():
                result63 = json.loads(proc.stdout.strip().splitlines()[-1])
                attempts_log.append({"attempt": name, "ok": True})
            else:
                attempts_log.append(
                    {"attempt": name,
                     "error": f"rc={proc.returncode}: "
                              f"{(proc.stderr or '')[-300:]}"})
        except (subprocess.TimeoutExpired, json.JSONDecodeError) as e:
            attempts_log.append({"attempt": name, "error": str(e)[:200]})

    chosen = result
    if (result63 is not None
            and result63["projected_500iter_s"] < result["projected_500iter_s"]):
        chosen = result63

    # one full 500-iteration run — no projection — at a size the session
    # budget allows; its projection_error audits the sliced methodology
    full_run = None
    if FULL_ROWS > 0:
        _ensure_data(FULL_ROWS)
        for attempt in range(2):     # one retry
            full_run = _run_child(
                ["--full-attempt", str(FULL_ROWS), str(chosen["max_bin"])],
                ATTEMPT_TIMEOUT,
                f"full 500-iter run @{FULL_ROWS}"
                + (" (retry)" if attempt else ""))
            if "error" not in full_run:
                break

    # dedicated predict A/B at the acceptance shape (500 trees x 50k rows):
    # tensorized vs sequential device engine vs native, + node-table
    # traffic roofline. Cheap (tiled forest, no 500-iteration training).
    predict_ab = None
    if os.environ.get("BENCH_PREDICT_AB", "1") != "0":
        predict_ab = _run_child(
            ["--predict-ab", "500", "50000"], 1800,
            "predict engine A/B (500 trees x 50k rows)")

    # sorted-vs-gather layout A/B at the headline shape (ISSUE 6): ABAB,
    # same session, shared dataset; the section the r06 acceptance reads
    layout_ab = None
    if os.environ.get("BENCH_LAYOUT_AB", "1") != "0" and result.get("fused"):
        layout_ab = _run_child(
            ["--layout-ab", str(chosen["rows"]), str(chosen["max_bin"]),
             str(ITERS_MEASURED)], ATTEMPT_TIMEOUT,
            "layout A/B (sorted vs gather)")

    # out-of-core stream vs resident A/B at a resident-capable shape
    # (ISSUE 7 acceptance: per-iter <= 1.5x, bit-identical trees,
    # transfer absorbed by h2d_prefetch overlap instead of chunk_wait)
    stream_ab = None
    if os.environ.get("BENCH_STREAM_AB", "1") != "0" and result.get("fused"):
        stream_ab = _run_child(
            ["--stream-ab", str(chosen["rows"]), str(chosen["max_bin"]),
             str(ITERS_MEASURED)], ATTEMPT_TIMEOUT,
            "stream A/B (out-of-core vs resident)")

    # warehouse batch-scoring A/B (ISSUE 18): predict_stream vs resident
    # predict_raw on the compiled engine — rows/s + bit-identity, the
    # ring overlap fractions, the 2^31-row extrapolation vs the 20 GB/s
    # stream bound, and the interactive-p99-protected co-tenant arm
    batch_ab = None
    if os.environ.get("BENCH_BATCH_AB", "1") != "0":
        batch_ab = _run_child(
            ["--batch-ab",
             os.environ.get("BENCH_BATCH_ROWS", str(1 << 18)),
             os.environ.get("BENCH_BATCH_TREES", "200"),
             os.environ.get("BENCH_BATCH_WINDOW", str(1 << 16))],
            ATTEMPT_TIMEOUT,
            "batch scoring A/B (predict_stream vs resident)")

    # constant-vs-linear leaves A/B (ISSUE 11): wall-clock-to-target-metric
    # at HIGGS- and MSLR-shaped configs — the per-iter cost the linear
    # solve adds vs the iterations it saves (arXiv:1802.05640)
    linear_ab = None
    if os.environ.get("BENCH_LINEAR_AB", "1") != "0":
        linear_ab = _run_child(
            ["--linear-ab", str(min(chosen["rows"], 1 << 20)),
             str(chosen["max_bin"]), str(ITERS_MEASURED * 2)],
            ATTEMPT_TIMEOUT, "linear-leaf A/B (constant vs linear)")

    # multi-chip scaling (ISSUE 8): fused data-parallel at 1/2/4/8
    # grids — real mesh when present, virtual CPU grids elsewhere —
    # with bit-identity across dd x ff grids on the quantized path, the
    # composed stream arm vs its same-grid resident peer, and the
    # three-collective wire traffic vs the ICI bound
    multichip = None
    if os.environ.get("BENCH_MULTICHIP", "1") != "0":
        multichip = _run_child(
            ["--multichip-scaling",
             os.environ.get("BENCH_MULTICHIP_ROWS", str(1 << 16)),
             "255", "6"], 5400,
            "multichip scaling (1x8/2x4/4x2/8x1 grids + stream arm)")

    # chip ceiling AFTER the attempts
    micro_post = (None if os.environ.get("BENCH_MICRO", "1") == "0"
                  else _run_child(["--micro"], 900, "microbench (post)"))

    # per-split fixed-cost probe: same tree shape, negligible bytes
    probe = None
    if os.environ.get("BENCH_PROBE", "1") != "0":
        probe = _run_child(["--fixed-probe", "65536",
                            str(chosen["max_bin"])], 900,
                           "fixed-cost probe @65536")

    # roofline: attainable per-iteration time on THIS chip from the
    # same-session microbench + EXACT work counts read off the trained
    # trees (visit_counts). Two attainable estimates bracket the truth:
    #   bytes_floor — traffic / streaming+gather bandwidth (a true lower
    #     bound: no access pattern moves fewer bytes);
    #   issue_est   — row-visit counts / the granule-matched random-row
    #     gather rates (the program's gathers follow a leaf-ordered
    #     permutation, i.e. near-random row access at these shapes, so
    #     this estimates what the chip sustains for THIS pattern; program
    #     locality can beat it, so it is an estimate, not a bound).
    # roofline_fraction uses the larger (more honest) of the two.
    roofline = None
    micros = [m for m in (micro_pre, micro_post)
              if m and "hbm_copy_gbps" in m]
    if micros:
        bw_s = max(m["hbm_copy_gbps"] for m in micros) * 1e9
        bw_g = max(m.get("hbm_gather_gbps", 0) for m in micros) * 1e9
        gb, sb = model_bytes_per_iter(chosen["rows"])
        model_bytes_floor = gb / (bw_g or bw_s) + sb / bw_s
        # ISSUE 19: prefer the cost plane's measured per-iteration traffic
        # (XLA's analytic bytes for the executables this attempt actually
        # dispatched) over the hand-derived model; the ledger does not
        # split gather vs stream, so the streaming bandwidth is the
        # honest (optimistic) divisor. The model stays as a cross-check.
        cp = chosen.get("costplane") or {}
        cp_bytes = cp.get("bytes_per_iter", 0.0)
        if cp_bytes:
            bytes_floor = cp_bytes / bw_s
            ratio = cp_bytes / max(gb + sb, 1.0)
            if not 0.5 <= ratio <= 2.0:
                print(f"[bench] costplane bytes/iter {cp_bytes:.3e} "
                      f"disagrees with the traffic model {gb + sb:.3e} "
                      f"({ratio:.2f}x) — trusting the ledger; re-derive "
                      "model_bytes_per_iter", file=sys.stderr, flush=True)
        else:
            bytes_floor = model_bytes_floor
        fixed_s = (probe or {}).get("per_iter_s", 0.0) or 0.0

        def _rate(name):
            vals = [m.get("gather_mrows_per_s", {}).get(name)
                    for m in micros]
            vals = [v for v in vals if v]
            return max(vals) * 1e6 if vals else None

        issue_est = None
        vc = chosen.get("visit_counts")
        pack_on = os.environ.get("LAMBDAGAP_PACK32", "1") != "0"
        r_hist = _rate("u32x10" if pack_on else "u8x40")
        r_col = _rate("u8x1")
        r_i32 = _rate("u32x1")
        if vc and r_hist and r_col and r_i32:
            # hist: one packed-row gather per (padded) visit; partition:
            # one 1 B column gather + one 4 B perm scatter per visit;
            # perm reads/copy-backs are contiguous window DMAs -> streams
            t_hist = vc["hist_rows_padded_per_iter"] / r_hist
            t_part = (vc["part_rows_padded_per_iter"] / r_col
                      + vc["part_rows_padded_per_iter"] / r_i32)
            stream_bytes = 4.0 * (vc["hist_rows_padded_per_iter"]
                                  + 3 * vc["part_rows_padded_per_iter"])
            t_stream = stream_bytes / bw_s
            issue_est = {
                "hist_gather_s": round(t_hist, 4),
                "part_gather_scatter_s": round(t_part, 4),
                "window_stream_s": round(t_stream, 4),
                "total_s": round(t_hist + t_part + t_stream + fixed_s, 4),
            }
        bytes_plus_fixed_s = bytes_floor + fixed_s
        floor_s = max(bytes_plus_fixed_s,
                      issue_est["total_s"] if issue_est else 0.0)
        frac = min(floor_s / chosen["per_iter_s"], 1.0)
        model_desc = (
            "attainable = max(bytes floor, granule-matched issue "
            "estimate) + measured per-split fixed cost (65536-row probe). "
            "Issue estimate = exact tree-derived row-visit counts / "
            "measured random-row gather rates at the ACTUAL payloads "
            "(u32-lane packed rows for hist, 1 B column + 4 B scatter for "
            "partition) — no granule mismatch; counts use smaller-child + "
            "window-padding accounting read off the trained trees. "
            "fraction > 1 before capping means the program's gathers beat "
            "the random-access microbench via partition locality.")
        roofline = {
            "model_gather_bytes_per_iter": int(gb),
            "model_stream_bytes_per_iter": int(sb),
            "hbm_copy_gbps_best": round(bw_s / 1e9, 3),
            "hbm_gather_gbps_best": round(bw_g / 1e9, 3),
            # pure bytes floor (round-4-comparable key) and the
            # fixed-cost-inclusive variant, kept separate so readers
            # never double-count fixed_s
            "bytes_floor_per_iter_s": round(bytes_floor, 4),
            "bytes_floor_source": "costplane" if cp_bytes else "model",
            "costplane_bytes_per_iter": int(cp_bytes) if cp_bytes else None,
            "costplane_flops_per_iter": (int(cp["flops_per_iter"])
                                         if cp_bytes else None),
            "model_bytes_floor_per_iter_s": round(model_bytes_floor, 4),
            "bytes_floor_plus_fixed_s": round(bytes_plus_fixed_s, 4),
            "issue_estimate": issue_est,
            "fixed_cost_per_iter_s": round(fixed_s, 4),
            "fixed_cost_probe": probe,
            "roofline_per_iter_s": round(floor_s, 4),
            "measured_per_iter_s": chosen["per_iter_s"],
            "roofline_fraction": round(frac, 4),
            "roofline_fraction_uncapped": round(
                floor_s / chosen["per_iter_s"], 4),
            "visit_counts": vc,
            "model": model_desc,
        }

    projected = chosen["projected_500iter_s"]
    note = ("full HIGGS size" if chosen["rows"] == 10_500_000 else
            f"reduced rows ({chosen['rows']}); vs_baseline not size-matched")
    if chosen.get("max_bin") != 255:
        note += (f"; headline uses max_bin={chosen.get('max_bin')}, "
                 "baseline is 255-bin CPU")
    print(json.dumps({
        "metric": "higgs_500iter_train_wall_clock_projected",
        "value": projected,
        "unit": "seconds",
        "vs_baseline": round(BASELINE_S / projected, 4),
        "detail": {
            **chosen,
            "max_bin_255": result,
            "max_bin_63": result63,
            "attempts": attempts_log,
            "baseline": "reference CPU 130.094s @10.5M rows "
                        "(docs/Experiments.rst:111-124)",
            "note": note,
            "microbench_pre": micro_pre,
            "microbench_post": micro_post,
            "layout_ab": layout_ab,
            "stream_ab": stream_ab,
            "batch_ab": batch_ab,
            "linear_ab": linear_ab,
            "multichip": multichip,
            "roofline": roofline,
            "full_run": full_run,
            "predict_tensor_ab": predict_ab,
            "ranking_mslr_shaped": ranking,
        },
    }))


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--attempt":
        run_attempt(int(sys.argv[2]), sys.argv[3] == "1",
                    int(sys.argv[4]) if len(sys.argv) > 4 else None)
    elif len(sys.argv) >= 3 and sys.argv[1] == "--rank-attempt":
        run_rank_attempt(int(sys.argv[2]),
                         int(sys.argv[3]) if len(sys.argv) > 3 else None)
    elif len(sys.argv) >= 5 and sys.argv[1] == "--layout-ab":
        run_layout_ab(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
    elif len(sys.argv) >= 5 and sys.argv[1] == "--stream-ab":
        run_stream_ab(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
    elif len(sys.argv) >= 5 and sys.argv[1] == "--batch-ab":
        run_batch_ab(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
    elif len(sys.argv) >= 5 and sys.argv[1] == "--linear-ab":
        run_linear_ab(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
    elif sys.argv[1:2] == ["--multichip-scaling"]:
        run_multichip_scaling(
            int(sys.argv[2]) if len(sys.argv) > 2
            else int(os.environ.get("BENCH_MULTICHIP_ROWS", str(1 << 17))),
            int(sys.argv[3]) if len(sys.argv) > 3 else 255,
            int(sys.argv[4]) if len(sys.argv) > 4 else 6)
    elif len(sys.argv) >= 6 and sys.argv[1] == "--multichip-attempt":
        run_multichip_attempt(sys.argv[2], int(sys.argv[3]),
                              int(sys.argv[4]), int(sys.argv[5]),
                              sys.argv[6] if len(sys.argv) > 6 else "hbm")
    elif sys.argv[1:2] == ["--micro"]:
        run_microbench()
    elif len(sys.argv) >= 4 and sys.argv[1] == "--predict-ab":
        run_predict_ab(int(sys.argv[2]), int(sys.argv[3]))
    elif len(sys.argv) >= 4 and sys.argv[1] == "--fixed-probe":
        run_fixed_probe(int(sys.argv[2]), int(sys.argv[3]))
    elif len(sys.argv) >= 4 and sys.argv[1] == "--full-attempt":
        run_full_attempt(int(sys.argv[2]), int(sys.argv[3]))
    else:
        main()
