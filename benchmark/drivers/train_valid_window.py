"""The training window with the folds watched: the job a ranking team runs.
``lgb.Dataset(train)``, one ``lgb.Dataset(fold, reference=train)`` a watched
fold, then ``engine.train``'s loop body in its order, once per boosting
iteration: ``Booster.update()``, the ``eval`` phase with ``eval_valid()``,
the ``early_stopping`` callback. Every placement knob at its default.

Set-up is seeded data generation on the host (the configuration's hold-out
queries, split into its ``folds`` in their order: the first is the one early
stopping watches), ``Dataset.construct()`` of each set, booster creation,
``add_valid`` and the traffic mix's warm-up iterations (they hold every
compile). The window continues the SAME booster: whole iterations, each
ended by ``block_until_ready`` on the training scores AND by the metric
values being Python floats in the callback's hands, until the first
iteration boundary at or after ``--seconds`` (or the callback's stop). After
the window, outside both clocks: the peak-memory reading, the training
scores, the folds' scores and the model text read back, the program's state
freed, and both plain references (``gbdt_check`` over the training side as
``train_window`` runs it, ``valid_check`` over the folds).

Hooks (``benchmark/tests``): ``update(bst)`` as ``train_window``;
``evaluate(bst) -> [(fold, metric, value, greater_is_better)]``;
``early_stopping(rounds, verbose=False)`` the callback's factory.
"""
from __future__ import annotations

import glob
import importlib
import os
import shutil
import time

import numpy as np

from .. import health, xplane
from ..reference import gbdt_check, valid_check
from .train_window import _annotate_phases, _resolved


def _folds(cfg: dict, data: dict) -> list:
    """The hold-out's queries split into the configuration's folds, in
    their order."""
    out, q, row = [], 0, 0
    group = np.asarray(data["group_hold"])
    for name, nq in cfg["folds"].items():
        g = group[q:q + int(nq)]
        rows = int(g.sum())
        out.append({"name": name, "X": data["X_hold"][row:row + rows],
                    "y": data["y_hold"][row:row + rows], "group": g})
        q, row = q + int(nq), row + rows
    if q != len(group):
        raise ValueError(f"folds hold {q} queries, the hold-out {len(group)}")
    return out


def run(ctx: dict) -> dict:
    import jax
    log, cell, seed = ctx["log"], ctx["cell"], ctx["seed"]
    traffic = cell["traffic"]
    cfg = dict(cell["config"])
    rehearse = ctx["rehearse"]
    if rehearse:
        over = cfg["rehearsal"]
        cfg.update({k: v for k, v in over.items() if k != "params"})
    params = dict(cfg["params"])
    if rehearse:
        params.update(cfg["rehearsal"].get("params", {}))
    params.update(ctx["control"].get("params", {}))
    traced = ctx["trace"]
    if traced:
        params["telemetry"] = True
    spans, counters = {}, {}

    # -- set-up ---------------------------------------------------------
    t = time.perf_counter()
    gen = importlib.import_module("benchmark.datagen." + cfg["datagen"])
    data = gen.generate(cfg, seed, int(cfg["num_rows"]),
                        int(cfg["holdout"]))
    folds = _folds(cfg, data)
    spans["datagen_s"] = time.perf_counter() - t
    log(f"data: {data['X'].shape[0]:,} x {data['X'].shape[1]} train rows in "
        f"{len(data['group'])} queries; watched folds "
        + ", ".join(f"{f['name']} {len(f['group']):,} queries / "
                    f"{f['X'].shape[0]:,} docs" for f in folds)
        + f"; generated in {spans['datagen_s']:.1f} s")
    if not rehearse:
        t = time.perf_counter()
        log(f"chip health: {health.microbench()} "
            f"({time.perf_counter() - t:.1f} s)")

    import lambdagap_tpu as lgb
    from lambdagap_tpu.callback import CallbackEnv, EarlyStopException
    ds = lgb.Dataset(data["X"], label=data["y"], group=data["group"],
                     params=params)
    watched = [lgb.Dataset(f["X"], label=f["y"], group=f["group"],
                           reference=ds) for f in folds]
    t = time.perf_counter()
    ds.construct()
    spans["construct_s"] = time.perf_counter() - t
    t = time.perf_counter()
    bst = lgb.Booster(params, ds)
    gb = bst._booster
    for f, vs in zip(folds, watched):
        bst.add_valid(vs, f["name"])
    spans["booster_s"] = time.perf_counter() - t
    k = int(params["eval_at"][0])
    metric = f"ndcg@{k}"
    patience = int(params["early_stopping_round"])
    stopper = ctx.get("early_stopping", lgb.early_stopping)(
        patience, verbose=False)
    evaluate = ctx.get("evaluate", lambda b: b._booster.eval_valid())
    evals, stopped = [], []

    def iteration() -> float:
        t0 = time.perf_counter()
        i = gb.iter_
        ctx["update"](bst)
        with gb.telemetry.phase("eval"):
            got = evaluate(bst)
        values = {d: float(v) for d, m, v, _ in got if m == metric}
        if values:
            evals.append({"iteration": i, "trees": len(gb.models),
                          "values": values})
        try:
            stopper(CallbackEnv(
                model=bst, params=params, iteration=i, begin_iteration=0,
                end_iteration=1 << 30, evaluation_result_list=got,
                telemetry=gb.telemetry))
        except EarlyStopException:
            stopped.append(i)
        jax.block_until_ready(gb.scores)
        return time.perf_counter() - t0

    warm = [iteration() for _ in range(int(traffic["warmup_iterations"]))]
    resolved = _resolved(gb)
    log(f"resolved learner: {resolved}; construct "
        f"{spans['construct_s']:.1f} s, booster + add_valid "
        f"{spans['booster_s']:.1f} s, warm-up iterations "
        f"{[round(w, 2) for w in warm]} s")
    tel = gb.telemetry if traced else None
    base = tel.watchdog.totals() if tel is not None else None
    if tel is not None:
        counters["compile_secs"] = base["compile_secs"]
        counters["setup_compiles"] = base["compiles"]
        _annotate_phases(tel, jax)
    trace_dir = os.path.join(ctx["out_dir"], "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)

    # -- the window -----------------------------------------------------
    setup_s = time.perf_counter() - ctx["t_start"]
    walls = []
    n_traced = int(traffic["trace_iterations"]) if traced else 0
    t_window = time.perf_counter()
    if n_traced:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    while True:
        if n_traced and len(walls) < n_traced:
            with jax.profiler.TraceAnnotation("lg_iteration"):
                walls.append(iteration())
            if len(walls) == n_traced:
                t0 = time.perf_counter()
                jax.profiler.stop_trace()
                spans["trace_stop_s"] = time.perf_counter() - t0
                spans["traced_window_s"] = t0 - t_window
        else:
            walls.append(iteration())
        window_s = time.perf_counter() - t_window
        if (window_s >= ctx["seconds"] or stopped) \
                and len(walls) >= n_traced:
            break
    iters = len(walls)
    stats = jax.local_devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    counters["hbm_peak_bytes"] = peak
    counters["hbm_reserved_bytes"] = int(stats.get("peak_bytes_reserved", 0))
    spans["iter_max_s"] = max(walls)
    log(f"window: {iters} iterations in {window_s:.3f} s "
        f"(slowest {max(walls):.3f} s, fastest {min(walls):.3f} s); "
        f"peak live device memory {peak / 1e9:.3f} GB, reserved for "
        f"programs' temporaries {counters['hbm_reserved_bytes'] / 1e9:.3f} GB")
    log(f"device memory stats: {stats}")
    records = []
    if tel is not None:
        now = tel.watchdog.totals()
        counters["window_compiles"] = now["compiles"] - base["compiles"]
        tel.close()
        records = list(tel.records)[-iters:]
        d2h = [r.get("counts", {}).get("eval_d2h_bytes") for r in records]
        if d2h and None not in d2h:
            counters["eval_d2h_bytes"] = sum(d2h) / len(d2h)
        log(f"compiles: {base['compiles']} in set-up "
            f"({base['compile_secs']:.1f} s), "
            f"{counters['window_compiles']} in the window")

    # -- after the window, outside both clocks --------------------------
    t = time.perf_counter()
    total_iters = len(warm) + iters
    lazy = sum(type(m).__name__ == "_LazyTree" for m in gb.models)
    train_scores = np.asarray(gb.scores)
    final_scores = {f["name"]: np.asarray(gb.valid_scores[vi])
                    for vi, f in enumerate(folds)}
    model_text = bst.model_to_string()
    readback_s = time.perf_counter() - t
    key = f"{folds[0]['name']} {metric}"
    state = getattr(stopper, "state", {})
    at = {e["iteration"]: n for n, e in enumerate(evals)}
    early = {"patience": patience,
             "best_iter": at.get(state.get("best_iter", {}).get(key), -1),
             "best_score": state.get("best_score", {}).get(key,
                                                           float("nan")),
             "stopped_at": at.get(stopped[0], -1) if stopped else -1}
    log(f"evaluations: {len(evals)} of {total_iters} iterations; last "
        f"{evals[-1]['values'] if evals else None}; early stopping "
        f"(patience {patience}, watching {key!r}): {early}; trees still on "
        f"the device at the window's end: {lazy} of {len(gb.models)}")
    del bst, ds, gb, watched, stopper
    t = time.perf_counter()
    numbers = gbdt_check.check(
        model_text, data, dict(params, objective_params=cfg.get(
            "objective_params", {})), train_scores, total_iters, seed)
    numbers["learner_mismatch"] = float(
        sum(resolved.get(k) != v for k, v in cfg["learner"].items()))
    check_s = time.perf_counter() - t
    t = time.perf_counter()
    numbers.update(valid_check.check(
        model_text, folds, evals, final_scores, total_iters, early, k=k,
        metric_tol=float(cell["limits"]["limits"]["valid_metric"])))
    valid_check_s = time.perf_counter() - t
    trees = gbdt_check.parse_model(model_text)
    log("quality (synthetic data, the reference's own float64 scores of the "
        "program's trees; information only): "
        + str({"data": "synthetic", **{
            f"{f['name']}_ndcg_at_{k}": numbers["ndcg_ref_" + f["name"]]
            for f in folds}}))
    log(f"scores + model text read back in {readback_s:.1f} s, reference "
        f"check {check_s:.1f} s, the folds' reference {valid_check_s:.1f} s")

    out = {
        "attempted": iters, "failed": 0, "numbers": numbers,
        "end_to_end": {"setup_s": setup_s,
                       "train_iter_s": window_s / iters},
        "memory_peak_bytes": peak, "spans": spans, "counters": counters,
        "records": records, "iterations": iters, "traced_iterations": n_traced,
        "trees": trees,
        "config": cfg, "params": params, "window_s": window_s,
    }
    if n_traced:
        paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        t = time.perf_counter()
        out["trace"] = xplane.read(paths[-1]) if paths else None
        log(f"trace: {os.path.getsize(paths[-1]) / 1e6:.1f} MB read in "
            f"{time.perf_counter() - t:.1f} s" if paths else "trace: none")
        shutil.rmtree(trace_dir, ignore_errors=True)
    return out
