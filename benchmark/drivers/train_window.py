"""The training window: the user's own loop, ``lgb.Dataset`` ->
``lgb.Booster`` -> ``Booster.update()`` once per boosting iteration, every
placement knob at its default.

Set-up is seeded data generation on the host, ``Dataset.construct()``,
booster creation and the traffic mix's warm-up iterations (they hold every
compile). The window continues the SAME booster: whole iterations, each
ended by ``block_until_ready`` on the training scores, until the first
iteration boundary at or after ``--seconds``. After the window, outside
both clocks: the peak-memory reading, the training scores and the model text
read back, the program's state freed, and the plain reference over what the
booster grew.
"""
from __future__ import annotations

import contextlib
import glob
import importlib
import os
import shutil
import time

import numpy as np

from .. import health, xplane
from ..reference import gbdt_check
from ..reference.quality import QUALITY


@contextlib.contextmanager
def _both(outer, inner):
    """Two context managers as one (an annotation around a telemetry span)."""
    with outer, inner:
        yield


def _annotate_phases(tel, jax) -> None:
    """Put the booster's telemetry phases on the profiler's clock: each
    ``tel.phase(name)`` also opens a ``TraceAnnotation("lg_phase:<name>")``,
    so an idle gap of the device can be named by what the host was doing.
    Done from here, on the live object, in traced runs only."""
    phase = tel.phase
    tel.phase = lambda name, legacy=None: _both(
        jax.profiler.TraceAnnotation("lg_phase:" + name), phase(name, legacy))


def _resolved(gb) -> dict:
    learner = gb.learner
    return {"class": type(learner).__name__,
            "hist_impl": getattr(learner, "hist_impl", None),
            "layout": getattr(learner, "layout", None),
            "residency": getattr(learner, "residency", None)}


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
    spans["datagen_s"] = time.perf_counter() - t
    log(f"data: {data['X'].shape[0]:,} x {data['X'].shape[1]} train rows, "
        f"{data['X_hold'].shape[0]:,} hold-out rows, "
        f"{'no' if data['group'] is None else len(data['group'])} queries, "
        f"generated in {spans['datagen_s']:.1f} s")
    if not rehearse:
        t = time.perf_counter()
        log(f"chip health: {health.microbench()} "
            f"({time.perf_counter() - t:.1f} s)")

    import lambdagap_tpu as lgb
    ds = lgb.Dataset(data["X"], label=data["y"], group=data["group"],
                     params=params)
    t = time.perf_counter()
    ds.construct()
    spans["construct_s"] = time.perf_counter() - t
    t = time.perf_counter()
    bst = lgb.Booster(params, ds)
    gb = bst._booster
    spans["booster_s"] = time.perf_counter() - t

    def iteration() -> float:
        t0 = time.perf_counter()
        ctx["update"](bst)
        jax.block_until_ready(gb.scores)
        return time.perf_counter() - t0

    warm = [iteration() for _ in range(int(traffic["warmup_iterations"]))]
    resolved = _resolved(gb)
    log(f"resolved learner: {resolved}; construct "
        f"{spans['construct_s']:.1f} s, booster {spans['booster_s']:.1f} s, "
        f"warm-up iterations {[round(w, 2) for w in warm]} s")
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
        # host spans come from TraceAnnotation; the Python tracer would
        # only add an event per Python call to the file and to the host
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
        if window_s >= ctx["seconds"] and len(walls) >= n_traced:
            break
    # the traced run's clock carries the profiler's stop; the end-to-end
    # metrics are taken with the profiler off
    iters = len(walls)
    stats = jax.local_devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    counters["hbm_peak_bytes"] = peak
    # the TPU runtime accounts for the loaded programs' temporaries apart
    # from live buffers; a per-layer metric of its own
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
        log(f"compiles: {base['compiles']} in set-up "
            f"({base['compile_secs']:.1f} s), "
            f"{counters['window_compiles']} in the window")

    # -- after the window, outside both clocks --------------------------
    t = time.perf_counter()
    total_iters = len(warm) + iters
    train_scores = np.asarray(gb.scores)
    model_text = bst.model_to_string()
    readback_s = time.perf_counter() - t
    del bst, ds, gb
    t = time.perf_counter()
    numbers = gbdt_check.check(
        model_text, data, dict(params, objective_params=cfg.get(
            "objective_params", {})), train_scores, total_iters, seed)
    numbers["learner_mismatch"] = float(
        sum(resolved.get(k) != v for k, v in cfg["learner"].items()))
    check_s = time.perf_counter() - t
    trees = gbdt_check.parse_model(model_text)
    quality = QUALITY[params["objective"]](
        data, gbdt_check.tree_scores(trees, data["X_hold"]))
    log(f"quality (synthetic data, the reference's traversal of the "
        f"program's trees over the hold-out; information only): {quality}")
    log(f"scores + model text read back in {readback_s:.1f} s, "
        f"reference check {check_s:.1f} s")

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
