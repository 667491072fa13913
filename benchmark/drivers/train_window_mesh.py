"""The training window on a mesh: ``train_window``'s closed loop
(``lgb.Dataset`` -> ``lgb.Booster`` -> ``Booster.update()`` once per boosting
iteration, every placement knob at its default but the configuration's own
``tree_learner`` and ``tpu_num_devices``), read on EVERY chip the job holds.

What differs from ``train_window``:

- ``memory_peak_bytes`` (and ``hbm_peak_gb``, ``hbm_reserved_gb``) is the
  FULLEST chip's reading; all four are logged. A data-parallel job is held
  back by its fullest rank, and the first chip also holds what the host
  placed there unsharded.
- the reference is the configuration's own copy, ``mesh_check``: each tree's
  leaves are found over the chips' row blocks in as many forked processes.
- the profiler traces one chip alone (``TRACED_CHIPS``): a device metric
  is that chip's time; the log names its plane.
- the log carries the run's wall by piece and the host's peak resident set,
  and whether EFB bundled any feature.

A CPU rehearsal runs on as many of the configuration's devices as JAX sees
(``tpu_num_devices`` capped): 4 of tier-1's 8 virtual devices, 1 under
``benchmark/tests``' own conftest.
"""
from __future__ import annotations

import importlib
import os
import resource
import shutil
import time

import numpy as np

from .. import health
from ..reference import gbdt_check, mesh_check
from ..reference.quality import QUALITY
from .train_window import _resolved, read_trace, reference_log


# chips the profiler traces. Every chip runs the same program on its own
# quarter of the rows; at four chips (~1.1M device events a chip in two
# iterations) the profiler's stop took 103 s of a traced run's 360, at one
# 49.5 s (PERF.md section 7, 24)
TRACED_CHIPS = 1


def _memory(log, counters: dict) -> int:
    """Peak and reserved bytes of every chip; the fullest's go out."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [int(s.get("peak_bytes_in_use", 0)) for s in stats]
    reserved = [int(s.get("peak_bytes_reserved", 0)) for s in stats]
    counters["hbm_peak_bytes"] = max(peaks)
    counters["hbm_reserved_bytes"] = max(reserved)
    log(f"peak live device memory by chip {[p / 1e9 for p in peaks]} GB "
        f"(fullest {max(peaks) / 1e9:.3f} GB = {max(peaks) / 2 ** 30:.3f} "
        f"GiB), reserved for programs' temporaries "
        f"{[r / 1e9 for r in reserved]} GB")
    for d, s in zip(jax.local_devices(), stats):
        log(f"device memory stats {d}: {s}")
    return max(peaks)


def run(ctx: dict) -> dict:
    import jax
    log, cell, seed = ctx["log"], ctx["cell"], ctx["seed"]
    traffic = cell["traffic"]
    cfg = dict(cell["config"])
    rehearse = ctx["rehearse"]
    if rehearse:
        cfg.update({k: v for k, v in cfg["rehearsal"].items()
                    if k != "params"})
    params = dict(cfg["params"])
    if rehearse:
        params.update(cfg["rehearsal"].get("params", {}))
        params["tpu_num_devices"] = min(int(params["tpu_num_devices"]),
                                        len(jax.devices()))
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
        f"{float(np.mean(data['y'])):.4f} positive, generated in "
        f"{spans['datagen_s']:.1f} s")
    if not rehearse:
        t = time.perf_counter()
        log(f"chip health: {health.microbench()} "
            f"({time.perf_counter() - t:.1f} s)")

    import lambdagap_tpu as lgb
    ds = lgb.Dataset(data["X"], label=data["y"], params=params)
    t = time.perf_counter()
    ds.construct()
    spans["construct_s"] = time.perf_counter() - t
    t = time.perf_counter()
    bst = lgb.Booster(params, ds)
    gb = bst._booster
    spans["booster_s"] = time.perf_counter() - t
    learner = gb.learner
    log(f"mesh: {dict(learner.mesh.shape)} over "
        f"{learner.mesh.devices.size} devices, {learner.n_loc:,} rows a "
        f"shard; EFB {'bundled' if learner.bundled else 'bundled nothing'}: "
        f"{learner.num_features} features in {learner.hx_rows.shape[1]} "
        f"columns")

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
        options.advanced_configuration = {
            "tpu_num_chips_to_profile_per_task": TRACED_CHIPS}
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
    iters = len(walls)
    peak = _memory(log, counters)
    spans["iter_max_s"] = max(walls)
    log(f"window: {iters} iterations in {window_s:.3f} s "
        f"(slowest {max(walls):.3f} s, fastest {min(walls):.3f} s)")
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
    del bst, ds, gb, learner
    t = time.perf_counter()
    pieces = {}
    numbers = mesh_check.check(
        model_text, data, dict(params, objective_params=cfg.get(
            "objective_params", {})), train_scores, total_iters, seed,
        seconds=pieces)
    numbers["learner_mismatch"] = float(
        sum(resolved.get(k) != v for k, v in cfg["learner"].items()))
    check_s = time.perf_counter() - t
    trees = gbdt_check.parse_model(model_text)
    log(reference_log(check_s, len(trees), pieces)
        + f"; leaves by {mesh_check.SHARDS} shard processes")
    quality = QUALITY[params["objective"]](
        data, gbdt_check.tree_scores(trees, data["X_hold"]))
    log(f"quality (synthetic data, the reference's traversal of the "
        f"program's trees over the hold-out; information only): {quality}")
    log(f"wall by piece: set-up {setup_s:.1f} s (data "
        f"{spans['datagen_s']:.1f}, construct {spans['construct_s']:.1f}, "
        f"booster {spans['booster_s']:.1f}, warm-up {sum(warm):.1f}), window "
        f"{window_s:.1f}, read-back {readback_s:.1f}, reference "
        f"{check_s:.1f}; process so far "
        f"{time.perf_counter() - ctx['t_start']:.1f} s; host peak resident "
        f"set {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20:.1f}"
        f" GiB")

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
        out["trace"] = read_trace(trace_dir, spans, log)
        planes = {e["plane"] for e in out["trace"] or []
                  if e["plane"].startswith("/device:")}
        log(f"trace: device planes {sorted(planes)}")
    return out
