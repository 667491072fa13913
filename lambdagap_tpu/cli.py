"""Command-line application.

(reference: src/main.cpp:13 + src/application/application.cpp — ``key=value``
arguments plus ``config=`` file, tasks train / predict / convert_model /
refit / save_binary :172-290; ``task=serve`` is framework-native, with no
reference analog.)

Usage::

    python -m lambdagap_tpu task=train data=train.csv objective=binary \
        num_iterations=100 output_model=model.txt

    # batched serving loop: one feature row per line (TSV/CSV) from
    # data= or stdin; 'swap=<model.txt>' lines hot-swap the model
    # mid-stream with zero dropped requests (docs/serving.md)
    python -m lambdagap_tpu task=serve input_model=model.txt \
        data=requests.tsv output_result=preds.tsv serve_stats_file=stats.json
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from .config import Config
from .data.loader import load_data_file, save_binary
from .models.gbdt import GBDT
from .models.dart import create_boosting
from .utils import log


def parse_args(argv: List[str]) -> Dict[str, str]:
    """``key=value`` args + config file lines (reference:
    application.cpp:31-86 LoadParameters + Config::KV2Map)."""
    params: Dict[str, str] = {}
    config_path = None
    for arg in argv:
        if "=" not in arg:
            log.warning("Unknown argument %r ignored", arg)
            continue
        k, v = arg.split("=", 1)
        k = k.strip()
        if Config.canonical_name(k) == "config":
            config_path = v.strip()
        else:
            params[k] = v.strip()
    if config_path:
        file_params: Dict[str, str] = {}
        with open(config_path) as f:
            for line in f:
                line = line.split("#", 1)[0].strip()
                if not line or "=" not in line:
                    continue
                k, v = line.split("=", 1)
                file_params[k.strip()] = v.strip()
        # command-line overrides config file (reference: application.cpp:50)
        file_params.update(params)
        params = file_params
    return params


def run_train(cfg: Config) -> None:
    if not cfg.data:
        log.fatal("task=train requires data=<file>")
    log.info("Loading training data from %s", cfg.data)
    if cfg.pre_partition and cfg.num_machines > 1:
        # distributed per-rank file loading: join the multi-process runtime
        # first, then sync bin mappers across ranks (reference:
        # application.cpp InitTrain -> Network::Init +
        # dataset_loader.cpp:1072 pre-partitioned construction)
        from .parallel.multiprocess import (init_distributed,
                                            load_pre_partitioned)
        init_distributed(config=cfg)
        train = load_pre_partitioned(cfg.data, cfg)
    else:
        train = load_data_file(cfg.data, cfg)
    booster = create_boosting(cfg, train)
    start_it = 0
    resumed = False
    if cfg.resume == "auto":
        # crash-safe auto-resume: pick up the latest valid snapshot (atomic
        # write + checksum + state sidecar; guard/snapshot.py) and continue
        # bit-consistently from its iteration
        from .guard.snapshot import latest_snapshot, restore_state
        from .models.model_text import load_model_from_string
        found = latest_snapshot(cfg.output_model)
        if found is not None:
            snap_path, model_text, state = found
            if cfg.input_model:
                log.warning("resume=auto found snapshot %s; input_model is "
                            "ignored", snap_path)
            _, trees = load_model_from_string(model_text)
            booster.resume_from(trees)
            restore_state(booster, state)
            start_it = booster.iter_
            resumed = True
            log.info("Resumed from snapshot %s (%d completed iterations)",
                     snap_path, start_it)
    if cfg.input_model and not resumed:
        # continued training (reference: application.cpp InitTrain with
        # input_model -> Boosting::CreateBoosting(type, filename))
        from .models.model_text import load_model_from_string
        with open(cfg.input_model) as f:
            _, trees = load_model_from_string(f.read())
        booster.resume_from(trees)
    valids = []
    if cfg.valid:
        for i, vf in enumerate(str(cfg.valid).split(",")):
            vds = load_data_file(vf.strip(), cfg, reference=train)
            booster.add_valid_set(vds, f"valid_{i}")
    for it in range(start_it, cfg.num_iterations):
        stop = booster.train_one_iter()
        if cfg.metric_freq > 0 and (it + 1) % cfg.metric_freq == 0:
            msgs = []
            with booster.telemetry.phase("eval"):
                if cfg.is_provide_training_metric:
                    msgs += [f"training {m}: {v:g}"
                             for (_, m, v, _) in booster.eval_train()]
                msgs += [f"{d} {m}: {v:g}"
                         for (d, m, v, _) in booster.eval_valid()]
            if msgs:
                log.info("[%d] %s", it + 1, "  ".join(msgs))
        if cfg.snapshot_freq > 0 and (it + 1) % cfg.snapshot_freq == 0:
            from .guard.snapshot import write_training_snapshot
            write_training_snapshot(booster, cfg.output_model,
                                    faults=booster.guard.plan,
                                    keep=cfg.guard_snapshot_keep)
        if stop:
            break
    if booster.telemetry.enabled:
        log.info("%s", booster.telemetry.report())
    booster.telemetry.close()
    if cfg.telemetry_out:
        log.info("Telemetry run log written to %s", cfg.telemetry_out)
    booster.save_model(cfg.output_model)
    log.info("Finished training; model saved to %s", cfg.output_model)


def run_predict(cfg: Config) -> None:
    if not cfg.data or not cfg.input_model:
        log.fatal("task=predict requires data=<file> and input_model=<model>")
    booster = GBDT.from_model_file(cfg.input_model, cfg)
    ds_raw = _load_raw_matrix(cfg.data, cfg)
    if cfg.predict_contrib:
        out = booster.predict_contrib(ds_raw, cfg.start_iteration_predict,
                                      cfg.num_iteration_predict)
    elif cfg.predict_leaf_index:
        out = booster.predict_leaf(ds_raw, cfg.start_iteration_predict,
                                   cfg.num_iteration_predict)
    else:
        out = booster.predict(ds_raw, raw_score=cfg.predict_raw_score,
                              start_iteration=cfg.start_iteration_predict,
                              num_iteration=cfg.num_iteration_predict)
    out_path = cfg.extra.get("output_result", "LightGBM_predict_result.txt")
    np.savetxt(out_path, out, fmt="%.10g",
               delimiter="\t" if np.ndim(out) > 1 else "\n")
    log.info("Predictions written to %s", out_path)


def _load_raw_matrix(path: str, cfg: Config) -> np.ndarray:
    from .data.loader import raw_matrix_of
    X, _, _, _, _ = raw_matrix_of(path, cfg)
    return X


def _parse_serve_models(spec: str):
    """``serve_models="name=path,name2=path2"`` -> [(name, path), ...]."""
    out = []
    for tok in (spec or "").split(","):
        tok = tok.strip()
        if not tok:
            continue
        if "=" not in tok:
            log.fatal("serve_models token %r is not name=path", tok)
        name, path = tok.split("=", 1)
        out.append((name.strip(), path.strip()))
    return out


def _configure_observability(cfg: Config):
    """Arm the graftscope v2 serve-side observability from the config
    knobs: the process span recorder (``serve_trace_*``) and, when a dump
    path is set, the flight recorder (fault/SIGTERM/interval dumps).
    Returns the armed FlightRecorder (or None) so callers can close it."""
    import os
    from .obs import trace as obs_trace
    obs_trace.configure(sample=cfg.serve_trace_sample,
                        out=cfg.serve_trace_out,
                        ring=cfg.serve_trace_ring,
                        proc=f"serve:{os.getpid()}")
    if not cfg.serve_flight_dump:
        return None
    return obs_trace.FlightRecorder(
        cfg.serve_flight_dump,
        interval_s=cfg.serve_flight_interval_s,
        params={"task": "serve", "pid": os.getpid()}).install()


def _build_serve_target(cfg: Config, booster):
    """The CLI's serve target: one ForestServer, or ``serve_replicas``
    shared-nothing replicas behind the health-aware router. Extra
    ``serve_models`` are registered on every replica (each keeps its own
    compiled copy — replicas share nothing). With
    ``fleet_scrape_interval_s > 0`` a router target also gets the fleet
    scraper + signal plane (docs/observability.md), so the frontend's
    ``signals`` and ``prometheus fleet`` verbs answer from live data.
    ``serve_autonomics=true`` additionally starts the fleet control loop
    (docs/robustness.md "Fleet autonomics"): the target is then always a
    router (a fleet of one is still self-healing and can scale out), a
    scraper/signal plane is forced on (at the controller's own interval
    when ``fleet_scrape_interval_s`` is 0), and local scale-out replicas
    are built from the SAME booster. Off by default: with the knob off,
    nothing here changes — no controller, no extra thread, byte-identical
    snapshots."""
    from .serve import (Autonomics, FleetScraper, ForestServer,
                        LocalReplica, Router, SignalPlane)

    def make_server():
        s = ForestServer(booster, raw_score=cfg.predict_raw_score,
                         start_iteration=cfg.start_iteration_predict,
                         num_iteration=cfg.num_iteration_predict)
        for name, path in _parse_serve_models(cfg.serve_models):
            s.add_model(name, path)
        return s

    n = max(int(cfg.serve_replicas), 1)
    servers = [make_server() for _ in range(n)]
    if n == 1 and not cfg.serve_autonomics:
        return servers[0]
    router = Router([LocalReplica(f"r{i}", s)
                     for i, s in enumerate(servers)], own_replicas=True)
    scrape_interval = cfg.fleet_scrape_interval_s
    if scrape_interval <= 0 and cfg.serve_autonomics:
        # the control loop senses through the scraper: force one on at
        # the controller's cadence rather than running blind
        scrape_interval = cfg.serve_autonomics_interval_s
    scraper = None
    if scrape_interval > 0:
        from .obs import trace as obs_trace
        scraper = FleetScraper(
            router, interval_s=scrape_interval,
            timeout_s=cfg.fleet_scrape_timeout_s,
            signals=SignalPlane(recorder=obs_trace.RECORDER)).start()
        router.attach_scraper(scraper)
    if cfg.serve_autonomics:
        from .guard.faults import plan_for

        def scale(index: int):
            # scale-out replicas continue the rN numbering past the
            # configured fleet; compile happens here, outside any lock
            return LocalReplica(f"r{n + index}", make_server())

        auto = Autonomics(
            router, signals=scraper.signals if scraper else None,
            scraper=scraper,
            interval_s=cfg.serve_autonomics_interval_s,
            scale=scale,
            revive_backoff_s=cfg.serve_autonomics_revive_backoff_s,
            revive_backoff_max_s=cfg.serve_autonomics_revive_backoff_max_s,
            probe_window=cfg.serve_autonomics_probe_window,
            scale_out_margin=cfg.serve_autonomics_scale_out_margin,
            scale_in_margin=cfg.serve_autonomics_scale_in_margin,
            min_replicas=cfg.serve_autonomics_min_replicas,
            max_replicas=cfg.serve_autonomics_max_replicas,
            cooldown_s=cfg.serve_autonomics_cooldown_s,
            hysteresis_ticks=cfg.serve_autonomics_hysteresis_ticks,
            placement=cfg.serve_autonomics_placement,
            placement_budget_bytes=int(cfg.serve_hbm_budget_mb * (1 << 20)),
            faults=plan_for(cfg)).start()
        router.attach_autonomics(auto)
        if cfg.serve_shadow_sample > 0:
            # continuous learning (docs/continuous-learning.md): watch the
            # candidate family a co-resident task=loop_train writes to
            # (output_model), shadow-evaluate new epochs on a mirrored
            # slice, and promote through the fleet-atomic delta rollout.
            # input_model is the rollback anchor for post-promote
            # regressions.
            from .loop import PromotionController
            PromotionController(
                router, auto, cfg.output_model,
                sample=cfg.serve_shadow_sample,
                min_requests=cfg.loop_shadow_min_requests,
                threshold=cfg.loop_promote_threshold,
                interval_s=cfg.loop_interval_s,
                base_source=cfg.input_model or None,
                signals=scraper.signals if scraper else None,
                faults=plan_for(cfg)).start()
    return router


def run_serve_frontend(cfg: Config, booster) -> None:
    """task=serve with ``serve_port``: bind the newline-JSON TCP front
    end (docs/serving.md wire protocol) over ``serve_replicas`` local
    replicas and serve until SIGTERM/SIGINT. The bound port is printed as
    ``SERVE_PORT=<port>`` on stdout so harnesses can use ``serve_port=0``
    (ephemeral) and still find the socket."""
    import signal
    import threading
    from .serve import ServeFrontend
    stop = threading.Event()
    try:
        # BEFORE the flight recorder arms: its SIGTERM hook chains to the
        # handler installed here, so a drain still dumps the ring first
        signal.signal(signal.SIGTERM, lambda *a: stop.set())
    except ValueError:                   # not the main thread (tests)
        log.warning("serve frontend: SIGTERM handler unavailable off the "
                    "main thread; close with SIGINT/KeyboardInterrupt")
    flight = _configure_observability(cfg)
    target = _build_serve_target(cfg, booster)
    fe = ServeFrontend(target, port=cfg.serve_port).start()
    print(f"SERVE_PORT={fe.port}", flush=True)
    log.info("task=serve frontend up on port %d (%d replica(s)); "
             "SIGTERM/SIGINT drains and exits", fe.port,
             max(int(cfg.serve_replicas), 1))
    try:
        stop.wait()
    except KeyboardInterrupt:
        log.info("task=serve frontend: interrupt — draining")
    fe.close()
    snap = target.stats_snapshot()
    target.close()
    if flight is not None:
        flight.close()
    if cfg.serve_trace_out:
        from .obs import trace as obs_trace
        obs_trace.RECORDER.close()
    if cfg.serve_stats_file:
        import json
        with open(cfg.serve_stats_file, "w") as f:
            json.dump(snap, f, indent=2)
    log.info("task=serve frontend drained (stats%s)",
             f" in {cfg.serve_stats_file}" if cfg.serve_stats_file else
             " not persisted; set serve_stats_file=")


def run_serve(cfg: Config) -> None:
    """task=serve: micro-batched inference loop over a request stream.

    Requests come from ``data=<file>`` or stdin, one feature row per line
    (TSV or CSV; all columns are features). Lines of the form
    ``swap=<model>`` atomically hot-swap the served model; ``stats``
    prints the live Prometheus exposition (``stats json`` the snapshot
    JSON) to stderr — the scrape hook for a sidecar exporter. Predictions
    go to ``output_result`` (default LightGBM_predict_result.txt); serving
    metrics JSON goes to ``serve_stats_file`` when set.

    With ``serve_port>=0`` the process instead binds the TCP front end
    (``serve_replicas`` local replicas behind the health-aware router) —
    see :func:`run_serve_frontend`."""
    if not cfg.input_model:
        log.fatal("task=serve requires input_model=<model>")
    from .serve import ForestServer, serve_loop
    booster = GBDT.from_model_file(cfg.input_model, cfg)
    if cfg.serve_port >= 0:
        run_serve_frontend(cfg, booster)
        return
    flight = _configure_observability(cfg)
    server = ForestServer(booster, raw_score=cfg.predict_raw_score,
                          start_iteration=cfg.start_iteration_predict,
                          num_iteration=cfg.num_iteration_predict)
    for name, path in _parse_serve_models(cfg.serve_models):
        server.add_model(name, path)
    if cfg.data:
        src = open(cfg.data)
    else:
        src = sys.stdin
        log.info("task=serve reading requests from stdin "
                 "(one feature row per line; 'swap=<model>' hot-swaps)")
    out_path = cfg.extra.get("output_result",
                             "LightGBM_predict_result.txt")
    try:
        with open(out_path, "w") as out:
            n = serve_loop(server, src, out,
                           on_swap=lambda tgt, gen: log.info(
                               "Hot-swapped to %s (generation %d)",
                               tgt, gen),
                           stats_stream=sys.stderr)
    finally:
        if src is not sys.stdin:
            src.close()
        server.close()
        if flight is not None:
            flight.close()
        if cfg.serve_trace_out:
            from .obs import trace as obs_trace
            obs_trace.RECORDER.close()
    snap = server.stats_snapshot()
    if cfg.serve_stats_file:
        import json
        with open(cfg.serve_stats_file, "w") as f:
            json.dump(snap, f, indent=2)
    log.info("Served %d requests (gen %d, health %s): %.0f req/s, "
             "p50=%.3fms p99=%.3fms, cache hit rate %.0f%%, %d shed, "
             "%d rejected, %d swap failures; predictions in %s", n,
             snap["generation"], snap["health"]["state"],
             snap["throughput_rps"], snap["latency_ms"]["p50"],
             snap["latency_ms"]["p99"], 100.0 * snap["cache"]["hit_rate"],
             snap["timeouts"], snap["rejected"], snap["swap_failures"],
             out_path)


def run_refit(cfg: Config) -> None:
    """Refit an existing model's leaf values on new data
    (reference: application.cpp:254-290 ConvertModel-adjacent refit task)."""
    if not cfg.data or not cfg.input_model:
        log.fatal("task=refit requires data=<file> and input_model=<model>")
    booster = GBDT.from_model_file(cfg.input_model, cfg)
    from .data.loader import raw_matrix_of
    X, y, weight, group, _ = raw_matrix_of(cfg.data, cfg)
    booster.refit(X, y, weight=weight, group=group)
    booster.save_model(cfg.output_model)
    log.info("Refitted model saved to %s", cfg.output_model)


def run_loop_train(cfg: Config, params: dict) -> None:
    """Continuous learning (docs/continuous-learning.md): tail a batch
    directory, fold fresh rows in without global rebinning, and emit
    epoch-tagged candidate snapshots for shadow evaluation. ``data=`` is
    a DIRECTORY of ``.npy`` batches (data/tail.py); crash-anywhere: a
    SIGKILLed trainer restarted with the same command resumes from the
    latest valid candidate (tools/loop_gate.py proves it)."""
    if not cfg.data:
        log.fatal("task=loop_train requires data=<batch directory>")
    from .data.tail import SequenceTail
    from .guard.faults import plan_for
    from .loop.trainer import TailingTrainer
    flight = _configure_observability(cfg)
    train_params = {k: v for k, v in params.items()
                    if k not in ("task", "data", "valid")}
    trainer = TailingTrainer(
        train_params, SequenceTail(cfg.data), cfg.output_model,
        iters_per_fold=cfg.loop_iters_per_fold,
        keep=cfg.guard_snapshot_keep, faults=plan_for(cfg))
    max_epochs = int(cfg.extra.get("loop_max_epochs", 0))
    log.info("tailing trainer on %s (iters_per_fold=%d, keep=%d, "
             "max_epochs=%d)", cfg.data, cfg.loop_iters_per_fold,
             cfg.guard_snapshot_keep, max_epochs)
    try:
        emitted = trainer.run(interval_s=cfg.loop_interval_s,
                              max_epochs=max_epochs)
    finally:
        if flight is not None:
            flight.close()
        if cfg.serve_trace_out:
            from .obs import trace as obs_trace
            obs_trace.RECORDER.close()
    log.info("tailing trainer done: %d candidates emitted (last epoch %d)",
             emitted, trainer.epoch)


def run_save_binary(cfg: Config) -> None:
    if not cfg.data:
        log.fatal("task=save_binary requires data=<file>")
    ds = load_data_file(cfg.data, cfg)
    save_binary(ds, cfg.data + ".bin")


def run_convert_model(cfg: Config) -> None:
    from .models.model_codegen import model_to_cpp
    if cfg.convert_model_language not in ("", "cpp"):
        log.fatal("convert_model_language=%r is not supported (only cpp)",
                  cfg.convert_model_language)
    booster = GBDT.from_model_file(cfg.input_model, cfg)
    code = model_to_cpp(booster)
    with open(cfg.convert_model, "w") as f:
        f.write(code)
    log.info("Model converted to %s", cfg.convert_model)


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    params = parse_args(argv)
    cfg = Config.from_params(params)
    # data-path params are canonicalized into cfg.extra by Config.update
    cfg.data = cfg.extra.get("data", "")
    cfg.valid = cfg.extra.get("valid", "")
    task = cfg.task
    if task == "train":
        run_train(cfg)
    elif task in ("predict", "prediction", "test"):
        run_predict(cfg)
    elif task == "serve":
        run_serve(cfg)
    elif task == "save_binary":
        run_save_binary(cfg)
    elif task == "convert_model":
        run_convert_model(cfg)
    elif task == "refit":
        run_refit(cfg)
    elif task == "loop_train":
        run_loop_train(cfg, params)
    else:
        log.fatal("Unknown task %r", task)
    return 0


def run() -> int:
    """Process entry (``python -m lambdagap_tpu``): place the persistent
    compile cache, then :func:`main`. Kept out of ``main`` so a program
    (or a test) that calls ``main(argv)`` in-process keeps its own JAX
    configuration."""
    from .utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    return main()


if __name__ == "__main__":
    raise SystemExit(run())
