#!/usr/bin/env python
"""Serving benchmark: compiled-forest micro-batched server vs naive
per-request ``Booster.predict``, plus the fleet rounds of ISSUE 9.

The naive side calls ``Booster.predict`` once per single-row request — the
only serving story the framework had before ``lambdagap_tpu.serve`` — so it
pays per-call Python/conversion overhead and (above the native-path
threshold) a full forest re-upload per call. The served side runs the same
request stream through ``ForestServer``: the forest is device-resident and
compiled once per padding bucket, and concurrent requests coalesce into
padded device batches. Clients keep a bounded window of in-flight async
requests (a streaming RPC client), which is what lets the batcher form
deep batches.

The closed-loop client above cannot measure saturation (offered load
collapses to whatever the server admits), so the fleet rounds drive the
OPEN-loop generator (serve/loadgen.py):

- ``open_loop`` — goodput (completed within ``--deadline-ms`` of
  scheduled arrival) vs offered load, swept up a rate ladder to
  saturation, for each fleet width in ``--replica-counts`` (shared-nothing
  local replicas behind the health-aware router);
- ``registry`` — a 2-model registry under an HBM budget that fits ~one
  compiled forest: alternating model traffic forces LRU eviction +
  re-admission, and the JSON carries the counts plus the recompile cost
  each flip pays;
- ``chaos`` — a replica killed mid-round behind the router: the gate-level
  invariant (every accepted request resolves; goodput holds) measured
  under the bench workload;
- ``trace_breakdown`` — where a request's p95 actually goes (queue vs
  registry vs dispatch vs transport shares, from sampled spans over a
  loopback frontend), plus the ABAB-measured latency cost of tracing at
  ``serve_trace_sample=1.0`` against the 0.0 default (ISSUE 12).

Usage::

    python bench_serve.py [out.json] [--trees 500] [--feats 32]
        [--requests 4000] [--clients 8] [--window 64] [--naive-requests 400]
        [--sweep-rates 50,100,200,400,800] [--replica-counts 1,2]
        [--deadline-ms 50] [--sweep-duration 1.5]

Output JSON: naive + served throughput, speedup, serve p50/p99 latency,
cache hit stats, and the three machine-readable fleet sections above
(the ``ServeStats`` schema of docs/serving.md).
"""
import argparse
import json
import sys
import threading
import time

import numpy as np


def build_booster(n_trees: int, rows: int, feats: int, leaves: int):
    """A ``n_trees``-tree booster, cheaply: train a base model and tile its
    trees (structure-realistic forest; serving cost only depends on tree
    count/shape, not on the training history)."""
    import lambdagap_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(rows, feats).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + np.sin(X[:, 2])
         + 0.1 * rng.randn(rows)).astype(np.float32)
    base = min(n_trees, 50)
    b = lgb.train({"objective": "regression", "num_leaves": leaves,
                   "verbose": -1}, lgb.Dataset(X, label=y),
                  num_boost_round=base)
    gb = b._booster
    host = gb.host_models
    reps = -(-n_trees // len(host))
    gb.models = (host * reps)[:n_trees]
    gb.iter_ = len(gb.models)
    gb.invalidate_predict_cache()
    return b, X


def bench_naive(booster, X, n_requests: int) -> dict:
    booster.predict(X[:1])                       # warm every lazy path
    t0 = time.perf_counter()
    for i in range(n_requests):
        booster.predict(X[i % len(X)][None, :])
    dt = time.perf_counter() - t0
    return {"requests": n_requests, "elapsed_s": dt,
            "throughput_rps": n_requests / dt,
            "mean_latency_ms": 1e3 * dt / n_requests}


def bench_naive_device(booster, X, n_requests: int) -> dict:
    """Naive per-request predict with the native single-row traverser
    suppressed: every request is its own device dispatch — what any
    deployment without a C++ toolchain gets, and the pre-serve pathology
    the ISSUE names (a forest conversion + dispatch per call)."""
    from lambdagap_tpu import native
    old = native.get_lib
    native.get_lib = lambda: None
    try:
        booster.predict(X[:1])                   # warm the 1-row executable
        t0 = time.perf_counter()
        for i in range(n_requests):
            booster.predict(X[i % len(X)][None, :])
        dt = time.perf_counter() - t0
    finally:
        native.get_lib = old
    return {"requests": n_requests, "elapsed_s": dt,
            "throughput_rps": n_requests / dt,
            "mean_latency_ms": 1e3 * dt / n_requests}


def bench_engines(booster, X) -> dict:
    """Warm big-batch device us/row for the tensorized engine next to the
    sequential scan and the native per-row baseline, same rows — so the
    serve JSON tracks the traversal-engine win alongside the batching win
    (ISSUE 3 satellite)."""
    gb = booster._booster
    fast = gb.config.tpu_fast_predict_rows
    engine0 = gb.config.predict_engine
    gb.config.tpu_fast_predict_rows = 0
    out = {"rows": len(X)}
    try:
        for eng in ("tensor", "scan", "compiled"):
            gb.config.predict_engine = eng
            gb.invalidate_predict_cache()
            booster.predict(X)               # compile + warm
            t0 = time.perf_counter()
            booster.predict(X)
            out[f"{eng}_us_per_row_warm"] = \
                1e6 * (time.perf_counter() - t0) / len(X)
    finally:
        gb.config.predict_engine = engine0
        gb.config.tpu_fast_predict_rows = fast
        gb.invalidate_predict_cache()
    out["tensor_speedup_vs_scan"] = (out["scan_us_per_row_warm"]
                                     / max(out["tensor_us_per_row_warm"],
                                           1e-9))
    out["compiled_speedup_vs_tensor"] = (
        out["tensor_us_per_row_warm"]
        / max(out["compiled_us_per_row_warm"], 1e-9))
    t0 = time.perf_counter()
    booster.predict(X[:4096])                # native single-row traverser
    out["native_us_per_row"] = 1e6 * (time.perf_counter() - t0) / 4096
    return out


def bench_pack_many_small(n_models: int = 6, trees: int = 24,
                          feats: int = 16, rows_per_tenant: int = 32,
                          windows: int = 30) -> dict:
    """The many-small-models shape (ISSUE 16): N per-tenant forests too
    small to fill a chip alone. Solo serving dispatches one executable
    per tenant per window; the cross-model pack pads all members into ONE
    executable and dispatches the mixed window once. Reports warm us/row
    both ways plus the dispatch count ratio — on CPU the ratio documents
    the mechanism (executable count), the chip run supplies the latency
    ratio (see BENCH_NOTES.md)."""
    import lambdagap_tpu as lgb
    from lambdagap_tpu.serve.cache import (CompiledForestCache, ModelPack,
                                           _plan)
    rng = np.random.RandomState(7)
    caches, tenants = {}, []
    for m in range(n_models):
        Xm = rng.randn(2000, feats).astype(np.float32)
        ym = (Xm[:, 0] - 0.3 * Xm[:, (m + 1) % feats]
              + 0.1 * rng.randn(2000)).astype(np.float32)
        b = lgb.train({"objective": "regression", "num_leaves": 15,
                       "verbose": -1, "tpu_fast_predict_rows": 0,
                       "predict_engine": "compiled"},
                      lgb.Dataset(Xm, label=ym), num_boost_round=trees)
        caches[f"t{m}"] = CompiledForestCache(b._booster)
        tenants.append((f"t{m}", Xm[:rows_per_tenant]))
    pack = ModelPack(caches)
    parts = [(name, rows, False) for name, rows in tenants]
    total_rows = sum(len(r) for _, r in tenants)

    solo = [caches[name].predict(rows) for name, rows in tenants]  # warm
    t0 = time.perf_counter()
    for _ in range(windows):
        for name, rows in tenants:
            caches[name].predict(rows)
    solo_us = 1e6 * (time.perf_counter() - t0) / (windows * total_rows)

    packed = pack.predict_mixed(parts)                             # warm
    t0 = time.perf_counter()
    for _ in range(windows):
        pack.predict_mixed(parts)
    pack_us = 1e6 * (time.perf_counter() - t0) / (windows * total_rows)

    exact = all(np.array_equal(p, s) for p, s in zip(packed, solo))
    plan = _plan(pack.buckets, total_rows)
    return {"models": n_models, "trees_per_model": trees,
            "rows_per_tenant": rows_per_tenant,
            "packed_trees": pack.packed.num_trees,
            "solo_dispatches_per_window": n_models,
            "packed_dispatches_per_window": len(plan),
            "solo_us_per_row_warm": solo_us,
            "packed_us_per_row_warm": pack_us,
            "pack_speedup_vs_solo": solo_us / max(pack_us, 1e-9),
            "bit_identical_to_solo": bool(exact)}


def bench_served(booster, X, n_requests: int, clients: int,
                 window: int, max_delay_ms: float) -> dict:
    server = booster.as_server(max_delay_ms=max_delay_ms)
    per = n_requests // clients
    errs = []

    def client(cid: int) -> None:
        try:
            inflight = []
            for i in range(per):
                inflight.append(server.submit(X[(cid * per + i) % len(X)]))
                if len(inflight) >= window:
                    inflight.pop(0).result(timeout=120)
            for f in inflight:
                f.result(timeout=120)
        except Exception as e:  # pragma: no cover
            errs.append(repr(e))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    snap = server.stats_snapshot()
    # exercise the obs.prom export path at bench time: the same exposition
    # the task=serve `stats` line prints (docs/observability.md)
    prom_samples = sum(1 for ln in server.prometheus().splitlines()
                       if ln and not ln.startswith("#"))
    server.close()
    return {"requests": per * clients, "clients": clients, "window": window,
            "elapsed_s": dt, "throughput_rps": per * clients / dt,
            "errors": errs, "stats": snap,
            "prometheus_samples": prom_samples}


def _make_fleet(booster, n_replicas: int, max_delay_ms: float):
    """N shared-nothing in-process replicas behind the router (the gate
    uses real subprocesses; the bench keeps replicas in-process so the
    sweep measures serving, not interpreter startup)."""
    from lambdagap_tpu.serve import LocalReplica, Router
    servers = [booster.as_server(max_delay_ms=max_delay_ms)
               for _ in range(n_replicas)]
    if n_replicas == 1:
        return servers[0], servers
    router = Router([LocalReplica(f"r{i}", s)
                     for i, s in enumerate(servers)], own_replicas=True)
    return router, servers


def bench_open_loop_sweep(booster, X, rates, replica_counts,
                          deadline_ms: float, duration_s: float,
                          max_delay_ms: float, good_ratio: float = 0.9
                          ) -> dict:
    """Goodput vs offered load, per fleet width: the saturation story the
    closed-loop client cannot tell."""
    from lambdagap_tpu.serve import run_open_loop
    out = {"deadline_ms": deadline_ms, "arrival": "poisson",
           "duration_s": duration_s, "good_ratio": good_ratio,
           "fleets": {}}
    for n in replica_counts:
        target, servers = _make_fleet(booster, n, max_delay_ms)
        rounds, saturation = [], None
        try:
            for rate in rates:
                n_req = max(50, int(rate * duration_s))
                r = run_open_loop(target.submit, X, rate, n_req,
                                  deadline_ms=deadline_ms, seed=17)
                r.pop("per_tenant", None)      # single-tenant sweep
                rounds.append(r)
                if r["goodput_ratio"] >= good_ratio:
                    saturation = rate
                print(f"  {n} replica(s) @ {rate:6.0f} rps offered: "
                      f"goodput {r['goodput_rps']:7.0f} rps "
                      f"(ratio {r['goodput_ratio']:.2f}, "
                      f"p99 {r['latency_ms']['p99']:.1f} ms)",
                      file=sys.stderr)
        finally:
            target.close()
            for s in servers:
                s.close()
        out["fleets"][str(n)] = {"rates": list(rates), "rounds": rounds,
                                 "saturation_rps": saturation}
    return out


def bench_registry(booster, X, flips: int = 10, per_flip: int = 20) -> dict:
    """2-model registry under an HBM budget that fits ~one forest:
    alternating traffic pays eviction + re-admission on every flip; the
    flip-vs-resident latency gap is the recompile cost the budget
    charges."""
    server = booster.as_server(buckets=(64,), max_delay_ms=0.5)
    try:
        ref = server.predict(X[:64])
        bytes0 = server.registry.entry("default").bytes
        server.registry.hbm_budget_bytes = int(1.5 * bytes0)
        server.add_model("b", booster._booster)   # same forest, own copy
        flip_ms, resident_ms = [], []
        for f in range(flips):
            name = "b" if f % 2 == 0 else "default"
            t0 = time.perf_counter()
            first = server.predict(X[:64], model=name)   # pays readmission
            flip_ms.append(1e3 * (time.perf_counter() - t0))
            assert np.array_equal(first, ref), "registry parity broke"
            for i in range(per_flip - 1):                # warm residence
                t0 = time.perf_counter()
                server.predict(X[64 * (i % 4):64 * (i % 4) + 64],
                               model=name)
                resident_ms.append(1e3 * (time.perf_counter() - t0))
        snap = server.stats_snapshot()
        return {
            "hbm_budget_bytes": server.registry.hbm_budget_bytes,
            "forest_bytes": bytes0,
            "models": snap["registry"]["registered_models"],
            "evictions": snap["evictions"],
            "readmissions": snap["readmissions"],
            "flips": flips,
            "readmit_request_ms_p50": float(np.median(flip_ms)),
            "resident_request_ms_p50": float(np.median(resident_ms)),
            "readmit_over_resident": float(
                np.median(flip_ms) / max(np.median(resident_ms), 1e-9)),
            "parity_ok": True,
            "per_model": snap["per_model"],
        }
    finally:
        server.close()


def bench_trace(booster, X, rate: float = 300.0, duration_s: float = 1.5,
                deadline_ms: float = 100.0, max_delay_ms: float = 2.0
                ) -> dict:
    """trace_breakdown (ISSUE 12): where a request's p95 actually goes —
    queue vs registry vs dispatch vs transport — derived from sampled
    spans over a loopback frontend, plus the ABAB cost of sampling
    itself (sample=0.0, the default, alternated with sample=1.0)."""
    from lambdagap_tpu.obs import trace
    from lambdagap_tpu.serve import FrontendClient, ServeFrontend, \
        run_open_loop
    n_req = max(100, int(rate * duration_s))
    server = booster.as_server(max_delay_ms=max_delay_ms)
    fe = ServeFrontend(server).start()
    client = FrontendClient("127.0.0.1", fe.port)
    arms = []
    agg = {}
    try:
        run_open_loop(client.submit, X, rate, n_req // 2,
                      deadline_ms=deadline_ms, seed=31)   # warm
        # ABAB: default-off / fully-sampled, interleaved so drift cannot
        # masquerade as tracing overhead (the BENCH_NOTES discipline);
        # three pairs + per-arm medians because a single CPU-container
        # scheduling hiccup in one arm otherwise dominates the ratio
        for sample in (0.0, 1.0, 0.0, 1.0, 0.0, 1.0):
            trace.RECORDER.reset()
            trace.RECORDER.configure(sample=sample)
            r = run_open_loop(client.submit, X, rate, n_req,
                              deadline_ms=deadline_ms, seed=37)
            arms.append({"sample": sample,
                         "p50_ms": r["latency_ms"]["p50"],
                         "p95_ms": r["latency_ms"]["p95"],
                         "goodput_ratio": r["goodput_ratio"],
                         "spans_recorded": trace.RECORDER.n_spans})
            if sample > 0:
                agg = trace.RECORDER.aggregates()
        trace.RECORDER.configure(sample=0.0)
    finally:
        client.close()
        fe.close()
        server.close()
        trace.RECORDER.reset()

    def p95_ms(name):
        return 1e3 * agg.get(name, {}).get("p95", 0.0)

    root = p95_ms("client_request")
    frontend = p95_ms("frontend")
    parts = {"queue_ms": p95_ms("queue_wait"),
             "readmit_ms": p95_ms("registry_get"),
             "dispatch_ms": p95_ms("dispatch"),
             "transport_ms": max(root - frontend, 0.0)}
    shares = {k.replace("_ms", "_share"): (v / root if root else 0.0)
              for k, v in parts.items()}
    off = sorted(a["p50_ms"] for a in arms if a["sample"] == 0.0)
    on = sorted(a["p50_ms"] for a in arms if a["sample"] > 0.0)
    med = lambda xs: xs[len(xs) // 2]    # noqa: E731
    return {
        "rate_rps": rate,
        "n_requests_per_arm": n_req,
        "client_request_p95_ms": root,
        "breakdown_p95": {**parts, **shares},
        "span_counts": {k: v.get("count", 0) for k, v in agg.items()},
        "overhead_abab": {
            "arms": arms,
            "p50_off_ms": med(off),
            "p50_on_ms": med(on),
            "p50_on_over_off": med(on) / max(med(off), 1e-9),
        },
    }


def bench_chaos(booster, X, rate: float, deadline_ms: float,
                duration_s: float, max_delay_ms: float) -> dict:
    """Kill one of two replicas mid-round: the serve-gate invariant under
    the bench forest — zero stranded futures, goodput holds."""
    from lambdagap_tpu.serve import run_open_loop
    target, servers = _make_fleet(booster, 2, max_delay_ms)
    n_req = max(100, int(rate * duration_s))

    def killer():
        time.sleep(duration_s * 0.4)
        servers[0].close()               # replica death mid-load

    k = threading.Thread(target=killer)
    k.start()
    try:
        r = run_open_loop(target.submit, X, rate, n_req,
                          deadline_ms=deadline_ms, seed=23)
    finally:
        k.join()
        snap = target.snapshot()
        target.close()
        for s in servers:
            s.close()
    c = r["counts"]
    resolved = (c["ok"] + c["rejected"] + c["timeout"] + c["transport"]
                + c["error"])
    return {
        "offered_rps": rate,
        "n_requests": n_req,
        "counts": c,
        "stranded": n_req - resolved,
        "goodput_ratio": r["goodput_ratio"],
        "latency_ms": r["latency_ms"],
        "router": snap,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("out", nargs="?", default="")
    ap.add_argument("--trees", type=int, default=500)
    ap.add_argument("--rows", type=int, default=8000)
    ap.add_argument("--feats", type=int, default=32)
    ap.add_argument("--leaves", type=int, default=31)
    ap.add_argument("--requests", type=int, default=4000)
    ap.add_argument("--naive-requests", type=int, default=400)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--window", type=int, default=64)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--sweep-rates", default="50,100,200,400,800",
                    help="offered-load ladder (rps) for the open-loop sweep")
    ap.add_argument("--replica-counts", default="1,2",
                    help="fleet widths to sweep")
    ap.add_argument("--deadline-ms", type=float, default=50.0,
                    help="goodput deadline from scheduled arrival")
    ap.add_argument("--sweep-duration", type=float, default=1.5,
                    help="seconds of offered load per sweep round")
    ap.add_argument("--skip-fleet", action="store_true",
                    help="skip the open-loop/registry/chaos fleet rounds")
    args = ap.parse_args(argv)

    import jax
    from lambdagap_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    print(f"building {args.trees}-tree forest "
          f"({args.feats} features, backend={jax.default_backend()})...",
          file=sys.stderr)
    booster, X = build_booster(args.trees, args.rows, args.feats,
                               args.leaves)

    # correctness gate before timing anything: the served path must agree
    # bit-for-bit with the one-shot DEVICE predict (naive timing below still
    # uses the default config, where small batches may take the native f64
    # traverser — the fastest baseline available)
    gb = booster._booster
    fast_rows = gb.config.tpu_fast_predict_rows
    gb.config.tpu_fast_predict_rows = 0
    ref = booster.predict(X[:600])               # 600 > 512 -> device path
    gb.config.tpu_fast_predict_rows = fast_rows
    server = booster.as_server()
    got = np.concatenate([server.predict(X[i:i + 37])
                          for i in range(0, 592, 37)])
    server.close()
    exact = bool(np.array_equal(got, ref[:592]))
    if not exact:
        print("FATAL: served outputs diverge from the device "
              "Booster.predict path", file=sys.stderr)
        return 1

    print("device engine A/B (tensor vs scan vs compiled vs native)...",
          file=sys.stderr)
    engines = bench_engines(booster, X)
    print(f"  tensor {engines['tensor_us_per_row_warm']:.1f} us/row, "
          f"scan {engines['scan_us_per_row_warm']:.1f}, "
          f"compiled {engines['compiled_us_per_row_warm']:.1f}, "
          f"native {engines['native_us_per_row']:.1f}", file=sys.stderr)

    print("cross-model pack (many small tenant forests)...",
          file=sys.stderr)
    pack_small = bench_pack_many_small()
    print(f"  {pack_small['models']} models: solo "
          f"{pack_small['solo_us_per_row_warm']:.1f} us/row @ "
          f"{pack_small['solo_dispatches_per_window']} dispatches, packed "
          f"{pack_small['packed_us_per_row_warm']:.1f} us/row @ "
          f"{pack_small['packed_dispatches_per_window']} "
          f"(exact={pack_small['bit_identical_to_solo']})",
          file=sys.stderr)
    if not pack_small["bit_identical_to_solo"]:
        print("FATAL: packed outputs diverge from solo member caches",
              file=sys.stderr)
        return 1

    print(f"naive per-request predict x{args.naive_requests}...",
          file=sys.stderr)
    naive = bench_naive(booster, X, args.naive_requests)
    print(f"  {naive['throughput_rps']:.0f} req/s", file=sys.stderr)

    nd = max(20, args.naive_requests // 8)
    print(f"naive per-request DEVICE predict x{nd}...", file=sys.stderr)
    naive_dev = bench_naive_device(booster, X, nd)
    print(f"  {naive_dev['throughput_rps']:.0f} req/s", file=sys.stderr)

    print(f"served stream x{args.requests} "
          f"({args.clients} clients, window {args.window})...",
          file=sys.stderr)
    served = bench_served(booster, X, args.requests, args.clients,
                          args.window, args.max_delay_ms)
    print(f"  {served['throughput_rps']:.0f} req/s", file=sys.stderr)

    open_loop = registry = chaos = trace_breakdown = None
    if not args.skip_fleet:
        rates = [float(r) for r in args.sweep_rates.split(",") if r]
        widths = [int(n) for n in args.replica_counts.split(",") if n]
        print(f"open-loop goodput sweep (deadline {args.deadline_ms:g} ms, "
              f"fleets {widths}, rates {rates})...", file=sys.stderr)
        open_loop = bench_open_loop_sweep(
            booster, X, rates, widths, args.deadline_ms,
            args.sweep_duration, args.max_delay_ms)
        print("registry eviction round (2 models, budget ~1 forest)...",
              file=sys.stderr)
        registry = bench_registry(booster, X)
        print(f"  evictions {registry['evictions']}, readmissions "
              f"{registry['readmissions']}, readmit/resident request = "
              f"{registry['readmit_over_resident']:.1f}x", file=sys.stderr)
        chaos_rate = rates[min(1, len(rates) - 1)]
        print(f"chaos round (kill 1 of 2 replicas @ {chaos_rate:g} rps)...",
              file=sys.stderr)
        chaos = bench_chaos(booster, X, chaos_rate, args.deadline_ms,
                            max(args.sweep_duration, 2.0),
                            args.max_delay_ms)
        print(f"  stranded {chaos['stranded']}, goodput ratio "
              f"{chaos['goodput_ratio']:.2f}, counts {chaos['counts']}",
              file=sys.stderr)
        trace_rate = rates[min(1, len(rates) - 1)]
        print(f"trace round (sampled spans @ {trace_rate:g} rps, "
              "ABAB overhead)...", file=sys.stderr)
        trace_breakdown = bench_trace(
            booster, X, rate=trace_rate,
            duration_s=max(args.sweep_duration, 1.5),
            deadline_ms=max(args.deadline_ms, 100.0),
            max_delay_ms=args.max_delay_ms)
        bd = trace_breakdown["breakdown_p95"]
        print(f"  p95 shares: queue {bd['queue_share']:.2f}, dispatch "
              f"{bd['dispatch_share']:.2f}, transport "
              f"{bd['transport_share']:.2f}; tracing p50 on/off = "
              f"{trace_breakdown['overhead_abab']['p50_on_over_off']:.3f}",
              file=sys.stderr)

    speedup = served["throughput_rps"] / max(naive["throughput_rps"], 1e-9)
    speedup_dev = (served["throughput_rps"]
                   / max(naive_dev["throughput_rps"], 1e-9))
    report = {
        "bench": "serve",
        "trees": args.trees,
        "feats": args.feats,
        "backend": jax.default_backend(),
        "bit_identical_to_device_predict": exact,
        "engine_ab": engines,
        "pack_many_small": pack_small,
        "naive": naive,
        "naive_device": naive_dev,
        "serve": served,
        "open_loop": open_loop,
        "registry": registry,
        "chaos": chaos,
        "trace_breakdown": trace_breakdown,
        "speedup": speedup,
        "speedup_vs_device_naive": speedup_dev,
        "serve_engine": served["stats"].get("engine"),
        "serve_device_us_per_row": served["stats"].get("device_us_per_row"),
        "prometheus_samples": served.get("prometheus_samples"),
        "serve_p50_ms": served["stats"]["latency_ms"]["p50"],
        "serve_p99_ms": served["stats"]["latency_ms"]["p99"],
        "cache_hit_rate": served["stats"]["cache"]["hit_rate"],
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    print(f"speedup: {speedup:.1f}x vs naive (native single-row path), "
          f"{speedup_dev:.1f}x vs naive device dispatch per request "
          f"(target >= 5x; p50={report['serve_p50_ms']:.2f}ms "
          f"p99={report['serve_p99_ms']:.2f}ms)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
