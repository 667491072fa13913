"""Open-loop load generation: offered load the server cannot gate.

A CLOSED-loop client keeps a bounded window in flight, so when the
server slows down the clients slow down with it and "offered load" silently collapses to whatever the server admits —
saturation becomes unmeasurable (every closed-loop bench reports a happy
server at 100% of its own pace). The generator here is OPEN-loop: request
arrival times are fixed IN ADVANCE from an arrival rate — deterministic
(``uniform``) or Poisson (seeded ``numpy.random.Generator``; never
wall-clock random) — and submission follows that schedule regardless of
how the fleet is doing. Overload therefore shows up honestly, as shed
requests and deadline misses rather than a politely slowed client.

Metrics separate three honest numbers per round:

- **offered_rps** — the schedule, what arrived;
- **throughput_rps** — requests that completed with a value, at any
  latency;
- **goodput_rps** — requests that completed within ``deadline_ms`` of
  their SCHEDULED arrival (a late answer is as useless to a caller as no
  answer; queue time the generator spends catching up counts against the
  server, as it does in production).

Latency is measured from scheduled arrival, per request and per tenant
(bounded reservoirs). ``sweep`` walks a rate ladder to saturation and
reports the knee: the last offered rate whose goodput stays within
``good_ratio`` of offered.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..guard.degrade import (ReplicaUnavailable, ServeOverloaded,
                             ServeTimeout)
from ..obs.reservoir import Reservoir


def arrival_times(rate_rps: float, n: int, kind: str = "poisson",
                  seed: int = 0) -> np.ndarray:
    """``n`` arrival offsets (seconds from start) at ``rate_rps``.
    ``uniform`` = deterministic 1/rate spacing; ``poisson`` = exponential
    inter-arrivals from a seeded generator (reproducible across runs)."""
    if rate_rps <= 0:
        raise ValueError("rate_rps must be > 0")
    if kind == "uniform":
        return (np.arange(n, dtype=np.float64) + 1.0) / rate_rps
    if kind == "poisson":
        rng = np.random.default_rng(seed)
        return rng.exponential(1.0 / rate_rps, size=n).cumsum()
    raise ValueError(f"unknown arrival kind {kind!r} (uniform/poisson)")


def run_open_loop(submit: Callable, X: np.ndarray, rate_rps: float,
                  n_requests: int, deadline_ms: float = 50.0,
                  tenants: Optional[Dict[str, float]] = None,
                  models: Optional[Sequence[str]] = None,
                  arrival: str = "poisson", seed: int = 0,
                  settle_timeout_s: float = 30.0) -> dict:
    """One open-loop round: ``n_requests`` single-row requests offered at
    ``rate_rps`` against ``submit(x, model=, tenant=)``. Tenants (name ->
    weight) and models are drawn per-request from the seeded generator, so
    a (seed, rate, n) triple is a fully reproducible workload."""
    tenants = tenants or {"t0": 1.0}
    names = sorted(tenants)
    rng = np.random.default_rng(seed + 1)
    probs = np.asarray([tenants[t] for t in names], np.float64)
    probs /= probs.sum()
    t_assign = rng.choice(len(names), size=n_requests, p=probs)
    m_assign = (rng.integers(0, len(models), size=n_requests)
                if models else None)
    row_assign = rng.integers(0, len(X), size=n_requests)
    sched = arrival_times(rate_rps, n_requests, kind=arrival, seed=seed)
    deadline_s = deadline_ms / 1e3

    lat_all = Reservoir(8192, seed=11)
    lat_tenant = {t: Reservoir(4096, seed=13 + i)
                  for i, t in enumerate(names)}
    counts = {"ok": 0, "good": 0, "late": 0, "rejected": 0, "timeout": 0,
              "transport": 0, "error": 0}
    per_tenant = {t: {"offered": 0, "ok": 0, "good": 0, "shed": 0}
                  for t in names}
    pending = []

    t0 = time.perf_counter()
    for i in range(n_requests):
        target = t0 + sched[i]
        now = time.perf_counter()
        if target > now:
            time.sleep(target - now)
        tenant = names[t_assign[i]]
        per_tenant[tenant]["offered"] += 1
        model = models[m_assign[i]] if models else None
        try:
            fut = submit(X[row_assign[i]][None, :], model=model,
                         tenant=tenant)
        except (ServeOverloaded, ReplicaUnavailable):
            counts["rejected"] += 1
            per_tenant[tenant]["shed"] += 1
            continue
        # stamp the COMPLETION time in the resolving thread — settling
        # below happens much later, and late bookkeeping must not smear
        # into the latency a caller actually saw
        stamp = [0.0]
        fut.add_done_callback(
            lambda f, s=stamp: s.__setitem__(0, time.perf_counter()))
        pending.append((fut, target, tenant, stamp))
    t_offered = time.perf_counter() - t0

    settle_by = time.perf_counter() + settle_timeout_s
    for fut, target, tenant, stamp in pending:
        try:
            fut.result(timeout=max(settle_by - time.perf_counter(), 0.01))
        except ServeTimeout:
            counts["timeout"] += 1
            per_tenant[tenant]["shed"] += 1
            continue
        except (ServeOverloaded, ReplicaUnavailable):
            counts["transport"] += 1
            per_tenant[tenant]["shed"] += 1
            continue
        except Exception:
            counts["error"] += 1
            continue
        # the callback races result() by microseconds at worst; fall back
        # to now if this thread won
        done = stamp[0] or time.perf_counter()
        lat = done - target              # from SCHEDULED arrival
        counts["ok"] += 1
        per_tenant[tenant]["ok"] += 1
        lat_all.add(lat)
        lat_tenant[tenant].add(lat)
        if lat <= deadline_s:
            counts["good"] += 1
            per_tenant[tenant]["good"] += 1
        else:
            counts["late"] += 1
    elapsed = max(time.perf_counter() - t0, 1e-9)
    span = max(t_offered, 1e-9)

    def _ms(d):
        return {k: v * 1e3 for k, v in d.items()}

    return {
        "offered_rps": rate_rps,
        "achieved_offer_rps": n_requests / span,
        "arrival": arrival,
        "seed": seed,
        "n_requests": n_requests,
        "deadline_ms": deadline_ms,
        "elapsed_s": elapsed,
        "counts": counts,
        "throughput_rps": counts["ok"] / span,
        "goodput_rps": counts["good"] / span,
        "goodput_ratio": counts["good"] / n_requests,
        "latency_ms": _ms(lat_all.percentiles()),
        "per_tenant": {
            t: {**per_tenant[t],
                "latency_ms": _ms(lat_tenant[t].percentiles())}
            for t in names
        },
    }


def sweep(submit: Callable, X: np.ndarray, rates: Sequence[float],
          n_requests: int = 500, deadline_ms: float = 50.0,
          tenants: Optional[Dict[str, float]] = None,
          models: Optional[Sequence[str]] = None,
          arrival: str = "poisson", seed: int = 0,
          good_ratio: float = 0.9) -> dict:
    """Walk ``rates`` (ascending offered load) and report the saturation
    knee: the last rate whose goodput holds ``good_ratio`` of offered.
    Each round reuses the seeded workload generator, so two sweeps of the
    same config measure the same request stream."""
    rounds: List[dict] = []
    saturation = None
    for rate in rates:
        r = run_open_loop(submit, X, rate, n_requests,
                          deadline_ms=deadline_ms, tenants=tenants,
                          models=models, arrival=arrival, seed=seed)
        rounds.append(r)
        if r["goodput_ratio"] >= good_ratio:
            saturation = rate
    return {
        "rates": list(rates),
        "deadline_ms": deadline_ms,
        "good_ratio": good_ratio,
        "saturation_rps": saturation,
        "rounds": rounds,
    }
