"""Prometheus text exposition for training and serving metrics.

Pure text rendering — no client library, no HTTP server: the ``task=serve``
CLI answers a ``stats`` request line with this exposition (docs/serving.md
line protocol), and anything that can scrape a file or a pipe can ingest
it. Format follows the Prometheus exposition format v0.0.4: ``# HELP`` /
``# TYPE`` headers and ``name{label="v"} value`` samples, one per line
(tests/test_obs.py parses every line against the grammar).

Metric names (full table in docs/observability.md):

- ``lambdagap_serve_*`` — rendered from a ``ServeStats.snapshot()`` dict.
- ``lambdagap_train_*`` — rendered from a :class:`~.telemetry.TrainTelemetry`.
"""
from __future__ import annotations

from typing import Dict, List, Optional


def _escape(v: str) -> str:
    return str(v).replace("\\", r"\\").replace('"', r'\"').replace(
        "\n", r"\n")


def _num(v) -> str:
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


class _Writer:
    def __init__(self) -> None:
        self.lines: List[str] = []

    def metric(self, name: str, value, help_: str, type_: str = "gauge",
               labels: Optional[Dict[str, str]] = None) -> None:
        self.sample_header(name, help_, type_)
        self.sample(name, value, labels)

    def sample_header(self, name: str, help_: str, type_: str) -> None:
        self.lines.append(f"# HELP {name} {help_}")
        self.lines.append(f"# TYPE {name} {type_}")

    def sample(self, name: str, value,
               labels: Optional[Dict[str, str]] = None) -> None:
        lab = ""
        if labels:
            inner = ",".join(f'{k}="{_escape(v)}"'
                             for k, v in sorted(labels.items()))
            lab = "{" + inner + "}"
        self.lines.append(f"{name}{lab} {_num(value)}")

    def text(self) -> str:
        return "\n".join(self.lines) + "\n"


def render_serve(snapshot: Dict) -> str:
    """``ServeStats.snapshot()`` (plus the ForestServer extras when
    present) -> Prometheus text."""
    w = _Writer()
    p = "lambdagap_serve_"
    w.metric(p + "requests_total", snapshot.get("requests", 0),
             "Served requests", "counter")
    w.metric(p + "rows_total", snapshot.get("rows", 0),
             "Served feature rows", "counter")
    w.metric(p + "errors_total", snapshot.get("errors", 0),
             "Failed requests", "counter")
    w.metric(p + "timeouts_total", snapshot.get("timeouts", 0),
             "Requests shed before dispatch (deadline expired)", "counter")
    w.metric(p + "rejected_total", snapshot.get("rejected", 0),
             "Submits rejected by full-queue backpressure", "counter")
    w.metric(p + "swap_failures_total", snapshot.get("swap_failures", 0),
             "Hot-swaps that failed and rolled back", "counter")
    w.metric(p + "throughput_rps", snapshot.get("throughput_rps", 0.0),
             "Requests per second since start")
    w.metric(p + "throughput_rows_per_s",
             snapshot.get("throughput_rows_per_s", 0.0),
             "Rows per second since start")
    for key, help_ in (("latency_ms", "End-to-end request latency (ms)"),
                       ("queue_wait_ms", "Batcher queue wait (ms)"),
                       ("device_ms", "Device dispatch share (ms)")):
        dist = snapshot.get(key, {})
        name = p + key
        w.sample_header(name, help_, "gauge")
        for q, v in sorted(dist.items()):
            w.sample(name, v, {"quantile": q})
    batches = snapshot.get("batches", {})
    w.metric(p + "batches_total", batches.get("count", 0),
             "Device batches dispatched", "counter")
    w.metric(p + "batch_mean_rows", batches.get("mean_rows", 0.0),
             "Mean rows per batch")
    w.metric(p + "device_us_per_row",
             snapshot.get("device_us_per_row", 0.0),
             "Per-dispatch device microseconds per row")
    cache = snapshot.get("cache", {})
    w.metric(p + "cache_hits_total", cache.get("hits", 0),
             "Padding-bucket executable cache hits", "counter")
    w.metric(p + "cache_misses_total", cache.get("misses", 0),
             "Padding-bucket executable cache misses", "counter")
    w.metric(p + "cache_hit_rate", cache.get("hit_rate", 0.0),
             "Cache hit fraction")
    w.metric(p + "forest_builds_total", cache.get("forest_builds", 0),
             "Device forest (re)builds", "counter")
    w.metric(p + "bucket_compiles_total", cache.get("bucket_compiles", 0),
             "Bucket executable compiles", "counter")
    w.metric(p + "compile_local_total", cache.get("compiles_local", 0),
             "Forest artifacts lowered by the local infer compiler",
             "counter")
    w.metric(p + "compile_shared_total", cache.get("compiles_shared", 0),
             "Forest builds satisfied by a fleet-shipped artifact "
             "(sha256 admission instead of a local compile)", "counter")
    w.metric(p + "packed_dispatches_total",
             cache.get("packed_dispatches", 0),
             "Cross-model pack dispatches (serve_pack_models)", "counter")
    w.metric(p + "swaps_total", snapshot.get("swaps", 0),
             "Model hot-swaps", "counter")
    w.metric(p + "evictions_total", snapshot.get("evictions", 0),
             "Registry forests evicted under the HBM budget", "counter")
    w.metric(p + "readmissions_total", snapshot.get("readmissions", 0),
             "Evicted models recompiled on first use", "counter")
    # per-model / per-tenant labeled breakdowns (docs/serving.md)
    for block_key, label in (("per_model", "model"),
                             ("per_tenant", "tenant")):
        block = snapshot.get(block_key) or {}
        if not block:
            continue
        for metric, help_, type_ in (
                ("requests_total", "Requests served", "counter"),
                ("rows_total", "Feature rows served", "counter"),
                ("shed_total", "Requests shed before dispatch", "counter"),
                ("rejected_total", "Submits rejected at admission",
                 "counter")):
            name = f"{p}{label}_{metric}"
            w.sample_header(name, f"{help_} per {label}", type_)
            key = metric.rsplit("_", 1)[0]
            for k, g in block.items():
                w.sample(name, g.get(key, 0), {label: k})
        name = f"{p}{label}_latency_ms"
        w.sample_header(name, f"End-to-end latency per {label} (ms)",
                        "gauge")
        for k, g in block.items():
            for q, v in sorted((g.get("latency_ms") or {}).items()):
                w.sample(name, v, {label: k, "quantile": q})
    registry = snapshot.get("registry")
    if registry:
        w.metric(p + "registry_models", registry.get("registered_models", 0),
                 "Models registered in the serve registry")
        w.metric(p + "registry_resident_models",
                 registry.get("resident_models", 0),
                 "Models with a resident compiled forest")
        w.metric(p + "registry_hbm_bytes",
                 registry.get("hbm_bytes_resident", 0),
                 "Resident compiled-forest bytes")
        w.metric(p + "registry_hbm_budget_bytes",
                 registry.get("hbm_budget_bytes", 0),
                 "Registry HBM byte budget (0 = unlimited)")
        name = p + "registry_model_resident"
        w.sample_header(name, "Per-model residency (1 = compiled forest "
                        "in HBM)", "gauge")
        for k, m in (registry.get("models") or {}).items():
            w.sample(name, 1 if m.get("resident") else 0, {"model": k})
    if "generation" in snapshot:
        w.metric(p + "generation", snapshot["generation"],
                 "Active model generation")
    health = snapshot.get("health")
    if health:
        # enum-as-labeled-gauge: exactly one state samples 1
        name = p + "health"
        w.sample_header(name, "Serving health state (ok/degraded/draining)",
                        "gauge")
        for state in ("ok", "degraded", "draining"):
            w.sample(name, 1 if health.get("state") == state else 0,
                     {"state": state})
        if "swap_breaker" in health:
            name = p + "swap_breaker_open"
            w.metric(name, 0 if health["swap_breaker"] == "closed" else 1,
                     "Swap circuit breaker tripped (open or probing)")
    return w.text()


def render_router(snapshot: Dict) -> str:
    """``Router.snapshot()`` -> Prometheus text: fleet-level dispatch
    counters plus per-replica routed/inflight/health labels."""
    w = _Writer()
    p = "lambdagap_router_"
    w.metric(p + "failovers_total", snapshot.get("failovers", 0),
             "Requests failed over to another replica", "counter")
    w.metric(p + "rejected_no_replica_total",
             snapshot.get("rejected_no_replica", 0),
             "Requests rejected with no live replica", "counter")
    replicas = snapshot.get("replicas") or {}
    for metric, help_, type_ in (
            ("routed_total", "Requests routed to the replica", "counter"),
            ("inflight", "Requests currently in flight", "gauge")):
        name = p + "replica_" + metric
        w.sample_header(name, help_, type_)
        key = metric.rsplit("_", 1)[0] if metric.endswith("_total") \
            else metric
        for rname, info in sorted(replicas.items()):
            w.sample(name, info.get(key, 0), {"replica": rname})
    name = p + "replica_health"
    w.sample_header(name, "Replica health (ok/degraded/draining/dead)",
                    "gauge")
    for rname, info in sorted(replicas.items()):
        for state in ("ok", "degraded", "draining", "dead"):
            w.sample(name, 1 if info.get("health") == state else 0,
                     {"replica": rname, "state": state})
    return w.text()


def render_fleet(merged: Dict, router: Optional[Dict] = None) -> str:
    """Fleet exposition (the ``prometheus fleet`` verb, docs/serving.md):
    the MERGED per-replica stats rendered through the same serve metric
    names (obs/fleet.merge_snapshots keeps the snapshot schema, so one
    scrape config covers a replica and a fleet), plus fleet-level gauges
    and — when the router's own snapshot is passed — the per-replica
    routing/health labels. Label values (model/tenant/replica names are
    user-supplied strings) go through the same exposition-format escaping
    as every other sample."""
    w = _Writer()
    p = "lambdagap_fleet_"
    w.metric(p + "replicas", merged.get("replica_count", 0),
             "Replicas merged into this exposition")
    w.metric(p + "unreachable_replicas",
             merged.get("unreachable_replicas", 0),
             "Replicas that failed the scrape (stats missing from the "
             "merge)")
    registry = merged.get("registry") or {}
    name = p + "model_resident_replicas"
    w.sample_header(name, "Replicas holding the model's compiled forest "
                    "resident", "gauge")
    for k, m in (registry.get("models") or {}).items():
        w.sample(name, m.get("resident_replicas", 0), {"model": k})
    parts = [w.text(), render_serve(merged)]
    if router:
        parts.append(render_router(router))
    return "".join(parts)


def render_train(telemetry) -> str:
    """:class:`TrainTelemetry` aggregates -> Prometheus text."""
    w = _Writer()
    p = "lambdagap_train_"
    s = telemetry.summary()
    w.metric(p + "iterations_total", s.get("iterations", 0),
             "Boosting iterations recorded", "counter")
    if not s.get("enabled"):
        return w.text()
    name = p + "phase_seconds_total"
    w.sample_header(name, "Exclusive seconds spent per phase", "counter")
    for phase, secs in s["phase_seconds_total"].items():
        w.sample(name, secs, {"phase": phase})
    name = p + "iter_wall_seconds"
    w.sample_header(name, "Device-complete per-iteration wall (s)", "gauge")
    for q, v in sorted(s["iter_wall_s"].items()):
        w.sample(name, v, {"quantile": q})
    w.metric(p + "compiles_total", s.get("compiles", 0),
             "XLA backend compiles observed", "counter")
    w.metric(p + "steady_compiles_total", s.get("steady_compiles", 0),
             "Compiles after the warmup window (R2 hazard)", "counter")
    w.metric(p + "transfers_total", s.get("transfers", 0),
             "Device transfers observed via jax.monitoring", "counter")
    w.metric(p + "compile_seconds_total", s.get("compile_secs", 0.0),
             "Seconds spent in XLA backend compiles", "counter")
    return w.text()


def render(telemetry=None, serve_snapshot: Optional[Dict] = None) -> str:
    """Combined exposition; either side may be absent."""
    parts = []
    if telemetry is not None:
        parts.append(render_train(telemetry))
    if serve_snapshot is not None:
        parts.append(render_serve(serve_snapshot))
    return "".join(parts)
