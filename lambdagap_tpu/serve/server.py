"""ForestServer: the serving front door.

Composes the serving pieces — :class:`ModelRegistry` (N compiled forests
under an HBM budget, per-model generations + hot-swap),
:class:`MicroBatcher` (request coalescing with weighted tenant fairness)
and the guard degradation layer — behind a two-call API::

    server = booster.as_server()          # or ForestServer(booster)
    y = server.predict(x_row)             # blocking, batched under the hood
    fut = server.submit(rows)             # async: Future[ServeResult]
    server.add_model("b", "model_b.txt")  # multi-model registry
    y_b = server.predict(x_row, model="b")
    server.swap("model_v2.txt")           # zero-downtime model replace
    print(server.stats_json())
    server.close()

Every response is a :class:`ServeResult` carrying the generation that
produced it, which is what makes hot-swap correctness testable: under a
concurrent stream, each result matches exactly one generation's forest.

The server owns POLICY (batching windows, shedding, tenant quotas,
health); the registry owns MECHANISM (which forests are resident, their
buckets, their generations) — the split ROADMAP item 2 prescribes, and
what lets several replica servers share nothing behind a router
(serve/router.py) while each runs its own registry.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from ..guard.degrade import HealthMonitor
from ..guard.faults import plan_for
from ..obs import trace as obs_trace
from ..utils import log
from .batcher import MicroBatcher, Request
from .cache import DEFAULT_BUCKETS, CompiledForestCache, ModelPack
from .registry import DEFAULT_MODEL, ModelRegistry
from .stats import ServeStats


class ServeResult(NamedTuple):
    """One request's predictions + the model generation that served it."""
    values: np.ndarray
    generation: int


def parse_tenant_weights(spec: str) -> Dict[str, float]:
    """``"tenant:weight,tenant2:weight2"`` -> dict (unlisted tenants weigh
    1.0 in the fair queue)."""
    out: Dict[str, float] = {}
    for tok in (spec or "").split(","):
        tok = tok.strip()
        if not tok:
            continue
        if ":" not in tok:
            raise ValueError(f"serve_tenant_weights token {tok!r} is not "
                             "'tenant:weight'")
        name, w = tok.rsplit(":", 1)
        out[name.strip()] = float(w)
    return out


class ForestServer:
    """Batched, hot-swappable, multi-model TPU inference server.

    Accepts a ``basic.Booster`` or a ``models.gbdt.GBDT`` as the initial
    (``"default"``) model. Defaults for the batching/bucket/registry knobs
    come from the booster's config (``serve_*`` parameters); keyword
    arguments override.
    """

    def __init__(self, model, buckets: Optional[Sequence[int]] = None,
                 max_batch: Optional[int] = None,
                 max_delay_ms: Optional[float] = None,
                 workers: Optional[int] = None,
                 warmup: Optional[bool] = None,
                 raw_score: bool = False,
                 start_iteration: int = 0, num_iteration: int = -1,
                 stats: Optional[ServeStats] = None,
                 max_queue: Optional[int] = None,
                 backpressure: Optional[str] = None,
                 timeout_ms: Optional[float] = None,
                 swap_breaker: Optional[int] = None,
                 hbm_budget_bytes: Optional[int] = None,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 tenant_max_share: Optional[float] = None) -> None:
        gbdt = model._booster if hasattr(model, "_booster") else model
        cfg = gbdt.config
        self.raw_score = bool(raw_score)
        self._buckets = tuple(buckets if buckets is not None
                              else (cfg.serve_buckets or DEFAULT_BUCKETS))
        self._warmup = bool(cfg.serve_warmup if warmup is None else warmup)
        self._si = int(start_iteration)
        self._ni = int(num_iteration)
        self.stats = stats if stats is not None else ServeStats()
        self._closed = False
        self._faults = plan_for(cfg)
        if hbm_budget_bytes is None:
            hbm_budget_bytes = int(cfg.serve_hbm_budget_mb * (1 << 20))
        # the replica-wide compiled-artifact store: builds consult it by
        # source key before lowering (peers ship artifacts over the wire,
        # push_artifact), so N replicas placing one model pay ONE compile
        from ..infer import ArtifactStore
        self.artifacts = ArtifactStore()
        # cross-model packing (serve_pack_models): resident compiled
        # models fuse into ONE executable so a mixed FairQueue batch
        # dispatches once; rebuilt lazily on membership/generation change
        self._pack_models = bool(cfg.serve_pack_models)
        self._pack: Optional[ModelPack] = None
        self._pack_lock = threading.Lock()
        self.registry = ModelRegistry(
            self._build_cache, stats=self.stats,
            hbm_budget_bytes=hbm_budget_bytes,
            breaker_threshold=int(cfg.serve_swap_breaker
                                  if swap_breaker is None else swap_breaker),
            artifact_store=self.artifacts)
        self.registry.install(DEFAULT_MODEL, gbdt)
        self.health = HealthMonitor(
            breaker=self.registry.entry(DEFAULT_MODEL).breaker)
        nw = int(cfg.serve_workers if workers is None else workers)
        if nw <= 0:                      # auto: overlap dispatches, bounded
            import os
            nw = max(1, min(4, (os.cpu_count() or 1) // 2))
        if tenant_weights is None:
            tenant_weights = parse_tenant_weights(cfg.serve_tenant_weights)
        self._batcher = MicroBatcher(
            self._run_batch,
            max_batch=int(cfg.serve_max_batch if max_batch is None
                          else max_batch),
            max_delay_ms=float(cfg.serve_max_delay_ms if max_delay_ms is None
                               else max_delay_ms),
            workers=nw,
            stats=self.stats,
            max_queue=int(cfg.serve_max_queue if max_queue is None
                          else max_queue),
            backpressure=(cfg.serve_backpressure if backpressure is None
                          else backpressure),
            timeout_ms=float(cfg.serve_timeout_ms if timeout_ms is None
                             else timeout_ms),
            health=self.health,
            tenant_weights=tenant_weights,
            tenant_max_share=float(cfg.serve_tenant_max_share
                                   if tenant_max_share is None
                                   else tenant_max_share))
        # serve-side profiler window keyed to the submitted-request count
        # (profile_serve_start_req/profile_serve_n_req): the inference
        # analog of profile_start_iter (docs/observability.md)
        from ..obs.profile import ProfileWindow
        self._profile = ProfileWindow(
            start_iter=int(getattr(cfg, "profile_serve_start_req", -1)),
            n_iters=int(getattr(cfg, "profile_serve_n_req", 1)),
            out_dir=getattr(cfg, "profile_dir", ""), unit="serve_request")

    # ------------------------------------------------------------------
    def _build_cache(self, gbdt, generation: int) -> CompiledForestCache:
        cache = CompiledForestCache(
            gbdt, buckets=self._buckets, start_iteration=self._si,
            num_iteration=self._ni, generation=generation, stats=self.stats,
            artifact_store=self.artifacts)
        if self._warmup:
            cache.warm()
        return cache

    @property
    def generation(self) -> int:
        return self.registry.generation(DEFAULT_MODEL)

    @property
    def num_features(self) -> int:
        """Width the active compiled forest consumes (1 + max split
        feature); narrower requests error unless
        predict_disable_shape_check pads them with NaN."""
        return self.registry.entry(DEFAULT_MODEL).width

    @property
    def _swap(self):
        """PR 1 compatibility shim: the default model's registry entry
        exposes the old SwapController surface (``.active``,
        ``.breaker``)."""
        return self.registry.entry(DEFAULT_MODEL)

    # -- model management ----------------------------------------------
    def add_model(self, name: str, source, params=None) -> int:
        """Register an additional model (path, model text, Booster or
        GBDT) under ``name``; it compiles (and warms) now, off the request
        path, subject to the registry's HBM budget."""
        return self.registry.install(name, source, params=params)

    def models(self) -> List[str]:
        return self.registry.names()

    def admit_artifact(self, payload: bytes,
                       expect_hash: Optional[str] = None) -> str:
        """Admit a peer replica's serialized compiled-forest artifact by
        content hash (docs/serving.md "Compiled forest artifacts"). The
        next compiled-engine build whose source key matches serves the
        admitted artifact instead of compiling — a mismatched or torn
        payload raises ``ArtifactMismatch`` and compiles locally instead,
        never serving the wrong model. Returns the verified hash."""
        return self.registry.admit_artifact(payload, expect_hash=expect_hash)

    def artifact_bytes(self, model: str = DEFAULT_MODEL) -> bytes:
        """Serialize ``model``'s compiled artifact for shipping to peers
        (requires predict_engine=compiled)."""
        return self.registry.artifact_bytes(model)

    # -- request path ---------------------------------------------------
    def submit(self, x, model: Optional[str] = None,
               tenant: Optional[str] = None,
               trace=None) -> "Future[ServeResult]":
        """Async predict: enqueue rows, return a Future of
        :class:`ServeResult`. ``x`` is one row [D] or a matrix [n, D];
        ``model`` routes to a registry model (default: the initial one);
        ``tenant`` bills the request to a fairness/accounting lane;
        ``trace`` is an incoming :class:`~lambdagap_tpu.obs.trace.
        TraceContext` (None = mint one per ``serve_trace_sample``, which
        defaults to never)."""
        if self._closed:
            raise RuntimeError("ForestServer is closed")
        name = model if model is not None else DEFAULT_MODEL
        if not self.registry.has(name):
            raise ValueError(f"unknown serve model {name!r} "
                             f"(registered: {self.models()})")
        x = np.asarray(x, dtype=np.float32)
        if x.ndim == 1:
            x = x[None, :]
        if x.ndim != 2:
            raise ValueError(f"serve requests are rows [n, D], got {x.shape}")
        if self._profile.enabled:        # request-count profiler window
            self._profile.tick()
        ctx = trace if trace is not None \
            else obs_trace.RECORDER.maybe_trace()
        if ctx is None:                  # the untraced fast path
            return self._batcher.submit(x, model=name, tenant=tenant)
        # the serve_request span covers submit -> future resolution; its
        # context rides the Request so queue/registry/dispatch spans nest
        # under it (recorded after the fact — span ids are pre-minted)
        child = ctx.child()
        t0_wall, t0 = time.time(), time.perf_counter()
        fut = self._batcher.submit(x, model=name, tenant=tenant,
                                   trace=child)
        attrs = {"model": name}
        if tenant is not None:
            attrs["tenant"] = tenant

        def _record(f) -> None:
            # ends where the future resolved, not where this callback
            # runs: the waiter is woken first (batcher.ResolvedAtFuture)
            obs_trace.RECORDER.record(
                "serve_request", ctx, t0_wall,
                (f.t_done or time.perf_counter()) - t0,
                span_id=child.span_id, **attrs)

        fut.add_done_callback(_record)
        return fut

    def predict(self, x, timeout: Optional[float] = None,
                model: Optional[str] = None,
                tenant: Optional[str] = None) -> np.ndarray:
        """Blocking predict with ``Booster.predict`` output semantics:
        [n] for single-class models, [n, K] for multiclass."""
        return self.submit(x, model=model, tenant=tenant).result(
            timeout).values

    # -- hot swap -------------------------------------------------------
    def swap(self, source, params=None, background: bool = False,
             model: str = DEFAULT_MODEL):
        """Atomically replace a served model (path, model text, Booster
        or GBDT). The new forest is compiled and pre-warmed BEFORE the
        generation pointer flips; in-flight requests finish on the old
        forest. Returns the new generation (or the worker thread when
        ``background=True``)."""
        return self.registry.swap(model, source, params=params,
                                  background=background)

    def swap_delta(self, delta, model: str = DEFAULT_MODEL) -> int:
        """Delta hot-swap: apply an appended-trees frame
        (serve/delta.py) against the resident host model, then compile /
        pre-warm / flip exactly like :meth:`swap`. Returns the new
        generation; a non-applying delta raises ``SwapFailed`` with the
        old generation untouched."""
        return self.registry.swap_delta(model, delta, faults=self._faults)

    def model_text(self, model: str = DEFAULT_MODEL) -> str:
        """The resident host model's full text (delta-swap base)."""
        return self.registry.model_text(model)

    def prefetch(self, model: str = DEFAULT_MODEL) -> Dict:
        """Make ``model`` resident NOW (re-admitting it if evicted) and
        report what that cost — the placement loop's actuation verb, so
        the readmission cliff is paid off the request path, by design
        (docs/serving.md "Model placement")."""
        info: Dict = {}
        self.registry.get(model, info=info)
        info.setdefault("readmitted", False)
        info["resident"] = True
        return info

    # -- metrics / lifecycle -------------------------------------------
    def stats_snapshot(self, reservoirs: bool = False,
                       timeout_s: Optional[float] = None) -> dict:
        """The serving metrics dict; ``reservoirs=True`` adds the raw
        reservoir states the fleet scraper merges (obs/fleet.py).
        ``timeout_s`` exists for scrape-surface uniformity with the
        router (an in-process snapshot cannot block on a peer)."""
        entry = self.registry.entry(DEFAULT_MODEL)
        snap = self.stats.snapshot(reservoirs=reservoirs)
        snap["generation"] = entry.generation
        snap["buckets"] = list(entry.buckets)
        snap["engine"] = entry.engine
        snap["health"] = self.health.snapshot()
        snap["registry"] = self.registry.snapshot()
        return snap

    def stats_json(self, **kwargs) -> str:
        import json
        kwargs.setdefault("indent", 2)
        return json.dumps(self.stats_snapshot(), **kwargs)

    def prometheus(self) -> str:
        """Prometheus text exposition of the serving metrics (the
        ``stats`` line of the task=serve loop; metric names in
        docs/observability.md)."""
        from ..obs import prom
        return prom.render_serve(self.stats_snapshot())

    def prometheus_fleet(self) -> str:
        """The ``prometheus fleet`` verb on a single server: a fleet of
        one, rendered through the same merge path the router uses — so
        scrape configs are identical whether a frontend fronts one
        replica or a router (docs/serving.md)."""
        from ..obs import fleet, prom
        merged = fleet.merge_snapshots(
            [self.stats_snapshot(reservoirs=True)])
        return prom.render_fleet(merged)

    def close(self, timeout: float = 30.0) -> None:
        """Flush queued requests and stop the batcher thread. Health
        reports DRAINING from the first close() call onward."""
        if not self._closed:
            self._closed = True
            self.health.set_draining()
            self._batcher.close(timeout)
            self._profile.close()

    def __enter__(self) -> "ForestServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------
    def _run_batch(self, batch: List[Request]) -> None:
        """Worker-thread batch execution: group the coalesced batch by
        registry model, snapshot each model's compiled forest once, run
        ONE padded dispatch per model, scatter results back to futures. A
        model that fails to resolve (removed, or its re-admission compile
        failed) fails only ITS requests; the other groups still serve."""
        self._faults.dispatch_fault()    # inert unless a fault plan is armed
        groups: Dict[str, List[Request]] = {}
        for r in batch:
            groups.setdefault(r.model or DEFAULT_MODEL, []).append(r)
        resolved: List[tuple] = []
        for name, reqs in sorted(groups.items()):
            info: Dict = {}
            t_reg_wall, t_reg = time.time(), time.perf_counter()
            try:
                slot = self.registry.get(name, info=info)  # LRU; may readmit
            except Exception as e:
                for r in reqs:
                    if not r.future.done():
                        r.future.set_exception(e)
                self.stats.record_error()
                continue
            reg_dur = time.perf_counter() - t_reg
            rec = obs_trace.RECORDER
            for r in reqs:
                if r.trace is None:
                    continue
                # queue_wait ends where the registry resolve begins, so
                # the three children (queue_wait, registry_get, dispatch)
                # TILE the serve_request span instead of double-counting
                rec.record("queue_wait", r.trace, r.t_wall,
                           t_reg - r.t_submit)
                # the registry resolve, per sampled request: a readmitted
                # group makes the 174x cliff visible on every trace that
                # paid it (registry_readmit nests the compile share; the
                # artifact_hash tag says WHICH compiled artifact was
                # rebuilt, so fleet traces join on the shared-compile key)
                sid = rec.record("registry_get", r.trace, t_reg_wall,
                                 reg_dur, model=name, **info)
                if info.get("readmitted"):
                    rec.record("registry_readmit", r.trace, t_reg_wall,
                               info.get("build_s", reg_dur), parent=sid,
                               model=name,
                               **({"artifact_hash": info["artifact_hash"]}
                                  if info.get("artifact_hash") else {}))
            resolved.append((name, slot, reqs))
        pack = self._model_pack() if (self._pack_models and resolved) else None
        if pack is not None:
            self._dispatch_packed(pack, resolved)
            return
        for name, slot, reqs in resolved:
            self._dispatch_group(name, slot, reqs)

    def _model_pack(self) -> Optional[ModelPack]:
        """The cross-model pack covering every registered model, rebuilt
        lazily whenever membership or any member's generation changes (the
        pack key is the (name, cache key) set). Resolving every member
        forces fleet-wide residency — packing implies the operator WANTS
        all tenants resident; the HBM budget still applies and an evicted
        member re-admits through the normal single-flight path. Returns
        None (per-model dispatch fallback) when any member cannot pack
        (non-compiled engine, early stop, or a failed build)."""
        try:
            slots: Dict[str, CompiledForestCache] = {}
            for name in self.registry.names():
                slot = self.registry.get(name)
                if slot._compiled is None or slot._es_freq:
                    return None
                slots[name] = slot
        except Exception as e:
            log.warning("serve: cross-model pack unavailable (%s); "
                        "dispatching per model", e)
            return None
        key = frozenset((n, c.key) for n, c in slots.items())
        with self._pack_lock:
            pack = self._pack
            if pack is None or pack.key != key:
                pack = ModelPack(slots, buckets=self._buckets,
                                 stats=self.stats)
                self._pack = pack
                log.info("serve: packed %d models into one executable "
                         "(%d trees, width %d, %d bytes)", len(slots),
                         pack.packed.num_trees, pack.width, pack.hbm_bytes)
            return pack

    def _gather_rows(self, name: str, slot,
                     reqs: List[Request]) -> tuple:
        """Shape-check one model's requests against its compiled width:
        returns (rows, good requests); violators fail their own future."""
        W = slot.width
        disable_check = slot.gbdt.config.predict_disable_shape_check
        rows: List[np.ndarray] = []
        good: List[Request] = []
        for r in reqs:
            x = r.x
            if x.shape[1] < W:
                if not disable_check:
                    r.future.set_exception(ValueError(
                        f"request has {x.shape[1]} features but model "
                        f"{name!r} needs {W}; set "
                        "predict_disable_shape_check=true to pad missing "
                        "features with NaN"))
                    self.stats.record_error()
                    continue
                x = np.concatenate(
                    [x, np.full((x.shape[0], W - x.shape[1]), np.nan,
                                np.float32)], axis=1)
            rows.append(np.ascontiguousarray(x[:, :W]))
            good.append(r)
        return rows, good

    def _dispatch_packed(self, pack: ModelPack, resolved: List[tuple]) -> None:
        """A mixed multi-model batch through ONE packed executable: every
        model's rows concatenate into shared cross-model padding buckets,
        the traversal dispatches once per bucket, and each request's slice
        comes back bit-identical to its member cache serving it alone."""
        t0_wall, t0 = time.time(), time.perf_counter()
        parts: List[tuple] = []
        for name, slot, reqs in resolved:
            rows, good = self._gather_rows(name, slot, reqs)
            if good:
                parts.append((name, slot, good, rows))
        if not parts:
            return
        mixed = [(name, rows[0] if len(rows) == 1
                  else np.concatenate(rows, axis=0), self.raw_score)
                 for name, _slot, _good, rows in parts]
        outs = pack.predict_mixed(mixed)
        t1 = time.perf_counter()
        total_rows = sum(x.shape[0] for _n, x, _r in mixed)
        self.stats.record_dispatch(rows=total_rows, device_s=t1 - t0)
        self.stats.record_packed_dispatch(models=len(parts), rows=total_rows)
        rec = obs_trace.RECORDER
        for (name, slot, good, rows), out in zip(parts, outs):
            lo = 0
            for r, x in zip(good, rows):
                n = x.shape[0]
                if r.trace is not None:
                    rec.record("dispatch", r.trace, t0_wall, t1 - t0,
                               rows=n, batch_rows=total_rows, model=name,
                               packed_models=len(parts))
                r.future.set_result(ServeResult(out[lo:lo + n],
                                                slot.generation))
                lo += n
                self.stats.record_request(
                    queue_wait=t0 - r.t_submit, device=t1 - t0,
                    total=time.perf_counter() - r.t_submit,
                    rows=n, model=name, tenant=r.tenant)

    def _dispatch_group(self, name: str, slot, reqs: List[Request]) -> None:
        """One model's share of a batch through one padded dispatch."""
        t0 = time.perf_counter()
        t0_wall = time.time()
        rows, good = self._gather_rows(name, slot, reqs)
        if not good:
            return
        X = rows[0] if len(rows) == 1 else np.concatenate(rows, axis=0)
        out = slot.predict(X, raw_score=self.raw_score)
        t1 = time.perf_counter()
        self.stats.record_dispatch(rows=X.shape[0], device_s=t1 - t0)
        lo = 0
        rec = obs_trace.RECORDER
        for r, x in zip(good, rows):
            n = x.shape[0]
            if r.trace is not None:
                # queue_wait + registry_get were recorded by _run_batch;
                # the dispatch span reuses the timestamps the stats
                # already take, so tracing adds no clock reads here
                rec.record("dispatch", r.trace, t0_wall, t1 - t0,
                           rows=n, batch_rows=X.shape[0], model=name)
            r.future.set_result(ServeResult(out[lo:lo + n],
                                            slot.generation))
            lo += n
            self.stats.record_request(queue_wait=t0 - r.t_submit,
                                      device=t1 - t0,
                                      total=time.perf_counter() - r.t_submit,
                                      rows=n, model=name, tenant=r.tenant)


def serve_loop(server: ForestServer, lines, out_stream,
               on_swap=None, stats_stream=None) -> int:
    """Drive a server from an iterable of text request lines (the CLI's
    ``task=serve`` loop; factored here so tests can drive it without a
    process). Line protocol (docs/serving.md):

    - one feature row per line (TSV or CSV) — a predict request;
    - ``swap=<model>`` — atomic hot-swap (``swap=name:<model>`` for a
      non-default registry model);
    - ``model=<name>`` — route subsequent predict lines to that registry
      model (``model=`` resets to the default);
    - ``stats`` — print the Prometheus exposition of the live serving
      metrics to ``stats_stream`` (default: stderr);
    - ``stats json`` — the ``ServeStats.snapshot()`` JSON instead;
    - ``prometheus fleet`` — the fleet-merged exposition (a single
      server renders as a fleet of one, same metric names as a router);
    - ``health`` — one-line health state to ``stats_stream``;
    - ``#``-prefixed lines and blanks are ignored.

    Returns the number of served requests."""
    import sys as _sys
    if stats_stream is None:
        stats_stream = _sys.stderr
    futures = []
    active_model = None
    for line in lines:
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        if line == "stats" or line == "stats prometheus":
            stats_stream.write(server.prometheus())
            stats_stream.flush()
            continue
        if line == "prometheus fleet":
            stats_stream.write(server.prometheus_fleet())
            stats_stream.flush()
            continue
        if line == "stats json":
            stats_stream.write(server.stats_json() + "\n")
            stats_stream.flush()
            continue
        if line == "health":
            stats_stream.write(server.health.state() + "\n")
            stats_stream.flush()
            continue
        if line.startswith("model="):
            name = line.split("=", 1)[1].strip()
            active_model = name or None
            continue
        if line.startswith("swap="):
            from ..guard.degrade import SwapFailed, SwapRejected
            target = line.split("=", 1)[1].strip()
            model = DEFAULT_MODEL
            if ":" in target:
                head, rest = target.split(":", 1)
                # "name:path" routes the swap; bare paths (which may
                # contain ':' on exotic systems) keep working because a
                # registered model name wins only when it exists
                if server.registry.has(head):
                    model, target = head, rest
            try:
                gen = server.swap(target, model=model)
            except (SwapFailed, SwapRejected) as e:
                # degraded, not dead: the active generation keeps serving
                # (stats carry swap_failures + the breaker state)
                log.warning("serve loop: %s", e)
                continue
            if on_swap is not None:
                on_swap(target, gen)
            continue
        delim = "\t" if "\t" in line else ","
        row = np.array([_parse_cell(tok) for tok in line.split(delim)],
                       dtype=np.float32)
        futures.append(server.submit(row, model=active_model))
    for f in futures:
        vals = np.atleast_1d(np.asarray(f.result().values)).reshape(-1)
        out_stream.write("\t".join(f"{v:.10g}" for v in vals) + "\n")
    return len(futures)


def _parse_cell(tok: str) -> float:
    try:
        return float(tok)
    except ValueError:
        return float("nan")
