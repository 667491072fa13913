"""Replica router: health-aware dispatch over shared-nothing serve workers.

A fleet is several :class:`~lambdagap_tpu.serve.server.ForestServer`
replicas — in-process (:class:`LocalReplica`) or behind a socket front end
(:class:`RemoteReplica`, serve/frontend.py) — that share NOTHING: each has
its own registry, batcher, and device executables. The router owns only
dispatch policy:

- **health-aware placement**: replicas reporting ``ok`` are preferred;
  ``degraded`` replicas serve only when no ok replica exists; ``draining``
  and dead replicas never take new work. Among candidates the least
  outstanding-requests replica wins (join-shortest-queue).
- **failover, never stranding** (graftlint R8 discipline): a request whose
  replica dies mid-flight — transport error, closed server, injected
  dispatch fault — is resubmitted once per remaining live replica; only
  when every replica has been tried (or none exists) does the caller see
  :class:`~lambdagap_tpu.guard.ReplicaUnavailable`. Every future the
  router hands out therefore terminates: result, per-request error
  (shape/timeout/overload), or an explicit no-replica rejection.
- **overload spill**: a replica rejecting at admission
  (:class:`ServeOverloaded`) is treated as momentarily full, and the
  request spills to the next candidate; only an all-full fleet surfaces
  the rejection.

Request-level failures (``ServeTimeout``, shape errors, unknown model) are
NOT failed over: the request itself is at fault, and replaying it
elsewhere would double latency for a deterministic error.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence

from ..guard.degrade import (DEGRADED, DRAINING, OK, ReplicaUnavailable,
                             ServeOverloaded)
from ..guard.faults import InjectedFault
from ..obs import trace as obs_trace
from ..utils import log
from .batcher import ResolvedAtFuture

# exceptions that indict the REPLICA, not the request: these trigger
# failover to another replica (transport failures additionally mark the
# replica dead until the router is rebuilt)
FAILOVER_EXCEPTIONS = (ReplicaUnavailable, ConnectionError, OSError,
                       InjectedFault)
_DEAD_MARKING = (ReplicaUnavailable, ConnectionError, OSError)


class LocalReplica:
    """An in-process ForestServer as a routable replica."""

    def __init__(self, name: str, server) -> None:
        self.name = name
        self.server = server

    def respawn(self) -> "LocalReplica":
        """A fresh in-process server under the same name, warmed from
        the dead server's registry-retained HOST models (eviction and
        close never drop those) — the local revival primitive
        (serve/autonomics.py). Generations restart at 0 on the new
        server; model identity is the host model text, not the counter."""
        from .registry import DEFAULT_MODEL
        from .server import ForestServer
        reg = self.server.registry
        server = ForestServer(reg.entry(DEFAULT_MODEL).gbdt,
                              buckets=self.server._buckets,
                              raw_score=self.server.raw_score,
                              start_iteration=self.server._si,
                              num_iteration=self.server._ni)
        for name in reg.names():
            if name != DEFAULT_MODEL:
                server.add_model(name, reg.entry(name).gbdt)
        return LocalReplica(self.name, server)

    def submit(self, x, model: Optional[str] = None,
               tenant: Optional[str] = None, trace=None) -> Future:
        try:
            return self.server.submit(x, model=model, tenant=tenant,
                                      trace=trace)
        except RuntimeError as e:
            if "closed" in str(e):       # a closed server is a dead replica
                raise ReplicaUnavailable(
                    f"replica {self.name!r} is closed") from e
            raise

    def health(self) -> str:
        return self.server.health.state()

    def close(self) -> None:
        self.server.close()


class RemoteReplica:
    """A serve worker behind a socket frontend (serve/frontend.py) as a
    routable replica. Health is polled over the wire and cached for
    ``health_ttl_s`` so the dispatch path never blocks on a health RPC; a
    transport failure reports the replica dead immediately."""

    def __init__(self, name: str, host: str, port: int,
                 health_ttl_s: float = 0.5, connect_timeout: float = 5.0
                 ) -> None:
        from .frontend import FrontendClient
        self.name = name
        # the address survives on the replica object so a revival can
        # reconnect the SAME endpoint (serve/autonomics.py)
        self.host = host
        self.port = int(port)
        self._connect_timeout = float(connect_timeout)
        self.client = FrontendClient(host, port, timeout=connect_timeout)
        self._ttl = float(health_ttl_s)
        self._health = OK
        self._health_at = 0.0
        self._health_lock = threading.Lock()

    def reconnect(self) -> "RemoteReplica":
        """A FRESH replica object for the same name/address — the remote
        revival primitive. Raises (ConnectionError/OSError) while the
        endpoint is still down; the revival backoff absorbs that."""
        return RemoteReplica(self.name, self.host, self.port,
                             health_ttl_s=self._ttl,
                             connect_timeout=self._connect_timeout)

    def submit(self, x, model: Optional[str] = None,
               tenant: Optional[str] = None, trace=None) -> Future:
        return self.client.submit(x, model=model, tenant=tenant,
                                  trace=trace)

    def health(self) -> str:
        import time
        if not self.client.alive:
            return "dead"
        now = time.perf_counter()
        with self._health_lock:
            fresh = now - self._health_at < self._ttl
            if fresh:
                return self._health
            self._health_at = now        # one prober per TTL window
        try:
            state = self.client.health(timeout=self._ttl)
        except Exception:                # transport failed: replica is dead
            state = "dead"
        with self._health_lock:
            self._health = state
        return state

    def close(self) -> None:
        self.client.close()


class Router:
    """Health-aware dispatch + failover over a replica group.

    ``replicas`` can mix :class:`LocalReplica` and :class:`RemoteReplica`.
    ``own_replicas=True`` makes :meth:`close` close them too.
    """

    def __init__(self, replicas: Sequence, own_replicas: bool = False
                 ) -> None:
        if not replicas:
            raise ValueError("router needs at least one replica")
        names = [r.name for r in replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate replica names: {names}")
        self._replicas = list(replicas)
        self._own = bool(own_replicas)
        self._lock = threading.Lock()
        self._inflight: Dict[str, int] = {r.name: 0 for r in replicas}
        self._routed: Dict[str, int] = {r.name: 0 for r in replicas}
        self._dead: Dict[str, bool] = {r.name: False for r in replicas}
        # probation: a revived replica serves in the DEGRADED tier until
        # the autonomics controller promotes it (docs/robustness.md);
        # placement: model -> preferred replica names holding it resident
        # (serve/placement.py). Both empty unless a controller is active,
        # so knob-off router snapshots stay byte-identical to pre-PR.
        self._probation: Dict[str, bool] = {}
        self._placement: Dict[str, tuple] = {}
        self._failovers = 0
        self._rejected_no_replica = 0
        self._closed = False
        self._scraper = None             # obs.fleet.FleetScraper, attached
        self._autonomics = None          # serve.autonomics.Autonomics
        self._shadow = None              # serve.shadow.ShadowMirror, armed
        self._loop = None                # loop.controller.PromotionController

    # -- dispatch -------------------------------------------------------
    def submit(self, x, model: Optional[str] = None,
               tenant: Optional[str] = None, trace=None) -> "Future":
        """Route one request; returns a Future of ``ServeResult``. The
        future ALWAYS terminates: a dead replica's in-flight requests are
        failed over to the remaining live replicas, and only a fleet with
        no live replica rejects (:class:`ReplicaUnavailable`). A sampled
        ``trace`` context gets a ``route`` span covering pick + failover
        until the future resolves (attrs: the replica that answered, the
        failover count paid)."""
        if self._closed:
            raise RuntimeError("router closed")
        ctx = trace if trace is not None \
            else obs_trace.RECORDER.maybe_trace()
        outer: Future = Future() if ctx is None else ResolvedAtFuture()
        hop = None
        if ctx is not None:
            hop = ctx.child()            # the route span's own context
            t0_wall, t0 = time.time(), time.perf_counter()
            route_state = {"replica": None, "failovers": 0}

            def _record(f) -> None:
                obs_trace.RECORDER.record(
                    "route", ctx, t0_wall,
                    (f.t_done or time.perf_counter()) - t0,
                    span_id=hop.span_id,
                    replica=route_state["replica"],
                    failovers=route_state["failovers"])

            outer.add_done_callback(_record)
            self._attempt(outer, x, model, tenant, tried=set(),
                          trace=hop, route_state=route_state)
        else:
            self._attempt(outer, x, model, tenant, tried=set())
        # shadow mirroring rides AFTER the live dispatch is in flight and
        # owns no stake in ``outer``: a coin flip + worker handoff, so a
        # dead/slow shadow cannot move a live answer (serve/shadow.py)
        mirror = self._shadow
        if mirror is not None:
            mirror.maybe_mirror(x, model, tenant, outer, ctx)
        return outer

    def predict(self, x, timeout: Optional[float] = None,
                model: Optional[str] = None,
                tenant: Optional[str] = None):
        return self.submit(x, model=model, tenant=tenant).result(
            timeout).values

    def _pick(self, tried: set, model: Optional[str] = None):
        """Least-loaded replica among the healthiest available tier.
        Probation replicas (freshly revived) are demoted to the DEGRADED
        tier regardless of reported health; when a placement plan names
        replicas holding ``model`` resident, those are preferred within
        the winning tier — model traffic stays where the forest already
        lives, so readmission cliffs are paid by placement decisions,
        never by routing accidents."""
        with self._lock:
            candidates = [r for r in self._replicas
                          if r.name not in tried and not self._dead[r.name]]
            resident = self._placement.get(model, ()) if model else ()
            probation = dict(self._probation)
        by_state: Dict[str, List] = {}
        for r in candidates:
            try:
                state = r.health()
            except Exception:            # pragma: no cover — health probe
                state = "dead"           # died under us: skip it
            if state in (DRAINING, "dead"):
                if state == "dead":
                    self._mark_dead(r)
                continue
            if state == OK and probation.get(r.name):
                state = DEGRADED         # revived: serves, never preferred
            by_state.setdefault(state, []).append(r)
        tier = by_state.get(OK) or by_state.get(DEGRADED) or []
        if not tier:
            return None
        if resident:
            preferred = [r for r in tier if r.name in resident]
            if preferred:
                tier = preferred
        with self._lock:
            return min(tier, key=lambda r: self._inflight[r.name])

    def _attempt(self, outer: Future, x, model, tenant, tried: set,
                 trace=None, route_state: Optional[Dict] = None) -> None:
        while True:
            replica = self._pick(tried, model=model)
            if replica is None:
                with self._lock:
                    self._rejected_no_replica += 1
                outer.set_exception(ReplicaUnavailable(
                    "no live replica can take the request "
                    f"(tried: {sorted(tried) or 'none'})"))
                return
            tried.add(replica.name)
            try:
                inner = replica.submit(x, model=model, tenant=tenant,
                                       trace=trace)
            # graftlint: disable=R8 — the continue re-enters the pick
            # loop, every exit of which terminates the future: a
            # successful submit chains resolution to on_done, and an
            # exhausted fleet set_exception()s ReplicaUnavailable above
            except FAILOVER_EXCEPTIONS as e:
                self._note_failure(replica, e)
                if route_state is not None:
                    route_state["failovers"] += 1
                continue                 # submit-time failover
            # graftlint: disable=R8 — same loop contract as above: spill
            # to a peer, or the empty-pick branch resolves the future
            except ServeOverloaded:
                with self._lock:
                    self._failovers += 1
                if route_state is not None:
                    route_state["failovers"] += 1
                continue                 # overload spill: try a peer
            except Exception as e:
                outer.set_exception(e)   # request-level error: no replay
                return
            break
        with self._lock:
            self._inflight[replica.name] += 1
            self._routed[replica.name] += 1
        if route_state is not None:
            route_state["replica"] = replica.name

        def on_done(f: Future) -> None:
            with self._lock:
                self._inflight[replica.name] -= 1
            exc = f.exception()
            if exc is None:
                outer.set_result(f.result())
            elif isinstance(exc, FAILOVER_EXCEPTIONS):
                # in-flight failover: the replica died under the request
                self._note_failure(replica, exc)
                if route_state is not None:
                    route_state["failovers"] += 1
                self._attempt(outer, x, model, tenant, tried,
                              trace=trace, route_state=route_state)
            else:
                outer.set_exception(exc)

        inner.add_done_callback(on_done)

    def _mark_dead(self, replica) -> None:
        with self._lock:
            already = self._dead[replica.name]
            self._dead[replica.name] = True
        if not already:
            log.warning("router: replica %r reports dead health; removed "
                        "from dispatch", replica.name)

    def _note_failure(self, replica, exc) -> None:
        with self._lock:
            self._failovers += 1
            if isinstance(exc, _DEAD_MARKING):
                self._dead[replica.name] = True
        log.warning("router: replica %r failed (%s); failing over%s",
                    replica.name, exc,
                    " and marking it dead"
                    if isinstance(exc, _DEAD_MARKING) else "")

    # -- replica lifecycle (the autonomics actuation surface; every
    # -- method takes the lock only around pointer/metadata flips — the
    # -- reconnect/respawn/compile work happens in the CALLER, outside
    # -- any router lock, which graftlint R9 enforces) ------------------
    def add_replica(self, replica, probation: bool = False) -> None:
        """Join a new replica to the rotation (scale-out). Name must be
        fresh; ``probation=True`` starts it in the degraded tier."""
        with self._lock:
            if any(r.name == replica.name for r in self._replicas):
                raise ValueError(f"replica name {replica.name!r} is "
                                 "already registered; use replace_replica")
            self._replicas.append(replica)
            self._inflight[replica.name] = 0
            self._routed.setdefault(replica.name, 0)
            self._dead[replica.name] = False
            if probation:
                self._probation[replica.name] = True
        log.info("router: replica %r joined the rotation%s", replica.name,
                 " (probation)" if probation else "")

    def replace_replica(self, name: str, replica,
                        probation: bool = True) -> None:
        """Swap a (typically dead) replica object for a freshly
        reconnected/respawned one under the SAME name — the revival
        flip. The new replica re-enters at probation (degraded tier)
        until the controller's probe window clears it. The old replica
        object is closed best-effort outside the lock."""
        if replica.name != name:
            raise ValueError(f"replacement replica is named "
                             f"{replica.name!r}, not {name!r}")
        with self._lock:
            idx = next((i for i, r in enumerate(self._replicas)
                        if r.name == name), None)
            if idx is None:
                raise KeyError(f"unknown replica {name!r}")
            old = self._replicas[idx]
            self._replicas[idx] = replica
            self._inflight[name] = 0
            self._dead[name] = False
            if probation:
                self._probation[name] = True
        if old is not replica:
            try:
                old.close()
            except Exception as e:       # a dead replica may fail to close
                log.debug("router: closing replaced replica %r failed: %s",
                          name, e)
        log.info("router: replica %r revived and re-entered rotation%s",
                 name, " at probation (degraded tier)" if probation else "")

    def remove_replica(self, name: str, close: bool = True) -> None:
        """Retire a replica from the rotation (scale-in). The replica is
        removed from dispatch first, then — outside the router lock —
        closed, which drains its queued requests (``ForestServer.close``
        flushes before stopping; a remote close resolves its pending
        futures)."""
        with self._lock:
            idx = next((i for i, r in enumerate(self._replicas)
                        if r.name == name), None)
            if idx is None:
                raise KeyError(f"unknown replica {name!r}")
            if len(self._replicas) == 1:
                raise ValueError("cannot remove the last replica")
            replica = self._replicas.pop(idx)
            self._inflight.pop(name, None)
            self._routed.pop(name, None)
            self._dead.pop(name, None)
            self._probation.pop(name, None)
            for model, names in list(self._placement.items()):
                if name in names:
                    self._placement[model] = tuple(n for n in names
                                                   if n != name)
        if close:
            try:
                replica.close()
            except Exception as e:
                log.warning("router: closing retired replica %r failed: %s",
                            name, e)
        log.info("router: replica %r retired from the rotation", name)

    def set_probation(self, name: str, probation: bool) -> None:
        """Enter/clear the probation (degraded-tier) state of a replica."""
        with self._lock:
            if not any(r.name == name for r in self._replicas):
                raise KeyError(f"unknown replica {name!r}")
            if probation:
                self._probation[name] = True
            else:
                self._probation.pop(name, None)

    def set_placement(self, plan: Dict[str, Sequence]) -> None:
        """Install a model -> preferred-replica-names plan
        (serve/placement.py); ``{}`` clears placement-aware routing."""
        with self._lock:
            self._placement = {str(m): tuple(names)
                               for m, names in (plan or {}).items()}

    def replica_names(self, live_only: bool = True) -> List[str]:
        with self._lock:
            return [r.name for r in self._replicas
                    if not (live_only and self._dead[r.name])]

    def replica(self, name: str):
        with self._lock:
            for r in self._replicas:
                if r.name == name:
                    return r
        raise KeyError(f"unknown replica {name!r}")

    def prefetch(self, model: Optional[str] = None,
                 replica: Optional[str] = None) -> dict:
        """Make a model resident (placement actuation; the compile
        happens on the replica, no router lock held). ``replica=None``
        prefetches on EVERY live replica — the ForestServer-compatible
        shape the frontend's ``prefetch`` op uses on a router target —
        and returns per-replica info keyed by name."""
        names = [replica] if replica is not None \
            else self.replica_names(live_only=True)
        out = {}
        for name in names:
            r = self.replica(name)
            if hasattr(r, "server"):
                out[name] = r.server.prefetch(**(
                    {} if model is None else {"model": model}))
            else:
                out[name] = r.client.prefetch(model=model)
        return out[replica] if replica is not None else out

    # -- fleet-wide operations (ForestServer-compatible surface, so a
    # -- ServeFrontend can front a whole replica group) -----------------
    def swap(self, source, params=None, model: Optional[str] = None,
             background: bool = False):
        """Fleet-wide model rollout: swap on EVERY live replica, in name
        order. Returns the last replica's new generation. A replica whose
        swap fails keeps its old forest (per-replica rollback) and the
        failure propagates after the remaining replicas were still
        attempted — a partial rollout is visible, not silent."""
        last = None
        first_exc = None
        for r in sorted(self._replicas, key=lambda r: r.name):
            with self._lock:
                if self._dead[r.name]:
                    continue
            kwargs = {} if model is None else {"model": model}
            try:
                if hasattr(r, "server"):
                    last = r.server.swap(source, params=params, **kwargs)
                else:
                    last = r.client.swap(source, **kwargs)
            except Exception as e:
                if first_exc is None:
                    first_exc = e
                log.warning("router: swap on replica %r failed: %s",
                            r.name, e)
        if first_exc is not None:
            raise first_exc
        return last

    def swap_delta(self, delta, model: Optional[str] = None):
        """Fleet-wide delta swap with :meth:`swap` semantics: attempt
        every live replica in name order, per-replica rollback on
        failure, first exception propagates AFTER the rest were
        attempted (a partial rollout is visible, not silent). The
        all-or-nothing rollout protocol — roll committed replicas back —
        is ``Autonomics.rollout_delta``, which holds the base text this
        method does not."""
        last = None
        first_exc = None
        for r in sorted(self._replicas, key=lambda r: r.name):
            with self._lock:
                if self._dead[r.name]:
                    continue
            kwargs = {} if model is None else {"model": model}
            try:
                if hasattr(r, "server"):
                    last = r.server.swap_delta(delta, **kwargs)
                else:
                    last = r.client.swap_delta(delta, **kwargs)
            except Exception as e:
                if first_exc is None:
                    first_exc = e
                log.warning("router: delta swap on replica %r failed: %s",
                            r.name, e)
        if first_exc is not None:
            raise first_exc
        return last

    def push_artifact(self, payload: bytes,
                      expect_hash: Optional[str] = None,
                      replica: Optional[str] = None) -> dict:
        """Ship one compiled-forest artifact to every live replica's
        store (``replica=<name>`` targets one), so the whole fleet pays
        exactly ONE compile for a model its members later place
        (docs/serving.md "Compiled forest artifacts"). Returns the
        verified hash per replica; a replica that rejects the payload
        (``ArtifactMismatch``) reports its error string instead and will
        fall back — loudly — to a local compile, never to a wrong-model
        serve. First failure propagates AFTER every replica was
        attempted, matching the swap rollout semantics."""
        names = [replica] if replica is not None \
            else self.replica_names(live_only=True)
        out = {}
        first_exc = None
        for name in names:
            r = self.replica(name)
            try:
                if hasattr(r, "server"):
                    out[name] = r.server.admit_artifact(
                        payload, expect_hash=expect_hash)
                else:
                    out[name] = r.client.push_artifact(
                        payload, expect_hash=expect_hash)
            except Exception as e:
                if first_exc is None:
                    first_exc = e
                out[name] = f"error: {e}"
                log.warning("router: artifact push to replica %r failed: "
                            "%s", name, e)
        if first_exc is not None:
            raise first_exc
        return out

    def swap_on(self, name: str, source, model: Optional[str] = None):
        """Full swap on ONE replica (the rollback half of a delta
        rollout; serve/autonomics.py)."""
        r = self.replica(name)
        kwargs = {} if model is None else {"model": model}
        if hasattr(r, "server"):
            return r.server.swap(source, **kwargs)
        return r.client.swap(source, **kwargs)

    def swap_delta_on(self, name: str, delta,
                      model: Optional[str] = None):
        """Delta swap on ONE replica; the fleet-atomic rollout protocol
        (apply everywhere or roll back everywhere) lives in
        ``Autonomics.rollout_delta``."""
        r = self.replica(name)
        kwargs = {} if model is None else {"model": model}
        if hasattr(r, "server"):
            return r.server.swap_delta(delta, **kwargs)
        return r.client.swap_delta(delta, **kwargs)

    def models(self) -> List[str]:
        """The first live replica's registry listing (replicas of one
        fleet serve the same model set by construction)."""
        for r in self._replicas:
            with self._lock:
                if self._dead[r.name]:
                    continue
            try:
                if hasattr(r, "server"):
                    return r.server.models()
                return r.client.models()
            except Exception:            # pragma: no cover — probe only
                continue
        return []

    @property
    def health(self) -> "_FleetHealth":
        return _FleetHealth(self)

    def stats_snapshot(self, reservoirs: bool = False,
                       timeout_s: Optional[float] = None) -> dict:
        """Router snapshot + every live replica's own stats, keyed by
        replica name — the fleet-level analog of
        ``ForestServer.stats_snapshot``. ``reservoirs=True`` asks each
        replica for its raw reservoir states so the fleet plane can merge
        latency distributions, not just counters. Replica fetches happen
        OUTSIDE the router lock (a blocking stats RPC under the dispatch
        lock would convoy every request; graftlint R9 enforces this)."""
        out = {"router": self.snapshot(), "replicas": {}}
        for r in self._replicas:
            with self._lock:
                if self._dead[r.name]:
                    continue
            try:
                if hasattr(r, "server"):
                    out["replicas"][r.name] = r.server.stats_snapshot(
                        reservoirs=reservoirs)
                else:
                    out["replicas"][r.name] = r.client.stats(
                        timeout=timeout_s if timeout_s else 30.0,
                        reservoirs=reservoirs)
            except Exception as e:
                out["replicas"][r.name] = {"unreachable": str(e)}
        return out

    # -- fleet metric plane (obs/fleet.py; docs/observability.md) -------
    def fleet_snapshot(self) -> dict:
        """Scrape + merge every live replica's stats into ONE snapshot
        (counter sums exact, reservoir-merged quantiles); prefers the
        attached scraper's cached snapshot when one is running so the
        request path never waits on a scrape."""
        if self._scraper is not None:
            return self._scraper.latest()
        from ..obs import fleet
        return fleet.fleet_snapshot(self.stats_snapshot(reservoirs=True))

    def prometheus_fleet(self) -> str:
        """The ``prometheus fleet`` verb: one exposition for the whole
        fleet — merged serve metrics + fleet gauges + per-replica
        routing/health labels (docs/serving.md)."""
        from ..obs import prom
        snap = self.fleet_snapshot()
        return prom.render_fleet(snap["merged"], router=self.snapshot())

    def attach_scraper(self, scraper) -> None:
        """Adopt a running :class:`~lambdagap_tpu.obs.fleet.FleetScraper`
        (and through it, its signal plane): ``fleet_snapshot`` reads its
        cache, ``signals`` answers from its plane, ``close`` stops it."""
        self._scraper = scraper

    def attach_autonomics(self, controller) -> None:
        """Adopt a running :class:`~lambdagap_tpu.serve.autonomics.
        Autonomics` controller: ``close`` stops its loop, and the
        ``autonomics`` block joins :meth:`snapshot` (only then — with
        the knob off, snapshots stay byte-identical to pre-autonomics
        behavior)."""
        self._autonomics = controller

    def attach_loop(self, controller) -> None:
        """Adopt a running :class:`~lambdagap_tpu.loop.controller.
        PromotionController`: ``close`` stops it, :meth:`loop_status`
        answers from it, and the ``loop`` block joins :meth:`snapshot`
        (only then — same knob-off byte-identity rule as autonomics)."""
        self._loop = controller

    def arm_shadow(self, mirror) -> None:
        """Install a built :class:`~lambdagap_tpu.serve.shadow.
        ShadowMirror` (construct it — replica spawn, warmup — OUTSIDE any
        lock; this is only the pointer flip). An already-armed mirror is
        disarmed first."""
        with self._lock:
            old, self._shadow = self._shadow, mirror
        if old is not None:
            old.close()

    def disarm_shadow(self) -> Optional[dict]:
        """Stop mirroring; returns the final shadow window snapshot (or
        None when nothing was armed)."""
        with self._lock:
            mirror, self._shadow = self._shadow, None
        if mirror is None:
            return None
        final = mirror.snapshot()
        mirror.close()
        return final

    def shadow_snapshot(self) -> Optional[dict]:
        """The armed shadow window's counters/deltas, or None."""
        mirror = self._shadow
        return mirror.snapshot() if mirror is not None else None

    def shadow_on(self, source, sample: float = 1.0) -> dict:
        """Operator entry point (wire op ``shadow_on``): build a shadow
        replica from a model ``source`` (path or model text) and arm it
        at ``sample``; ``sample<=0`` disarms instead and returns the
        final window. The replica build runs before the pointer flip, so
        the reply path never waits on it."""
        if sample <= 0.0:
            final = self.disarm_shadow()
            return {"armed": False, "final": final}
        from ..loop.controller import default_make_shadow
        from .shadow import ShadowMirror
        text = source
        if isinstance(source, str) and "\n" not in source:
            with open(source, "r") as f:
                text = f.read()
        mirror = ShadowMirror(default_make_shadow(text),
                              sample=float(sample))
        self.arm_shadow(mirror)
        return {"armed": True, "sample": float(sample)}

    def loop_status(self) -> dict:
        """The promotion state machine's position (docs/continuous-
        learning.md) — ``{"state": "off"}`` when no controller is
        attached."""
        loop = self._loop
        if loop is None:
            return {"state": "off"}
        return loop.status()

    def signals(self) -> dict:
        """The current control-signal tick (obs/signals.py). Requires an
        attached scraper with a signal plane — the CLI wires one when
        ``fleet_scrape_interval_s > 0``."""
        if self._scraper is None or self._scraper.signals is None:
            raise ValueError(
                "no signal plane attached (set fleet_scrape_interval_s > 0 "
                "or Router.attach_scraper(FleetScraper(..., "
                "signals=SignalPlane())))")
        return self._scraper.signals.snapshot()

    def prometheus(self) -> str:
        from ..obs import prom
        return prom.render_router(self.snapshot())

    # -- reporting / lifecycle -----------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            replicas = {
                r.name: {
                    "inflight": self._inflight[r.name],
                    "routed": self._routed[r.name],
                    "dead": self._dead[r.name],
                } for r in self._replicas
            }
            # probation/placement/autonomics keys appear ONLY when the
            # control loop put them there: knob-off snapshots must stay
            # byte-identical to the pre-autonomics schema (acceptance
            # criterion of ISSUE 13)
            for name in self._probation:
                if name in replicas:
                    replicas[name]["probation"] = True
            out = {
                "replicas": replicas,
                "failovers": self._failovers,
                "rejected_no_replica": self._rejected_no_replica,
            }
            if self._placement:
                out["placement"] = {m: list(names)
                                    for m, names in
                                    sorted(self._placement.items())}
            autonomics = self._autonomics
            shadow = self._shadow
            loop = self._loop
        if autonomics is not None:
            out["autonomics"] = autonomics.snapshot()
        # shadow/loop keys appear ONLY while armed/attached — same
        # knob-off byte-identity contract as the autonomics block
        if shadow is not None:
            out["shadow"] = shadow.snapshot()
        if loop is not None:
            out["loop"] = loop.status()
        for r in self._replicas:         # health probes outside the lock
            try:
                replicas[r.name]["health"] = (
                    "dead" if out["replicas"][r.name]["dead"]
                    else r.health())
            except Exception:            # pragma: no cover
                replicas[r.name]["health"] = "dead"
        return out

    def close(self) -> None:
        self._closed = True
        if self._loop is not None:
            self._loop.close()
        self.disarm_shadow()
        if self._autonomics is not None:
            self._autonomics.close()
        if self._scraper is not None:
            self._scraper.close()
        if self._own:
            for r in self._replicas:
                try:
                    r.close()
                except Exception as e:   # a dead replica may fail to close
                    log.warning("router: closing replica %r failed: %s",
                                r.name, e)

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class _FleetHealth:
    """Aggregate health view over a router's replicas: ``ok`` while any
    replica is ok, ``degraded`` while only degraded replicas remain, and
    ``draining`` when nothing can take a request — the same three honest
    answers a single server gives, lifted to the fleet."""

    def __init__(self, router: Router) -> None:
        self._router = router

    def state(self) -> str:
        states = [info["health"]
                  for info in self._router.snapshot()["replicas"].values()]
        if OK in states:
            return OK
        if DEGRADED in states:
            return DEGRADED
        return DRAINING

    def snapshot(self) -> dict:
        snap = self._router.snapshot()
        return {"state": self.state(),
                "replicas": {name: info["health"]
                             for name, info in snap["replicas"].items()}}
