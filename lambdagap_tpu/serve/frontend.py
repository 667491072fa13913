"""Line-protocol socket front end: callers outside the process.

Until this PR every serve caller lived in-process. ``ServeFrontend``
binds a TCP socket (loopback by default) and speaks newline-delimited
JSON — one object per line, matching the ``task=serve`` loop verbs:

    {"op": "predict", "id": 1, "x": [[...]], "model": "m", "tenant": "t",
     "trace": {"id": "<trace_id>", "parent": "<span_id>"}}
    {"op": "swap",    "id": 2, "source": "model_v2.txt", "model": "m"}
    {"op": "swap_delta", "id": 8, "model": "m", "delta": {...}}
    {"op": "stats",   "id": 3, "reservoirs": true}
    {"op": "prometheus", "id": 5, "scope": "fleet"}
    {"op": "health",  "id": 4}            {"op": "models",  "id": 6}
    {"op": "signals", "id": 7}            {"op": "prefetch", "id": 9,
                                           "model": "m"}
    {"op": "artifact", "id": 10, "payload": "<b64>", "expect_hash": "..."}
    {"op": "artifact_get", "id": 11, "model": "m"}
    {"op": "shadow_on", "id": 12, "source": "cand.txt", "sample": 0.1}
    {"op": "loop_status", "id": 13}

The optional ``trace`` field carries the distributed-tracing context
(obs/trace.py): the server records frontend/serve/dispatch child spans
under the given parent, so one trace id connects the client's wall to
every hop inside the fleet. ``stats`` with ``reservoirs=true`` adds the
raw latency-reservoir states a fleet scraper merges; ``prometheus`` with
``scope="fleet"`` answers the fleet-merged exposition; ``signals`` is the
control-signal plane (router targets with a scraper attached).

Responses carry the request ``id`` back (predict responses may arrive out
of submit order — the id is the correlation key):

    {"id": 1, "ok": true, "values": [...], "generation": 0}
    {"id": 2, "ok": false, "error": "...", "kind": "SwapFailed"}

A malformed frame (bad JSON, unknown op, bad shapes) answers an
``ok=false`` frame with a null id and the connection SURVIVES — a
confused client must not take down its neighbors' streams. Numeric
fidelity: JSON floats carry Python's shortest-roundtrip repr, and
float32 -> float64 -> JSON -> float64 -> float32 is exact, so frontend
responses stay bit-identical to in-process serving (the parity test
asserts it).

``FrontendClient`` is the matching caller: ``submit`` returns a Future
resolved by a reader thread; when the socket dies, every pending future
resolves with :class:`~lambdagap_tpu.guard.ReplicaUnavailable` — never a
hang (R8 discipline) — which is exactly the signal
:class:`~lambdagap_tpu.serve.router.RemoteReplica` converts into
failover.
"""
from __future__ import annotations

import json
import socket
import threading
import time
from concurrent.futures import Future
from typing import Dict, Optional

import numpy as np

from ..guard.degrade import (ReplicaUnavailable, ServeOverloaded,
                             ServeTimeout, SwapFailed, SwapRejected)
from ..infer import ArtifactMismatch
from ..obs import trace as obs_trace
from ..utils import log

# wire error kinds <-> exception classes (client re-raises the real type,
# so router/loadgen accounting is identical for local and remote replicas).
# graftlint R13 enforces that every guard/degrade.py exception class has a
# row here: an unmapped class would degrade to RuntimeError client-side
# and the router's class-dispatched failover would silently stop matching
# it (ReplicaUnavailable was exactly that gap — a replica fronting an
# all-dead fleet answered RuntimeError instead of the failover trigger)
_KINDS = {
    "ReplicaUnavailable": ReplicaUnavailable,
    "ServeOverloaded": ServeOverloaded,
    "ServeTimeout": ServeTimeout,
    "SwapFailed": SwapFailed,
    "SwapRejected": SwapRejected,
    "ArtifactMismatch": ArtifactMismatch,
    "ValueError": ValueError,
    "KeyError": KeyError,
}


def _error_frame(req_id, exc) -> dict:
    kind = type(exc).__name__
    return {"id": req_id, "ok": False, "error": str(exc),
            "kind": kind if kind in _KINDS else "RuntimeError"}


class _Conn:
    """One accepted client connection: a reader loop + a serialized
    writer. Predict responses are written from batcher worker threads
    (future callbacks), so the send side takes a per-connection mutex."""

    def __init__(self, sock: socket.socket, frontend: "ServeFrontend"
                 ) -> None:
        self.sock = sock
        self.frontend = frontend
        self._tx = threading.Lock()
        self._open = True

    def send(self, frame: dict) -> None:
        data = (json.dumps(frame) + "\n").encode()
        try:
            with self._tx:
                if self._open:
                    # graftlint: disable=R9 — deliberate: frames must not
                    # interleave, so mutual exclusion must span the whole
                    # write; frames are small, the socket is loopback-class,
                    # and the only contenders are this conn's reply callbacks.
                    # (R9 resolves _tx to a real threading.Lock identity that
                    # R5's name heuristic never sees — the old disable=R5
                    # here was inert, the R14 dead-suppression class)
                    self.sock.sendall(data)
        except OSError:
            # client went away mid-response; its futures already resolved
            # server-side, nothing to strand
            self._open = False

    def run(self) -> None:
        f = self.sock.makefile("rb")
        try:
            for raw in f:
                raw = raw.strip()
                if not raw:
                    continue
                self.handle(raw)
        except OSError as e:
            log.debug("frontend: connection reset (%s) — normal teardown", e)
        finally:
            self._open = False
            try:
                self.sock.close()
            except OSError:
                log.debug("frontend: close raced the peer reset")
            self.frontend._forget(self)

    def handle(self, raw: bytes) -> None:
        # frame receipt time, BEFORE the decode: the frontend span of a
        # traced predict starts here, so decode cost is inside it
        self._t_in_wall = time.time()
        self._t_in = time.perf_counter()
        try:
            frame = json.loads(raw.decode())
            if not isinstance(frame, dict):
                raise ValueError("frame must be a JSON object")
        except (ValueError, UnicodeDecodeError) as e:
            self.send({"id": None, "ok": False,
                       "error": f"malformed frame: {e}",
                       "kind": "ValueError"})
            return
        req_id = frame.get("id")
        op = frame.get("op")
        try:
            handler = getattr(self, f"_op_{op}", None) if op else None
            if handler is None or not isinstance(op, str) \
                    or op.startswith("_"):
                raise ValueError(f"unknown op {op!r}")
            handler(req_id, frame)
        except Exception as e:           # op-level failure: answer, survive
            self.send(_error_frame(req_id, e))

    # -- ops ------------------------------------------------------------
    def _op_predict(self, req_id, frame) -> None:
        # wire trace context (docs/serving.md): {"trace": {"id", "parent"}}
        # — malformed values fall back to untraced, never to an error
        ctx = obs_trace.TraceContext.from_wire(frame.get("trace"))
        hop = ctx.child() if ctx is not None else None
        t_in_wall, t_in = self._t_in_wall, self._t_in
        x = np.asarray(frame["x"], dtype=np.float32)
        fut = self.frontend.target.submit(x, model=frame.get("model"),
                                          tenant=frame.get("tenant"),
                                          trace=hop)

        def reply(f: Future) -> None:
            exc = f.exception()
            if exc is not None:
                self.send(_error_frame(req_id, exc))
                if hop is not None:
                    obs_trace.RECORDER.record(
                        "frontend", ctx, t_in_wall,
                        time.perf_counter() - t_in,
                        span_id=hop.span_id, error=type(exc).__name__)
                return
            res = f.result()
            if hop is None:
                self.send({"id": req_id, "ok": True,
                           "values": np.asarray(res.values).tolist(),
                           "generation": int(res.generation)})
                return
            with obs_trace.RECORDER.span("encode", hop):
                self.send({"id": req_id, "ok": True,
                           "values": np.asarray(res.values).tolist(),
                           "generation": int(res.generation)})
            # the frontend span closes only after the reply hit the
            # socket: decode + serve + encode tile it (span tree
            # discipline, obs/trace.validate_tree)
            obs_trace.RECORDER.record(
                "frontend", ctx, t_in_wall, time.perf_counter() - t_in,
                span_id=hop.span_id)

        fut.add_done_callback(reply)

    def _op_swap(self, req_id, frame) -> None:
        kwargs = {}
        if frame.get("model") is not None:
            kwargs["model"] = frame["model"]
        gen = self.frontend.target.swap(frame["source"], **kwargs)
        self.send({"id": req_id, "ok": True, "generation": int(gen)})

    def _op_swap_delta(self, req_id, frame) -> None:
        # appended-trees rollout frame (serve/delta.py); a non-applying
        # delta answers SwapFailed and the old generation keeps serving
        kwargs = {}
        if frame.get("model") is not None:
            kwargs["model"] = frame["model"]
        gen = self.frontend.target.swap_delta(frame["delta"], **kwargs)
        self.send({"id": req_id, "ok": True, "generation": int(gen)})

    def _op_prefetch(self, req_id, frame) -> None:
        # placement actuation: make the model resident off the request
        # path (pays any readmission compile HERE, not on a request)
        kwargs = {}
        if frame.get("model") is not None:
            kwargs["model"] = frame["model"]
        info = self.frontend.target.prefetch(**kwargs)
        self.send({"id": req_id, "ok": True, "info": info})

    def _op_artifact(self, req_id, frame) -> None:
        # compiled-forest artifact admission (docs/serving.md "Compiled
        # forest artifacts"): the payload is the base64 of
        # ForestArtifact.to_bytes(); the content hash is verified before
        # the store mutates, so a torn/tampered frame answers
        # ArtifactMismatch and the replica compiles locally instead —
        # loudly, never serving a wrong model
        import base64
        payload = base64.b64decode(frame["payload"])
        h = self.frontend.target.admit_artifact(
            payload, expect_hash=frame.get("expect_hash"))
        self.send({"id": req_id, "ok": True, "hash": h})

    def _op_artifact_get(self, req_id, frame) -> None:
        # the publisher side: serialize a model's compiled artifact so a
        # peer (or an operator) can ship it to the rest of the fleet
        import base64
        kwargs = {}
        if frame.get("model") is not None:
            kwargs["model"] = frame["model"]
        payload = self.frontend.target.artifact_bytes(**kwargs)
        self.send({"id": req_id, "ok": True,
                   "payload": base64.b64encode(payload).decode()})

    def _op_stats(self, req_id, frame) -> None:
        # reservoirs=true adds the raw reservoir states a fleet scraper
        # merges (bounded; obs/fleet.py)
        self.send({"id": req_id, "ok": True,
                   "stats": self.frontend.target.stats_snapshot(
                       reservoirs=bool(frame.get("reservoirs")))})

    def _op_prometheus(self, req_id, frame) -> None:
        target = self.frontend.target
        if frame.get("scope") == "fleet":
            text = target.prometheus_fleet()
        else:
            text = target.prometheus()
        self.send({"id": req_id, "ok": True, "text": text})

    def _op_signals(self, req_id, frame) -> None:
        self.send({"id": req_id, "ok": True,
                   "signals": self.frontend.target.signals()})

    def _op_health(self, req_id, frame) -> None:
        health = self.frontend.target.health
        self.send({"id": req_id, "ok": True, "state": health.state(),
                   "snapshot": health.snapshot()})

    def _op_models(self, req_id, frame) -> None:
        self.send({"id": req_id, "ok": True,
                   "models": self.frontend.target.models()})

    def _op_shadow_on(self, req_id, frame) -> None:
        # arm (or with sample<=0 disarm) shadow mirroring on the fronted
        # router (docs/continuous-learning.md). Strictly off the reply
        # path: live answers are bit-identical with shadow armed.
        info = self.frontend.target.shadow_on(
            frame.get("source"), sample=float(frame.get("sample", 1.0)))
        self.send({"id": req_id, "ok": True, "shadow": info})

    def _op_loop_status(self, req_id, frame) -> None:
        # promotion state machine position (loop/controller.py): state,
        # candidate/promoted epochs, counters, live shadow window
        self.send({"id": req_id, "ok": True,
                   "status": self.frontend.target.loop_status()})


class ServeFrontend:
    """TCP front end for one serve target (a ForestServer — or anything
    with the same submit/swap/stats/health surface). ``port=0`` binds an
    ephemeral port, exposed as :attr:`port` after :meth:`start`."""

    def __init__(self, target, host: str = "127.0.0.1", port: int = 0,
                 backlog: int = 64, bind_retry_s: float = 5.0) -> None:
        self.target = target
        self.host = host
        self._port = int(port)
        self._backlog = int(backlog)
        self._bind_retry_s = max(float(bind_retry_s), 0.0)
        self._sock: Optional[socket.socket] = None
        self._conns: set = set()
        self._conn_lock = threading.Lock()
        self._accept_thread: Optional[threading.Thread] = None
        self._closed = False

    @property
    def port(self) -> int:
        return self._port

    def start(self) -> "ServeFrontend":
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        # SO_REUSEADDR + a bounded EADDRINUSE retry window: a revived
        # replica re-binding its OLD fixed port must win against the dead
        # process's lingering socket (TIME_WAIT, or a SIGKILLed peer the
        # kernel has not fully reaped) instead of failing the respawn —
        # the rapid kill/revive cycle the autonomics controller drives
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        deadline = time.perf_counter() + self._bind_retry_s
        while True:
            try:
                sock.bind((self.host, self._port))
                break
            except OSError as e:
                import errno
                if (e.errno != errno.EADDRINUSE or self._port == 0
                        or time.perf_counter() >= deadline):
                    raise
                time.sleep(0.05)
        sock.listen(self._backlog)
        self._port = sock.getsockname()[1]
        self._sock = sock
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True,
            name=f"lambdagap-serve-frontend-{self._port}")
        self._accept_thread.start()
        log.info("serve frontend listening on %s:%d (newline-JSON "
                 "protocol; ops: predict/swap/swap_delta/prefetch/"
                 "artifact/artifact_get/stats/prometheus/signals/health/"
                 "models/shadow_on/loop_status)", self.host, self._port)
        return self

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                client, addr = self._sock.accept()
            except OSError:
                break                    # listener closed
            client.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conn = _Conn(client, self)
            with self._conn_lock:
                # close() takes its snapshot of _conns under this lock
                # after setting _closed: a connection accepted while it
                # ran is either in the snapshot or refused here, never
                # left open behind a closed door
                if self._closed:
                    client.close()
                    break
                self._conns.add(conn)
            threading.Thread(target=conn.run, daemon=True,
                             name=f"lambdagap-serve-conn-{addr[1]}").start()

    def _forget(self, conn: _Conn) -> None:
        with self._conn_lock:
            self._conns.discard(conn)

    def close(self) -> None:
        """Stop accepting and drop connections. The target server is NOT
        closed — the frontend is a door, not the house."""
        with self._conn_lock:
            self._closed = True
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                log.debug("frontend: listener close raced")
        with self._conn_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                log.debug("frontend: conn shutdown raced")
        if self._accept_thread is not None:
            self._accept_thread.join(5.0)

    def __enter__(self) -> "ServeFrontend":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()


class FrontendClient:
    """Async client for :class:`ServeFrontend`: one socket, one reader
    thread, futures correlated by request id."""

    def __init__(self, host: str, port: int, timeout: float = 5.0) -> None:
        from .server import ServeResult
        self._result_type = ServeResult
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.settimeout(None)       # reader blocks; writes are sendall
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._tx = threading.Lock()
        self._pending_lock = threading.Lock()
        self._pending: Dict[int, Future] = {}
        self._next_id = 0
        self.alive = True
        self._reader = threading.Thread(
            target=self._read_loop, daemon=True,
            name=f"lambdagap-serve-client-{port}")
        self._reader.start()

    # ------------------------------------------------------------------
    def _send(self, frame: dict) -> Future:
        fut: Future = Future()
        with self._pending_lock:
            if not self.alive:
                raise ReplicaUnavailable("frontend connection is closed")
            self._next_id += 1
            frame["id"] = self._next_id
            self._pending[self._next_id] = fut
        data = (json.dumps(frame) + "\n").encode()
        try:
            with self._tx:
                # graftlint: disable=R9 — deliberate, mirror of
                # _Conn.send: whole-frame writes must not interleave, and
                # the submit path is the only contender on this mutex
                # (R9 sees the _tx lock identity; R5's name heuristic never
                # does, so the old disable=R5 here was inert — R14 class)
                self.sock.sendall(data)
        except OSError as e:
            self._die(e)
            raise ReplicaUnavailable(
                f"frontend connection died mid-send: {e}") from e
        return fut

    def _read_loop(self) -> None:
        f = self.sock.makefile("rb")
        err: Exception = ReplicaUnavailable("frontend connection closed")
        try:
            for raw in f:
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    frame = json.loads(raw.decode())
                except ValueError:
                    log.warning("frontend client: undecodable frame %r",
                                raw[:80])
                    continue
                self._resolve(frame)
        except OSError as e:
            err = ReplicaUnavailable(f"frontend connection died: {e}")
        self._die(err)

    def _resolve(self, frame: dict) -> None:
        with self._pending_lock:
            fut = self._pending.pop(frame.get("id"), None)
        if fut is None:
            return                       # stats pushed for a forgotten id
        if frame.get("ok"):
            if "values" in frame:
                fut.set_result(self._result_type(
                    np.asarray(frame["values"], dtype=np.float32),
                    int(frame.get("generation", -1))))
            else:
                fut.set_result(frame)
        else:
            exc_type = _KINDS.get(frame.get("kind"), RuntimeError)
            fut.set_exception(exc_type(frame.get("error", "remote error")))

    def _die(self, exc: Exception) -> None:
        """Terminal: resolve EVERY pending future with the transport
        error so no caller hangs on a dead socket."""
        with self._pending_lock:
            if not self.alive:
                return
            self.alive = False
            pending = list(self._pending.values())
            self._pending.clear()
        for fut in pending:
            if not fut.done():
                fut.set_exception(exc)
        try:
            self.sock.close()
        except OSError:
            log.debug("frontend client: close raced the reset")

    # -- API ------------------------------------------------------------
    def submit(self, x, model: Optional[str] = None,
               tenant: Optional[str] = None, trace=None) -> Future:
        """Async predict over the wire. ``trace`` is an incoming
        :class:`~lambdagap_tpu.obs.trace.TraceContext`; with none given,
        one is minted per the process ``serve_trace_sample`` knob — the
        client is where a fleet trace is born. The sampled context rides
        the frame's ``trace`` field and a ``client_request`` span records
        the full client-observed wall (submit -> future resolution), the
        root the server-side spans must tile."""
        x = np.asarray(x, dtype=np.float32)
        if x.ndim == 1:
            x = x[None, :]
        frame = {"op": "predict", "x": x.tolist()}
        if model is not None:
            frame["model"] = model
        if tenant is not None:
            frame["tenant"] = tenant
        ctx = trace if trace is not None \
            else obs_trace.RECORDER.maybe_trace()
        if ctx is None:
            return self._send(frame)
        if trace is None:                # minted here: this IS the root
            span, parent = ctx, ""
        else:
            span, parent = ctx.child(), None
        frame["trace"] = span.to_wire()
        t0_wall, t0 = time.time(), time.perf_counter()
        fut = self._send(frame)

        def _record(_f) -> None:
            obs_trace.RECORDER.record(
                "client_request", ctx, t0_wall,
                time.perf_counter() - t0, span_id=span.span_id,
                parent=parent)

        fut.add_done_callback(_record)
        return fut

    def predict(self, x, timeout: Optional[float] = None,
                model: Optional[str] = None,
                tenant: Optional[str] = None) -> np.ndarray:
        return self.submit(x, model=model, tenant=tenant).result(
            timeout).values

    def _call(self, op: str, timeout: Optional[float] = 30.0, **kw) -> dict:
        frame = {"op": op}
        frame.update({k: v for k, v in kw.items() if v is not None})
        return self._send(frame).result(timeout)

    def swap(self, source, model: Optional[str] = None,
             timeout: Optional[float] = 120.0) -> int:
        return int(self._call("swap", timeout=timeout, source=source,
                              model=model)["generation"])

    def swap_delta(self, delta, model: Optional[str] = None,
                   timeout: Optional[float] = 120.0) -> int:
        """Delta hot-swap over the wire: only the appended trees (plus
        header/tail) cross the socket (serve/delta.py)."""
        return int(self._call("swap_delta", timeout=timeout, delta=delta,
                              model=model)["generation"])

    def prefetch(self, model: Optional[str] = None,
                 timeout: Optional[float] = 120.0) -> dict:
        """Make a registry model resident on the remote replica now
        (placement actuation; pays any readmission off the request
        path)."""
        return self._call("prefetch", timeout=timeout, model=model)["info"]

    def push_artifact(self, payload: bytes,
                      expect_hash: Optional[str] = None,
                      timeout: Optional[float] = 120.0) -> str:
        """Ship a serialized compiled-forest artifact to the remote
        replica's store; its next matching build skips the compile
        (the fleet-wide one-compile contract). Returns the verified
        hash; a corrupt payload raises ``ArtifactMismatch``."""
        import base64
        return self._call("artifact", timeout=timeout,
                          payload=base64.b64encode(payload).decode(),
                          expect_hash=expect_hash)["hash"]

    def fetch_artifact(self, model: Optional[str] = None,
                       timeout: Optional[float] = 120.0) -> bytes:
        """The publisher side: the remote replica's serialized compiled
        artifact for ``model`` (requires predict_engine=compiled)."""
        import base64
        return base64.b64decode(
            self._call("artifact_get", timeout=timeout,
                       model=model)["payload"])

    def stats(self, timeout: Optional[float] = 30.0,
              reservoirs: bool = False) -> dict:
        return self._call("stats", timeout=timeout,
                          reservoirs=True if reservoirs else None)["stats"]

    def prometheus(self, timeout: Optional[float] = 30.0,
                   scope: Optional[str] = None) -> str:
        return self._call("prometheus", timeout=timeout,
                          scope=scope)["text"]

    def signals(self, timeout: Optional[float] = 30.0) -> dict:
        """The router-side control-signal tick (requires the remote
        frontend to front a router with a signal plane attached)."""
        return self._call("signals", timeout=timeout)["signals"]

    def health(self, timeout: Optional[float] = 30.0) -> str:
        return self._call("health", timeout=timeout)["state"]

    def models(self, timeout: Optional[float] = 30.0) -> list:
        return self._call("models", timeout=timeout)["models"]

    def shadow_on(self, source, sample: float = 1.0,
                  timeout: Optional[float] = 120.0) -> dict:
        """Arm shadow mirroring of a candidate model on the remote
        router (``sample<=0`` disarms and returns the final window).
        Shadow traffic never touches live answers — see
        docs/continuous-learning.md."""
        return self._call("shadow_on", timeout=timeout, source=source,
                          sample=sample)["shadow"]

    def loop_status(self, timeout: Optional[float] = 30.0) -> dict:
        """Where the continuous-learning state machine is: state,
        candidate/promoted epochs, counters, live shadow window."""
        return self._call("loop_status", timeout=timeout)["status"]

    def close(self) -> None:
        self._die(ReplicaUnavailable("frontend client closed"))

    def __enter__(self) -> "FrontendClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
