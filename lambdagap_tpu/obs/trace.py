"""Distributed request tracing + the serve flight recorder (graftscope v2).

PR 4 gave ONE process phase-accurate telemetry; a fleet request crosses
frontend -> router -> replica -> batcher -> registry -> device and until
this module left no connected record. A **trace** is the connected record:
a ``trace_id`` minted where the request enters the system (the TCP
frontend client, the router, or ``ForestServer.submit`` itself), carried
in the newline-JSON wire frames and the in-process
:class:`~lambdagap_tpu.serve.batcher.Request`, with one **span** recorded
at every hop:

========================  ====================================================
span name                 hop
========================  ====================================================
``client_request``        root: submit -> future resolution, client process
``route``                 router pick + failover window (attrs: replica,
                          failovers)
``frontend``              server-side frame decode -> reply written
``encode``                response serialization + socket write
``serve_request``         ``ForestServer.submit`` -> future resolution
``queue_wait``            batcher FairQueue wait (submit -> dispatch start)
``registry_get``          registry resolve; ``readmitted=True`` + the
                          compile seconds when the 174x readmission cliff
                          was paid BY THIS REQUEST
``dispatch``              padded device dispatch (attrs: rows, batch_rows)
========================  ====================================================

Spans are wall-aligned across processes: ``t0`` is ``time.time()`` (same
host => same epoch), durations are ``perf_counter`` deltas. A parent-linked
span tree therefore TILES the client-observed latency — the PR 4
span-sum≈wall discipline applied across processes — and
:func:`validate_tree` checks exactly that (containment + coverage within a
tolerance).

Records are the versioned JSONL schema of :mod:`lambdagap_tpu.obs.events`
(record type ``span``), so ``events.validate_file`` covers trace logs, and
the recorder keeps a bounded ring of recent spans/events per process — the
**flight recorder** — dumped atomically (guard's pid-tmp+fsync+rename
discipline) on uncaught exception / SIGTERM / a bounded interval, so even
a SIGKILLed replica leaves a valid recent-history file for
``tools/postmortem.py``.

Hot-path discipline (graftlint R1 guards this file): span enter/exit is
pure host bookkeeping — no jax import, no device sync, ever. Disabled
tracing (``serve_trace_sample=0`` and no explicit context) records
NOTHING: the request path pays one ``is None`` test per hop.
"""
from __future__ import annotations

import json
import os
import random
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..utils import log
from .events import run_header
from .reservoir import Reservoir


def new_id(rng: Optional[random.Random] = None) -> str:
    """16-hex span/trace id; ``os.urandom`` so forked replicas never
    collide (a seeded rng is for tests only)."""
    if rng is not None:
        return f"{rng.getrandbits(64):016x}"
    return os.urandom(8).hex()


class TraceContext:
    """One node of a trace: the ids a child span needs. ``span_id`` is the
    id the NEXT hop should use as its parent. Immutable and tiny — it
    rides ``Request`` slots and wire frames (``{"id": trace_id,
    "parent": span_id}``)."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str,
                 sampled: bool = True) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = bool(sampled)

    def child(self) -> "TraceContext":
        """A fresh context for a child span (new span id, same trace)."""
        return TraceContext(self.trace_id, new_id(), self.sampled)

    def to_wire(self) -> Dict[str, str]:
        return {"id": self.trace_id, "parent": self.span_id}

    @classmethod
    def from_wire(cls, obj: Any) -> Optional["TraceContext"]:
        """Parse the optional ``trace`` field of a wire frame; hostile or
        malformed values yield None (an untraced request, never an
        error — tracing must not take down serving)."""
        if not isinstance(obj, dict):
            return None
        tid, parent = obj.get("id"), obj.get("parent")
        if not (isinstance(tid, str) and isinstance(parent, str)
                and tid and parent):
            return None
        return cls(tid, parent, sampled=True)


class SpanRecorder:
    """Per-process span/event sink: a bounded ring (the flight-recorder
    buffer), optional JSONL output with bounded-interval flushing, and
    per-name duration reservoirs (the aggregate the signal plane
    reads). Thread-safe; records are plain
    dicts in the :mod:`.events` schema."""

    def __init__(self, ring: int = 4096, out: str = "",
                 proc: str = "", flush_every: int = 1,
                 flush_interval_s: float = 0.25) -> None:
        self._lock = threading.Lock()
        self.ring: "deque[Dict]" = deque(maxlen=max(int(ring), 16))
        self.proc = proc or f"pid:{os.getpid()}"
        self.sample = 0.0
        self._rng = random.Random(os.getpid() ^ int(time.time() * 1e3))
        self.n_spans = 0
        self.n_events = 0
        self._agg: Dict[str, Reservoir] = {}
        self._f = None
        self._out_path = ""
        self._flush_every = max(int(flush_every), 1)
        self._flush_interval = float(flush_interval_s)
        self._unflushed = 0
        self._last_flush = time.perf_counter()
        if out:
            self.open_out(out)

    # -- configuration --------------------------------------------------
    def configure(self, sample: Optional[float] = None,
                  out: Optional[str] = None, ring: Optional[int] = None,
                  proc: Optional[str] = None) -> "SpanRecorder":
        with self._lock:
            if sample is not None:
                self.sample = min(max(float(sample), 0.0), 1.0)
            if proc:
                self.proc = proc
            if ring is not None and ring != self.ring.maxlen:
                self.ring = deque(self.ring, maxlen=max(int(ring), 16))
        if out is not None and out != self._out_path:
            self.open_out(out)
        return self

    def open_out(self, path: str) -> None:
        """Attach a JSONL sink; leads with a run_header so
        ``events.validate_file`` accepts the file as-is."""
        with self._lock:
            if self._f is not None:
                self._f.close()
            self._f = open(path, "w", encoding="utf-8") if path else None
            self._out_path = path
            if self._f is not None:
                hdr = run_header({"proc": self.proc, "kind": "trace"})
                self._f.write(json.dumps(hdr, separators=(",", ":"),
                                         default=str) + "\n")
                self._f.flush()

    def maybe_trace(self) -> Optional[TraceContext]:
        """Mint a new sampled root context, or None (the common case):
        one random draw against ``serve_trace_sample``."""
        if self.sample <= 0.0:
            return None
        with self._lock:
            if self.sample < 1.0 and self._rng.random() >= self.sample:
                return None
            rid = f"{self._rng.getrandbits(64):016x}"
            sid = f"{self._rng.getrandbits(64):016x}"
        return TraceContext(rid, sid, sampled=True)

    # -- recording ------------------------------------------------------
    def record(self, name: str, ctx: Optional[TraceContext],
               t0: float, dur_s: float,
               span_id: Optional[str] = None,
               parent: Optional[str] = None,
               **attrs: Any) -> Optional[str]:
        """One finished span. ``ctx`` carries trace id + default parent;
        None is a no-op (the untraced fast path). ``t0`` is epoch seconds
        (``time.time()``), ``dur_s`` a perf_counter delta. Returns the
        span id (for callers that parented children before the parent
        closed)."""
        if ctx is None or not ctx.sampled:
            return None
        sid = span_id or new_id()
        rec: Dict[str, Any] = {
            "type": "span", "trace": ctx.trace_id, "span": sid,
            "parent": ctx.span_id if parent is None else (parent or None),
            "name": name, "proc": self.proc,
            "t0": round(float(t0), 6), "dur": round(max(float(dur_s), 0.0), 9),
        }
        if attrs:
            rec["attrs"] = attrs
        self._append(rec, is_span=True, name=name, dur=rec["dur"])
        return sid

    def span(self, name: str, ctx: Optional[TraceContext],
             **attrs: Any) -> "_LiveSpan":
        """Context manager recording ``name`` around a code block; yields
        a child :class:`TraceContext` (``.ctx``) for nested hops. No-op
        when ``ctx`` is None."""
        return _LiveSpan(self, name, ctx, attrs)

    def event(self, event: str, **fields: Any) -> None:
        """A punctual event into the flight-recorder ring (and the JSONL
        sink when attached): faults, health flips, scrape errors."""
        rec = {"type": "event", "event": event, "proc": self.proc,
               "time_unix": time.time(), **fields}
        self._append(rec, is_span=False)

    def _append(self, rec: Dict, is_span: bool, name: str = "",
                dur: float = 0.0) -> None:
        line = None
        with self._lock:
            self.ring.append(rec)
            if is_span:
                self.n_spans += 1
                agg = self._agg.get(name)
                if agg is None:
                    agg = self._agg[name] = Reservoir(cap=4096,
                                                      seed=len(self._agg))
                agg.add(dur)
            else:
                self.n_events += 1
            if self._f is not None:
                line = json.dumps(rec, separators=(",", ":"), default=str)
                self._f.write(line + "\n")
                self._unflushed += 1
                now = time.perf_counter()
                if (self._unflushed >= self._flush_every
                        or now - self._last_flush >= self._flush_interval):
                    # bounded-interval durability: a SIGKILLed process
                    # loses at most flush_every records / flush_interval
                    # seconds (events.validate_file tolerates the torn
                    # final line)
                    self._f.flush()
                    self._unflushed = 0
                    self._last_flush = now

    # -- reading --------------------------------------------------------
    def tail(self, n: int = 0) -> List[Dict]:
        with self._lock:
            recs = list(self.ring)
        return recs[-n:] if n else recs

    def spans(self, trace_id: Optional[str] = None) -> List[Dict]:
        return [r for r in self.tail() if r.get("type") == "span"
                and (trace_id is None or r.get("trace") == trace_id)]

    def aggregates(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name duration percentiles (seconds) + counts — the
        signal plane's readmission-cost input and the bench's breakdown."""
        with self._lock:
            names = list(self._agg.items())
        out = {}
        for name, res in names:
            p = res.percentiles()
            p["count"] = res.seen
            out[name] = p
        return out

    def reset(self) -> None:
        with self._lock:
            self.ring.clear()
            self._agg.clear()
            self.n_spans = 0
            self.n_events = 0

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None
                self._out_path = ""


class _LiveSpan:
    """One open span; ``.ctx`` is the child context nested hops parent
    to. Reused as the no-op for untraced requests (ctx None)."""

    __slots__ = ("_rec", "_name", "_parent", "_attrs", "ctx", "_t0", "_tp")

    def __init__(self, rec: SpanRecorder, name: str,
                 parent: Optional[TraceContext], attrs: Dict) -> None:
        self._rec = rec
        self._name = name
        self._parent = parent
        self._attrs = attrs
        self.ctx = parent.child() if parent is not None else None

    def __enter__(self) -> "_LiveSpan":
        self._t0 = time.time()
        self._tp = time.perf_counter()
        return self

    def __exit__(self, etype, evalue, tb) -> bool:
        if self._parent is not None:
            if etype is not None:
                self._attrs = dict(self._attrs, error=etype.__name__)
            self._rec.record(self._name, self._parent, self._t0,
                             time.perf_counter() - self._tp,
                             span_id=self.ctx.span_id, **self._attrs)
        return False


#: the process-wide recorder every serve component records into; tests and
#: benches may swap in their own via the ``recorder=`` hooks, but one
#: process = one flight-recorder ring is the designed shape
RECORDER = SpanRecorder()


def configure(sample: Optional[float] = None, out: Optional[str] = None,
              ring: Optional[int] = None, proc: Optional[str] = None
              ) -> SpanRecorder:
    """Configure the process recorder from the ``serve_trace_*`` knobs."""
    return RECORDER.configure(sample=sample, out=out, ring=ring, proc=proc)


def start_trace() -> TraceContext:
    """An explicitly sampled root context (gates/tests/benches; the knob
    path goes through :meth:`SpanRecorder.maybe_trace`)."""
    return TraceContext(new_id(), new_id(), sampled=True)


# ---------------------------------------------------------------------------
# span-tree assembly + the cross-process tiling check
# ---------------------------------------------------------------------------
def build_tree(records: List[Dict], trace_id: Optional[str] = None
               ) -> Tuple[List[Dict], Dict[str, Dict]]:
    """(roots, by_span_id) from span records (one trace or all). Children
    are attached under ``"children"``, sorted by t0."""
    spans = [dict(r) for r in records if r.get("type") == "span"
             and (trace_id is None or r.get("trace") == trace_id)]
    by_id = {s["span"]: s for s in spans}
    roots = []
    for s in spans:
        s.setdefault("children", [])
    for s in spans:
        parent = by_id.get(s.get("parent") or "")
        if parent is None:
            roots.append(s)
        else:
            parent["children"].append(s)
    for s in spans:
        s["children"].sort(key=lambda c: c["t0"])
    roots.sort(key=lambda s: s["t0"])
    return roots, by_id


def _union_seconds(intervals: List[Tuple[float, float]]) -> float:
    total = 0.0
    last_end = None
    for lo, hi in sorted(intervals):
        if last_end is None or lo > last_end:
            total += hi - lo
            last_end = hi
        elif hi > last_end:
            total += hi - last_end
            last_end = hi
    return total


def validate_tree(records: List[Dict], trace_id: str,
                  tolerance: float = 0.25,
                  min_cover: float = 0.5) -> List[str]:
    """The cross-process tiling discipline, checked. Errors (empty list =
    valid):

    - exactly one root; every other span's parent EXISTS in the set
      (parent-linked, no orphans);
    - every span's interval is contained in its parent's, with slack
      ``tolerance * root_dur`` (cross-process clocks share an epoch but
      not a quartz crystal);
    - the union of the root's descendants covers >= ``min_cover`` of the
      root duration, and no level's child-sum exceeds ``(1 + tolerance)``
      x the parent — spans must TILE the client-observed wall, not
      overlap-double-count it.
    """
    roots, by_id = build_tree(records, trace_id)
    errs: List[str] = []
    if not by_id:
        return [f"trace {trace_id}: no spans recorded"]
    if len(roots) != 1:
        names = [r["name"] for r in roots]
        return [f"trace {trace_id}: expected exactly one root span, got "
                f"{len(roots)} ({names}) — a span references a parent "
                "that was never recorded"]
    root = roots[0]
    slack = max(tolerance * root["dur"], 2e-3)
    for s in by_id.values():
        parent = by_id.get(s.get("parent") or "")
        if parent is None:
            continue
        if s["t0"] < parent["t0"] - slack \
                or s["t0"] + s["dur"] > parent["t0"] + parent["dur"] + slack:
            errs.append(
                f"span {s['name']} [{s['t0']:.6f}+{s['dur']:.6f}s] escapes "
                f"parent {parent['name']} "
                f"[{parent['t0']:.6f}+{parent['dur']:.6f}s] beyond "
                f"{slack * 1e3:.1f}ms slack")
    for s in by_id.values():
        kids = s.get("children") or []
        if not kids:
            continue
        child_sum = sum(c["dur"] for c in kids)
        if child_sum > s["dur"] * (1.0 + tolerance) + slack:
            errs.append(
                f"children of {s['name']} sum to {child_sum * 1e3:.2f}ms > "
                f"parent {s['dur'] * 1e3:.2f}ms + tolerance — spans "
                "double-count instead of tiling")
    def _descend(s):
        for c in s.get("children") or []:
            yield (c["t0"], c["t0"] + c["dur"])
            yield from _descend(c)
    covered = _union_seconds(
        [(max(lo, root["t0"]), min(hi, root["t0"] + root["dur"]))
         for lo, hi in _descend(root)
         if min(hi, root["t0"] + root["dur"]) > max(lo, root["t0"])])
    if root["dur"] > 0 and covered < min_cover * root["dur"]:
        errs.append(
            f"descendants cover {covered * 1e3:.2f}ms of the "
            f"{root['dur'] * 1e3:.2f}ms root ({covered / root['dur']:.0%}) "
            f"< {min_cover:.0%} — the trace does not tile the "
            "client-observed latency")
    return errs


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class FlightRecorder:
    """Bounded recent-history dump for serve processes.

    Holds no data of its own — it snapshots :class:`SpanRecorder`'s ring
    (spans AND events) and writes a self-contained JSONL file (run_header
    first, guard's pid-tmp+fsync+rename atomic write) so the file on disk
    is ALWAYS a complete, schema-valid dump:

    - on uncaught exception (``sys.excepthook`` chained, never replaced),
    - on SIGTERM (chained; best-effort — only installable from the main
      thread),
    - every ``interval_s`` seconds from a daemon thread — the SIGKILL
      story: a hard-killed replica leaves its last periodic dump intact
      (atomic replace means a kill mid-dump preserves the previous one).
    """

    def __init__(self, path: str, recorder: Optional[SpanRecorder] = None,
                 interval_s: float = 0.0,
                 params: Optional[Dict[str, Any]] = None) -> None:
        self.path = path
        self.recorder = recorder if recorder is not None else RECORDER
        self.interval_s = max(float(interval_s), 0.0)
        self.params = dict(params or {})
        self.dumps = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._prev_excepthook: Optional[Callable] = None
        self._prev_sigterm = None
        self._installed = False

    def dump(self, reason: str = "manual") -> str:
        """Write the ring to ``self.path`` atomically; returns the path."""
        from ..guard.snapshot import atomic_write_text
        hdr = run_header({**self.params, "proc": self.recorder.proc,
                          "kind": "flight", "reason": reason})
        recs = self.recorder.tail()
        lines = [json.dumps(hdr, separators=(",", ":"), default=str)]
        lines += [json.dumps(r, separators=(",", ":"), default=str)
                  for r in recs]
        atomic_write_text(self.path, "\n".join(lines) + "\n")
        self.dumps += 1
        return self.path

    # -- hooks ----------------------------------------------------------
    def install(self) -> "FlightRecorder":
        import sys as _sys
        if self._installed:
            return self
        self._installed = True
        self._prev_excepthook = _sys.excepthook

        def _hook(etype, evalue, tb):
            try:
                self.recorder.event("uncaught_exception",
                                    exc=f"{etype.__name__}: {evalue}")
                self.dump(reason="uncaught_exception")
            except Exception:            # the dump must never mask the crash
                log.warning("flight recorder: dump on crash failed")
            self._prev_excepthook(etype, evalue, tb)

        _sys.excepthook = _hook
        try:
            import signal as _signal
            self._prev_sigterm = _signal.getsignal(_signal.SIGTERM)

            def _on_term(signum, frame):
                try:
                    self.recorder.event("sigterm")
                    self.dump(reason="sigterm")
                except Exception:
                    log.warning("flight recorder: dump on SIGTERM failed")
                prev = self._prev_sigterm
                if callable(prev):
                    prev(signum, frame)

            _signal.signal(_signal.SIGTERM, _on_term)
        except (ValueError, OSError):    # not the main thread
            log.debug("flight recorder: SIGTERM hook unavailable off the "
                      "main thread; periodic + excepthook dumps only")
        if self.interval_s > 0:
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name="lambdagap-flight-recorder")
            self._thread.start()
        log.info("flight recorder armed: ring=%d -> %s (interval %.1fs)",
                 self.recorder.ring.maxlen, self.path, self.interval_s)
        return self

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.dump(reason="interval")
            except Exception as e:       # pragma: no cover - disk full etc.
                log.warning("flight recorder: periodic dump failed: %s", e)

    def close(self, final_dump: bool = True) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(5.0)
        if final_dump:
            try:
                self.dump(reason="close")
            except Exception as e:       # pragma: no cover
                log.warning("flight recorder: final dump failed: %s", e)
