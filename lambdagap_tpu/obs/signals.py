"""Derived control signals: the signal plane fleet autonomics consume.

Two cliffs of a served fleet — the open-loop goodput knee and the cost of
readmitting an evicted model — have to be *continuously computed online*
before a control loop (revival, placement, autoscaling) can act on them;
neither has been measured on a chip. This module turns the fleet metric
plane's scrape stream (obs/fleet.py) into three documented signals:

``goodput`` — an online knee estimator. Each scrape yields an interval
    offered rate (Δ accepted+shed requests / Δt) and a deadline-met
    fraction (1 − Δ(timeouts+rejected+errors)/Δoffered — the server-side
    proxy for loadgen's goodput ratio; requests the server itself shed or
    failed are by definition not good). Both are EWMA-smoothed; the knee
    is the highest smoothed offered rate recently sustained at
    ``good_ratio`` goodput, decayed toward the current rate so a stale
    peak cannot hide saturation. ``knee_margin`` = (knee − offered)/knee:
    positive = headroom, near 0 = at the knee, negative = past it — the
    autoscaler's scale-out trigger.

``residency`` — per-model placement pressure from the registry counters:
    resident-replica counts, readmission and eviction rates over the
    scrape interval, ``eviction_pressure`` (evictions/s per resident
    model — how hard the HBM budget is churning), and the measured
    ``readmit_cost_ms`` (p50 of ``registry_get`` spans that paid a
    readmission, straight from the trace recorder's aggregates) — the
    input the placement loop bin-packs against.

``health`` — a bounded per-replica health timeline ring
    (:class:`HealthTimeline`): state transitions with timestamps, the
    revival loop's evidence of who died when and whether a degraded
    replica is recovering or flapping.

Every signal tick is a ``signals`` record (obs/events.py schema), so the
flight recorder and run logs carry them, and :func:`validate_signals`
checks the documented schema (docs/observability.md "Signal plane").
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

SIGNALS_VERSION = 1


class KneeEstimator:
    """Online goodput-knee estimation over the scrape stream (EWMA of
    deadline-met fraction vs offered rate over a sliding window)."""

    def __init__(self, alpha: float = 0.3, good_ratio: float = 0.9,
                 knee_decay: float = 0.02) -> None:
        self.alpha = float(alpha)
        self.good_ratio = float(good_ratio)
        self.knee_decay = float(knee_decay)
        self.offered_rps = 0.0           # EWMA
        self.good_fraction = 1.0         # EWMA
        self.knee_rps = 0.0
        self.ticks = 0

    def observe(self, offered_rps: float, good_fraction: float) -> None:
        a = self.alpha if self.ticks else 1.0
        self.offered_rps += a * (offered_rps - self.offered_rps)
        self.good_fraction += a * (good_fraction - self.good_fraction)
        self.ticks += 1
        if self.good_fraction >= self.good_ratio:
            # sustained-at-goodput rate raises the knee immediately...
            self.knee_rps = max(self.knee_rps, self.offered_rps)
        # ...and the knee decays toward the current offered rate, so a
        # long-gone traffic peak stops vouching for capacity it no longer
        # demonstrates (a knee is evidence, not a constant)
        self.knee_rps += self.knee_decay * (self.offered_rps
                                            - self.knee_rps)

    @property
    def knee_margin(self) -> float:
        """(knee − offered)/knee in [−inf, 1]; 0 when no knee is known
        yet (no headroom has been demonstrated)."""
        if self.knee_rps <= 0:
            return 0.0
        return (self.knee_rps - self.offered_rps) / self.knee_rps

    def snapshot(self) -> Dict[str, float]:
        return {
            "offered_rps": round(self.offered_rps, 3),
            "good_fraction": round(self.good_fraction, 6),
            "knee_rps": round(self.knee_rps, 3),
            "knee_margin": round(self.knee_margin, 6),
            "good_ratio": self.good_ratio,
            "ticks": self.ticks,
        }


class HealthTimeline:
    """Bounded per-replica health history: one ring of (t, replica,
    state) transitions — repeated identical states collapse, so the ring
    holds N state CHANGES, not N scrapes."""

    def __init__(self, ring: int = 256) -> None:
        self._ring: "deque" = deque(maxlen=max(int(ring), 8))
        self._last: Dict[str, str] = {}
        self._lock = threading.Lock()

    def note(self, replica: str, state: str,
             t: Optional[float] = None) -> bool:
        """Record a state observation; returns True on a TRANSITION."""
        with self._lock:
            if self._last.get(replica) == state:
                return False
            self._last[replica] = state
            self._ring.append({"t": round(t if t is not None
                                          else time.time(), 3),
                               "replica": replica, "state": state})
            return True

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"current": dict(self._last),
                    "transitions": list(self._ring)}


class SignalPlane:
    """Fold successive fleet snapshots into the signal set. One instance
    per control point (typically the router process); ``update`` is called
    by the fleet scraper per scrape, ``snapshot`` by the autonomics loop
    (and the frontend's ``signals`` verb)."""

    def __init__(self, alpha: float = 0.3, good_ratio: float = 0.9,
                 health_ring: int = 256, recorder=None) -> None:
        self.knee = KneeEstimator(alpha=alpha, good_ratio=good_ratio)
        self.health = HealthTimeline(ring=health_ring)
        self._recorder = recorder
        self._lock = threading.Lock()
        self._prev: Optional[Dict] = None
        self._latest: Optional[Dict] = None
        self._shadow: Optional[Dict] = None
        self.ticks = 0

    def note_shadow(self, shadow: Optional[Dict]) -> None:
        """The promotion controller's shadow-delta window joins the signal
        stream: subsequent ticks carry it as the OPTIONAL ``shadow`` block
        (absent unless a shadow is armed — the schema stays backward-
        compatible). Pass None to clear it."""
        with self._lock:
            self._shadow = dict(shadow) if shadow is not None else None

    # -- folding ---------------------------------------------------------
    @staticmethod
    def _offered_count(merged: Dict) -> int:
        # offered = everything that knocked: served + shed + rejected
        return (merged.get("requests", 0) + merged.get("timeouts", 0)
                + merged.get("rejected", 0))

    def update(self, fleet_snap: Dict) -> Dict:
        """One scrape tick -> the current signals dict (also cached for
        :meth:`snapshot` and recorded as a ``signals`` event)."""
        merged = fleet_snap.get("merged") or {}
        now = fleet_snap.get("time_unix") or time.time()
        with self._lock:
            prev = self._prev
            self._prev = {"t": now,
                          "offered": self._offered_count(merged),
                          "bad": (merged.get("timeouts", 0)
                                  + merged.get("rejected", 0)
                                  + merged.get("errors", 0)),
                          "evictions": merged.get("evictions", 0),
                          "readmissions": merged.get("readmissions", 0)}
        interval: Dict[str, float] = {"dt_s": 0.0, "offered_rps": 0.0,
                                      "good_fraction": 1.0}
        if prev is not None and now > prev["t"]:
            dt = now - prev["t"]
            d_off = max(self._prev["offered"] - prev["offered"], 0)
            d_bad = max(self._prev["bad"] - prev["bad"], 0)
            interval["dt_s"] = round(dt, 3)
            interval["offered_rps"] = round(d_off / dt, 3)
            interval["good_fraction"] = round(
                1.0 - d_bad / d_off, 6) if d_off else 1.0
            self.knee.observe(interval["offered_rps"],
                              interval["good_fraction"])
        residency = self._residency(merged, prev)
        for name, state in (fleet_snap.get("router", {})
                            .get("replicas") or {}).items():
            if isinstance(state, dict):
                self.health.note(name, state.get("health", "unknown"), now)
        signals = {
            "type": "signals",
            "signals_version": SIGNALS_VERSION,
            "time_unix": now,
            "interval": interval,
            "goodput": self.knee.snapshot(),
            "residency": residency,
            "health": self.health.snapshot(),
        }
        with self._lock:
            if self._shadow is not None:
                signals["shadow"] = self._shadow
            self._latest = signals
            self.ticks += 1
        if self._recorder is not None:
            # the signal tick rides the flight-recorder ring (bounded), so
            # a postmortem sees the signals the autonomics were acting on
            self._recorder.event("signals_tick",
                                 goodput=signals["goodput"],
                                 interval=interval)
        return signals

    def _residency(self, merged: Dict, prev: Optional[Dict]
                   ) -> Dict[str, Any]:
        registry = merged.get("registry") or {}
        models = registry.get("models") or {}
        dt = ((self._prev["t"] - prev["t"])
              if prev is not None and self._prev["t"] > prev["t"] else 0.0)
        evict_rate = ((self._prev["evictions"] - prev["evictions"]) / dt
                      if prev is not None and dt > 0 else 0.0)
        readmit_rate = ((self._prev["readmissions"]
                         - prev["readmissions"]) / dt
                        if prev is not None and dt > 0 else 0.0)
        resident = registry.get("resident_models", 0)
        readmit_cost_ms = 0.0
        if self._recorder is not None:
            agg = self._recorder.aggregates().get("registry_readmit")
            if agg and agg.get("count"):
                readmit_cost_ms = round(agg["p50"] * 1e3, 3)
        return {
            "registered_models": registry.get("registered_models", 0),
            "resident_models": resident,
            "hbm_bytes_resident": registry.get("hbm_bytes_resident", 0),
            "hbm_budget_bytes": registry.get("hbm_budget_bytes", 0),
            "eviction_rate_per_s": round(max(evict_rate, 0.0), 4),
            "readmission_rate_per_s": round(max(readmit_rate, 0.0), 4),
            "eviction_pressure": round(max(evict_rate, 0.0)
                                       / max(resident, 1), 6),
            "readmit_cost_ms": readmit_cost_ms,
            "per_model": {
                name: {
                    "resident_replicas": m.get("resident_replicas",
                                               1 if m.get("resident")
                                               else 0),
                    "replicas": m.get("replicas", 1),
                    "builds": m.get("builds", 0),
                    "hbm_bytes": m.get("hbm_bytes", 0),
                } for name, m in sorted(models.items())
            },
        }

    def snapshot(self) -> Dict:
        """The latest signals tick (empty-but-valid before the first)."""
        with self._lock:
            if self._latest is not None:
                return self._latest
        return {
            "type": "signals", "signals_version": SIGNALS_VERSION,
            "time_unix": time.time(),
            "interval": {"dt_s": 0.0, "offered_rps": 0.0,
                         "good_fraction": 1.0},
            "goodput": self.knee.snapshot(),
            "residency": {"registered_models": 0, "resident_models": 0,
                          "hbm_bytes_resident": 0, "hbm_budget_bytes": 0,
                          "eviction_rate_per_s": 0.0,
                          "readmission_rate_per_s": 0.0,
                          "eviction_pressure": 0.0,
                          "readmit_cost_ms": 0.0, "per_model": {}},
            "health": self.health.snapshot(),
        }


def validate_signals(obj: Any) -> List[str]:
    """Schema check for one signals tick (docs/observability.md table);
    empty list = valid. This is the contract the autonomics loop codes
    against, so it is enforced by tests, not prose."""
    errs: List[str] = []
    if not isinstance(obj, dict):
        return [f"signals is {type(obj).__name__}, not an object"]
    if obj.get("type") != "signals":
        errs.append(f"type {obj.get('type')!r} != 'signals'")
    if obj.get("signals_version") != SIGNALS_VERSION:
        errs.append(f"signals_version {obj.get('signals_version')!r} "
                    f"!= {SIGNALS_VERSION}")
    if not isinstance(obj.get("time_unix"), (int, float)):
        errs.append("missing time_unix")
    good = obj.get("goodput")
    if not isinstance(good, dict):
        errs.append("missing goodput block")
    else:
        for key in ("offered_rps", "good_fraction", "knee_rps",
                    "knee_margin"):
            if not isinstance(good.get(key), (int, float)):
                errs.append(f"goodput.{key} missing or non-numeric")
        if isinstance(good.get("knee_margin"), (int, float)) \
                and good["knee_margin"] > 1.0 + 1e-9:
            errs.append(f"goodput.knee_margin {good['knee_margin']} > 1")
    res = obj.get("residency")
    if not isinstance(res, dict):
        errs.append("missing residency block")
    else:
        for key in ("resident_models", "eviction_pressure",
                    "readmit_cost_ms", "per_model"):
            if key not in res:
                errs.append(f"residency.{key} missing")
    health = obj.get("health")
    if not isinstance(health, dict):
        errs.append("missing health block")
    elif not isinstance(health.get("transitions"), list) \
            or not isinstance(health.get("current"), dict):
        errs.append("health block needs 'current' map + 'transitions' "
                    "list")
    shadow = obj.get("shadow")
    if shadow is not None:                # OPTIONAL: only while armed
        if not isinstance(shadow, dict):
            errs.append("shadow block must be an object")
        else:
            for key in ("sample", "dead", "mirrored", "compared", "shed"):
                if key not in shadow:
                    errs.append(f"shadow.{key} missing")
    return errs
