"""Recompile & transfer watchdog over ``jax.monitoring`` events.

XLA recompiles and host<->device transfers are the two silent performance
cliffs of this codebase (graftlint R1/R2 catch them statically; this module
catches them at runtime). jax reports both through ``jax.monitoring``:
``/jax/core/compile/backend_compile_duration`` fires once per backend
compile, and transfer-instrumented builds emit ``*transfer*`` events. The
watchdog registers listeners, attributes each event to the telemetry's
current (iteration, phase) context, and — the R2 hazard class — WARNS when
a steady-state iteration (``iter >= warmup``) triggers a fresh compile:
after warmup every shape should be compiled, so a steady-state compile
means a shape-unstable program (e.g. a non-power-of-2 pad, a closed-over
mutable attribute) silently recompiling every iteration.

Nothing registers unless :meth:`install` is called (the telemetry-off path
must add zero ``jax.monitoring`` hooks), and :meth:`uninstall` removes the
listeners again.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Optional

from ..utils import log

# steady-state warnings are load-bearing but a recompile-per-iteration bug
# would otherwise spam one warning per iteration for 500 iterations
_MAX_WARNINGS = 5


def _is_compile_event(event: str) -> bool:
    # "/jax/core/compile/backend_compile_duration" (the actual backend
    # compile); trace/lowering events also live under /compile/ but only
    # backend_compile implies a fresh executable
    return "backend_compile" in event


def _is_transfer_event(event: str) -> bool:
    return "transfer" in event


# jax.monitoring kwargs keys that identify WHICH executable a compile
# event belongs to, in preference order. Current jax versions fire
# backend_compile with empty kwargs (every compile is then an anonymous
# per-phase count, as before), but fingerprint/module kwargs exist in the
# instrumented builds and newer versions — when present, the watchdog
# attributes the compile to them so `totals()["compiles_by_module"]`
# names the recompiling program instead of just its phase.
_MODULE_KWARGS = ("fingerprint", "module_name", "fun_name", "module",
                  "name")


def _module_of(kwargs: Dict) -> Optional[str]:
    for key in _MODULE_KWARGS:
        val = kwargs.get(key)
        if val:
            return str(val)
    return None


class XlaWatchdog:
    """Counts compiles/transfers per phase; warns on steady-state compiles.

    Counters are cumulative; :class:`~.telemetry.TrainTelemetry` snapshots
    them at iteration boundaries and diffs. ``phase_getter`` supplies the
    innermost active phase name (or None) for attribution; ``iteration``
    is maintained by the telemetry via :meth:`set_iteration`.
    """

    def __init__(self, warmup: int = 2,
                 phase_getter: Optional[Callable[[], Optional[str]]] = None,
                 on_steady_compile: Optional[Callable] = None) -> None:
        self.warmup = int(warmup)
        self._phase_getter = phase_getter or (lambda: None)
        self._on_steady_compile = on_steady_compile
        self._lock = threading.Lock()
        self.installed = False
        self.iteration: Optional[int] = None   # None = outside training
        self.compiles = 0
        self.steady_compiles = 0
        self.transfers = 0
        self.compiles_by_phase: Dict[str, int] = {}
        self.compiles_by_module: Dict[str, int] = {}
        self.transfers_by_phase: Dict[str, int] = {}
        self.compile_secs = 0.0
        self._warnings = 0

    # -- lifecycle ------------------------------------------------------
    def install(self) -> None:
        if self.installed:
            return
        import jax.monitoring
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        self.installed = True

    def uninstall(self) -> None:
        if not self.installed:
            return
        import jax.monitoring
        pairs = (("unregister_event_listener", "_event_listeners",
                  self._on_event),
                 ("unregister_event_duration_listener",
                  "_event_duration_secs_listeners", self._on_duration))
        for public, private, callback in pairs:
            try:
                unregister = getattr(jax.monitoring, public, None)
                if unregister is not None:
                    unregister(callback)
                else:       # older jax: no unregister API, drop it by hand
                    from jax._src import monitoring as _m
                    getattr(_m, private).remove(callback)
            except Exception:  # pragma: no cover - jax internals moved
                log.warning("could not unregister a jax.monitoring "
                            "listener; the watchdog callback stays "
                            "registered (harmless but counted across runs)")
        self.installed = False

    def set_iteration(self, iteration: Optional[int]) -> None:
        self.iteration = iteration

    # -- listeners ------------------------------------------------------
    def _on_event(self, event: str, **kwargs) -> None:
        if _is_compile_event(event):
            self._record_compile(event, 0.0, kwargs)
        elif _is_transfer_event(event):
            with self._lock:
                self.transfers += 1
                phase = self._phase_getter() or "outside"
                self.transfers_by_phase[phase] = \
                    self.transfers_by_phase.get(phase, 0) + 1

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if _is_compile_event(event):
            self._record_compile(event, float(duration), kwargs)
        elif _is_transfer_event(event):
            self._on_event(event)

    def _record_compile(self, event: str, duration: float,
                        kwargs: Optional[Dict] = None) -> None:
        module = _module_of(kwargs) if kwargs else None
        with self._lock:
            self.compiles += 1
            self.compile_secs += duration
            phase = self._phase_getter() or "outside"
            self.compiles_by_phase[phase] = \
                self.compiles_by_phase.get(phase, 0) + 1
            if module is not None:
                self.compiles_by_module[module] = \
                    self.compiles_by_module.get(module, 0) + 1
            it = self.iteration
            steady = it is not None and it >= self.warmup
            if steady:
                self.steady_compiles += 1
                warn = self._warnings < _MAX_WARNINGS
                self._warnings += 1
        if steady:
            if warn:
                log.warning(
                    "steady-state recompile at iteration %d (phase %s, "
                    "%.3fs): a fresh compile after %d warmup iterations "
                    "is either a shape-unstable program recompiling per "
                    "iteration (graftlint R2 hazard class) or a late "
                    "first-use shape (e.g. a new padding bucket); if it "
                    "repeats every iteration, it is the former",
                    it, phase, duration, self.warmup)
            if self._on_steady_compile is not None:
                self._on_steady_compile(monitor_event=event, iteration=it,
                                        phase=phase, duration=duration)

    # -- reporting ------------------------------------------------------
    def totals(self) -> Dict:
        with self._lock:
            return {
                "compiles": self.compiles,
                "steady_compiles": self.steady_compiles,
                "compile_secs": self.compile_secs,
                "transfers": self.transfers,
                "compiles_by_phase": dict(self.compiles_by_phase),
                "compiles_by_module": dict(self.compiles_by_module),
                "transfers_by_phase": dict(self.transfers_by_phase),
            }
