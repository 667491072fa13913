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

Each backend compile is also sorted by what the persistent compile cache did
for it: jax fires ``/jax/compilation_cache/compile_requests_use_cache`` when
the request asks the cache, then ``/cache_hits`` and the duration
``/cache_retrieval_time_sec`` when the cache served it, all on the compiling
thread and inside the backend-compile duration that wraps them
(``jax/_src/compiler.py:compile_or_get_cached``). A compile the cache served
is ``loaded`` (its seconds are the retrieval); any other is ``fresh`` (a
miss, or a request that did not use the cache). The two sum to the
compile count and ``compile_secs``, overall and per program (``fun_name``).

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


# the persistent cache's events (plain events, then a duration on a hit)
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def _add_program(by_program: Dict[str, Dict], name: str,
                 duration: float) -> None:
    entry = by_program.setdefault(name, {"n": 0, "secs": 0.0})
    entry["n"] += 1
    entry["secs"] += duration


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
        self.transfers_by_phase: Dict[str, int] = {}
        self.compile_secs = 0.0
        # each compile is fresh or loaded (module docstring)
        self.fresh = self.loaded = 0
        self.fresh_secs = self.load_secs = 0.0
        self.fresh_by_program: Dict[str, Dict] = {}
        self.loaded_by_program: Dict[str, Dict] = {}
        # whether the cache served the compile in flight on this thread
        self._pending = threading.local()
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
        if event == _CACHE_REQUEST:
            self._pending.hit = False
        elif event == _CACHE_HIT:
            self._pending.hit = True
        elif _is_compile_event(event):
            self._record_compile(event, 0.0, kwargs)
        elif _is_transfer_event(event):
            with self._lock:
                self.transfers += 1
                phase = self._phase_getter() or "outside"
                self.transfers_by_phase[phase] = \
                    self.transfers_by_phase.get(phase, 0) + 1

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == _CACHE_RETRIEVAL:
            self._pending.hit = True
        elif _is_compile_event(event):
            self._record_compile(event, float(duration), kwargs)
        elif _is_transfer_event(event):
            self._on_event(event)

    def _record_compile(self, event: str, duration: float,
                        kwargs: Optional[Dict] = None) -> None:
        program = str((kwargs or {}).get("fun_name") or "unnamed")
        loaded = getattr(self._pending, "hit", False)
        self._pending.hit = False
        with self._lock:
            self.compiles += 1
            self.compile_secs += duration
            if loaded:
                self.loaded += 1
                self.load_secs += duration
                _add_program(self.loaded_by_program, program, duration)
            else:
                self.fresh += 1
                self.fresh_secs += duration
                _add_program(self.fresh_by_program, program, duration)
            phase = self._phase_getter() or "outside"
            self.compiles_by_phase[phase] = \
                self.compiles_by_phase.get(phase, 0) + 1
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
                "transfers_by_phase": dict(self.transfers_by_phase),
                "fresh": self.fresh, "fresh_secs": self.fresh_secs,
                "loaded": self.loaded, "load_secs": self.load_secs,
                "fresh_by_program": {k: dict(v) for k, v in
                                     self.fresh_by_program.items()},
                "loaded_by_program": {k: dict(v) for k, v in
                                      self.loaded_by_program.items()},
            }
