"""Training guardrails: device-side finiteness sentinels with a policy.

One non-finite gradient — an exploding objective, a poisoned label, an
overflowed hessian — silently corrupts every subsequent tree: scores go
NaN, splits stop firing, and the run "finishes" with a garbage model. The
guard computes a device-side sentinel (``isfinite(grad).all() &
isfinite(hess).all() & isfinite(scores).all()``) each iteration and applies
the ``guard_nonfinite`` policy:

- ``raise`` (default) — emit a diagnostic JSONL event (obs/events.py) and
  raise :class:`NonFiniteError`. Fail loudly, keep the blast radius small.
- ``skip_tree`` — drop the iteration's tree(s) and restore the exact
  pre-iteration score state (scores are immutable jax arrays, so the
  restore point is a handful of retained references — free). Training
  continues; the bad iteration simply contributes no tree.
- ``clip`` — sanitize gradients/hessians on device before the tree ever
  sees them (NaN -> 0, ±Inf -> ±``guard_clip``); no sentinel read needed.
- ``off`` — no checks, bit-for-bit the pre-guard training loop.

Sync discipline (graftlint R1): the sentinel is an async device reduction
issued with the iteration's work; its ONE host read happens at the same
once-per-iteration device-complete boundary graftscope's
``TrainTelemetry.end_iteration`` established — by then the device is idle
and the read returns a completed buffer, so the guard adds no second sync
point to the steady loop.
"""
from __future__ import annotations

import functools
import json
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..utils import log
from . import faults as faults_mod

POLICIES = ("off", "raise", "skip_tree", "clip")


class NonFiniteError(FloatingPointError):
    """Raised under ``guard_nonfinite=raise`` when grad/hess/scores go
    non-finite."""


@jax.jit
def _finite_flag(grad, hess):
    """Scalar device bool: every gradient and hessian entry is finite.
    Module-level jit: ONE executable per (shape, dtype) for the whole
    process (a fresh jit per call would recompile every iteration — R2)."""
    return jnp.all(jnp.isfinite(grad)) & jnp.all(jnp.isfinite(hess))


@functools.partial(jax.jit, static_argnames=("clip",))
def _sanitize(x, clip: float):
    """NaN -> 0, ±Inf -> ±clip, values beyond ±clip clamped."""
    x = jnp.where(jnp.isnan(x), jnp.zeros((), x.dtype), x)
    return jnp.clip(x, -clip, clip)


@jax.jit
def _combine_ok(flag, scores):
    """Fold the post-update score sentinel into the grad/hess flag."""
    return jnp.logical_and(flag, jnp.all(jnp.isfinite(scores)))


class TrainGuard:
    """Per-booster guardrail state. Inert when ``policy == 'off'``.

    Lifecycle inside ``train_one_iter`` (DART calls ``begin_iteration``
    before its dropout mutates scores; the base class call is then a
    no-op for that iteration):

    - :meth:`begin_iteration` — crash fault point + (skip_tree only)
      capture the restore point via ``gbdt._guard_state_capture()``.
    - :meth:`admit_gradients` — fault injection, clip sanitation, or the
      async sentinel launch.
    - :meth:`end_iteration` — the boundary read + policy action. Returns
      True when the iteration was skipped (state already restored).
    """

    def __init__(self, policy: str = "off", clip: float = 1e30,
                 plan: Optional[faults_mod.FaultPlan] = None) -> None:
        if policy not in POLICIES:
            log.fatal("unknown guard_nonfinite policy %r (choose from %s)",
                      policy, "/".join(POLICIES))
        self.policy = policy
        self.clip = float(clip)
        self.plan = plan if plan is not None else faults_mod.plan_for(None)
        self._flag = None
        self._restore: Optional[Dict[str, Any]] = None
        self._captured = False

    @classmethod
    def from_config(cls, config) -> "TrainGuard":
        # fallback mirrors the declared Config default (graftlint R11
        # checks the two stay in agreement)
        return cls(policy=getattr(config, "guard_nonfinite", "raise"),
                   clip=getattr(config, "guard_clip", 1e30),
                   plan=faults_mod.plan_for(config))

    @property
    def enabled(self) -> bool:
        return self.policy != "off" or self.plan.active

    # ------------------------------------------------------------------
    def begin_iteration(self, gbdt) -> None:
        if not self.enabled:
            return
        self.plan.crash_point(gbdt.iter_)
        if self.policy == "skip_tree" and not self._captured:
            self._restore = gbdt._guard_state_capture()
            self._captured = True

    def admit_gradients(self, gbdt, grad, hess):
        if not self.enabled:
            return grad, hess
        grad, hess = self.plan.corrupt_gradients(gbdt.iter_, grad, hess)
        if self.policy == "clip":
            return _sanitize(grad, self.clip), _sanitize(hess, self.clip)
        if self.policy in ("raise", "skip_tree"):
            # async device reduction; the host read waits for the
            # end-of-iteration boundary
            self._flag = _finite_flag(grad, hess)
        return grad, hess

    def end_iteration(self, gbdt) -> bool:
        """Boundary check; True when the iteration was skipped."""
        if not self.enabled:
            return False
        restore, self._restore = self._restore, None
        self._captured = False
        flag, self._flag = self._flag, None
        if self.policy not in ("raise", "skip_tree") or flag is None:
            return False
        # the once-per-iteration boundary: the device already completed the
        # iteration's work (TrainTelemetry.end_iteration blocks on the score
        # state when telemetry is on), so this is a completed-buffer fetch,
        # not a second sync point
        ok = bool(jax.device_get(_combine_ok(flag, gbdt.scores)))
        if ok:
            return False
        event = self._emit_event(gbdt)
        if self.policy == "raise":
            raise NonFiniteError(
                f"non-finite gradients/hessians/scores at iteration "
                f"{event['iter']} (guard_nonfinite=raise; see the "
                f"'guard_nonfinite' diagnostic event)")
        if restore is not None:
            gbdt._guard_state_restore(restore)
        log.warning("guard: non-finite gradients at iteration %d — tree "
                    "dropped, scores restored (guard_nonfinite=skip_tree)",
                    event["iter"])
        return True

    # ------------------------------------------------------------------
    def _emit_event(self, gbdt) -> Dict[str, Any]:
        """Diagnostic event through obs/events.py: written to the booster's
        JSONL run log when one is open, otherwise logged as a single JSON
        line (grep-able either way)."""
        from ..obs import events
        record = {"type": "event", "event": "guard_nonfinite",
                  "policy": self.policy, "iter": int(gbdt.iter_),
                  "num_trees": len(gbdt.models)}
        errs = events.validate_record(record)
        if errs:  # pragma: no cover - schema and record are both local
            log.warning("guard event failed schema validation: %s", errs)
        run_log = getattr(getattr(gbdt, "telemetry", None), "run_log", None)
        if run_log is not None:
            run_log.event("guard_nonfinite", policy=self.policy,
                          iter=int(gbdt.iter_), num_trees=len(gbdt.models))
        else:
            log.warning("guard diagnostic: %s",
                      json.dumps(record, separators=(",", ":")))
        return record


#: shared inert guard for boosters constructed without a training config
NULL_GUARD = TrainGuard(policy="off", plan=faults_mod.FaultPlan(""))


# graftir IR contracts
from ..analysis.ir.contracts import register_program

register_program("nonfinite._finite_flag", collective_free=True)
register_program("nonfinite._combine_ok", collective_free=True)
