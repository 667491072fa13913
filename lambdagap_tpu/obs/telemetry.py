"""TrainTelemetry: per-iteration phase spans for the boosting loop.

The training analog of serve's ``ServeStats``: every boosting iteration
produces one record with named phase spans (gradients, sampling, tree,
histogram, split, partition, score_update, eval, device_wait), kept in a
bounded ring buffer and aggregated into totals + an iteration-wall
reservoir. The GPU GBDT
literature (arXiv:1806.11248, arXiv:2005.09148) attributes its wins with
exactly this phase-level breakdown; here it is a first-class subsystem so
every perf PR ships its own evidence.

Timing discipline (the part that keeps this graftlint-R1 clean):

- Phase spans are host wall-clock between dispatches — they never force
  the device. Under async dispatch a span measures the time to *issue* its
  work plus any sync its phase already contains.
- Device-complete time is taken ONCE per iteration, at the boundary: a
  single ``jax.block_until_ready`` on the score state inside
  :meth:`end_iteration`, recorded as the ``device_wait`` phase. Phases +
  device_wait therefore tile the iteration wall (tests assert ±10%).
- Spans NEST with exclusive accounting: a learner-internal ``histogram``
  span carves its time out of the enclosing ``tree`` span, so the per-phase
  map sums to the wall without double counting.

The iteration record is emitted (ring + JSONL) when the NEXT iteration
begins or at :meth:`close`, which lets late phases (the engine's ``eval``)
attach to the iteration that produced them.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..utils import log
from .events import RunLog
from .profile import ProfileWindow
from .reservoir import Reservoir
from .xla_watch import XlaWatchdog

# canonical phase names (docs/observability.md); "tree" holds whatever the
# learner does not attribute to a finer phase (the fused learner's whole
# on-device program lands here — its internal structure shows up in
# profiler windows via jax.named_scope, not host spans). "layout_apply"
# is the tree_layout=sorted reorder pre-pass (the per-tree leaf-ordered
# rebuild of the packed row matrix); the in-program per-split
# permutation-apply rides the tree span like the rest of the fused program
# "h2d_prefetch" / "chunk_wait" are the data_residency=stream ring phases
# (data/stream.py ShardRing): prefetch is the host-side window fetch +
# async device_put issue, chunk_wait is the ring-slot completion block —
# together they tile the streaming overhead into the iteration wall, so
# overlap efficiency (chunk_wait ~ 0) is a measured number. "d2h_scores"
# is the predict_stream score-ring counterpart (infer/stream.py
# ScoreRing): the async copy_to_host_async issue plus the residual block
# when the result is consumed — the D2H half of the batch-scoring
# overlap story, measured the same way
PHASES = ("gradients", "sampling", "layout_apply", "histogram", "split",
          "partition", "tree", "score_update", "eval", "device_wait",
          "h2d_prefetch", "chunk_wait", "d2h_scores")

# the closed vocabulary of ``jax.named_scope`` names on the DEVICE side: every
# op of the fused tree program, the sorted-layout pre-pass, the gradient
# program and the score update is traced under exactly one innermost
# ("leaf") scope of this list (tests/test_scopes.py lowers the programs and
# checks it), so a profiler window tiles device time by these names the way
# PHASES tile the host's wall. ``partition_decide`` / ``partition_scatter``
# open INSIDE ``partition`` (the while body), and the mesh learners'
# collectives open ``hist_allreduce`` inside whatever scope calls them: a
# selector on a path component reads the outer scope with what it holds.
# docs/observability.md has the table of what goes under each.
DEVICE_SCOPES = ("histogram", "partition", "partition_decide",
                 "partition_scatter", "partition_copyback", "split_scan",
                 "tree_init", "leaf_select", "split_state", "hist_subtract",
                 "hist_allreduce", "row_leaf", "layout_apply", "gradients",
                 "score_update")

# inner scopes of the ranking objectives' gradient program, opened INSIDE
# ``gradients`` (a tuple of their own: DEVICE_SCOPES tiles the programs, these
# tile one of its scopes): the bucket gather of scores and labels, the
# per-query sort, the pair lattice with its reductions and normalisation,
# the write back to document order. Every op of that program carries
# ``gradients`` and at most one of these (tests/test_scopes.py).
GRADIENT_SCOPES = ("rank_gather", "rank_sort", "rank_lattice", "rank_scatter")

# inner scopes of the tree program under ``tree_layout=gather`` (a tuple of
# their own, like GRADIENT_SCOPES): ``pack_rows`` is the per-tree repack of
# the binned rows with their gradients (``FusedTreeLearner._pack_rows``),
# opened INSIDE ``tree_init``; ``hist_gather`` is a histogram window's row
# gather from that matrix, opened INSIDE ``histogram``. A selector on the
# outer scope reads them with the rest of it; the sorted layout opens
# neither.
TREE_INNER_SCOPES = ("pack_rows", "hist_gather")

# scopes of the evaluation programs, which run only with a validation set
# (or a training metric) attached: ``valid_score`` is the routing of a set's
# binned rows through the iteration's tree and the add into its scores
# (``models.gbdt._valid_tree_score``), ``valid_metric`` a metric computed on
# device-resident scores (``metrics.rank._ndcg_at``). A tuple of their own:
# DEVICE_SCOPES tiles the training programs and a benchmark file holds a
# copy of it. Every op of the two programs carries exactly one of these.
EVAL_SCOPES = ("valid_score", "valid_metric")

# names on the profiler's clock: one ITER_ANNOTATION per boosting iteration
# (begin_iteration .. end_iteration, stat ``iter``) and one
# PHASE_ANNOTATION + <phase> per span, device_wait included
ITER_ANNOTATION = "lg_iter"
PHASE_ANNOTATION = "lg_phase:"


def device_scope(name: str):
    """``jax.named_scope(name)`` for a name of DEVICE_SCOPES,
    GRADIENT_SCOPES, TREE_INNER_SCOPES or EVAL_SCOPES (and only those: the
    vocabulary is closed, a typo fails at trace time)."""
    if name not in (DEVICE_SCOPES + GRADIENT_SCOPES + TREE_INNER_SCOPES
                    + EVAL_SCOPES):
        raise ValueError(f"{name!r} is not in obs.telemetry.DEVICE_SCOPES, "
                         "GRADIENT_SCOPES, TREE_INNER_SCOPES or EVAL_SCOPES")
    import jax
    return jax.named_scope(name)


class _NullSpan:
    """Reusable no-op context manager for the disabled path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _rounded(by_program: Dict[str, Dict]) -> Dict[str, Dict]:
    return {k: {"n": v["n"], "secs": round(v["secs"], 6)}
            for k, v in sorted(by_program.items())}


class _Span:
    """One live phase span; see TrainTelemetry.phase."""
    __slots__ = ("tel", "name", "ann")

    def __init__(self, tel: "TrainTelemetry", name: str) -> None:
        self.tel = tel
        self.name = name
        self.ann = tel._annotate_phase(name)

    def __enter__(self):
        # the same span on the profiler's clock (a no-op outside a trace)
        self.ann.__enter__()
        # stack frame: [name, t_enter, child_inclusive_acc]
        self.tel._stack.append([self.name, time.perf_counter(), 0.0])
        return self

    def __exit__(self, *exc):
        tel = self.tel
        name, t0, child = tel._stack.pop()
        dt = time.perf_counter() - t0
        self.ann.__exit__(*exc)
        tel._add_phase(name, dt - child)
        if tel._stack:
            tel._stack[-1][2] += dt
        return False


class TrainTelemetry:
    """Per-iteration training telemetry (phase spans, ring buffer, JSONL,
    recompile watchdog, profiler windows).

    Created by ``GBDT._setup_training`` via :meth:`from_config`; reachable
    as ``booster._booster.telemetry`` and on ``CallbackEnv.telemetry``.
    All methods are no-ops when ``enabled`` is False — the off path holds
    no buffers, writes no files and registers no ``jax.monitoring`` hooks.
    """

    def __init__(self, enabled: bool = False, out: str = "",
                 ring: int = 256, warmup: int = 2,
                 profile: Optional[ProfileWindow] = None,
                 params: Optional[Dict[str, Any]] = None) -> None:
        self.enabled = bool(enabled)
        self.records: "deque[Dict]" = deque(maxlen=max(int(ring), 1))
        self.iterations = 0
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self._stack: List[list] = []
        self._cur: Optional[Dict] = None
        self._t0 = 0.0
        self._train_done = False
        self._closed = False
        self.run_log: Optional[RunLog] = None
        self.watchdog: Optional[XlaWatchdog] = None
        self.profile = profile
        if not self.enabled:
            return
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self._iter_ann = None
        # (device arrays, reducer) pairs read after end_iteration's block
        self._deferred: List[tuple] = []
        self.work_totals: Dict[str, int] = {}
        self.wall_res = Reservoir(cap=4096, seed=5)
        if out:
            self.run_log = RunLog(out, params=params)
        self.watchdog = XlaWatchdog(
            warmup=warmup, phase_getter=self.current_phase,
            on_steady_compile=self._on_steady_compile)
        self.watchdog.install()
        self._watch_base = self.watchdog.totals()

    # ------------------------------------------------------------------
    @classmethod
    def from_config(cls, config, params: Optional[Dict[str, Any]] = None
                    ) -> "TrainTelemetry":
        """Build from the ``telemetry*`` / ``profile_*`` config knobs.
        ``telemetry_out`` or a configured profiler window implies
        ``telemetry=true``."""
        out = getattr(config, "telemetry_out", "") or ""
        profile = ProfileWindow(
            start_iter=getattr(config, "profile_start_iter", -1),
            n_iters=getattr(config, "profile_n_iters", 1),
            out_dir=getattr(config, "profile_dir", "") or "")
        enabled = (bool(getattr(config, "telemetry", False)) or bool(out)
                   or profile.enabled)
        return cls(enabled=enabled, out=out,
                   ring=getattr(config, "telemetry_ring", 256),
                   warmup=getattr(config, "telemetry_warmup", 2),
                   profile=profile if profile.enabled else None,
                   params=params if params is not None
                   else getattr(config, "to_dict", dict)())

    # -- span / iteration API -------------------------------------------
    def current_phase(self) -> Optional[str]:
        return self._stack[-1][0] if self._stack else None

    def phase(self, name: str):
        """Context manager timing one named phase (nested spans use
        exclusive accounting). Cheap no-op when disabled or when no
        iteration record is open."""
        if not self.enabled or self._cur is None:
            return _NULL_SPAN
        return _Span(self, name)

    def _annotate_phase(self, name: str):
        return self._annotation(PHASE_ANNOTATION + name,
                                iter=self._cur["iter"])

    def defer_counts(self, arrays: Any, reducer) -> None:
        """Work counts of the open iteration, without a sync: ``arrays``
        (device arrays the iteration's programs returned) are fetched after
        :meth:`end_iteration`'s one ``block_until_ready`` and
        ``reducer(host_arrays) -> {name: int}`` is summed into the
        record's ``counts``. Nothing is kept when telemetry is off."""
        if not self.enabled or self._cur is None:
            return
        self._deferred.append((arrays, reducer))

    def add_counts(self, counts: Dict[str, int]) -> None:
        """Host-known counts summed into the open record's ``counts`` at
        once: for what happens after :meth:`end_iteration` has read the
        deferred ones (the engine's ``eval`` phase)."""
        if not self.enabled or self._cur is None:
            return
        rec = self._cur.setdefault("counts", {})
        for k, v in counts.items():
            rec[k] = rec.get(k, 0) + int(v)
            self.work_totals[k] = self.work_totals.get(k, 0) + int(v)

    def begin_iteration(self, iteration: int) -> None:
        """Open the record for ``iteration`` (finalizing the previous
        one). Called at the top of ``GBDT.train_one_iter``."""
        if not self.enabled or self._closed:
            return
        self._finalize()
        if self.profile is not None:
            toggled = self.profile.on_iteration_start(iteration)
            if toggled and self.run_log is not None:
                self.run_log.event(f"profile_{toggled}",
                                   iter=int(iteration),
                                   dir=self.profile.out_dir)
        self.watchdog.set_iteration(iteration)
        self._watch_base = self.watchdog.totals()
        self._cur = {"type": "iteration", "iter": int(iteration),
                     "phases": {}}
        self._train_done = False
        self._iter_ann = self._annotation(ITER_ANNOTATION,
                                          iter=int(iteration))
        self._iter_ann.__enter__()
        self._t0 = time.perf_counter()

    def end_iteration(self, sync: Any = None) -> None:
        """Close the iteration's device-complete train window: ONE
        ``block_until_ready`` on ``sync`` (the score state), recorded as
        the ``device_wait`` phase; stamps ``wall_s``. The record stays open
        for late phases (eval) until the next :meth:`begin_iteration` or
        :meth:`close`, which stamps its compile/transfer deltas."""
        if not self.enabled or self._cur is None or self._train_done:
            return
        import jax
        t = time.perf_counter()
        if sync is not None:
            with self._annotate_phase("device_wait"):
                try:
                    jax.block_until_ready(sync)
                except Exception:  # pragma: no cover - deleted buffers etc.
                    pass
        now = time.perf_counter()
        self._add_phase("device_wait", now - t)
        self._cur["wall_s"] = now - self._t0
        self._close_iter_annotation()
        self._read_deferred()
        self._train_done = True
        self.watchdog.set_iteration(None)

    def close(self) -> None:
        """Finalize the pending record, stop any open profiler window,
        unregister the monitoring hooks and close the JSONL log.
        Idempotent; further spans become no-ops."""
        if not self.enabled or self._closed:
            return
        self._finalize()
        if self.profile is not None:
            self.profile.close(self.iterations)
        self.watchdog.uninstall()
        if self.run_log is not None:
            self.run_log.close()
        self._closed = True

    # -- internals ------------------------------------------------------
    def _close_iter_annotation(self) -> None:
        if self._iter_ann is not None:
            self._iter_ann.__exit__(None, None, None)
            self._iter_ann = None

    def _read_deferred(self) -> None:
        """The iteration's work counts: one small D2H of what
        :meth:`defer_counts` queued, after the iteration's block (the
        programs that wrote them have finished) and outside every phase
        and ``wall_s``."""
        if not self._deferred:
            return
        import jax
        pending, self._deferred = self._deferred, []
        counts: Dict[str, int] = {}
        for host, (_, reducer) in zip(
                jax.device_get([arrays for arrays, _ in pending]), pending):
            for k, v in reducer(host).items():
                counts[k] = counts.get(k, 0) + int(v)
        self.add_counts(counts)

    def _add_phase(self, name: str, exclusive: float) -> None:
        if self._cur is not None:
            ph = self._cur["phases"]
            ph[name] = ph.get(name, 0.0) + exclusive
        self.totals[name] = self.totals.get(name, 0.0) + exclusive
        self.counts[name] = self.counts.get(name, 0) + 1

    def _stamp_watch(self) -> None:
        tot = self.watchdog.totals()
        base = self._watch_base
        by_phase = {k: v - base["compiles_by_phase"].get(k, 0)
                    for k, v in tot["compiles_by_phase"].items()
                    if v - base["compiles_by_phase"].get(k, 0)}
        # the iteration's compiles, fresh and loaded, and the run's to date
        # (the benchmark reads set-up's from the window's first record)
        self._cur["compiles"] = {
            "total": tot["compiles"] - base["compiles"],
            "steady": tot["steady_compiles"] - base["steady_compiles"],
            "secs": round(tot["compile_secs"] - base["compile_secs"], 6),
            "by_phase": by_phase,
            "fresh": tot["fresh"] - base["fresh"],
            "fresh_secs": round(tot["fresh_secs"] - base["fresh_secs"], 6),
            "loaded": tot["loaded"] - base["loaded"],
            "load_secs": round(tot["load_secs"] - base["load_secs"], 6),
            "run": {
                "total": tot["compiles"],
                "secs": round(tot["compile_secs"], 6),
                "fresh": tot["fresh"],
                "fresh_secs": round(tot["fresh_secs"], 6),
                "loaded": tot["loaded"],
                "load_secs": round(tot["load_secs"], 6),
                "fresh_by_program": _rounded(tot["fresh_by_program"]),
                "loaded_by_program": _rounded(tot["loaded_by_program"]),
            },
        }
        self._cur["transfers"] = {
            "total": tot["transfers"] - base["transfers"],
        }

    def _on_steady_compile(self, **fields) -> None:
        if self.run_log is not None:
            self.run_log.event("steady_compile", **fields)

    def _finalize(self) -> None:
        if self._cur is None:
            return
        rec = self._cur
        if "wall_s" not in rec:         # end_iteration never ran
            rec["wall_s"] = time.perf_counter() - self._t0
            self._close_iter_annotation()
            self._deferred = []
        # what the iteration compiled, late phases included: the records'
        # deltas tile the run from the first record's start
        self._stamp_watch()
        # round phase seconds for a compact JSONL (µs resolution)
        rec["phases"] = {k: round(v, 6) for k, v in rec["phases"].items()}
        rec["wall_s"] = round(rec["wall_s"], 6)
        self._cur = None
        self.records.append(rec)
        self.iterations += 1
        self.wall_res.add(rec["wall_s"])
        if self.run_log is not None:
            self.run_log.write(rec)

    # -- reporting ------------------------------------------------------
    def summary(self) -> Dict:
        """Aggregate view (the BENCH JSON ``telemetry`` section)."""
        if not self.enabled:
            return {"enabled": False}
        n = max(self.iterations, 1)
        out: Dict[str, Any] = {
            "enabled": True,
            "iterations": self.iterations,
            "phase_seconds_total": {k: round(v, 6)
                                    for k, v in sorted(self.totals.items())},
            "phase_seconds_per_iter": {k: round(v / n, 6)
                                       for k, v in sorted(self.totals.items())},
            "iter_wall_s": self.wall_res.percentiles(),
        }
        if self.work_totals:
            out["counts_total"] = dict(sorted(self.work_totals.items()))
        out.update({k: v for k, v in self.watchdog.totals().items()
                    if k in ("compiles", "steady_compiles", "transfers",
                             "compile_secs")})
        return out

    def report(self) -> str:
        """Human-readable phase table."""
        if not self.enabled:
            return "telemetry disabled"
        lines = [f"TrainTelemetry ({self.iterations} iterations):"]
        for name in sorted(self.totals, key=self.totals.get, reverse=True):
            lines.append(f"  {name}: {self.totals[name]:.4f}s "
                         f"x{self.counts[name]}")
        w = self.watchdog.totals()
        lines.append(f"  compiles: {w['compiles']} "
                     f"({w['steady_compiles']} steady-state), "
                     f"transfers: {w['transfers']}")
        return "\n".join(lines)


#: shared inert instance — the default for anything that may run without a
#: booster-owned telemetry (e.g. a bare SerialTreeLearner in tests)
NULL_TELEMETRY = TrainTelemetry(enabled=False)
