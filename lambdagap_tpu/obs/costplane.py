"""Analytic cost plane (graftmeter): per-executable FLOP/byte/HBM ledger.

Every BENCH/MULTICHIP number this repo produces is CPU-shaped, so nothing
hardware-independent says whether a PR regressed a hot program's compute
or memory traffic. XLA already knows: ``Lowered.cost_analysis()`` reports
analytic flops / transcendentals / bytes-accessed for the lowered program
and ``Compiled.memory_analysis()`` reports argument/output/temp/code HBM —
exact on any backend, at compile time, with zero steady-state cost. This
module captures both, once per (program, padding bucket), at the jit
entry points the repo actually dispatches:

- the three learners — ``train.serial.{histogram,split,partition}``
  (models/learner.py), ``train.fused`` (models/fused_learner.py),
  ``train.fused2d`` (parallel/fused_parallel.py, with its mesh spec);
- the three predict engines — ``predict.scan`` (ops/predict.py),
  ``predict.tensor`` (ops/predict_tensor.py), ``predict.compiled``
  (infer/engine.py);
- the out-of-core window scorer — ``predict_stream.window``
  (infer/stream.py, captured at bucket pre-warm);
- SHAP — ``predict.shap`` (models/gbdt.py): a host numpy loop, recorded
  from an analytic traffic model instead of an XLA lowering.

The ledger joins measured wall-time (``note_wall`` — fed by
``TrainTelemetry.close`` per phase, by ``GBDT.predict_raw`` and the serve
cache per dispatch window) against a per-backend peak table to report
achieved fraction-of-roofline per phase and whether the phase is flop- or
byte-bound. It exports through ``prom.render_costplane``, rides flight
recorder dumps, persists as ``COSTS.json`` (``cost_plane_out=``), and
``tools/cost_gate.py`` diffs it against ``tools/cost_budget.json`` in CI.

Everything is inert unless armed (``cost_plane=true`` / ``cost_plane_out=``):
the off path is one attribute test per observed dispatch. Capture failures
never propagate — a program that refuses to lower is logged at debug and
skipped, and each (program, bucket) is attempted at most once.
"""
from __future__ import annotations

import json
import math
import threading
import time
from typing import Any, Dict, Optional, Tuple

from ..utils import log

SCHEMA_VERSION = 1

# Per-backend peak tables: dense-matmul FLOP/s, HBM bandwidth B/s, HBM
# capacity bytes. TPU rows are the published per-chip peaks (v5e bf16
# 197 TFLOP/s / 819 GB/s / 16 GiB; v4 275/1228/32; v5p 459/2765/95);
# ``measured`` False marks placeholders (the CPU container) whose
# roofline fractions are indicative only — the ledger's flops/bytes stay
# exact there, which is all the CI gate consumes. A device_kind no row
# names gets NO peaks (``peaks()`` says so and ``attribution()`` reports
# no roofline numbers) — never another device's row.
_PEAK_TABLE: Tuple[Tuple[Tuple[str, ...], Dict[str, Any]], ...] = (
    (("v5 lite", "v5e"), {"name": "tpu-v5e", "flops": 197e12,
                          "bandwidth": 819e9, "hbm": 16 * 2**30,
                          "measured": True}),
    (("v5p", "v5"), {"name": "tpu-v5p", "flops": 459e12,
                     "bandwidth": 2765e9, "hbm": 95 * 2**30,
                     "measured": True}),
    (("v4",), {"name": "tpu-v4", "flops": 275e12, "bandwidth": 1228e9,
               "hbm": 32 * 2**30, "measured": True}),
    (("cpu",), {"name": "cpu-container", "flops": 1e11, "bandwidth": 2e10,
                "hbm": 8 * 2**30, "measured": False}),
)


def _leaf_nbytes(x: Any) -> int:
    """Bytes of one argument leaf (array, tracer or ShapeDtypeStruct);
    0 for statics/scalars without shape+dtype."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(math.prod(shape)) * int(dtype.itemsize)
    except Exception:
        return 0


class _WallSpan:
    """Context manager feeding one measured wall into the plane; inert
    when the plane is disarmed. The caller is responsible for device
    completion inside the bracket (a terminal ``device_get`` /
    ``block_until_ready``), so the noted wall is device-complete."""

    __slots__ = ("_plane", "_phase", "_t0")

    def __init__(self, plane: "CostPlane", phase: str) -> None:
        self._plane = plane
        self._phase = phase
        self._t0 = 0.0

    def __enter__(self) -> "_WallSpan":
        if self._plane.enabled:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._plane.enabled and exc[0] is None:
            self._plane.note_wall(self._phase,
                                  time.perf_counter() - self._t0)


class CostPlane:
    """Process-global analytic cost ledger (module singleton ``PLANE``).

    ``observed_call`` wraps a jitted callable's dispatch: bookkeeping under
    the lock is O(1), the one-time capture (trace -> lower ->
    cost_analysis, optionally compile -> memory_analysis) runs OUTSIDE the
    lock (graftlint R9: never compile under a lock), and the actual
    dispatch is returned unchanged — bit-identical results, zero
    steady-state overhead beyond a dict increment."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.enabled = False
        self.memory_mode = "compiled"
        self.out_path = ""
        self._peaks_override = ""
        # "program|bucket" -> captured entry (static facts)
        self.entries: Dict[str, Dict[str, Any]] = {}
        # "program|bucket" -> observed dispatch count
        self.calls: Dict[str, int] = {}
        # phase -> {"seconds": float, "calls": int} measured wall joins
        self.walls: Dict[str, Dict[str, float]] = {}
        self._attempted: set = set()

    # -- lifecycle ------------------------------------------------------
    def configure(self, config: Any) -> None:
        """Arm/disarm from the ``cost_plane*`` knobs. Does NOT clear the
        ledger: one process can accumulate several scenarios (the CI gate
        trains every learner and predicts through every engine into one
        ledger). Last configure wins, matching the telemetry knobs."""
        out = getattr(config, "cost_plane_out", "") or ""
        self.enabled = bool(getattr(config, "cost_plane", False)) or bool(out)
        if out:
            self.out_path = out
        self.memory_mode = getattr(config, "cost_plane_memory", "compiled")
        self._peaks_override = getattr(config, "cost_plane_peaks", "") or ""

    def reset(self) -> None:
        with self._lock:
            self.entries.clear()
            self.calls.clear()
            self.walls.clear()
            self._attempted.clear()

    # -- capture --------------------------------------------------------
    def observed_call(self, program: str, fn: Any, args: tuple,
                      kwargs: Optional[dict] = None, *, bucket: Any = "",
                      phase: str = "", shard_spec: str = "") -> Any:
        """Dispatch ``fn(*args, **kwargs)``, recording its analytic cost
        once per (program, bucket). The disarmed path is one attribute
        test; capture failures are swallowed (debug-logged) so the plane
        can never break a training or serving run."""
        kwargs = kwargs or {}
        if not self.enabled:
            return fn(*args, **kwargs)
        key = f"{program}|{bucket}"
        capture = False
        with self._lock:
            self.calls[key] = self.calls.get(key, 0) + 1
            if key not in self._attempted:
                # mark BEFORE trying: a capture that fails must not retry
                # on every subsequent dispatch of a hot program
                self._attempted.add(key)
                capture = True
        if capture and self._trace_clean():
            try:
                entry = self._capture(fn, args, kwargs)
            except Exception as e:  # pragma: no cover - backend-dependent
                log.debug("cost plane: capture of %s failed: %s", key, e)
            else:
                entry.update(program=program, bucket=str(bucket),
                             phase=phase, shard_spec=shard_spec)
                with self._lock:
                    self.entries[key] = entry
        elif capture:
            with self._lock:
                # under a tracer (e.g. an engine dispatched inside the
                # predict_stream scorer) the abstract args cannot be
                # re-traced; allow a later concrete call to capture
                self._attempted.discard(key)
        return fn(*args, **kwargs)

    @staticmethod
    def _trace_clean() -> bool:
        try:
            import jax
            return bool(jax.core.trace_state_clean())
        except Exception:  # pragma: no cover - jax internals moved
            return True

    def _capture(self, fn: Any, args: tuple, kwargs: dict) -> Dict[str, Any]:
        """AOT-inspect one dispatch: analytic cost from the lowering; HBM
        from the compiled executable (``cost_plane_memory=compiled``) or
        from aval arithmetic (``analytic`` — no second backend compile)."""
        import jax

        lowered = fn.trace(*args, **kwargs).lower()
        cost = lowered.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):  # some backends return a list
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0) or 0.0)
        transcendentals = float(cost.get("transcendentals", 0.0) or 0.0)
        bytes_accessed = float(cost.get("bytes accessed", 0.0) or 0.0)
        arg_bytes = sum(_leaf_nbytes(a) for a in jax.tree_util.tree_leaves(
            (args, kwargs)))
        out_bytes = sum(_leaf_nbytes(a) for a in jax.tree_util.tree_leaves(
            lowered.out_info))
        temp_bytes = 0
        code_bytes = 0
        source = "analytic"
        if self.memory_mode == "compiled":
            try:
                ma = lowered.compile().memory_analysis()
                arg_bytes = int(ma.argument_size_in_bytes)
                out_bytes = int(ma.output_size_in_bytes)
                temp_bytes = int(ma.temp_size_in_bytes)
                code_bytes = int(ma.generated_code_size_in_bytes)
                source = "compiled"
            except Exception as e:  # pragma: no cover - backend-dependent
                log.debug("cost plane: memory_analysis unavailable (%s); "
                          "falling back to aval arithmetic", e)
        if source == "analytic":
            # XLA's bytes-accessed counts operand + output + intermediate
            # traffic; what is neither argument nor output bounds the
            # temporaries a fused program touches
            temp_bytes = int(max(0.0, bytes_accessed - arg_bytes
                                 - out_bytes))
        peak_hbm = int(arg_bytes + out_bytes + temp_bytes + code_bytes)
        dev = jax.devices()[0]
        return {
            "flops": flops,
            "transcendentals": transcendentals,
            "bytes_accessed": bytes_accessed,
            "arg_bytes": int(arg_bytes),
            "out_bytes": int(out_bytes),
            "temp_bytes": int(temp_bytes),
            "code_bytes": int(code_bytes),
            "peak_hbm_bytes": peak_hbm,
            "memory_source": source,
            "arithmetic_intensity": round(flops / bytes_accessed, 4)
            if bytes_accessed > 0 else None,
            "backend": dev.platform,
            "device_kind": dev.device_kind,
            "num_devices": jax.device_count(),
        }

    def record_host(self, program: str, *, flops: float,
                    bytes_accessed: float, peak_hbm_bytes: int,
                    phase: str = "", bucket: Any = "") -> None:
        """Ledger entry for a host-evaluated program (SHAP's numpy loop):
        same schema, ``memory_source="host_analytic"``, counted once per
        (program, bucket) like a captured executable."""
        if not self.enabled:
            return
        key = f"{program}|{bucket}"
        with self._lock:
            self.calls[key] = self.calls.get(key, 0) + 1
            if key in self.entries:
                return
            self._attempted.add(key)
            self.entries[key] = {
                "program": program, "bucket": str(bucket), "phase": phase,
                "shard_spec": "", "flops": float(flops),
                "transcendentals": 0.0,
                "bytes_accessed": float(bytes_accessed),
                "arg_bytes": int(bytes_accessed), "out_bytes": 0,
                "temp_bytes": 0, "code_bytes": 0,
                "peak_hbm_bytes": int(peak_hbm_bytes),
                "memory_source": "host_analytic",
                "arithmetic_intensity": round(flops / bytes_accessed, 4)
                if bytes_accessed > 0 else None,
                "backend": "host", "device_kind": "host", "num_devices": 0,
            }

    # -- wall joins ------------------------------------------------------
    def note_wall(self, phase: str, seconds: float, calls: int = 1) -> None:
        """Accumulate measured device-complete wall for ``phase``; joined
        against the ledger's analytic totals by :meth:`attribution`."""
        if not self.enabled or seconds < 0:
            return
        with self._lock:
            w = self.walls.setdefault(phase, {"seconds": 0.0, "calls": 0})
            w["seconds"] += float(seconds)
            w["calls"] += int(calls)

    def wall(self, phase: str) -> _WallSpan:
        """``with PLANE.wall("predict"): ...`` measured-wall bracket; the
        body must end device-complete (see _WallSpan)."""
        return _WallSpan(self, phase)

    # -- attribution -----------------------------------------------------
    def peaks(self) -> Dict[str, Any]:
        """The active peak row: ``cost_plane_peaks="flops:bw:hbm"``
        override, else the table row matched on device_kind. An unknown
        device_kind returns a row whose peaks are ``None``."""
        if self._peaks_override:
            try:
                f, bw, hbm = (float(x) for x in
                              self._peaks_override.split(":"))
                return {"name": "override", "flops": f, "bandwidth": bw,
                        "hbm": hbm, "measured": True}
            except ValueError:
                log.warning("cost plane: bad cost_plane_peaks %r (want "
                            "'flops:bandwidth:hbm_bytes'); using the "
                            "table", self._peaks_override)
        import jax
        kind = jax.devices()[0].device_kind
        for needles, row in _PEAK_TABLE:
            if any(n in kind.lower() for n in needles):
                return dict(row)
        return {"name": "unknown", "device_kind": kind, "flops": None,
                "bandwidth": None, "hbm": None, "measured": False}

    def attribution(self) -> Dict[str, Any]:
        """Per-phase roofline join: total analytic flops/bytes (entry x
        observed calls) vs the peak table, against the measured wall.
        ``bound`` says which roofline arm dominates; ``roofline_s`` is the
        attainable floor; ``fraction_of_roofline`` = floor / wall (1.0 =
        the phase runs at the machine's analytic limit). On a device the
        peak table does not name, the three are absent and the document
        carries a ``roofline`` note saying why."""
        peaks = self.peaks()
        with self._lock:
            entries = {k: dict(v) for k, v in self.entries.items()}
            calls = dict(self.calls)
            walls = {k: dict(v) for k, v in self.walls.items()}
        phases: Dict[str, Dict[str, float]] = {}
        for key, e in entries.items():
            ph = e.get("phase") or "unattributed"
            n = calls.get(key, 1)
            agg = phases.setdefault(ph, {"flops": 0.0, "bytes": 0.0,
                                         "calls": 0})
            agg["flops"] += e["flops"] * n
            agg["bytes"] += e["bytes_accessed"] * n
            agg["calls"] += n
        out: Dict[str, Any] = {"peaks": peaks, "phases": {}}
        known = peaks["flops"] is not None
        if not known:
            out["roofline"] = ("not computed: no peak row for device_kind "
                               f"{peaks['device_kind']!r}")
        for ph, agg in sorted(phases.items()):
            rec: Dict[str, Any] = {
                "flops_total": agg["flops"],
                "bytes_total": agg["bytes"],
                "calls": int(agg["calls"]),
            }
            wall = walls.get(ph, {}).get("seconds", 0.0)
            if wall > 0:
                rec["wall_s"] = round(wall, 6)
            if known:
                t_flop = agg["flops"] / peaks["flops"]
                t_byte = agg["bytes"] / peaks["bandwidth"]
                roofline_s = max(t_flop, t_byte)
                rec["bound"] = "flop" if t_flop >= t_byte else "byte"
                rec["roofline_s"] = round(roofline_s, 6)
                if wall > 0:
                    rec["fraction_of_roofline"] = round(
                        min(roofline_s / wall, 1.0), 4)
                    rec["fraction_of_roofline_uncapped"] = round(
                        roofline_s / wall, 4)
            out["phases"][ph] = rec
        return out

    # -- export ----------------------------------------------------------
    def to_json(self) -> Dict[str, Any]:
        """The COSTS.json document (schema in docs/observability.md)."""
        backend, kind, n_dev = "unknown", "unknown", 0
        try:
            import jax
            d = jax.devices()[0]
            backend, kind = d.platform, d.device_kind
            n_dev = jax.device_count()
        except Exception as e:  # pragma: no cover - backendless process
            log.debug("cost plane: no backend identity for the ledger "
                      "header (%s)", e)
        with self._lock:
            entries = {k: dict(v, calls=self.calls.get(k, 0))
                       for k, v in sorted(self.entries.items())}
            walls = {k: {"seconds": round(v["seconds"], 6),
                         "calls": int(v["calls"])}
                     for k, v in sorted(self.walls.items())}
        return {
            "schema_version": SCHEMA_VERSION,
            "backend": backend,
            "device_kind": kind,
            "num_devices": n_dev,
            "peaks": self.peaks(),
            "entries": entries,
            "walls": walls,
            "attribution": self.attribution(),
        }

    def write(self, path: Optional[str] = None) -> Optional[str]:
        """Persist the ledger (atomic replace — the file on disk is always
        a complete document, like flight-recorder dumps)."""
        path = path or self.out_path
        if not path or not self.enabled:
            return None
        from ..guard.snapshot import atomic_write_text
        atomic_write_text(path, json.dumps(self.to_json(), indent=1,
                                           sort_keys=True) + "\n")
        return path

    def by_program(self) -> Dict[str, Dict[str, float]]:
        """Program-level maxima over padding buckets (what the budget file
        records: the hot bucket is the binding one)."""
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            for key, e in self.entries.items():
                p = e["program"]
                agg = out.setdefault(p, {"bytes_accessed": 0.0,
                                         "peak_hbm_bytes": 0.0,
                                         "flops": 0.0, "calls": 0})
                agg["bytes_accessed"] = max(agg["bytes_accessed"],
                                            e["bytes_accessed"])
                agg["peak_hbm_bytes"] = max(agg["peak_hbm_bytes"],
                                            e["peak_hbm_bytes"])
                agg["flops"] = max(agg["flops"], e["flops"])
                agg["calls"] += self.calls.get(key, 0)
        return out

    def train_traffic(self, iterations: int) -> Optional[Dict[str, Any]]:
        """Measured train-side traffic per iteration for bench.py's
        roofline: total bytes/flops of the train-phase entries scaled by
        observed calls, divided by the iteration count. None when the
        ledger holds no train programs."""
        train_phases = ("histogram", "split", "partition", "tree",
                        "layout_apply")
        flops = bytes_a = 0.0
        n = 0
        with self._lock:
            for key, e in self.entries.items():
                if e.get("phase") in train_phases:
                    c = self.calls.get(key, 1)
                    flops += e["flops"] * c
                    bytes_a += e["bytes_accessed"] * c
                    n += 1
        if n == 0 or iterations <= 0:
            return None
        return {"programs": n,
                "bytes_per_iter": bytes_a / iterations,
                "flops_per_iter": flops / iterations}


#: the process-global ledger every capture site feeds
PLANE = CostPlane()


def observed_call(program: str, fn: Any, args: tuple,
                  kwargs: Optional[dict] = None, *, bucket: Any = "",
                  phase: str = "", shard_spec: str = "") -> Any:
    """Module-level convenience over ``PLANE.observed_call`` (the form the
    capture sites use; keeps their import surface to one name)."""
    return PLANE.observed_call(program, fn, args, kwargs, bucket=bucket,
                               phase=phase, shard_spec=shard_spec)
