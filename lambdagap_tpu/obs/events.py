"""Structured JSONL run log: the machine-readable training artifact.

One ``telemetry_out=`` file per run; every line is one JSON object. This is
the artifact regression triage diffs against, so
the schema is versioned and validated (``validate_record`` /
``validate_file`` — used by tests/test_obs.py and the run_full_suite.sh
telemetry gate).

Record types (``"type"`` field; full table in docs/observability.md):

- ``run_header`` — first line: schema version, wall time, the resolved
  training params, device topology, package versions.
- ``iteration`` — one per boosting iteration: ``iter``, device-complete
  ``wall_s``, the per-phase exclusive-seconds map ``phases``, ``compiles``
  (total / steady-state / per-phase) and ``transfers`` counters.
- ``event`` — anything punctual: steady-state recompile warnings, profiler
  window start/stop, serve swaps, errors, and the guard layer's
  ``guard_nonfinite`` diagnostics (lambdagap_tpu.guard: policy + iteration
  when gradients/hessians/scores went non-finite — the last record a
  ``guard_nonfinite=raise`` run writes before failing).
- ``span`` — one hop of a distributed request trace (obs/trace.py): trace
  / span / parent ids, span name, recording process, epoch start ``t0``
  and duration ``dur`` — the record type trace logs and flight-recorder
  dumps are made of.
- ``signals`` — one tick of the derived control-signal plane
  (obs/signals.py): goodput-knee, residency/eviction-pressure, and
  per-replica health signals, validated by that module's own schema.

Writes flush per line (or on a small bounded interval for high-rate span
logs): a crashed run keeps every completed record — the whole point of a
flight recorder. Reading tolerates the complement: a process SIGKILLed
mid-write leaves a final line without its newline, which
:func:`validate_file` / :func:`read_file` report as truncation, not as a
corrupt file.
"""
from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

SCHEMA_VERSION = 1

# iteration-record required keys -> validator (docs/observability.md schema)
_ITER_REQUIRED = {
    "iter": lambda v: isinstance(v, int) and v >= 0,
    "wall_s": lambda v: isinstance(v, (int, float)) and v >= 0,
    "phases": lambda v: isinstance(v, dict) and all(
        isinstance(k, str) and isinstance(x, (int, float))
        for k, x in v.items()),
    "compiles": lambda v: isinstance(v, dict) and "total" in v
    and "steady" in v,
    "transfers": lambda v: isinstance(v, dict) and "total" in v,
}

# span-record required keys (obs/trace.py; docs/observability.md span table)
_SPAN_REQUIRED = {
    "trace": lambda v: isinstance(v, str) and v != "",
    "span": lambda v: isinstance(v, str) and v != "",
    "name": lambda v: isinstance(v, str) and v != "",
    "t0": lambda v: isinstance(v, (int, float)) and v >= 0,
    "dur": lambda v: isinstance(v, (int, float)) and v >= 0,
}


def run_header(params: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The run-identity record: enough to reproduce and to diff two runs'
    environments without parsing logs."""
    header: Dict[str, Any] = {
        "type": "run_header",
        "schema_version": SCHEMA_VERSION,
        "time_unix": time.time(),
        "params": params or {},
        "versions": {"python": sys.version.split()[0]},
    }
    try:
        import jax
        header["device"] = {
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "devices": [str(d) for d in jax.devices()],
        }
        # the registry mesh axes (parallel/sharding.py): run logs of
        # distributed trainings are diffable on mesh geometry — the
        # actual placement rides params (tpu_num_devices/mesh_shape)
        from ..parallel.sharding import MESH_AXES
        header["device"]["mesh_axes"] = list(MESH_AXES)
        header["versions"]["jax"] = jax.__version__
    except Exception:  # pragma: no cover - jax import is repo-wide
        header["device"] = {}
    try:
        import numpy
        header["versions"]["numpy"] = numpy.__version__
    except Exception:  # pragma: no cover
        pass
    return header


class RunLog:
    """Line-per-record JSONL writer. Flushes per record by default;
    ``flush_every > 1`` batches flushes for high-rate writers (span logs)
    while a ``flush_interval_s`` clock bounds the worst-case data loss a
    SIGKILL can cause — the reader side tolerates the torn final line."""

    def __init__(self, path: str,
                 params: Optional[Dict[str, Any]] = None,
                 flush_every: int = 1,
                 flush_interval_s: float = 0.25) -> None:
        self.path = path
        self._f = open(path, "w", encoding="utf-8")
        self._flush_every = max(int(flush_every), 1)
        self._flush_interval = float(flush_interval_s)
        self._unflushed = 0
        self._last_flush = time.perf_counter()
        self.write(run_header(params))

    def write(self, record: Dict[str, Any]) -> None:
        if self._f is None:
            return
        self._f.write(json.dumps(record, separators=(",", ":"),
                                 default=_json_default) + "\n")
        self._unflushed += 1
        now = time.perf_counter()
        if (self._unflushed >= self._flush_every
                or now - self._last_flush >= self._flush_interval):
            self._f.flush()
            self._unflushed = 0
            self._last_flush = now

    def event(self, event: str, **fields: Any) -> None:
        self.write({"type": "event", "event": event,
                    "time_unix": time.time(), **fields})

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


def _json_default(o):
    """Last-resort coercion for numpy scalars riding in records."""
    for attr in ("item",):
        if hasattr(o, attr):
            return o.item()
    return str(o)


# ---------------------------------------------------------------------------
# schema validation (tests + the run_full_suite.sh telemetry gate)
# ---------------------------------------------------------------------------
def validate_record(obj: Any) -> List[str]:
    """Errors for one parsed JSONL record; empty when valid."""
    errs: List[str] = []
    if not isinstance(obj, dict):
        return [f"record is {type(obj).__name__}, not an object"]
    rtype = obj.get("type")
    if rtype not in ("run_header", "iteration", "event", "span", "signals"):
        return [f"unknown record type {rtype!r}"]
    if rtype == "run_header":
        if obj.get("schema_version") != SCHEMA_VERSION:
            errs.append(f"schema_version {obj.get('schema_version')!r} != "
                        f"{SCHEMA_VERSION}")
        if not isinstance(obj.get("params"), dict):
            errs.append("run_header.params must be an object")
    elif rtype == "iteration":
        for key, check in _ITER_REQUIRED.items():
            if key not in obj:
                errs.append(f"iteration record missing {key!r}")
            elif not check(obj[key]):
                errs.append(f"iteration.{key} failed validation: "
                            f"{obj[key]!r}")
    elif rtype == "event":
        if not isinstance(obj.get("event"), str):
            errs.append("event record missing 'event' name")
    elif rtype == "span":
        for key, check in _SPAN_REQUIRED.items():
            if key not in obj:
                errs.append(f"span record missing {key!r}")
            elif not check(obj[key]):
                errs.append(f"span.{key} failed validation: {obj[key]!r}")
        parent = obj.get("parent")
        if parent is not None and not isinstance(parent, str):
            errs.append(f"span.parent must be a string or null, "
                        f"got {parent!r}")
    elif rtype == "signals":
        if not isinstance(obj.get("time_unix"), (int, float)):
            errs.append("signals record missing 'time_unix'")
        if not isinstance(obj.get("goodput"), dict):
            errs.append("signals record missing 'goodput' block")
    return errs


def _scan_file(path: str) -> Tuple[List[Tuple[int, Any]], List[str], bool]:
    """Shared reader: ((line_no, parsed), errors, truncated). A final
    line with NO trailing newline that fails to parse is a SIGKILL-torn
    tail: reported as truncation, never as an error — the flight-recorder
    / postmortem path reads logs from hard-killed processes."""
    errs: List[str] = []
    records: List[Tuple[int, Any]] = []
    truncated = False
    # errors="replace": a dump torn mid-byte-sequence (SIGKILL during a
    # non-atomic copy, a half-recovered disk) must degrade to a torn/
    # garbage LINE — which the per-line parse below already tolerates —
    # not to a UnicodeDecodeError that loses the whole file's evidence
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        content = f.read()
    lines = content.split("\n")
    last_complete = len(lines) - 1       # split leaves "" after a final \n
    for i, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError as e:
            if i > last_complete:        # the newline-less final line
                truncated = True
                continue
            errs.append(f"line {i}: not JSON ({e})")
            continue
        records.append((i, obj))
    return records, errs, truncated


def validate_file(path: str) -> List[str]:
    """Validate a whole JSONL run log. Returns a list of
    ``"line N: problem"`` strings; empty means the file conforms (non-empty,
    parses line-by-line, leads with a run_header, every record valid). A
    torn final line — one cut mid-write, without its newline — is
    tolerated: everything before it still validates (SIGKILLed serve
    processes leave exactly this shape)."""
    records, errs, _truncated = _scan_file(path)
    for n, (i, obj) in enumerate(records):
        if n == 0 and (not isinstance(obj, dict)
                       or obj.get("type") != "run_header"):
            errs.append(f"line {i}: first record must be a run_header")
        for e in validate_record(obj):
            errs.append(f"line {i}: {e}")
    if not records:
        errs.append("empty run log")
    return errs


def read_file(path: str) -> Tuple[List[Dict[str, Any]], bool]:
    """(records, truncated): every parseable record in file order, plus
    whether a torn final line was dropped. The lenient reader the
    postmortem tooling uses — unparseable interior lines are skipped, not
    fatal (a half-recovered disk is still evidence)."""
    records, _errs, truncated = _scan_file(path)
    return [obj for _i, obj in records if isinstance(obj, dict)], truncated
