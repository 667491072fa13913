"""From a profiler trace to the numbers the per-layer metrics read.

One reduction for every cell and every later PR: device busy time is the
union of the intervals in which an op ran (``XLA Ops`` line of each
``/device:`` plane, averaged over the chips), an op's time is its SELF time
(its duration minus the ops nested inside it, so a ``while`` is not counted
on top of its body), its program is the ``XLA Modules`` event it started
in, and its scope is the ``jax.named_scope`` path the profiler recorded
with it (``tf_op``). Idle gaps are named by the ``lg_phase:*`` annotation
the host was inside when the gap opened (the driver puts the booster's
telemetry phases on the profiler's clock in traced runs).
Nothing here names a cell, a kernel or a program: selectors come from
``layer_metrics/<name>.json``.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ITERATION = "lg_iteration"
PHASE = "lg_phase:"


def _union(intervals: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _self_times(ops: List[dict]) -> None:
    """``self_ns`` on every op of one line: duration minus direct children
    (events are properly nested on a device line)."""
    ops.sort(key=lambda e: (e["start_ns"], -e["dur_ns"]))
    stack: List[dict] = []
    for e in ops:
        e["self_ns"] = e["dur_ns"]
        end = e["start_ns"] + e["dur_ns"]
        while stack and stack[-1]["start_ns"] + stack[-1]["dur_ns"] \
                <= e["start_ns"]:
            stack.pop()
        if stack:
            parent = stack[-1]
            parent_end = parent["start_ns"] + parent["dur_ns"]
            parent["self_ns"] -= max(min(end, parent_end) - e["start_ns"], 0)
        stack.append(e)


def reduce(events: List[dict]) -> Dict:
    """busy_s, window_s, ops (name, module, scope, self_s), idle gaps."""
    host = [e for e in events if not e["plane"].startswith("/device:")]
    iters = [e for e in host if e["name"] == ITERATION]
    phases = sorted((e for e in host if e["name"].startswith(PHASE)),
                    key=lambda e: e["start_ns"])
    planes = sorted({e["plane"] for e in events
                     if e["plane"].startswith("/device:")})
    all_ops: List[dict] = []
    busy, window = [], None
    gaps: List[tuple] = []
    for plane in planes:
        ops = [e for e in events
               if e["plane"] == plane and e["line"] == OPS_LINE]
        mods = sorted((e for e in events
                       if e["plane"] == plane and e["line"] == MODULES_LINE),
                      key=lambda e: e["start_ns"])
        if not ops:
            continue
        if iters:
            lo = min(e["start_ns"] for e in iters)
            hi = max(e["start_ns"] + e["dur_ns"] for e in iters)
        else:
            lo = min(e["start_ns"] for e in ops)
            hi = max(e["start_ns"] + e["dur_ns"] for e in ops)
        window = (lo, hi)
        _self_times(ops)
        starts = [m["start_ns"] for m in mods]
        by_id = {m["name"][m["name"].rfind("(") + 1:-1]: m["name"]
                 for m in mods if m["name"].endswith(")")}
        for e in ops:
            e["module"] = by_id.get(str(e["stats"].get("program_id", "")), "")
            if not e["module"]:
                i = bisect.bisect_right(starts, e["start_ns"]) - 1
                if i >= 0 and e["start_ns"] < \
                        mods[i]["start_ns"] + mods[i]["dur_ns"]:
                    e["module"] = mods[i]["name"]
            e["scope"] = str(e["stats"].get("tf_op", ""))
        merged = _union([(max(e["start_ns"], lo),
                          min(e["start_ns"] + e["dur_ns"], hi))
                         for e in ops
                         if e["start_ns"] < hi
                         and e["start_ns"] + e["dur_ns"] > lo])
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1] - edges[i])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        all_ops += ops
    if window is None:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": [], "gaps": []}
    p_starts = [p["start_ns"] for p in phases]

    def phase_at(t: float) -> str:
        i = bisect.bisect_right(p_starts, t) - 1
        while i >= 0:
            p = phases[i]
            if t < p["start_ns"] + p["dur_ns"]:
                return p["name"][len(PHASE):]
            i -= 1
            if i >= 0 and p_starts[i] + 60e9 < t:
                break
        inside = any(e["start_ns"] <= t < e["start_ns"] + e["dur_ns"]
                     for e in iters)
        return "iteration_other" if inside else "between_iterations"
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (window[1] - window[0]) / 1e9,
        "ops": [{"name": e["name"], "display": e.get("display", ""),
                 "module": e["module"], "scope": e["scope"],
                 "self_s": e["self_ns"] / 1e9} for e in all_ops],
        "gaps": [{"phase": phase_at(t), "seconds": d / 1e9}
                 for t, d in sorted(gaps, key=lambda g: -g[1])[:200]],
        "chips": len(busy),
    }


def _matches(op: dict, select: dict) -> bool:
    if "any" in select:
        return any(_matches(op, s) for s in select["any"])
    return all(re.search(pattern, op.get(key, "")) is not None
               for key, pattern in select.items())


def selected_seconds(reduced: Dict, select: dict) -> float:
    """Self time of the ops the selector matches (keys ``module``,
    ``scope``, ``name``: regular expressions, all must match; ``any``: a
    list of such selectors), per chip."""
    total = sum(op["self_s"] for op in reduced["ops"]
                if _matches(op, select))
    return total / max(reduced.get("chips", 1), 1)


def breakdown(reduced: Dict, metrics: List[dict], top: int = 10) -> Dict:
    """The contract's optional ``breakdown``: device time under the names
    of the per-layer metrics that select device ops, then the largest
    programs; and the longest idle gaps by the host's phase."""
    rows = []
    for m in metrics:
        if m.get("reduction") == "self_ms_per_iteration":
            rows.append([m["name"], selected_seconds(reduced, m["select"])])
    by_module: Dict[str, float] = {}
    for op in reduced["ops"]:
        key = "program:" + (re.sub(r"\(\d+\)$", "", op["module"]) or "none")
        by_module[key] = by_module.get(key, 0.0) + op["self_s"]
    rows += sorted(by_module.items(), key=lambda kv: -kv[1])
    by_phase: Dict[str, float] = {}
    for g in reduced["gaps"]:
        by_phase[g["phase"]] = by_phase.get(g["phase"], 0.0) + g["seconds"]
    return {"device_ops": [[k, v] for k, v in rows[:top]],
            "idle_gaps": [[k, v] for k, v in
                          sorted(by_phase.items(), key=lambda kv: -kv[1])[:top]]}
