"""Device time of the traced window set against the program's own work
counts, and the share of it the program left unnamed.

``reduced`` is ``trace_reduce.reduce``'s (every device op with its self
time, program and named-scope path); ``records`` are the window's iteration
records as ``obs/telemetry.py`` wrote them, the traced iterations first,
each with ``counts`` (splits, rows and ``while`` trips of the partition and
histogram passes) where the program keeps them.

- ``time_per_count``: self time of the ops ``select`` matches over the
  traced iterations' sum of ``counts[<count>]``, times ``scale`` (1e9: ns
  per row; 1e6: us per trip or per split).
- ``unscoped_pct``: of the self time ``select`` matches (whole programs),
  the share of ops whose scope path holds none of ``scopes`` as a component.
Nothing to read (no device op, a scope no op carries, a record without the
count) returns nothing, never 0."""
from __future__ import annotations

import re

from .. import trace_reduce


def read(metric: dict, view: dict):
    red = view.get("reduced")
    if not red or not red["ops"]:
        return None
    secs = trace_reduce.selected_seconds(red, metric["select"])
    if secs <= 0:
        return None
    kind = metric["reduction"]
    if kind == "unscoped_pct":
        named = re.compile("(^|/)(" + "|".join(map(re.escape, metric[
            "scopes"])) + ")(/|$)")
        outside = sum(op["self_s"] for op in red["ops"]
                      if trace_reduce._matches(op, metric["select"])
                      and not named.search(op.get("scope", "")))
        return 100.0 * outside / max(red.get("chips", 1), 1) / secs
    if kind == "time_per_count":
        traced = (view.get("records") or [])[:view["traced_iterations"]]
        counts = [r.get("counts", {}).get(metric["count"]) for r in traced]
        if not counts or None in counts or sum(counts) <= 0:
            return None
        return metric["scale"] * secs / sum(counts)
    raise ValueError(f"unknown reduction {kind!r}")
