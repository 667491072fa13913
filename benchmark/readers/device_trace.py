"""A number from the profiler's device trace of the traced window, as
``trace_reduce.reduce`` left it (``reduced``): every device op with its self
time, the program it ran in and the named scope it was traced under.

- ``self_ms_per_iteration``: self time of the ops ``select`` matches, per
  traced iteration.
- ``idle_pct``: 1 - the union of op intervals over the traced window.
- ``roofline_pct``: the least time the chip could take for the work
  ``work/<work>.py`` counts from the traced trees (the larger of ops over
  peak FLOP/s and bytes over peak bytes/s), over the selected self time.
A reader that finds nothing to read returns nothing."""
from __future__ import annotations

import importlib

from .. import trace_reduce


def read(metric: dict, view: dict):
    red = view.get("reduced")
    if not red or not red["ops"]:
        return None
    kind = metric["reduction"]
    if kind == "idle_pct":
        return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    secs = trace_reduce.selected_seconds(red, metric["select"])
    if secs <= 0:
        return None
    iters = max(view["traced_iterations"], 1)
    if kind == "self_ms_per_iteration":
        return 1e3 * secs / iters
    if kind == "roofline_pct":
        cfg = view["config"]
        first = len(view["trees"]) - view["iterations"]
        traced = view["trees"][first:first + iters]
        w = importlib.import_module("benchmark.work." + metric["work"]).work(
            traced, int(cfg["num_features"]), int(view["params"]["max_bin"]))
        peaks = view["peaks"]
        least = max(w["ops"] / peaks["bf16_flops_per_s"],
                    w["bytes"] / peaks["hbm_bytes_per_s"])
        return 100.0 * least / secs
    raise ValueError(f"unknown reduction {kind!r}")
