"""The program's own host-clock spans: ``obs/telemetry.py``'s per-iteration
records of the window (phase seconds and the iteration's wall).

``wall_minus_phase_ms``: per iteration, the wall minus the named phase
(``device_wait``: what is left is the host's own work), averaged."""


def read(metric: dict, view: dict):
    records = view.get("records") or []
    if not records:
        return None
    if metric["reduction"] == "wall_minus_phase_ms":
        rest = [r["wall_s"] - r["phases"].get(metric["phase"], 0.0)
                for r in records]
        return 1e3 * sum(rest) / len(rest)
    raise ValueError(f"unknown reduction {metric['reduction']!r}")
