"""One phase of the program's own host-clock spans: ``obs/telemetry.py``'s
per-iteration records of the window (``phases``: seconds a named phase, the
late ones -- the ``eval`` phase opened after the iteration's block --
included).

``phase_ms``: the named phase's milliseconds an iteration, averaged over the
window's records. No record, or none that holds the phase (a program or a
run that never opened it), reads nothing, never 0."""


def read(metric: dict, view: dict):
    records = view.get("records") or []
    if metric["reduction"] != "phase_ms":
        raise ValueError(f"unknown reduction {metric['reduction']!r}")
    spent = [r["phases"][metric["phase"]] for r in records
             if metric["phase"] in r.get("phases", {})]
    if not spent:
        return None
    return 1e3 * sum(spent) / len(records)
