"""A field of the program's own iteration records (``obs/telemetry.py``),
as the window's records carry it: ``records``, the first one the window's
first iteration.

- ``before_window``: ``compiles.run.<field>`` of the window's first record
  (the run's total when that record closed) less the record's own
  ``compiles.<field>``: what the run held before the window began, i.e.
  set-up's share. ``field`` is one of the watchdog's run totals
  (``fresh_secs``: seconds of XLA compiles the persistent cache did not
  serve; ``load_secs``: seconds loading executables it did serve).
No record, or one without the keys (a program that does not keep them),
reads nothing, never 0."""


def read(metric: dict, view: dict):
    if metric["reduction"] != "before_window":
        raise ValueError(f"unknown reduction {metric['reduction']!r}")
    records = view.get("records") or []
    if not records:
        return None
    compiles = records[0].get("compiles") or {}
    run = compiles.get("run") or {}
    field = metric["field"]
    if field not in run or field not in compiles:
        return None
    return run[field] - compiles[field]
