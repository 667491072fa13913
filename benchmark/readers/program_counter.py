"""A count the program keeps: the compile watchdog's totals, the device's
own memory statistics (``counters``), scaled by ``scale``."""


def read(metric: dict, view: dict):
    value = view["counters"].get(metric["counter"])
    return None if value is None else value * metric.get("scale", 1.0)
