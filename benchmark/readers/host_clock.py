"""A span the benchmark timed itself on the host's clock (``spans``)."""


def read(metric: dict, view: dict):
    return view["spans"].get(metric["span"])
