"""``train_window``'s loop, unchanged, for a cell of thousands of features,
whose plain reference is ``reference/wide_check.py`` (``gbdt_check``'s
numbers, each node's best split searched a block of features at a time)
with a split-search sample of its own: the configuration's
``reference.sample_rows`` (``gbdt_check.check``'s default is 2^19 rows).
The reference still follows every tree over every row; only the rows on
which ``split_gap`` searches the best split at the root and its children
are fewer, because that search costs ~6 ns a feature-row and a wide row
has thousands of features."""
from __future__ import annotations

from ..reference import gbdt_check, wide_check
from . import train_window


class _Sampled:
    """``gbdt_check`` with ``check`` taken by ``wide_check.check`` at the
    sample fixed; everything else is the module's own."""

    def __init__(self, rows: int) -> None:
        self.rows = rows

    def __getattr__(self, name):
        return getattr(gbdt_check, name)

    def check(self, *args, **kw):
        return wide_check.check(*args, sample_rows=self.rows, **kw)


def run(ctx: dict) -> dict:
    rows = int(ctx["cell"]["config"]["reference"]["sample_rows"])
    ctx["log"](f"reference: split_gap on a sample of {rows:,} rows")
    saved = train_window.gbdt_check
    train_window.gbdt_check = _Sampled(rows)
    try:
        return train_window.run(ctx)
    finally:
        train_window.gbdt_check = saved
