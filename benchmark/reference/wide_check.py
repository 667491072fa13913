"""The ``epsilon`` configuration's copy of the plain reference. It follows
the program's trees exactly as ``gbdt_check.check`` does, with
``gbdt_check``'s own functions, imported and unchanged (``_follow`` and what
it calls), and differs in one way: ``split_gap``'s best split of a node is
searched over a block of features at once (``best_gain``), not one feature
at a time. The arithmetic is ``gbdt_check._best_gain``'s, element for
element and in the same order (a feature's bins are summed by one
``bincount`` over the block whose entries for that feature come in row
order), so every number is ``gbdt_check.check``'s to the last digit.
Why: ``_best_gain`` makes ~15 numpy calls a feature a node; at 2,000
features that is ~3.2 s a tree of calls whatever the sample on the chip's
host (3.9 s a tree at 32,768 rows, 12.2 s at 400,000), ~40 trees a run: the
per-feature cost, not the rows, put a run past its limit. Sixteen features
a call keep a call's arrays a few MB at 100,000 rows."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from . import gbdt_check

BLOCK = 16          # features a numpy call covers


def best_gain(cols, rows, g, h, l2, min_hess=0.0, min_rows=0.0, grid=512,
              pool=None):
    """``gbdt_check._best_gain``, ``BLOCK`` features a call."""
    G, H = g.sum(), h.sum()
    parent = G * G / (H + l2)
    r = rows.size

    def block(lo):
        x = cols[lo:lo + BLOCK][:, rows].astype(np.float64)    # [k, r]
        k = x.shape[0]
        xlo, xhi = x.min(axis=1), x.max(axis=1)
        flat = xhi > xlo                # a constant feature has no split
        scale = grid / np.where(flat, xhi - xlo, 1.0)
        x -= xlo[:, None]
        x *= scale[:, None]
        b = x.astype(np.int64)
        np.minimum(b, grid - 1, out=b)
        b += (np.arange(k) * grid)[:, None]
        b = b.ravel()

        def sums(weights=None):
            w = None if weights is None else np.broadcast_to(
                weights, (k, r)).ravel()
            return np.cumsum(np.bincount(b, weights=w, minlength=k * grid)
                             .reshape(k, grid), axis=1)[:, :-1]
        gl, hl = sums(g), sums(h)
        gr, hr = G - gl, H - hl
        ok = (hl > min_hess) & (hr > min_hess) & flat[:, None]
        if min_rows > 0:
            nl = sums()
            ok &= (nl >= min_rows) & (r - nl >= min_rows)
        gain = np.where(ok, gl * gl / np.where(ok, hl + l2, 1.0)
                        + gr * gr / np.where(ok, hr + l2, 1.0) - parent, 0.0)
        return float(gain.max()), int(ok.sum())
    found = gbdt_check._each(pool if r >= 1 << 15 else None, block,
                             range(0, cols.shape[0], BLOCK))
    return (max([0.0] + [gain for gain, _ in found]),
            sum(searched for _, searched in found))


def check(*args, seconds: Optional[Dict[str, float]] = None,
          **kw) -> Dict[str, float]:
    """``gbdt_check.check``'s numbers, each node's best split searched by
    ``best_gain``; ``gbdt_check`` is as it was afterwards."""
    saved = gbdt_check._best_gain
    gbdt_check._best_gain = best_gain
    try:
        return gbdt_check.check(*args, seconds=seconds, **kw)
    finally:
        gbdt_check._best_gain = saved
