"""Batched linear-leaf ops: MXU moment accumulation + one solve per tree.

Piece-wise linear regression trees ("Gradient Boosting With Piece-Wise
Linear Regression Trees", arXiv:1802.05640) fit a ridge-regularized linear
model in every leaf over the numeric features used on the leaf's path. The
reference implementation (src/treelearner/linear_tree_learner.cpp
CalculateLinear) loops leaves on the host, gathering each leaf's raw rows
and running one small normal-equations solve per leaf — exactly the shape
a TPU is worst at (many tiny host-driven solves) and the MXU is best at
when batched.

This module is the TPU formulation, and the SINGLE implementation both the
serial and the fused learners call — fused==serial bit-identity for linear
trees is by construction, not by parallel maintenance of two codepaths:

* :func:`accumulate_leaf_moments` — ONE jitted pass over the raw matrix in
  dataset-row order (chunked; each chunk contracts a one-hot leaf-membership
  matrix against the per-row design vectors on the MXU) producing
  ``X^T H X`` ``[L+1, P, P]``, ``X^T g`` ``[L+1, P]`` and valid-row counts
  per leaf, where ``P = FL + 1`` (padded feature slots + intercept). Row
  order is canonical (dataset order), so the accumulation is independent
  of which learner produced the row->leaf map.
* :func:`solve_linear_leaves` — ONE batched float64 solve over the
  ``[L, P, P]`` stack (``linear_lambda`` on the feature diagonal, identity
  rows on padding slots), with the reference's fallbacks: a singular or
  non-finite system, too few non-NaN rows, or an empty feature set leaves
  the constant leaf in place.
* :func:`linear_leaf_values` — the device-side per-row leaf evaluation
  (``const + coeff . x`` with the NaN fallback to the constant leaf value)
  shared verbatim by BOTH predict engines (ops/predict.py scan oracle and
  ops/predict_tensor.py), so tensor==scan ``array_equal`` holds for linear
  forests the same way it does for constant ones.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def leaf_feature_width(num_numeric: int, num_leaves: int) -> int:
    """The padded per-leaf feature-slot count FL, FIXED per config.

    A leaf's path can reference at most ``min(num_numeric, num_leaves-1)``
    distinct numeric features; padding to that bound (rounded to a
    multiple of 8, floor 8) keeps the jitted accumulation at ONE compiled
    shape for the whole run — per-tree widths would retrace the program
    every time a deeper path appeared (the steady-state recompile class
    the telemetry gate forbids)."""
    need = max(1, min(int(num_numeric), max(int(num_leaves) - 1, 1)))
    return max(8, ((need + 7) // 8) * 8)


def moment_chunk_rows(num_leaves: int, width: int) -> int:
    """Rows per accumulation chunk: the [W, (L+1)*P] one-hot design
    operand is the peak intermediate; bound it near 64 MB so HIGGS- and
    MSLR-shaped configs both fit comfortably beside the training state."""
    P = width + 1
    budget = (64 << 20) // max((num_leaves + 1) * P * 4, 1)
    return max(256, min(4096, budget))


@functools.partial(jax.jit, static_argnames=("num_leaves", "chunk"))
def accumulate_leaf_moments(X: jax.Array, leaf_idx: jax.Array,
                            grad: jax.Array, hess: jax.Array,
                            feat_tbl: jax.Array, *, num_leaves: int,
                            chunk: int
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-leaf normal-equation moments in ONE device pass.

    X: [N, D] raw float32 features (the linear_tree-retained matrix).
    leaf_idx: [N] int32 row->leaf map (searchsorted order from either
        learner; values in [0, L)).
    grad/hess: [N] float32 sampled gradients.
    feat_tbl: [L+1, FL] int32 per-leaf sorted numeric path features,
        ``-1`` on padding slots (row L is the dump row — all padding).

    Returns (XtHX [L+1, P, P] f32, Xtg [L+1, P] f32, cnt [L+1] f32) with
    P = FL + 1; slot P-1 is the intercept. Rows with NaN in any of their
    leaf's REAL feature slots contribute nothing (the reference's NaN
    fallback); their count is excluded so the eligibility check matches
    the per-leaf loop it replaces. Chunks accumulate in dataset-row order
    with a fixed trip count, so the result is independent of the learner
    that produced ``leaf_idx`` — the fused==serial bit-identity anchor.
    """
    N, D = X.shape
    Lp1, FL = feat_tbl.shape
    assert Lp1 == num_leaves + 1
    P = FL + 1
    nch = (N + chunk - 1) // chunk
    pad = nch * chunk - N
    Xp = jnp.concatenate([X, jnp.zeros((pad, D), X.dtype)]) if pad else X
    lp = jnp.concatenate(
        [leaf_idx.astype(jnp.int32),
         jnp.full(pad, num_leaves, jnp.int32)]) if pad else leaf_idx
    gp = jnp.concatenate([grad, jnp.zeros(pad, grad.dtype)]) if pad else grad
    hp = jnp.concatenate([hess, jnp.zeros(pad, hess.dtype)]) if pad else hess

    def body(carry, c):
        XtHX, Xtg, cnt = carry
        sl = lambda a: lax.dynamic_slice_in_dim(a, c * chunk, chunk)
        xw = sl(Xp)                            # [W, D]
        lw = jnp.clip(sl(lp), 0, num_leaves)   # [W]
        gw, hw = sl(gp), sl(hp)
        feats = feat_tbl[lw]                   # [W, FL]
        slot = feats >= 0
        vals = jnp.take_along_axis(xw, jnp.clip(feats, 0, D - 1), axis=1)
        nan_row = jnp.any(slot & jnp.isnan(vals), axis=1)
        ok = ~nan_row & (lw < num_leaves)
        v = jnp.where(slot & ~jnp.isnan(vals), vals, 0.0)
        v = jnp.concatenate([v, jnp.ones((chunk, 1), v.dtype)], axis=1)
        g = jnp.where(ok, gw, 0.0)
        h = jnp.where(ok, hw, 0.0)
        onehot = (lw[:, None] == jnp.arange(Lp1, dtype=jnp.int32)[None, :]
                  ) & ok[:, None]              # [W, L+1]
        oh = onehot.astype(jnp.float32)
        # the MXU contraction: per-leaf sum of h-weighted outer products
        # — one [ (L+1)*P x W ] @ [ W x P ] matmul per chunk
        # precision=HIGHEST: the TPU default for f32 operands is a single
        # bf16 pass, which the normal equations do not survive (CPU is
        # exact f32 either way)
        vh = v * h[:, None]                    # [W, P]
        XtHX = XtHX + jnp.einsum("wl,wp,wq->lpq", oh, vh, v,
                                 precision=lax.Precision.HIGHEST)
        Xtg = Xtg + jnp.einsum("wl,wp->lp", oh, v * g[:, None],
                               precision=lax.Precision.HIGHEST)
        cnt = cnt + jnp.sum(oh, axis=0)
        return (XtHX, Xtg, cnt), None

    init = (jnp.zeros((Lp1, P, P), jnp.float32),
            jnp.zeros((Lp1, P), jnp.float32),
            jnp.zeros(Lp1, jnp.float32))
    (XtHX, Xtg, cnt), _ = lax.scan(body, init,
                                   jnp.arange(nch, dtype=jnp.int32))
    return XtHX, Xtg, cnt


def solve_linear_leaves(XtHX: np.ndarray, Xtg: np.ndarray, cnt: np.ndarray,
                        nfeat: np.ndarray, linear_lambda: float
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """ONE batched regularized solve over the [L, P, P] moment stack.

    Host float64 (the coefficients are serialized into model text and
    replayed exactly — float64 solve output is the payload contract).
    ``linear_lambda`` rides the FEATURE diagonal only (the intercept is
    unregularized, matching the reference); padding slots get identity
    rows so the batch stays non-singular regardless of ragged per-leaf
    widths. Returns (sol [L, P] f64, ok [L] bool) where ``ok`` is the
    reference's eligibility: >= 1 path feature, more valid rows than
    unknowns, finite solution, non-singular system.
    """
    L, P = Xtg.shape
    FL = P - 1
    M = XtHX.astype(np.float64).copy()
    b = -Xtg.astype(np.float64)
    slots = np.arange(FL)[None, :] < nfeat[:, None]          # [L, FL]
    fd = np.arange(FL)
    M[:, fd, fd] += np.where(slots, float(linear_lambda), 0.0)
    # padding slots (and the intercept row of feature-less leaves) would be
    # all-zero rows; identity them so ONE batched solve covers the ragged
    # stack, then mask ineligible leaves after
    dead = np.concatenate([~slots, np.zeros((L, 1), bool)], axis=1)
    for j in range(P):
        rows = dead[:, j]
        if rows.any():
            M[rows, j, :] = 0.0
            M[rows, :, j] = 0.0
            M[rows, j, j] = 1.0
            b[rows, j] = 0.0
    try:
        sol = np.linalg.solve(M, b[..., None])[..., 0]
        solved = np.ones(L, bool)
    except np.linalg.LinAlgError:
        # rare (linear_lambda=0 + degenerate leaf): retry leaf-by-leaf so
        # one singular system only constant-falls ITS leaf
        sol = np.zeros((L, P), np.float64)
        solved = np.zeros(L, bool)
        for leaf in range(L):
            try:
                sol[leaf] = np.linalg.solve(M[leaf], b[leaf])
                solved[leaf] = True
            # graftlint: disable=R8 — a singular leaf system IS the signal:
            # solved[leaf] stays False and the caller keeps the constant
            # leaf (the reference's CalculateLinear fallback); there is
            # nothing to log per leaf
            except np.linalg.LinAlgError:
                pass
    ok = (solved & (nfeat >= 1) & (cnt >= nfeat + 1)
          & np.isfinite(sol).all(axis=1))
    return sol, ok


# ---------------------------------------------------------------------------
# device-side linear leaf evaluation (shared by BOTH predict engines)
# ---------------------------------------------------------------------------

def linear_leaf_values(x: jax.Array, leaf_flat: jax.Array,
                       leaf_value_flat: jax.Array,
                       leaf_const_flat: jax.Array,
                       leaf_feat_flat: jax.Array,
                       leaf_coeff_flat: jax.Array) -> jax.Array:
    """Per-row linear leaf outputs on device, f32.

    x: [R, D] raw float rows; leaf_flat: [R, K] flat leaf indices into the
    (tree-major) flattened leaf tables (K = trees evaluated per row: 1 for
    the scan engine's per-tree call, Tt for a tensor tile).
    leaf_*_flat: [T*L(, FL)] flattened per-leaf tables; feature ``-1``
    marks a padding slot.

    Semantics replicate ``models.tree.linear_leaf_outputs`` decision for
    decision: a row with NaN in any REAL slot of its leaf falls back to the
    constant ``leaf_value``; otherwise ``leaf_const + sum_j coeff_j * x_j``
    accumulated in fixed slot order (a fori_loop, so the f32 addition
    order — and therefore the bits — are identical wherever this runs:
    scan engine, tensor engine, any tile shape)."""
    R, K = leaf_flat.shape
    FL = leaf_feat_flat.shape[-1]
    D = x.shape[1]
    feats = leaf_feat_flat[leaf_flat]                  # [R, K, FL]
    slot = feats >= 0
    safe = jnp.clip(feats, 0, D - 1)
    vals = jnp.take_along_axis(x, safe.reshape(R, K * FL),
                               axis=1).reshape(R, K, FL)
    nan_row = jnp.any(slot & jnp.isnan(vals), axis=-1)           # [R, K]
    v = jnp.where(slot & ~jnp.isnan(vals), vals, jnp.float32(0.0))
    coeff = leaf_coeff_flat[leaf_flat]                 # [R, K, FL]

    def body(j, acc):
        return acc + coeff[..., j] * v[..., j]

    lin = lax.fori_loop(0, FL, body, leaf_const_flat[leaf_flat])
    return jnp.where(nan_row, leaf_value_flat[leaf_flat], lin)


# graftir IR contract
from ..analysis.ir.contracts import register_program

register_program(
    "linear.accumulate_leaf_moments", collective_free=True,
    notes="linear-leaf Gram/moment accumulation stays on device")
