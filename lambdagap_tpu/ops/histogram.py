"""Histogram construction over the binned matrix.

The TPU replacement for the reference's histogram kernels
(reference: src/io/dense_bin.hpp:99-141 ConstructHistogramInner on CPU;
src/treelearner/cuda/cuda_histogram_constructor.cu:20-130 on CUDA).

TPUs have no fast scatter-add, so instead of atomics the default strategy is a
one-hot expansion contracted on the MXU: for a block of rows, build
``onehot[r, f*B + b] = (bin[r, f] == b)`` and contract with the per-row
``(grad, hess, 1)`` channels — a ``[C, R] @ [R, F*B]`` matmul whose N dimension
(total bins) is large, keeping the systolic array busy. Blocks are accumulated
with ``lax.scan`` so the one-hot tensor never materializes in HBM.

Histograms are ``float32 [F, B, 3]`` with channels (sum_grad, sum_hess, count).
The reference approximates per-bin counts by ``RoundInt(hess * cnt_factor)``
(src/treelearner/feature_histogram.hpp:843); we track exact counts in a third
channel — the MXU pads the channel dim anyway, so it is free.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

HIST_CHANNELS = 3  # (sum_grad, sum_hess, count)


def split_bf16(x: jax.Array):
    """``x = hi + lo`` with both halves exact in bf16.

    ``hi`` comes from ``lax.reduce_precision``, not from a convert
    round-trip: XLA's TPU pipeline treats ``f32 -> bf16 -> f32`` as
    removable excess precision, which turns ``x - f32(bf16(x))`` into an
    exact zero and silently drops the low half (measured on a v5e, PR 21:
    split-precision sums came back with bf16 error, ~1e-3 relative). On
    CPU the two spellings round identically."""
    hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def gh_contract(gh: jax.Array, onehot2d: jax.Array,
                precision: str) -> jax.Array:
    """Contract per-row (grad, hess, count) channels with a one-hot matrix on
    the MXU: ``[C, R] @ [R, FB] -> [C, FB]`` float32.

    precision (config ``tpu_hist_precision``):
      * ``split`` — two-term bf16 decomposition ``g = hi + lo`` with
        ``hi = bf16(g)``, ``lo = bf16(g - hi)``; both halves ride one fused
        matmul (channel dim 2C) and are summed after, recovering ~f32
        accuracy at bf16 MXU throughput. The reference accumulates f32/double
        histograms (src/io/bin.h HistogramSumReducer), so this is the parity
        default.
      * ``bf16`` — raw bf16 cast of the operands (fastest, ~2^-9 relative
        error per gradient).
      * ``f32`` — full float32 matmul.
    """
    if precision not in ("split", "bf16", "f32"):
        raise ValueError(f"tpu_hist_precision must be split/bf16/f32, "
                         f"got {precision!r}")
    C = gh.shape[1]
    if precision == "f32":
        return lax.dot_general(
            gh.T, onehot2d.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST,   # TPU default is one bf16 pass
            preferred_element_type=jnp.float32)
    if precision == "bf16":
        return lax.dot_general(
            gh.astype(jnp.bfloat16).T, onehot2d,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    hi, lo = split_bf16(gh)
    ghs = jnp.concatenate([hi, lo], axis=1)          # [R, 2C]
    part = lax.dot_general(
        ghs.T, onehot2d,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return part[:C] + part[C:]


def gather_leaf_rows(perm: jax.Array, begin: jax.Array, count: jax.Array,
                     padded_size: int):
    """Row indices of one leaf from the partition permutation array.

    Analog of reading ``indices_[leaf_begin_ .. leaf_begin_+leaf_count_]``
    (reference: src/treelearner/data_partition.hpp:21-63), padded to a static
    size so downstream shapes are jit-stable. Out-of-range lanes are clamped
    (callers mask them with ``valid``).
    """
    lane = jnp.arange(padded_size, dtype=jnp.int32)
    idx = jnp.clip(begin + lane, 0, perm.shape[0] - 1)
    rows = perm[idx]
    valid = lane < count
    return rows, valid


@functools.partial(jax.jit, static_argnames=("num_bins", "rows_per_block",
                                             "precision"))
def histogram_from_rows(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                        valid: jax.Array, num_bins: int,
                        rows_per_block: int = 4096,
                        precision: str = "split") -> jax.Array:
    """Histogram of a padded row block.

    Parameters
    ----------
    bins : uint8/uint16 [P, F] — gathered binned rows
    grad, hess : float32 [P]
    valid : bool [P] — padding mask
    num_bins : static B (uniform per-feature bin budget, e.g. 256)

    Returns float32 [F, B, 3].
    """
    P, F = bins.shape
    B = num_bins
    gh = jnp.stack([grad * valid, hess * valid,
                    valid.astype(jnp.float32)], axis=1)  # [P, 3]

    block = min(rows_per_block, P)
    if P % block != 0:
        # pad rows to a block multiple; masked lanes contribute zeros
        pad = block - P % block
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        gh = jnp.pad(gh, ((0, pad), (0, 0)))
        P += pad
    nblocks = P // block

    bins_blocks = bins.reshape(nblocks, block, F)
    gh_blocks = gh.reshape(nblocks, block, HIST_CHANNELS)
    bin_iota = jnp.arange(B, dtype=bins.dtype)

    def body(acc, xs):
        b_blk, gh_blk = xs
        # [R, F, B] one-hot, built in registers/VMEM and fed straight to the MXU
        onehot = (b_blk[:, :, None] == bin_iota).astype(jnp.bfloat16)
        onehot2d = onehot.reshape(block, F * B)
        # [C, R] @ [R, F*B] -> [C, F*B]: N dim is big -> good MXU tiling
        part = gh_contract(gh_blk, onehot2d, precision)
        return acc + part, None

    # zeros-of-inputs trick keeps the carry's device-varying annotation
    # consistent when this runs inside shard_map (per-shard partial hists)
    init = (jnp.zeros((HIST_CHANNELS, F * B), dtype=jnp.float32)
            + gh[0, 0] * 0 + bins[0, 0].astype(jnp.float32) * 0)
    acc, _ = lax.scan(body, init, (bins_blocks, gh_blocks))
    return acc.reshape(HIST_CHANNELS, F, B).transpose(1, 2, 0)


@functools.partial(jax.jit,
                   static_argnames=("padded_size", "num_bins",
                                    "rows_per_block", "precision"))
def leaf_histogram(x_binned: jax.Array, perm: jax.Array, grad: jax.Array,
                   hess: jax.Array, begin: jax.Array, count: jax.Array,
                   padded_size: int, num_bins: int,
                   rows_per_block: int = 4096,
                   row_mask: Optional[jax.Array] = None,
                   precision: str = "split") -> jax.Array:
    """Histogram for one leaf's rows: gather + block-accumulate.

    Analog of ``SerialTreeLearner::ConstructHistograms`` for the smaller leaf
    (reference: src/treelearner/serial_tree_learner.cpp:408-476); the larger
    sibling is obtained by subtraction (:func:`subtract_histogram`).

    ``row_mask`` (bool [N]) marks in-bag rows when bagging/GOSS is active so
    the count channel only counts sampled rows (out-of-bag rows still live in
    the partition; their grad/hess are pre-zeroed by the sample strategy).
    """
    rows, valid = gather_leaf_rows(perm, begin, count, padded_size)
    if row_mask is not None:
        valid = valid & row_mask[rows]
    bins = x_binned[rows]
    g = grad[rows]
    h = hess[rows]
    return histogram_from_rows(bins, g, h, valid, num_bins, rows_per_block,
                               precision)


@functools.partial(jax.jit, static_argnames=("padded_size", "num_bins",
                                             "rows_per_block", "precision"))
def leaf_histogram_sorted(x_sorted: jax.Array, gh_sorted: jax.Array,
                          begin: jax.Array, count: jax.Array,
                          padded_size: int, num_bins: int,
                          rows_per_block: int = 4096,
                          precision: str = "split") -> jax.Array:
    """Histogram for one leaf under ``tree_layout=sorted``: the leaf's rows
    occupy a contiguous position slice of the physically reordered matrix
    (maintained by :func:`..ops.partition.split_partition_sorted`), so the
    read is a consecutive-index window — no row gather through the
    permutation (docs/performance.md).

    gh_sorted: f32 [N, 2 or 3] — (grad, hess[, in-bag]) permuted alongside
    the rows; the optional third channel carries the bagging mask so the
    count channel matches the gather path's ``row_mask`` semantics.
    """
    lane = jnp.arange(padded_size, dtype=jnp.int32)
    idx = jnp.clip(begin + lane, 0, x_sorted.shape[0] - 1)
    valid = lane < count
    bins = x_sorted[idx]
    gh = gh_sorted[idx]
    if gh_sorted.shape[1] > 2:
        valid = valid & (gh[:, 2] > 0)
    return histogram_from_rows(bins, gh[:, 0], gh[:, 1], valid, num_bins,
                               rows_per_block, precision)


def unbundle_hist(hist_b: jax.Array, src: jax.Array, kind: jax.Array,
                  parent_g, parent_h, parent_c) -> jax.Array:
    """Expand a bundled-column histogram back to per-feature space.

    hist_b: f32 [C, Bb, 3] histogram over EFB-bundled columns.
    src/kind: the precomputed gather map (data.bundling.unbundle_map) —
    COPY bins gather from the flattened bundle histogram; a bundled
    feature's default bin is the leaf residual ``total - sum(COPY bins)``
    (the analog of FixHistogram's sum patching, reference:
    src/treelearner/feature_histogram.hpp GatherInfoForThreshold).
    Returns f32 [F, B, 3].
    """
    flat = hist_b.reshape(-1, HIST_CHANNELS)
    out = flat[src]                                     # [F, B, 3]
    copy = (kind == 1)[..., None]
    out = jnp.where(copy, out, 0.0)
    nzsum = jnp.sum(out, axis=1)                        # [F, 3]
    totals = jnp.stack([parent_g, parent_h, parent_c])  # [3]
    resid = totals[None, :] - nzsum                     # [F, 3]
    return jnp.where((kind == 2)[..., None], resid[:, None, :], out)


def subtract_histogram(parent_hist: jax.Array, child_hist: jax.Array) -> jax.Array:
    """The histogram-subtraction trick
    (reference: src/treelearner/feature_histogram.hpp ``Subtract``)."""
    return parent_hist - child_hist


def write_children(hist: jax.Array, parent: jax.Array, hist_small: jax.Array,
                   small_is_left: jax.Array, wl: jax.Array, wn: jax.Array):
    """Replace leaf ``parent``'s histogram in the carried state
    ``hist [L + 1, C, Bb, 3]`` by its two children's: the left one at slot
    ``wl``, the right one at slot ``wn`` (the right wins where they
    coincide), the larger child by subtraction of ``hist_small``.

    The parent's slice is read once and both children are materialised
    before either write, so neither write reads the state again and both
    land in place. Fused into the writes, the second one re-read the
    parent out of the loop's incoming carry after the first had changed
    it, and the compiler copied the whole state twice a split to keep that
    carry intact. Returns ``(hist, hist_left, hist_right)``."""
    # each child fills exactly one slot; ``parent``, ``wl`` and ``wn`` are
    # leaf indices or the dump slot L, all rows of ``hist``
    assert hist.shape[1:] == hist_small.shape, (hist.shape, hist_small.shape)
    hist_large = subtract_histogram(
        lax.dynamic_index_in_dim(hist, parent, keepdims=False), hist_small)
    hist_left = jnp.where(small_is_left, hist_small, hist_large)
    hist_right = jnp.where(small_is_left, hist_large, hist_small)
    hist_left, hist_right = lax.optimization_barrier((hist_left, hist_right))
    at = (0,) * hist_small.ndim
    hist = lax.dynamic_update_slice(hist, hist_left[None], (wl,) + at)
    hist = lax.dynamic_update_slice(hist, hist_right[None], (wn,) + at)
    return hist, hist_left, hist_right


# ---------------------------------------------------------------------------
# data_residency=stream kernels (docs/performance.md "Out-of-core"): the
# binned matrix lives in host shards; windows arrive as UPLOADED buffers
# while grad/hess/mask stay device-resident. Accumulation replicates the
# resident kernels' order window-for-window (same gh_contract shapes, same
# sequential f32 adds), so streamed histograms are bit-identical.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_bins", "precision"))
def histogram_block_acc(acc: jax.Array, bins_blk: jax.Array,
                        grad: jax.Array, hess: jax.Array,
                        row_mask: Optional[jax.Array], start: jax.Array,
                        num_bins: int, precision: str = "split") -> jax.Array:
    """One streamed block of the root histogram: ``acc + contract(block)``.

    ``bins_blk`` is the uploaded rows ``[start, start+block)`` in dataset
    order (host zero-pads the ragged tail, matching the resident
    ``histogram_from_rows`` tail padding); grad/hess/mask index on device.
    Carrying ``acc`` across dispatches reproduces the resident scan's
    sequential block adds exactly.
    """
    block, F = bins_blk.shape
    B = num_bins
    N = grad.shape[0]
    lane = jnp.arange(block, dtype=jnp.int32)
    idxg = start + lane
    in_range = idxg < N
    idx = jnp.clip(idxg, 0, N - 1)
    valid = in_range if row_mask is None else in_range & row_mask[idx]
    vf = valid.astype(jnp.float32)
    # same construction as the resident gh matrix (grad * valid), with the
    # tail rows forced to exact 0.0 like jnp.pad's zeros
    g = jnp.where(in_range, grad[idx] * vf, 0.0)
    h = jnp.where(in_range, hess[idx] * vf, 0.0)
    gh_blk = jnp.stack([g, h, vf], axis=1)
    bin_iota = jnp.arange(B, dtype=bins_blk.dtype)
    onehot = (bins_blk[:, :, None] == bin_iota).astype(jnp.bfloat16)
    part = gh_contract(gh_blk, onehot.reshape(block, F * B), precision)
    return acc + part


def finish_histogram_acc(acc: jax.Array, num_features: int,
                         num_bins: int) -> jax.Array:
    """[3, F*B] streamed accumulator -> the [F, B, 3] histogram layout."""
    return acc.reshape(HIST_CHANNELS, num_features,
                       num_bins).transpose(1, 2, 0)


@functools.partial(jax.jit, static_argnames=("num_bins", "rows_per_block",
                                             "precision"))
def leaf_histogram_streamed(bins: jax.Array, rows: jax.Array,
                            grad: jax.Array, hess: jax.Array,
                            count: jax.Array, num_bins: int,
                            rows_per_block: int = 4096,
                            row_mask: Optional[jax.Array] = None,
                            precision: str = "split") -> jax.Array:
    """:func:`leaf_histogram` with the row gather done on the HOST: the
    leaf's binned rows arrive uploaded (``bins``, padded like
    ``gather_leaf_rows`` pads) together with their dataset row indices
    (``rows``) so grad/hess/mask still index device-resident arrays.
    Identical values into the same :func:`histogram_from_rows` → identical
    histogram."""
    P = bins.shape[0]
    lane = jnp.arange(P, dtype=jnp.int32)
    valid = lane < count
    if row_mask is not None:
        valid = valid & row_mask[rows]
    return histogram_from_rows(bins, grad[rows], hess[rows], valid,
                               num_bins, rows_per_block, precision)


@functools.partial(jax.jit, static_argnames=("num_bins", "rows_per_block",
                                             "precision"))
def leaf_histogram_sorted_streamed(bins: jax.Array, gh_sorted: jax.Array,
                                   begin: jax.Array, count: jax.Array,
                                   num_bins: int,
                                   rows_per_block: int = 4096,
                                   precision: str = "split") -> jax.Array:
    """:func:`leaf_histogram_sorted` with the contiguous window read done
    on the HOST (the sorted payload lives in host shards under stream
    residency); the gradient channels stay device-resident and slice at
    the same clamped positions as the resident kernel."""
    P = bins.shape[0]
    lane = jnp.arange(P, dtype=jnp.int32)
    idx = jnp.clip(begin + lane, 0, gh_sorted.shape[0] - 1)
    valid = lane < count
    gh = gh_sorted[idx]
    if gh_sorted.shape[1] > 2:
        valid = valid & (gh[:, 2] > 0)
    return histogram_from_rows(bins, gh[:, 0], gh[:, 1], valid, num_bins,
                               rows_per_block, precision)


@functools.partial(jax.jit, static_argnames=("num_bins", "rows_per_block",
                                             "precision"))
def full_histogram(x_binned: jax.Array, grad: jax.Array, hess: jax.Array,
                   sample_mask: Optional[jax.Array], num_bins: int,
                   rows_per_block: int = 4096,
                   precision: str = "split") -> jax.Array:
    """Histogram over the whole dataset (root node), optionally bagging-masked."""
    N = x_binned.shape[0]
    valid = (jnp.ones(N, dtype=bool) if sample_mask is None
             else sample_mask.astype(bool))
    return histogram_from_rows(x_binned, grad, hess, valid, num_bins,
                               rows_per_block, precision)


# graftir IR contracts (`python -m lambdagap_tpu.analysis --ir`)
from ..analysis.ir.contracts import register_program

register_program(
    "histogram.full_histogram", collective_free=True,
    notes="root histogram over the full training slab; fixed shape")
register_program(
    "histogram.leaf_histogram", collective_free=True, max_traces=5,
    notes="host-serial per-leaf slices retrace per pow2 row bucket by "
          "design (the fused paths are where one-trace is contractual); "
          "the 1603-row scenario exercises 3 buckets")
