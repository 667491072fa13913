"""Pallas TPU histogram kernel (the default on TPU since tpu_hist_impl=auto
graduated it from prototype; docs/performance.md).

The performance-critical replacement for the XLA one-hot histogram
(see :mod:`lambdagap_tpu.ops.histogram`): the CUDA analog builds per-block
shared-memory histograms with atomics
(reference: src/treelearner/cuda/cuda_histogram_constructor.cu:20-130).
TPUs have no atomics; the idiomatic equivalent is a one-hot contraction on
the MXU — but done *inside* a kernel so the one-hot operand lives only in
VMEM, block by block, instead of being materialized to HBM by XLA (round
1's main bandwidth sink: at HIGGS shape the XLA intermediate is ~28x the
size of the uint8 rows it encodes).

Grid layout: ``(feature_blocks, row_blocks)`` with the row dimension inner.
Each ``[row_tile, feature_tile]`` grid cell accumulates into an explicit
f32/int32 VMEM scratch block (``acc_ref``); the HBM output block is written
ONCE, when the last row block of a feature block retires — the canonical
Pallas accumulate-then-flush pattern. Each feature contributes one
``[BLK, B]`` one-hot built in registers and contracted against the per-row
channel matrix; channels are the split-precision pair
(g_hi, g_lo, h_hi, h_lo, count, pad...) so a single bf16 matmul chain
yields ~f32-accurate sums (same trick as ops.histogram.gh_contract
'split'). The channel dim (8) rides the f32 sublane tile exactly.

Ragged leaf slices: the kernel masks rows past the dynamic ``count``
IN-KERNEL (a per-block row iota against the live count), so the tail of
the final row block may carry arbitrary junk — under ``tree_layout=sorted``
a leaf's window routinely runs into the next leaf's rows, which are NOT
zero-channel. Callers still zero the channels of rows excluded by a
bagging mask (that information is per-row, not a prefix).

Off TPU the kernel runs in Pallas interpret mode (pure XLA semantics, slow
but exact) — the CPU tier-1 parity path only; on TPU Mosaic compiles it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .histogram import split_bf16

HIST_C = 3

# int8 gradient levels fit signed int8: the hard cap on num_grad_quant_bins
# (config validation names the knob; see exact_accum_limit)
MAX_QUANT_BINS = 127


def exact_accum_limit(hist_impl: str) -> int:
    """Largest integer the quantized-histogram level accumulator holds
    exactly under ``hist_impl`` — the ONE source of the row-limit guard
    queried by both the fused learner and config validation (it used to be
    two diverging literals at models/fused_learner.py and here):

    * ``pallas`` — raw int8 levels accumulate in int32 inside the kernel:
      int32 max.
    * anything else — levels accumulate as integer-valued float32 in the
      one-hot contraction: 2**24, the last exactly-representable contiguous
      integer.
    """
    return 2**31 - 1 if hist_impl == "pallas" else 2**24


def _interpret() -> bool:
    """Mosaic compiles only for TPU; everywhere else the kernel runs in
    interpret mode (slow, exact — the CPU tier-1 parity path)."""
    return jax.default_backend() != "tpu"


def _hist_kernel(count_ref, bins_ref, gh_ref, out_ref, acc_ref, *,
                 num_bins: int, fblk: int, blk: int, nrb: int):
    """One ``[row_tile, feature_tile]`` grid cell. The channel dtype picks
    the arithmetic: bf16 channels -> bf16 one-hot, f32 accumulation;
    int8 channels (quantized levels) -> int8 one-hot, exact int32
    accumulation."""
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # compute is gated on the dynamic row count: a call padded to a large
    # static row budget only pays DMA for the dead blocks (the analog of the
    # CUDA kernel's early-exit on out-of-range rows). Rows past count in
    # the live boundary block are masked in-kernel — their bins/channels
    # may be junk (a sorted-layout window running into the next leaf).
    @pl.when(r * blk < count_ref[0])
    def _():
        bins = bins_ref[:].astype(jnp.int32)                # [BLK, FBLK]
        live = count_ref[0] - r * blk
        rmask = lax.broadcasted_iota(jnp.int32, (blk, 1), 0) < live
        gh = jnp.where(rmask, gh_ref[:], 0)                 # [BLK, 8]
        iota_b = lax.broadcasted_iota(jnp.int32, (1, num_bins), 1)
        B = num_bins
        for f in range(fblk):
            onehot = (bins[:, f:f + 1] == iota_b).astype(gh.dtype)
            acc_ref[:, f * B:(f + 1) * B] += lax.dot_general(
                gh, onehot,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=acc_ref.dtype)       # [8, B]

    # one HBM flush per [row_tile, feature_tile] grid column
    @pl.when(r == nrb - 1)
    def _():
        out_ref[:] = acc_ref[:]


# Block-size bounds Mosaic was shown to take on a v5e (docs/performance.md
# "Histogram kernel blocks"): the per-feature loop is unrolled, so the
# feature tile bounds compile time (~0.1 s per feature) as well as VMEM;
# past ~48k accumulator elements the compiler materializes the whole
# [BLK, FBLK*B] one-hot at once and the scoped allocation passes 64 MB.
_MAX_FBLK = 192
_MAX_ACC_ELEMS = 192 * 256
_LANES = 128


def _pick_blocks(F: int, B: int, P: int):
    """Row block 1024 (2048 for small feature counts); the feature tile is
    the whole feature axis when it fits the bounds above, else one
    128-lane tile of the bins block (the only narrower tile Mosaic's
    (8, 128) block rule admits; the feature axis is padded to it)."""
    blk = 2048 if F * B <= 8192 else 1024
    blk = min(blk, max(256, P))
    fblk = F if F <= min(_MAX_FBLK, _MAX_ACC_ELEMS // B) else _LANES
    return blk, fblk


def row_tiles(P: int, F: int, B: int) -> int:
    """What one kernel call over ``[P, F]`` rows of ``B`` bins works
    through: its padded rows times its feature tiles (``Fp / fblk``), the
    telemetry's ``hist_row_tiles`` per call (``_hist_call`` pads both)."""
    blk, fblk = _pick_blocks(F, B, P)
    return -(-P // blk) * blk * -(-F // fblk)


def _vmem_limit(blk: int, fblk: int, B: int, bins_itemsize: int) -> int:
    """Scoped-VMEM request from the block arithmetic: the accumulator, the
    double-buffered output/bins/channel blocks, and the worst case the
    compiler was seen to want — every feature's one-hot live at once, one
    byte per element. Never below Mosaic's 16 MiB default, never above
    what a v5e core has."""
    acc = 8 * fblk * B * 4
    io = 2 * (acc + blk * fblk * bins_itemsize + blk * _LANES * 2)
    need = acc + io + blk * fblk * B
    return int(min(max(need * 5 // 4, 16 << 20), 100 << 20))


KERNEL_NAME = "lg_hist"


def _hist_call(bins: jax.Array, gh: jax.Array, num_bins: int, count,
               acc_dtype) -> jax.Array:
    """Pad to the block grid, run the kernel, return ``[8, F, B]`` channel
    sums in ``acc_dtype``."""
    P, F = bins.shape
    B = num_bins
    blk, fblk = _pick_blocks(F, B, P)
    if P % blk != 0:
        pad = blk - P % blk
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        gh = jnp.pad(gh, ((0, pad), (0, 0)))
        P += pad
    Fp = ((F + fblk - 1) // fblk) * fblk
    if Fp != F:
        # padded feature columns produce junk histograms, sliced off below
        bins = jnp.pad(bins, ((0, 0), (0, Fp - F)))
    count = jnp.asarray([P if count is None else count], jnp.int32)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_bins=B, fblk=fblk, blk=blk,
                          nrb=P // blk),
        out_shape=jax.ShapeDtypeStruct((8, Fp * B), acc_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Fp // fblk, P // blk),
            in_specs=[
                pl.BlockSpec((blk, fblk), lambda f, r, c: (r, f),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((blk, 8), lambda f, r, c: (r, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((8, fblk * B), lambda f, r, c: (0, f),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((8, fblk * B), acc_dtype)],
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(blk, fblk, B,
                                         bins.dtype.itemsize)),
        interpret=_interpret(),
        # the kernel's name in a profiler trace: selects the kernel alone,
        # apart from the slice/unpack/pack that feed it under ``histogram``
        name=KERNEL_NAME,
    )(count, bins, gh)
    return out.reshape(8, Fp, B)[:, :F]


@functools.partial(jax.jit, static_argnames=("num_bins",))
def hist_pallas(bins: jax.Array, gh8: jax.Array, num_bins: int,
                count=None) -> jax.Array:
    """Histogram of a row block via the Pallas kernel.

    bins : uint8/uint16 [P, F] binned rows — either a gathered block or a
           contiguous sorted-layout leaf slice; rows past ``count`` may
           hold anything (masked in-kernel)
    gh8  : bf16 [P, 8] — (g_hi, g_lo, h_hi, h_lo, count, 0, 0, 0),
           see :func:`pack_gh8`; bagging-masked rows must carry zero
           channels (the count mask only covers the ragged tail)
    count: optional dynamic number of live rows (<= P); blocks past it skip
           compute, so heavily padded calls cost ~DMA only
    Returns f32 [F, B, 3] (sum_grad, sum_hess, count).
    """
    out = _hist_call(bins, gh8, num_bins, count, jnp.float32)
    sg = out[0] + out[1]
    sh = out[2] + out[3]
    cnt = out[4]
    return jnp.stack([sg, sh, cnt], axis=-1)                # [F, B, 3]


def pack_gh8(grad: jax.Array, hess: jax.Array, valid: jax.Array) -> jax.Array:
    """Split-precision channel packing for :func:`hist_pallas`."""
    g = jnp.where(valid, grad, 0.0)
    h = jnp.where(valid, hess, 0.0)
    g_hi, g_lo = split_bf16(g)
    h_hi, h_lo = split_bf16(h)
    cnt = valid.astype(jnp.bfloat16)
    zero = jnp.zeros_like(cnt)
    return jnp.stack([g_hi, g_lo, h_hi, h_lo, cnt, zero, zero, zero], axis=1)


# ---------------------------------------------------------------------------
# quantized-gradient path: int8 one-hot matmul with exact int32 accumulation
# (reference: src/treelearner/gradient_discretizer.hpp + the 16/32-bit
# integer histogram variants of feature_histogram.hpp)
#
# Measured (round 2, 500k rows x 255 leaves, one throttled chip): AUC parity
# with fp32 at qb=64, per-iter 233ms vs 216ms fp32 — the discretize pass
# costs more than the int8 matmul saves while per-split fixed costs
# dominate. Expected to win once histogram FLOPs are the bottleneck
# (larger N/F or full-speed MXU).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_bins",))
def hist_pallas_q(bins: jax.Array, ghq8: jax.Array, num_bins: int,
                  count=None) -> jax.Array:
    """Quantized histogram: int8 channels, exact int32 accumulation.

    ghq8: int8 [P, 8] — (g_q, h_q, in_bag, 0...), see :func:`pack_ghq8`.
    Rows past ``count`` are masked in-kernel (sorted-layout windows may
    carry the next leaf's rows there). Returns int32 [F, B, 3]
    (sum_gq, sum_hq, count).
    """
    out = _hist_call(bins, ghq8, num_bins, count, jnp.int32)
    return jnp.stack([out[0], out[1], out[2]], axis=-1)     # [F, B, 3] i32


def pack_ghq8(gq: jax.Array, hq: jax.Array, valid: jax.Array) -> jax.Array:
    """Channel packing for :func:`hist_pallas_q` (int8 quantized grads)."""
    v8 = valid.astype(jnp.int8)
    g = gq.astype(jnp.int8) * v8
    h = hq.astype(jnp.int8) * v8
    zero = jnp.zeros_like(v8)
    return jnp.stack([g, h, v8, zero, zero, zero, zero, zero], axis=1)


def quantize_gradients(grad: jax.Array, hess: jax.Array, key,
                       num_bins: int, stochastic: bool = True,
                       gmax=None, hmax=None):
    """Discretize grad/hess to signed int8 levels with stochastic rounding
    (reference: GradientDiscretizer::DiscretizeGradients,
    src/treelearner/gradient_discretizer.cpp). Returns
    (g_q i8, h_q i8, g_scale, h_scale).

    ``gmax``/``hmax`` override the locally-measured extrema — the
    pre-partitioned multi-process path passes GLOBAL maxima so every rank
    derives identical scales (the distributed analog of the reference
    syncing gradient scales before histogram reduction)."""
    qb = max(2, min(num_bins, MAX_QUANT_BINS))
    half = max(qb // 2, 1)
    if gmax is None:
        gmax = jnp.maximum(jnp.max(jnp.abs(grad)), 1e-12)
    if hmax is None:
        hmax = jnp.maximum(jnp.max(hess), 1e-12)
    gs = gmax / half
    hs = hmax / qb
    g = grad / gs
    h = hess / hs
    if stochastic:
        import jax.random as jrandom
        k1, k2 = jrandom.split(key)
        g = jnp.floor(g + jrandom.uniform(k1, g.shape))
        h = jnp.floor(h + jrandom.uniform(k2, h.shape))
    else:
        g = jnp.round(g)
        h = jnp.round(h)
    gq = jnp.clip(g, -MAX_QUANT_BINS, MAX_QUANT_BINS).astype(jnp.int8)
    hq = jnp.clip(h, 0, MAX_QUANT_BINS).astype(jnp.int8)
    return gq, hq, gs, hs
