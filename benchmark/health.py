"""Chip-health microbench, run in set-up and printed before the result line:
a sick or shared chip shows here before it shows as a slow cell. Both loops
donate their operand, so the device never holds more than two of the 256 MiB
arrays: the host runs ahead of the device, and without donation the eight
queued outputs (9 x 256 MiB) set the run's memory peak above what training
holds (2.450 GB read in both cells, my chip run, PR 25)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def microbench(reps: int = 8) -> dict:
    x = jnp.ones((64 * 1024 * 1024,), jnp.float32)
    a = jnp.full((4096, 4096), 1.0 / 4096, jnp.bfloat16)  # b @ a == b
    b = jnp.ones((4096, 4096), jnp.bfloat16)
    copy = jax.jit(lambda v: v + 1.0, donate_argnums=0)
    mm = jax.jit(lambda p, q: (p @ q).astype(jnp.bfloat16), donate_argnums=0)
    x, b = copy(x), mm(b, a)
    jax.block_until_ready((x, b))
    t = time.perf_counter()
    for _ in range(reps):
        x = copy(x)
    jax.block_until_ready(x)
    copy_s = (time.perf_counter() - t) / reps
    t = time.perf_counter()
    for _ in range(reps):
        b = mm(b, a)
    jax.block_until_ready(b)
    mm_s = (time.perf_counter() - t) / reps
    return {"copy_gb_per_s": 2 * x.nbytes / copy_s / 1e9,
            "bf16_tflop_per_s": 2 * 4096 ** 3 / mm_s / 1e12}
