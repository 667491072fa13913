"""Microbenchmark: histogram strategies on TPU.

Races the XLA one-hot contraction (ops.histogram.histogram_from_rows)
against the Pallas VMEM kernel (ops.hist_pallas.hist_pallas) across
(rows, bins) shapes — the TPU analog of TrainingShareStates timing col-wise
vs row-wise on the first iterations (reference: src/io/train_share_states.cpp).

Timing note: every timed call's output is folded into an accumulator that
is read back at the end, so the timed region ends device-complete.

Usage: python tools/bench_hist.py [P ...]
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from lambdagap_tpu.ops.histogram import histogram_from_rows  # noqa: E402
from lambdagap_tpu.ops.hist_pallas import hist_pallas, pack_gh8  # noqa: E402

NVAR = 4  # distinct inputs cycled to defeat any cross-call caching


def timeit(fn, variants, reps=12):
    acc = jnp.zeros((), jnp.float32) + jnp.sum(fn(*variants[0]))
    float(acc)  # warmup + compile
    acc = jnp.zeros((), jnp.float32)
    t0 = time.perf_counter()
    for i in range(reps):
        acc = acc + jnp.sum(fn(*variants[i % NVAR]))
    force = float(acc)
    return (time.perf_counter() - t0) / reps, force


def main():
    sizes = [int(a) for a in sys.argv[1:]] or [16384, 65536, 262144]
    F = 28
    rng = np.random.RandomState(0)
    for B in (64, 256):
        for P in sizes:
            vx, vp, vs = [], [], []
            for _ in range(NVAR):
                bins = jnp.asarray(rng.randint(0, B, (P, F), dtype=np.uint8))
                grad = jnp.asarray(rng.randn(P).astype(np.float32))
                hess = jnp.asarray(np.abs(rng.randn(P)).astype(np.float32))
                valid = jnp.ones(P, dtype=bool)
                gh8 = pack_gh8(grad, hess, valid)
                vx.append((bins, grad, hess, valid))
                vp.append((bins, gh8))
                vs.append((bins, gh8))

            t_x, _ = timeit(lambda b, g, h, v: histogram_from_rows(
                b, g, h, v, B, 4096, "split"), vx)
            t_p, _ = timeit(lambda b, g: hist_pallas(b, g, B), vp)
            cnt = jnp.int32(2048)
            t_s, _ = timeit(lambda b, g: hist_pallas(b, g, B, cnt), vs)
            h_x = histogram_from_rows(*vx[0], B, 4096, "split")
            h_p = hist_pallas(*vp[0], B)
            err = float(jnp.max(jnp.abs(h_x - h_p)) /
                        (1e-6 + float(jnp.max(jnp.abs(h_x)))))
            print(f"B={B:3d} P={P:7d}: onehot {t_x*1e3:8.3f} ms  "
                  f"pallas {t_p*1e3:8.3f} ms  speedup {t_x/t_p:5.2f}x  "
                  f"gated@2k {t_s*1e3:7.3f} ms  rel_err {err:.2e}",
                  flush=True)


if __name__ == "__main__":
    main()
