"""One partition window in isolation, at the benchmark cell's shape: how a
window's rows (``[W]`` i32 ids + ``[W, SW]`` u32 payload) reach their side.

    chiprun -- python3 tools/partition_window_bench.py [variant ...]

Variants: ``scatter`` (the two ``.at[pos].set`` writes the fused tree
program had up to PR 26), ``sort`` (one ``lax.sort`` keyed on the local
position, then two masked window writes), ``network`` (what the program has
now: ``ops.partition.route_window``; PERF.md section 6, PR 27,
has what else was tried). Every variant has to leave the same buffers; the script checks that before it reports a time. Prints one JSON
line: ms per window and ns per row of each variant.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from lambdagap_tpu.ops.partition import route_window, write_front

W = int(os.environ.get("PWB_W", 32768))
SW = int(os.environ.get("PWB_SW", 9))
N = int(os.environ.get("PWB_N", 10_500_000))
TRIPS = int(os.environ.get("PWB_TRIPS", 200))
LIVE = W - 37                      # a ragged last window, like most are


def _decide(ids):
    """A split that sends about half of the lanes left, in no pattern."""
    h = (ids.astype(jnp.uint32) * jnp.uint32(2654435761)) >> 13
    lane = jnp.arange(W, dtype=jnp.int32)
    valid = lane < LIVE
    gl = ((h & 1) == 1) & valid
    cums = jnp.cumsum(gl.astype(jnp.int32))
    return lane, valid, gl, cums, cums[W - 1]


def _scatter(pbuf, sbuf, rows, dw, lcur, rcur):
    lane, valid, gl, cums, nl = _decide(rows)
    prefix_valid = jnp.minimum(lane + 1, LIVE)
    pos = jnp.where(gl, lcur + cums - 1,
                    jnp.where(valid, rcur - (prefix_valid - cums), N))
    return (pbuf.at[pos].set(rows, mode="drop"),
            sbuf.at[pos].set(dw, mode="drop"))


def _two_writes(pbuf, sbuf, lrows, ldw, rrows, rdw, lcur, rcur, nl, nr):
    """``rrows`` / ``rdw``: the rights at the front, in reversed lane order."""
    pbuf = write_front(pbuf, lrows, lcur, nl)
    pbuf = write_front(pbuf, rrows, rcur - nr, nr)
    sbuf = write_front(sbuf, ldw, lcur, nl)
    sbuf = write_front(sbuf, rdw, rcur - nr, nr)
    return pbuf, sbuf


def _network(pbuf, sbuf, rows, dw, lcur, rcur):
    _, valid, gl, _, _ = _decide(rows)
    return route_window((pbuf, sbuf), (rows, dw), gl, ~gl & valid, lcur,
                        rcur)[0]


def _sort(pbuf, sbuf, rows, dw, lcur, rcur):
    lane, valid, gl, cums, nl = _decide(rows)
    nr = LIVE - nl
    prefix_valid = jnp.minimum(lane + 1, LIVE)
    # local target: lefts 0..nl-1 in lane order, then the rights REVERSED
    # (nl .. nl+nr-1 holds what [rcur-nr, rcur) gets), then the dead lanes
    rank_r = prefix_valid - cums                       # 1-based among rights
    key = jnp.where(gl, cums - 1,
                    jnp.where(valid, nl + nr - rank_r, W + lane))
    out = lax.sort((key, rows) + tuple(dw[:, j] for j in range(SW)),
                   num_keys=1)
    srt_rows, srt_dw = out[1], jnp.stack(out[2:], axis=1)
    # rights start at local nl: bring them to the front of a window
    assert srt_rows.shape == (W,)         # nl <= W: neither slice clamps
    rrows = lax.dynamic_slice(jnp.concatenate([srt_rows, srt_rows]), (nl,),
                              (W,))
    rdw = lax.dynamic_slice(jnp.concatenate([srt_dw, srt_dw]), (nl, 0),
                            (W, SW))
    return _two_writes(pbuf, sbuf, srt_rows, srt_dw, rrows, rdw, lcur, rcur,
                       nl, nr)


VARIANTS = {"scatter": _scatter, "sort": _sort, "network": _network}


def make(variant):
    step = VARIANTS[variant]
    span = (N // W) * W

    def run(perm, srows, pbuf, sbuf):
        # every start is < span <= N and the buffers carry a W-row tail pad
        assert perm.shape[0] == N + W and srows.shape[0] == N + W
        def body(i, s):
            pbuf, sbuf = s
            start = (i * (7 * W)) % span
            rows = lax.dynamic_slice(perm, (start,), (W,))
            dw = lax.dynamic_slice(srows, (start, 0), (W, SW))
            return step(pbuf, sbuf, rows, dw, start, start + LIVE)
        return lax.fori_loop(0, TRIPS, body, (pbuf, sbuf))

    return jax.jit(run, donate_argnums=(2, 3))


def main(argv):
    names = argv or list(VARIANTS)
    dev = jax.devices()[0]
    key = jax.random.PRNGKey(0)
    perm = jax.random.permutation(key, N + W).astype(jnp.int32)
    srows = jax.random.bits(key, (N + W, SW), jnp.uint32)
    report = {"device": dev.device_kind, "platform": dev.platform,
              "W": W, "SW": SW, "N": N, "trips": TRIPS}
    sums = {}
    for name in names:
        fn = make(name)
        t0 = time.perf_counter()
        out = jax.block_until_ready(
            fn(perm, srows, jnp.zeros_like(perm), jnp.zeros_like(srows)))
        first = time.perf_counter() - t0
        # the first N rows are the contract (row N is the scatter's dump)
        sums[name] = (np.asarray(jnp.sum(out[0][:N].astype(jnp.uint32)
                                         * jnp.arange(N, dtype=jnp.uint32))),
                      np.asarray(jnp.sum(out[1][:N]
                                         * jnp.arange(N, dtype=jnp.uint32
                                                      )[:, None], axis=0)))
        best = None
        for _ in range(3):
            bufs = jax.block_until_ready(
                (jnp.zeros_like(perm), jnp.zeros_like(srows)))
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(perm, srows, *bufs))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        del out, bufs
        report[name] = {"first_call_s": round(first, 2),
                        "ms_per_window": best / TRIPS * 1e3,
                        "ns_per_row": best / TRIPS / W * 1e9}
        print(name, report[name], flush=True)
    ref = sums[names[0]]
    report["same_buffers"] = all(
        np.array_equal(s[0], ref[0]) and np.array_equal(s[1], ref[1])
        for s in sums.values())
    print(json.dumps(report))
    return 0 if report["same_buffers"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
