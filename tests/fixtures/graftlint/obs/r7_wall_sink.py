"""R7 fixture: a clock delta handed to a recorder as a call argument.

A wall noted into a stats sink is later divided into rates, so an unsynced
bracket poisons every rate built on it: the bad bracket times only the
enqueue of the dispatch it wraps. Good brackets end device-complete
(device_get / block_until_ready) before the clock is read.
"""
import time

import jax
import jax.numpy as jnp


def bad_wall_join(plane, x):
    t0 = time.perf_counter()
    y = jnp.tanh(x)
    plane.note_wall("predict", time.perf_counter() - t0)  # BAD:R7
    return y


def good_device_complete_wall(plane, x):
    t0 = time.perf_counter()
    y = jax.device_get(jnp.tanh(x))
    plane.note_wall("predict", time.perf_counter() - t0)
    return y


def good_blocked_window(plane, scorer, dev):
    # the predict_stream pump's shape: the scorer result is blocked on
    # inside the bracket, so the noted window wall is device-complete
    t0 = time.perf_counter()
    scorer(dev).block_until_ready()
    plane.note_wall("predict_stream", time.perf_counter() - t0)


def suppressed_dispatch_wall(plane, x):
    t0 = time.perf_counter()
    y = jnp.sum(x)
    # graftlint: disable=R7 — measures enqueue latency on purpose (a
    # dispatch-overhead counter, not a roofline wall)
    plane.note_wall("dispatch_only", time.perf_counter() - t0)
    return y
