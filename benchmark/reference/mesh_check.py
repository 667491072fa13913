"""The ``criteo-dp4`` configuration's copy of the plain reference.

It follows the program's trees exactly as ``gbdt_check.check`` does, with
``gbdt_check``'s own functions, imported and unchanged (``_follow`` and what
it calls), and differs in one way: each tree's leaves are found over the
four chips' row blocks in four forked worker processes, not on one thread.
The workers are forked once, after ``feature_major(X)`` is made, so they
share it (and the tree's rows) by the fork; each routes its block with
``gbdt_check.leaf_index`` and writes its leaves into one buffer shared with
the parent. The rule is a row's own, so every row gets the same leaf as on
one thread, and every number is ``gbdt_check.check``'s to the last digit.

Why: ``leaf_index`` on one thread costs ~0.8 s a tree per 10.5M x 28 rows
(PERF.md section 7, 12), ~3.3 s at 42M x 67: it would set the pace of the
reference's chain and put a run near its 360 s. On four processes the next
tree's leaves are found while this tree's gradients, sums and splits run.

The workers only route numpy arrays: a forked child never calls into JAX.
"""
from __future__ import annotations

import mmap
import multiprocessing
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional

import numpy as np

from . import gbdt_check

SHARDS = 4
TIMEOUT_S = 5.0   # how often a wait looks whether a worker died


def _worker(cols: np.ndarray, lo: int, hi: int, bufs, conn) -> None:
    """Route rows ``lo .. hi`` of ``cols`` for every tree the parent sends,
    into the shared buffer it names; reply when done."""
    out = [np.frombuffer(b, np.int64) for b in bufs]
    block = cols[:, lo:hi]
    while True:
        msg = conn.recv()
        if msg is None:
            return
        tree, slot = msg
        try:
            out[slot][lo:hi] = gbdt_check.leaf_index(tree, block)
            conn.send(slot)
        except BaseException as e:  # the parent raises it
            conn.send(repr(e))
            return


class _ShardPool:
    """The pool ``gbdt_check._follow`` runs on: its threads for everything
    but ``leaf_index``, which goes to the shard processes. Two shared
    buffers take turns: ``_follow`` holds one tree's leaves while it asks
    for the next's."""

    def __init__(self, threads: ThreadPoolExecutor, shards: int) -> None:
        self.threads, self.shards = threads, shards
        self.procs, self.conns, self.bufs = [], [], []
        self.turn = 0

    def _start(self, cols: np.ndarray) -> None:
        self.n = n = cols.shape[1]
        self.bufs = [mmap.mmap(-1, max(n, 1) * 8) for _ in range(2)]
        edges = [n * i // self.shards for i in range(self.shards + 1)]
        ctx = multiprocessing.get_context("fork")
        for lo, hi in zip(edges[:-1], edges[1:]):
            parent, child = ctx.Pipe()
            p = ctx.Process(target=_worker,
                            args=(cols, lo, hi, self.bufs, child),
                            daemon=True)
            with warnings.catch_warnings():
                # JAX and Python warn at any fork of a process with threads
                # (JAX's among them): the child runs numpy alone and exits
                # by os._exit, it never touches a lock those threads hold
                warnings.simplefilter("ignore", RuntimeWarning)
                warnings.simplefilter("ignore", DeprecationWarning)
                p.start()
            child.close()
            self.procs.append(p)
            self.conns.append(parent)

    def submit(self, fn, *args, **kw):
        if fn is not gbdt_check.leaf_index:
            return self.threads.submit(fn, *args, **kw)
        tree, cols = args
        if not self.procs:
            self._start(cols)
        slot, self.turn = self.turn, 1 - self.turn
        for conn in self.conns:
            conn.send((tree, slot))
        return _Leaves(self, slot)

    def gather(self, slot: int) -> np.ndarray:
        for conn, p in zip(self.conns, self.procs):
            while not conn.poll(TIMEOUT_S):
                if not p.is_alive():
                    raise RuntimeError(f"mesh_check: shard worker {p.pid} "
                                       f"died (exit code {p.exitcode})")
            got = conn.recv()
            if got != slot:
                raise RuntimeError(f"mesh_check: shard worker: {got}")
        return np.frombuffer(self.bufs[slot], np.int64)[:self.n]

    def map(self, fn, items):
        return self.threads.map(fn, items)

    def close(self) -> None:
        for conn in self.conns:
            try:
                conn.send(None)
            except OSError:
                pass
        for p in self.procs:
            p.join(timeout=10)
            if p.is_alive():
                p.kill()


class _Leaves:
    """What ``_follow`` waits on for a tree's leaves: the shards' replies,
    read on the caller's thread when it asks (no pool thread waits)."""

    def __init__(self, pool: _ShardPool, slot: int) -> None:
        self.pool, self.slot = pool, slot

    def result(self) -> np.ndarray:
        return self.pool.gather(self.slot)


def check(model_text: str, data: dict, params: dict,
          train_scores: np.ndarray, iterations_run: int, seed: int,
          sample_rows: int = 1 << 19, workers: Optional[int] = None,
          shards: int = SHARDS,
          seconds: Optional[Dict[str, float]] = None) -> Dict[str, float]:
    """``gbdt_check.check``'s numbers, each tree's leaves found by
    ``shards`` processes over contiguous row blocks."""
    workers = gbdt_check.default_workers() if workers is None \
        else int(workers)
    with ThreadPoolExecutor(workers) as threads:
        pool = _ShardPool(threads, shards)
        try:
            return gbdt_check._follow(
                model_text, data, params, train_scores, iterations_run, seed,
                sample_rows, pool, {} if seconds is None else seconds)
        finally:
            pool.close()
