"""Single-call cluster training — the Dask-module analog.

(reference: python-package/lightgbm/dask.py — ``_train`` :375-520 builds the
machine list, finds open ports, ships one data part to every worker and
drives per-worker distributed training automatically; the user just says
"here is a cluster, train on it".)

TPU shape: JAX multi-process is coordinator-based, so the launcher picks a
free coordinator port, row-partitions the input into per-worker files
(query-boundary-aligned when ``group`` is given), and spawns one process
per worker through the CLI's ``pre_partition=true`` flow — which joins the
distributed runtime BEFORE the package import touches the backend, loads
its own part, syncs bin mappers from allgathered samples, and trains over
the global device mesh with one histogram psum per split. Rank 0's model
(byte-identical to every other rank's) is returned as a Booster.

For multi-HOST clusters the same worker command runs on each host with
``machines=<coordinator_ip>:<port> num_machines=K machine_rank=r`` — this
launcher automates the single-host multi-process case and documents the
multi-host invocation it generates (``verbose_command``).

On accelerators this is a CPU / one-process-per-host facility: the caller
has imported the package (which initializes the JAX backend, so on a TPU
host the parent already holds the chips) and every worker inherits the
parent's environment, so N workers on one host would each try to claim
every local chip — a chip belongs to one process at a time. Use it with
``worker_env={"JAX_PLATFORMS": "cpu", ...}`` on one host, or run the
generated command once per host; one process drives all of a host's chips
through ``tree_learner=data``.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import tempfile
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..utils import log


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _params_to_cli(params: Dict[str, Any]) -> List[str]:
    toks = []
    for k, v in params.items():
        if isinstance(v, (list, tuple)):
            v = ",".join(str(x) for x in v)
        elif isinstance(v, bool):
            v = "true" if v else "false"
        toks.append(f"{k}={v}")
    return toks


def _partition_bounds(n: int, k: int,
                      group: Optional[np.ndarray]) -> List[int]:
    """Row bounds of k contiguous parts; query-aligned when group sizes are
    given (a query must not straddle ranks — the reference's dask module
    likewise keeps each part's groups intact)."""
    if group is None:
        # floor-balanced: never an empty part for n >= k
        return [i * n // k for i in range(k + 1)]
    qb = np.concatenate([[0], np.cumsum(np.asarray(group, np.int64))])
    if qb[-1] != n:
        log.fatal("group sizes sum to %d but data has %d rows", qb[-1], n)
    targets = [round(i * n / k) for i in range(k + 1)]
    bounds = [0]
    for t in targets[1:-1]:
        j = int(np.searchsorted(qb, t, side="left"))
        bounds.append(int(qb[min(j, len(qb) - 1)]))
    bounds.append(n)
    return bounds


def train_cluster(params: Dict[str, Any], data, label=None, *,
                  num_workers: int = 2,
                  weight=None, group=None,
                  num_boost_round: Optional[int] = None,
                  workdir: Optional[str] = None,
                  timeout: float = 1800.0,
                  worker_env: Optional[Dict[str, str]] = None,
                  keep_files: bool = False):
    """Train one model across ``num_workers`` local processes with a single
    call (reference behavior: lightgbm.dask train()/DaskLGBM*.fit()).

    ``data`` is either a (rows, features) matrix — partitioned and written
    per-worker here — or a list of ``num_workers`` pre-partitioned file
    paths (the multi-host layout: every host already holds its own shard).
    Returns a :class:`lambdagap_tpu.Booster` built from rank 0's model
    (all ranks build byte-identical models).
    """
    from ..basic import Booster

    if num_workers < 2:
        log.fatal("train_cluster needs num_workers >= 2 (use lgb.train "
                  "for single-process training)")
    tmp = workdir or tempfile.mkdtemp(prefix="lambdagap_cluster_")
    os.makedirs(tmp, exist_ok=True)

    if isinstance(data, (list, tuple)) and data and isinstance(
            data[0], (str, os.PathLike)):
        if len(data) != num_workers:
            log.fatal("got %d part files for %d workers", len(data),
                      num_workers)
        if label is not None or weight is not None or group is not None:
            log.fatal("label/weight/group must live in the part files (or "
                      "their sidecars) when data is a list of paths")
        part_files = [str(p) for p in data]
    else:
        X = np.asarray(data, dtype=np.float64)
        if label is None:
            log.fatal("label is required when data is a matrix")
        y = np.asarray(label, dtype=np.float64).reshape(-1)
        bounds = _partition_bounds(len(X), num_workers, group)
        part_files = []
        for r in range(num_workers):
            lo, hi = bounds[r], bounds[r + 1]
            if lo >= hi:
                log.fatal("partitioning produced an empty part for worker "
                          "%d (%d rows over %d workers)", r, len(X),
                          num_workers)
            path = os.path.join(tmp, f"part{r}.tsv")
            np.savetxt(path, np.column_stack([y[lo:hi], X[lo:hi]]),
                       delimiter="\t", fmt="%.17g")
            if weight is not None:
                np.savetxt(path + ".weight",
                           np.asarray(weight, np.float64)[lo:hi],
                           fmt="%.17g")
            if group is not None:
                qb = np.concatenate([[0], np.cumsum(np.asarray(group,
                                                               np.int64))])
                sizes = np.diff(qb[(qb >= lo) & (qb <= hi)])
                np.savetxt(path + ".query", sizes, fmt="%d")
            part_files.append(path)

    port = _free_port()
    machines = f"127.0.0.1:{port}"
    run_params = dict(params)
    if num_boost_round is not None:
        run_params["num_iterations"] = num_boost_round
    run_params.pop("pre_partition", None)

    procs = []
    cmds = []
    log_paths = []
    env = dict(os.environ)
    env.update(worker_env or {})
    for r in range(num_workers):
        model_path = os.path.join(tmp, f"model{r}.txt")
        cmd = [sys.executable, "-m", "lambdagap_tpu", "task=train",
               f"data={part_files[r]}", "pre_partition=true",
               f"num_machines={num_workers}", f"machine_rank={r}",
               f"machines={machines}", f"output_model={model_path}",
               *_params_to_cli(run_params)]
        cmds.append(" ".join(cmd))
        # per-rank log FILES, not pipes: a verbose worker that fills a 64KB
        # pipe buffer blocks mid-collective and drags every rank to the
        # timeout kill; files never backpressure the workers
        lp = os.path.join(tmp, f"worker{r}.log")
        log_paths.append(lp)
        lf = open(lp, "w")
        try:
            procs.append(subprocess.Popen(cmd, stdout=lf,
                                          stderr=subprocess.STDOUT,
                                          cwd=os.getcwd(), env=env))
        finally:
            lf.close()          # the child holds its own descriptor
    def _tail(path, n=3000):
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(f.tell() - n, 0))
                return f.read().decode(errors="replace")
        except OSError:
            return ""

    import time
    deadline = time.monotonic() + timeout
    for p in procs:
        try:
            p.wait(timeout=max(deadline - time.monotonic(), 0.1))
        except subprocess.TimeoutExpired:
            # reap already-finished ranks first: kill() does not set
            # returncode, so without poll() every unwaited-but-exited
            # worker would be misreported as stalled
            stalled = [r for r, q in enumerate(procs) if q.poll() is None]
            for q in procs:
                q.kill()
            detail = "\n".join(
                f"--- worker {r} ({log_paths[r]}) ---\n{_tail(log_paths[r])}"
                for r in stalled)
            log.fatal("cluster training timed out after %.0fs "
                      "(stalled ranks: %s)\n%s", timeout, stalled, detail)
    for r, p in enumerate(procs):
        if p.returncode != 0:
            log.fatal("cluster worker %d failed (rc=%d):\n%s", r,
                      p.returncode, _tail(log_paths[r]))

    with open(os.path.join(tmp, "model0.txt")) as f:
        model_str = f.read()
    booster = Booster(model_str=model_str)
    booster.cluster_commands = cmds       # the multi-host recipe, verbatim
    if not keep_files and workdir is None:
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
    return booster
