"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The driver/bench run on real TPU; tests exercise the same code paths on CPU
(the reference's analog: CPU-vs-GPU parity tests, tests/python_package_test/
test_dual.py). 8 virtual devices let distributed learners be tested without
hardware (SURVEY.md §4).

``jax.config.update("jax_platforms", "cpu")`` beats a ``JAX_PLATFORMS`` the
environment may set, as long as no backend has been initialized yet — so it
happens here, before any test import touches a jax array.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(42)


# ---------------------------------------------------------------------------
# multi-process capability probe
# ---------------------------------------------------------------------------
# The CPU PJRT client cannot execute computations spanning processes; every
# multi-process test on a CPU-only box dies with this exact message deep in
# a subprocess. Probing it ONCE per session and skipping loudly keeps those
# tests from masquerading as failures (and from masking real regressions:
# any OTHER failure in the children still fails the test).
MP_CPU_REASON = "Multiprocess computations aren't implemented on the CPU backend"

_MP_PROBE_CHILD = r"""
import os, sys
sys.path.insert(0, %r)
import numpy as np
import jax
rank = int(sys.argv[1]); port = sys.argv[2]
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=rank)
from lambdagap_tpu.parallel.sharding import (DATA_AXIS, make_mesh, shard_map,
                                             spec)
import jax.numpy as jnp
mesh = make_mesh(0)
x = jax.make_array_from_process_local_data(
    jax.sharding.NamedSharding(mesh, spec("grad")), np.ones(4, np.float32))
op = jax.jit(shard_map(lambda v: jax.lax.psum(jnp.sum(v), DATA_AXIS),
                       mesh=mesh, in_specs=(spec("grad"),),
                       out_specs=spec("rep"), check_vma=False))
print("MP_PROBE_" + "OK", float(np.asarray(op(x))))
"""

_mp_probe_result = {}


def multiprocess_cpu_error() -> str:
    """"" when 2-process collectives work here; the skip reason otherwise.

    Spawns two minimal children (distributed init + one cross-process psum)
    in the same environment the real multi-process tests use.
    Cached for the session — the probe runs once, not per test.
    """
    if "err" in _mp_probe_result:
        return _mp_probe_result["err"]
    import socket
    import subprocess
    import sys
    import tempfile
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    child = _MP_PROBE_CHILD % (os.getcwd(),)
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "mp_probe.py")
        with open(script, "w") as f:
            f.write(child)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        procs = [subprocess.Popen([sys.executable, script, str(r), port],
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True,
                                  cwd=os.getcwd(), env=env)
                 for r in range(2)]
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=180)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                out = "probe timed out"
            outs.append(out)
    # reason check FIRST: a failed child's traceback quotes its own source,
    # so the success marker must never gate a failure
    if any(MP_CPU_REASON in o for o in outs):
        err = MP_CPU_REASON
    elif all("MP_PROBE_OK" in o for o in outs):
        err = ""
    else:
        # an unexpected probe failure must NOT skip-convert real test
        # failures — report capability as present and let the test fail
        # with its own diagnostics
        err = ""
    _mp_probe_result["err"] = err
    return err


def skip_unless_multiprocess() -> None:
    """pytest.skip (with the exact backend message) when this host cannot
    run cross-process JAX computations."""
    err = multiprocess_cpu_error()
    if err:
        pytest.skip(err)
