"""graftir mutation suite: seeded violations that the checkers MUST catch.

Each builder constructs a tiny toy program with one planted contract
break — an extra collective, a host callback (the IR-level shape a
sneaky ``float(x)``/``device_get`` takes once it has to lower), an f64
literal visible under the x64 retrace, a pre-psum gradient scale in the
quantized reduction, an unbucketed retrace — and runs it through the
REAL check functions. ``selftest()`` reports, per mutation, whether the
planted break produced the expected finding; the G0 gate runs it via
``worker --selftest`` so the suite's teeth are proven on every run, not
assumed (a checker that silently stopped matching primitives would
otherwise keep passing everything).

Imports jax — worker-subprocess only, like :mod:`.scenarios`.
"""
# graftlint: disable-file=R10 — the builders below PLANT violations in
# tiny self-contained toy programs (a raw 2-device mesh, literal P()
# specs, a bare shard_map import); routing the analyzer's own
# seeded-violation fixtures through parallel/sharding.py would couple
# them to the very registry layer graftir exists to police.
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import capture, checks
from .contracts import ProgramContract, psum


def _mesh():
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2])
    return Mesh(devs, ("data",))


def _trace(fun, *args):
    """AOT-trace through the REAL (unpatched) jit, like CallRecord.trace."""
    real_jit = capture._real_jit or jax.jit
    return real_jit(fun).trace(*args).jaxpr


def _contract(name: str, **fields) -> ProgramContract:
    c = ProgramContract(name=name, path="lambdagap_tpu/analysis/ir/"
                        "mutations.py", line=1, **fields)
    c.sources = (c.path,)
    return c


def mutation_extra_psum() -> Dict:
    """C1: one psum declared, two lowered — the classic 'a second
    reduction snuck into the split step'."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = _mesh()

    def body(x):
        return jax.lax.psum(jax.lax.psum(x, "data"), "data")

    def prog(x):
        return shard_map(body, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"), check_rep=False)(x)

    traced = _trace(prog, jnp.ones((8, 4), jnp.float32))
    contract = _contract(
        "mutation.extra_psum",
        setup_collectives=(psum("data", 1, "histogram"),))
    found = checks.check_c1(contract, "selftest", traced, {})
    return {"name": "extra_psum", "expect": "I1",
            "caught": any(f.rule == "I1" for f in found),
            "n": len(found)}


def mutation_sneaky_callback() -> Dict:
    """C2: a host callback inside a hot program — the lowered form a
    sneaky ``float(x)`` / ``jax.device_get`` takes when someone 'fixes'
    the ConcretizationTypeError with a pure_callback."""
    def prog(x):
        y = x * 2.0
        jax.debug.callback(lambda v: None, y)
        return y

    traced = _trace(prog, jnp.ones((4,), jnp.float32))
    contract = _contract("mutation.sneaky_callback", hot=True)
    found = checks.check_c2(contract, "selftest", traced)
    return {"name": "sneaky_callback", "expect": "I2",
            "caught": any(f.rule == "I2" for f in found),
            "n": len(found)}


def mutation_f64_literal() -> Dict:
    """C3a: an implicitly-typed numpy double in the closure — invisible
    at x64=off, a silent f64 upcast the moment x64 is on."""
    scale = np.float64(1.5)         # the planted drift hazard

    def prog(x):
        return x * scale

    with jax.enable_x64():
        traced64 = _trace(prog, jnp.ones((4,), jnp.float32))
    contract = _contract("mutation.f64_literal", forbid_f64=True)
    found = checks.check_c3_f64(contract, "selftest", traced64)
    return {"name": "f64_literal", "expect": "I3",
            "caught": any(f.rule == "I3" for f in found),
            "n": len(found)}


def mutation_scaled_quant_wire() -> Dict:
    """C3b: gradient scales applied BEFORE the histogram psum — the
    reduction is no longer a raw-level integer sum, so cross-shard
    determinism and width-invariance silently die."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = _mesh()

    def body(hist, scale):
        return jax.lax.psum(hist * scale, "data")     # scales pre-wire

    def prog(hist, scale):
        return shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                         out_specs=P(), check_rep=False)(hist, scale)

    traced = _trace(prog, jnp.ones((8, 16), jnp.float32),
                    jnp.float32(0.25))
    contract = _contract("mutation.scaled_quant_wire",
                         quant_int_reduction=True)
    found = checks.check_c3_quant(contract, "selftest", traced)
    return {"name": "scaled_quant_wire", "expect": "I3",
            "caught": any(f.rule == "I3" for f in found),
            "n": len(found)}


def mutation_float_int_slice() -> Dict:
    """C3b, integer-wire form: an int psum whose payload was produced by
    rounding a float — float contamination inside the 'integer'
    reduction (the Pallas-path violation)."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = _mesh()

    def body(x):
        levels = jnp.round(x * 3.7).astype(jnp.int32)  # float feeds wire
        return jax.lax.psum(levels, "data")

    def prog(x):
        return shard_map(body, mesh=mesh, in_specs=P("data"),
                         out_specs=P(), check_rep=False)(x)

    traced = _trace(prog, jnp.ones((8, 16), jnp.float32))
    contract = _contract("mutation.float_int_slice",
                         quant_int_reduction=True)
    found = checks.check_c3_quant(contract, "selftest", traced)
    return {"name": "float_int_slice", "expect": "I3",
            "caught": any(f.rule == "I3" for f in found),
            "n": len(found)}


def mutation_unbucketed_shape() -> Dict:
    """C4: a shape that escapes its padding bucket — two distinct traces
    where the contract allows one. Exercised through the real capture
    shim: the retrace count IS the distinct-record count."""
    assert capture.installed()
    capture.set_scenario("mutation-c4")

    @jax.jit
    def prog(x):                    # captured by the shim
        return x + 1

    prog(jnp.ones((601,), jnp.float32))
    prog(jnp.ones((602,), jnp.float32))     # unbucketed: new shape
    n = len([r for r in capture.records()
             if r.program.endswith("mutation_unbucketed_shape.prog")
             and r.scenario == "mutation-c4"])
    contract = _contract("mutation.unbucketed_shape", max_traces=1)
    found = checks.check_c4(contract, "selftest", n)
    return {"name": "unbucketed_shape", "expect": "I4",
            "caught": n == 2 and any(f.rule == "I4" for f in found),
            "n": len(found)}


def selftest() -> List[Dict]:
    return [mutation_extra_psum(), mutation_sneaky_callback(),
            mutation_f64_literal(), mutation_scaled_quant_wire(),
            mutation_float_int_slice(), mutation_unbucketed_shape()]
