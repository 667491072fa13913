"""graftir contract registry (ISSUE 17).

A :class:`ProgramContract` declares what the LOWERED form of one jitted
hot program must look like — the IR-shaped counterpart of graftlint's
AST rules. Learners and engines register their contracts at definition
site (``register_program`` right next to the ``jax.jit`` that builds the
program), so the declared schedule lives with the code it constrains and
editing that file invalidates exactly its programs' cached verdicts.

Contract clauses (checked by :mod:`.checks` over captured traces):

- **C1 collective schedule** — exact eqn count + kind (psum/all_gather)
  + mesh axis per split step (the subtree of the outermost loop
  primitive that contains collectives), with optional payload-byte
  formulas sourced from the sharding registry's layout, verified across
  every virtual grid the worker runs (1x8/2x4/4x2/8x1).
- **C2 transfer-freedom** (``hot=True``) — no host callback / infeed /
  outfeed primitives anywhere in the program.
- **C3 precision discipline** — ``forbid_f64``: re-tracing under
  ``jax.enable_x64`` must introduce NO float64 eqns (a
  silent-upcast site is invisible at x64=off and a real drift hazard the
  moment anyone enables x64 — graftlint R4's rationale, enforced on the
  IR); ``quant_int_reduction``: in quantized scenarios the histogram
  psum over ``data`` must carry an integer payload whose backward slice
  is float-free (the PR 8 width-invariance argument, made structural).
- **C4 retrace-freedom** — the number of distinct traces per scenario
  stays within ``max_traces`` while the worker replays
  perturbed-but-bucketed shapes (pow2 stream buckets, padding buckets).

This module is deliberately stdlib-only: registration happens at import
time of heavy modules, and the graftlint CLI imports it for cache keys
WITHOUT importing jax.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Callable, Dict, List, Optional, Tuple

PKG_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the I-series rule catalog (graftlint's R-series counterpart); the CLI
# and SARIF renderer read this without importing jax
IR_RULES = {
    "I1": "collective-schedule violation: lowered psum/all_gather "
          "count, kind, mesh axis or payload bytes differ from the "
          "program's declared contract",
    "I2": "host-boundary op (callback/infeed/outfeed/host device_put) "
          "inside a program the contract declares hot",
    "I3": "precision violation: silent f64 under the x64 retrace, or "
          "float contamination in the quantized histogram reduction",
    "I4": "retrace at a bucketed shape: more distinct traces per "
          "scenario than the contract allows",
    "I5": "inventory gap: a registered contract whose program no "
          "scenario captured",
}


@dataclasses.dataclass(frozen=True)
class CollectiveSpec:
    """One expected collective group: ``count`` eqns of ``kind`` over
    mesh ``axis`` inside the checked scope. ``payload`` names the logical
    array (sharding-registry vocabulary) for diagnostics; ``bytes_of``
    optionally pins the per-device payload bytes as a function of the
    scenario dims dict (mismatch = finding)."""

    kind: str                      # "psum" | "all_gather"
    axis: str                      # "data" | "feature"
    count: int
    payload: str = ""
    bytes_of: Optional[Callable[[Dict], int]] = None


def psum(axis: str, count: int = 1, payload: str = "",
         bytes_of: Optional[Callable[[Dict], int]] = None) -> CollectiveSpec:
    return CollectiveSpec("psum", axis, count, payload, bytes_of)


def all_gather(axis: str, count: int = 1, payload: str = "",
               bytes_of: Optional[Callable[[Dict], int]] = None
               ) -> CollectiveSpec:
    return CollectiveSpec("all_gather", axis, count, payload, bytes_of)


@dataclasses.dataclass
class ProgramContract:
    """The declared IR shape of one jitted program.

    ``name`` is the capture key: ``OwnerClass.method`` for (possibly
    partial-wrapped, shard_map-wrapped) bound methods — the owning
    INSTANCE's class, so five learners sharing ``_train_tree_impl``
    register five distinct contracts — or ``module.function`` for plain
    functions.
    """

    name: str
    hot: bool = True               # C2: no callbacks/transfers inside
    forbid_f64: bool = True        # C3a: x64 retrace stays f64-free
    quant_int_reduction: bool = False  # C3b: int hist psum in quant runs
    step_collectives: Optional[Tuple[CollectiveSpec, ...]] = None  # C1
    setup_collectives: Optional[Tuple[CollectiveSpec, ...]] = None
    collective_free: bool = False  # C1: zero collectives anywhere
    max_traces: int = 1            # C4: distinct traces per scenario
    notes: str = ""
    # registration site, for finding anchors + cache keys
    path: str = ""                 # repo-relative, e.g. lambdagap_tpu/...
    line: int = 0
    sources: Tuple[str, ...] = ()  # repo-relative files keying the cache


_REGISTRY: Dict[str, ProgramContract] = {}


def register_program(name: str, **fields) -> ProgramContract:
    """Declare (or re-declare — module reloads happen under pytest) the
    contract for ``name``. Captures the caller's file/line so findings
    anchor to the registration site next to the constrained code."""
    frame = sys._getframe(1)
    fpath = os.path.abspath(frame.f_code.co_filename)
    try:
        rel = os.path.relpath(fpath, os.path.dirname(PKG_ROOT))
    except ValueError:          # different drive (windows) — keep abs
        rel = fpath
    rel = rel.replace(os.sep, "/")
    contract = ProgramContract(name=name, path=rel,
                               line=frame.f_lineno, **fields)
    if not contract.sources:
        contract.sources = (rel,)
    _REGISTRY[name] = contract
    return contract


def get_contract(name: str) -> Optional[ProgramContract]:
    return _REGISTRY.get(name)


def all_contracts() -> List[ProgramContract]:
    return [c for _, c in sorted(_REGISTRY.items())]


def clear() -> None:
    """Test hook: drop every registered contract."""
    _REGISTRY.clear()
