"""graftir checkers: captured traces x declared contracts -> findings.

Pure functions over (list of :class:`~.capture.CallRecord`, registry of
:class:`~.contracts.ProgramContract`). Findings reuse graftlint's
:class:`~..core.Finding` dataclass — the rule ids extend the R-series
with an I-series so the two passes share baselines, SARIF rendering, and
CLI conventions:

- **I1** collective-schedule violation (count/kind/axis/payload bytes)
- **I2** transfer/callback op inside a hot program
- **I3** precision violation (f64 under the x64 retrace, or a float op
  feeding the quantized histogram reduction)
- **I4** retrace at a bucketed shape (more distinct traces than the
  contract allows)
- **I5** inventory gap: a registered contract whose program was never
  captured, or a captured hot-looking program with no contract — the
  sweep is only evidence if it actually covered the inventory

Walking happens on the jaxpr level (StableHLO would lose the mesh-axis
names that make C1 checkable); sub-jaxprs of while/scan/cond/pjit/
shard_map/pallas_call eqns are walked recursively.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from ..core import Finding
from .contracts import CollectiveSpec, ProgramContract

COLLECTIVE_PRIMS = {"psum", "all_gather", "all_to_all", "ppermute",
                    "pbroadcast", "reduce_scatter", "pmax", "pmin"}
# host-boundary primitives: a callback (debug/pure/io), infeed/outfeed
# or host transfer inside a jitted hot program breaks transfer-freedom
TRANSFER_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                  "callback", "outside_call", "infeed", "outfeed",
                  "device_put"}
LOOP_PRIMS = {"while", "scan"}


def _sub_jaxprs(eqn) -> Iterable:
    for val in eqn.params.values():
        objs = val if isinstance(val, (list, tuple)) else (val,)
        for obj in objs:
            core = getattr(obj, "jaxpr", None)
            if core is not None:        # ClosedJaxpr
                yield core
            elif hasattr(obj, "eqns"):  # raw Jaxpr
                yield obj


def iter_eqns(jaxpr, depth: int = 0):
    """(eqn, loop_depth) over the whole nest; loop_depth counts enclosing
    while/scan primitives."""
    for eqn in jaxpr.eqns:
        yield eqn, depth
        inner = depth + (1 if eqn.primitive.name in LOOP_PRIMS else 0)
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, inner)


def _axes_of(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if not isinstance(axes, (list, tuple)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _payload_bytes(eqn) -> int:
    total = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            n = 1
            for d in aval.shape:
                n *= int(d)
            total += n * aval.dtype.itemsize
    return total


def collect_collectives(jaxpr) -> List[Dict]:
    """Every collective eqn in the nest: kind, per-axis entries (an eqn
    over k axes contributes k entries), loop depth, payload bytes."""
    out = []
    for eqn, depth in iter_eqns(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr")
                                else jaxpr):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            for ax in _axes_of(eqn):
                out.append({"kind": eqn.primitive.name, "axis": ax,
                            "loop_depth": depth,
                            "bytes": _payload_bytes(eqn)})
    return out


def _schedule(colls: Sequence[Dict], in_loop: bool) -> Dict[Tuple[str, str],
                                                            List[Dict]]:
    sched: Dict[Tuple[str, str], List[Dict]] = {}
    for c in colls:
        if (c["loop_depth"] > 0) == in_loop:
            sched.setdefault((c["kind"], c["axis"]), []).append(c)
    return sched


def _finding(rule: str, contract: ProgramContract, msg: str) -> Finding:
    return Finding(rule=rule, path=contract.path, line=contract.line,
                   col=0, message=msg, severity="error",
                   snippet=f"ir-contract {contract.name}")


def _check_schedule(contract: ProgramContract, scenario: str,
                    colls: Sequence[Dict],
                    specs: Tuple[CollectiveSpec, ...], in_loop: bool,
                    dims: Dict) -> List[Finding]:
    scope = "split step" if in_loop else "setup"
    out: List[Finding] = []
    sched = _schedule(colls, in_loop)
    want = {(s.kind, s.axis): s for s in specs}
    for (kind, axis), group in sorted(sched.items()):
        spec = want.get((kind, axis))
        if spec is None:
            out.append(_finding("I1", contract, (
                f"[{scenario}] undeclared collective in the {scope}: "
                f"{len(group)}x {kind} over {axis!r} (payloads "
                f"{sorted(c['bytes'] for c in group)} B) — the contract "
                f"declares none; an extra collective per split is wire "
                f"cost the schedule never budgeted")))
        elif len(group) != spec.count:
            out.append(_finding("I1", contract, (
                f"[{scenario}] collective count drift in the {scope}: "
                f"{len(group)}x {kind} over {axis!r}, contract declares "
                f"{spec.count}x ({spec.payload or 'unnamed payload'})")))
    for (kind, axis), spec in sorted(want.items()):
        group = sched.get((kind, axis), [])
        if not group:
            out.append(_finding("I1", contract, (
                f"[{scenario}] missing collective in the {scope}: the "
                f"contract declares {spec.count}x {kind} over {axis!r} "
                f"({spec.payload or 'unnamed payload'}) and the lowered "
                f"program has none — the schedule silently changed")))
        elif spec.bytes_of is not None and dims:
            measured = sum(c["bytes"] for c in group)
            expect = int(spec.bytes_of(dims))
            if measured != expect:
                out.append(_finding("I1", contract, (
                    f"[{scenario}] payload-byte drift for {kind} over "
                    f"{axis!r} ({spec.payload}): measured {measured} B "
                    f"per {scope}, registry-derived expectation "
                    f"{expect} B")))
    return out


def check_c1(contract: ProgramContract, scenario: str, traced,
             dims: Optional[Dict] = None) -> List[Finding]:
    colls = collect_collectives(traced)
    out: List[Finding] = []
    if contract.collective_free:
        if colls:
            kinds = sorted({f"{c['kind']}/{c['axis']}" for c in colls})
            out.append(_finding("I1", contract, (
                f"[{scenario}] {len(colls)} collective eqn(s) "
                f"({', '.join(kinds)}) in a program the contract "
                f"declares collective-free")))
        return out
    if contract.step_collectives is not None:
        out += _check_schedule(contract, scenario, colls,
                               contract.step_collectives, True, dims or {})
    if contract.setup_collectives is not None:
        out += _check_schedule(contract, scenario, colls,
                               contract.setup_collectives, False,
                               dims or {})
    return out


def check_c2(contract: ProgramContract, scenario: str,
             traced) -> List[Finding]:
    if not contract.hot:
        return []
    out = []
    for eqn, _ in iter_eqns(traced.jaxpr):
        name = eqn.primitive.name
        if name == "device_put":
            # only a host-memory target breaks transfer-freedom; a
            # device-to-device put (resharding) is schedule, not a sync
            devs = " ".join(str(d) for d in
                            (eqn.params.get("devices") or ()))
            if "host" not in devs:
                continue
        if name in TRANSFER_PRIMS:
            out.append(_finding("I2", contract, (
                f"[{scenario}] host-boundary op {name!r} inside a "
                f"program the contract declares hot — every call syncs "
                f"the device; hot loops must stay transfer-free "
                f"(graftlint R1's runtime counterpart)")))
    return out


def check_c3_f64(contract: ProgramContract, scenario: str,
                 traced_x64) -> List[Finding]:
    if not contract.forbid_f64:
        return []
    bad = {}
    for eqn, _ in iter_eqns(traced_x64.jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and str(getattr(aval, "dtype", "")) == \
                    "float64":
                bad[eqn.primitive.name] = bad.get(eqn.primitive.name,
                                                  0) + 1
    if not bad:
        return []
    ops = ", ".join(f"{k} x{v}" for k, v in sorted(bad.items()))
    return [_finding("I3", contract, (
        f"[{scenario}] silent f64: re-tracing under enable_x64 "
        f"introduces float64 eqns ({ops}) — an implicitly-typed constant "
        f"or conversion upcasts the moment x64 is on; pin dtypes "
        f"explicitly (graftlint R4's IR counterpart)"))]


def _backward_slice_has_float(jaxpr, target_eqn) -> Optional[str]:
    """Walk producers of ``target_eqn``'s operands inside ``jaxpr``.
    Returns a description of the first float-typed eqn output or jaxpr
    input feeding the reduction, or None when the slice is integer-pure."""
    producer = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            producer[v] = eqn
    frontier = list(target_eqn.invars)
    seen = set()
    while frontier:
        v = frontier.pop()
        if id(v) in seen:
            continue
        seen.add(id(v))
        aval = getattr(v, "aval", None)
        dt = str(getattr(aval, "dtype", "")) if aval is not None else ""
        eqn = producer.get(v)
        if eqn is None:
            if dt.startswith(("float", "bfloat")):
                return f"float input {dt} reaches the reduction"
            continue
        if dt.startswith(("float", "bfloat")):
            return (f"float op {eqn.primitive.name!r} ({dt}) feeds the "
                    f"reduction")
        frontier.extend(eqn.invars)
    return None


# dtype/shape plumbing that does not change the VALUES on the wire: the
# producer walk for the scale-free check skips through these
_PASS_THROUGH = {"reshape", "transpose", "slice", "dynamic_slice",
                 "squeeze", "broadcast_in_dim", "convert_element_type",
                 "concatenate", "pad", "while", "scan", "add"}
_SCALE_PRIMS = {"mul", "div", "sub"}


def _wire_producer(jaxpr, eqn) -> Optional[str]:
    """The first non-pass-through primitive feeding ``eqn``'s payload
    (first operand chain), or None when it comes straight from a jaxpr
    input / the accumulation loop."""
    producer = {}
    for e in jaxpr.eqns:
        for v in e.outvars:
            producer[v] = e
    v = eqn.invars[0] if eqn.invars else None
    for _ in range(64):
        e = producer.get(v)
        if e is None:
            return None
        if e.primitive.name not in _PASS_THROUGH:
            return e.primitive.name
        v = e.invars[0] if e.invars else None
    return None


def check_c3_quant(contract: ProgramContract, scenario: str, traced,
                   data_axis: str = "data") -> List[Finding]:
    """In a quantized scenario, every histogram psum over ``data`` must
    reduce RAW level sums with the gradient scales applied only after
    the wire. Two lowered forms are legal (fused_learner acc_dtype):
    an integer payload (Pallas path) whose backward slice must be
    float-free, or an integer-VALUED float payload (one-hot fallback,
    exact below the accumulator limit) that must come straight from the
    accumulation loop — a mul/div on the wire means the scales moved
    pre-psum and width-invariance is gone."""
    if not contract.quant_int_reduction:
        return []
    out: List[Finding] = []
    checked = 0

    def walk(jaxpr):
        nonlocal checked
        for eqn in jaxpr.eqns:
            # a histogram is [columns, bins, channels]; a 1-D psum (the
            # tree's integer row counts, fused_learner count_rows) is not
            if eqn.primitive.name == "psum" and \
                    data_axis in _axes_of(eqn) and eqn.invars and \
                    len(getattr(eqn.invars[0].aval, "shape", ())) > 1:
                checked += 1
                dt = str(getattr(getattr(eqn.invars[0], "aval", None),
                                 "dtype", "")) if eqn.invars else ""
                if dt.startswith(("int", "uint")):
                    why = _backward_slice_has_float(jaxpr, eqn)
                    if why:
                        out.append(_finding("I3", contract, (
                            f"[{scenario}] float contamination in the "
                            f"integer histogram reduction: {why} — the "
                            f"accumulation must stay integer up to the "
                            f"psum (scales apply post-reduction)")))
                else:
                    prod = _wire_producer(jaxpr, eqn)
                    if prod in _SCALE_PRIMS:
                        out.append(_finding("I3", contract, (
                            f"[{scenario}] quantized histogram psum over "
                            f"{data_axis!r} reduces a payload produced "
                            f"by {prod!r} — the gradient scales moved "
                            f"BEFORE the wire; the reduction must sum "
                            f"raw level values (scales post-psum) to "
                            f"stay exact and width-invariant")))
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(traced.jaxpr)
    if checked == 0:
        out.append(_finding("I3", contract, (
            f"[{scenario}] contract declares a quantized integer "
            f"reduction but the lowered program has no psum over "
            f"{data_axis!r} to check — the reduction moved or the "
            f"capture missed it")))
    return out


def check_c4(contract: ProgramContract, scenario: str,
             n_traces: int) -> List[Finding]:
    if n_traces <= contract.max_traces:
        return []
    return [_finding("I4", contract, (
        f"[{scenario}] retrace: {n_traces} distinct traces where the "
        f"contract allows {contract.max_traces} — a shape escaped its "
        f"padding/pow2 bucket, so steady state recompiles (the telemetry "
        f"watchdog would flag this at runtime; graftir catches it at "
        f"lowering time)"))]
