"""graftir capture worker: runs the scenario inventory, checks contracts.

Spawned by ``python -m lambdagap_tpu.analysis --ir`` (and by
``tools/graftir_gate.py``) as a SUBPROCESS with ``LAMBDAGAP_IR_CAPTURE=1``
and 8 virtual CPU devices — the env hook at the top of
``lambdagap_tpu/__init__.py`` installs the jit capture shim before any
heavy module imports, so import-time ``functools.partial(jax.jit, ...)``
decorations are captured too. Emits ONE JSON object (stdout, and
``--out FILE`` for a log-free copy):

  {"findings": [...],
   "programs": {name: {sources, scenarios, coverage, findings}},
   "uncontracted": [...], "elapsed_s": ..., "env": {...}}

``--scenarios a,b`` runs a subset (the per-program cache re-runs only the
scenarios a stale program appeared in); ``--discover`` traces EVERY
captured program and dumps its collective schedule (a development tool
for writing contracts, not a gate mode).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Dict, List


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="graftir-worker")
    ap.add_argument("--scenarios", default=None,
                    help="comma-separated scenario subset")
    ap.add_argument("--discover", action="store_true")
    ap.add_argument("--out", default=None,
                    help="write the result JSON here (stdout carries "
                         "workload logs)")
    ap.add_argument("--selftest", action="store_true",
                    help="run the seeded-violation mutation suite "
                         "through the real checkers and report whether "
                         "each planted break was caught")
    args = ap.parse_args(argv)

    if not os.environ.get("LAMBDAGAP_IR_CAPTURE"):
        print("graftir worker needs LAMBDAGAP_IR_CAPTURE=1 in the "
              "environment (the lambdagap_tpu import hook installs the "
              "jit capture shim)", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    import jax
    import lambdagap_tpu  # noqa: F401  (hook installs capture)
    from . import capture, checks
    from .contracts import all_contracts, get_contract
    from .scenarios import inventory

    assert capture.installed(), "capture hook did not install"

    if args.selftest:
        from . import mutations
        results = mutations.selftest()
        ok = all(r["caught"] for r in results)
        payload = {"selftest": results, "ok": ok,
                   "elapsed_s": round(time.perf_counter() - t0, 3)}
        text = json.dumps(payload)
        if args.out:
            with open(args.out, "w", encoding="utf-8") as f:
                f.write(text + "\n")
        print(text)
        print("GRAFTIR-SELFTEST-" + ("OK" if ok else "FAIL"))
        return 0 if ok else 1

    only = set(args.scenarios.split(",")) if args.scenarios else None
    ran: List[str] = []
    for scen in inventory():
        if only is not None and scen.name not in only:
            continue
        capture.set_scenario(scen.name, **scen.flags)
        scen.run()
        ran.append(scen.name)

    # group records by (program, scenario)
    groups: Dict[str, Dict[str, List]] = {}
    for rec in capture.records():
        groups.setdefault(rec.program, {}).setdefault(rec.scenario,
                                                      []).append(rec)

    scen_dims = {s.name: s.dims for s in inventory()}
    findings = []
    programs_out: Dict[str, Dict] = {}
    uncontracted = []

    if args.discover:
        for prog, scens in sorted(groups.items()):
            for scen, recs in sorted(scens.items()):
                traced = recs[0].trace()
                colls = checks.collect_collectives(traced)
                sched = {}
                for c in colls:
                    key = (f"{c['kind']}/{c['axis']}/"
                           f"{'loop' if c['loop_depth'] else 'setup'}")
                    ent = sched.setdefault(key, {"n": 0, "bytes": []})
                    ent["n"] += 1
                    ent["bytes"].append(c["bytes"])
                print(json.dumps({"program": prog, "scenario": scen,
                                  "traces": len(recs),
                                  "collectives": sched}))
        return 0

    from jax import enable_x64
    for prog, scens in sorted(groups.items()):
        contract = get_contract(prog)
        if contract is None:
            uncontracted.append(prog)
            continue
        prog_findings: List = []
        coverage: Dict[str, Dict] = {}
        for scen, recs in sorted(scens.items()):
            dims = scen_dims.get(scen, {})
            flags = recs[0].flags
            traced = recs[0].trace()
            prog_findings += checks.check_c1(contract, scen, traced, dims)
            prog_findings += checks.check_c2(contract, scen, traced)
            if flags.get("quant"):
                prog_findings += checks.check_c3_quant(contract, scen,
                                                       traced)
            if contract.forbid_f64:
                with enable_x64():
                    traced64 = recs[0].trace()
                prog_findings += checks.check_c3_f64(contract, scen,
                                                     traced64)
            prog_findings += checks.check_c4(contract, scen, len(recs))
            coverage[scen] = {
                "traces": len(recs),
                "collectives": len(checks.collect_collectives(traced)),
            }
        fdicts = [dataclasses.asdict(f) for f in prog_findings]
        programs_out[prog] = {
            "sources": sorted(contract.sources),
            "scenarios": sorted(coverage),
            "coverage": coverage,
            "findings": fdicts,
        }
        findings += fdicts

    if only is None:
        # inventory completeness (I5): a registered contract whose
        # program never compiled means the sweep silently lost coverage
        from ..core import Finding
        for contract in all_contracts():
            if contract.name not in groups:
                f = Finding(
                    rule="I5", path=contract.path, line=contract.line,
                    col=0, severity="error",
                    message=(f"contract {contract.name!r} was never "
                             f"captured by any scenario — the program "
                             f"was renamed, the scenario inventory lost "
                             f"it, or the jit moved out of capture "
                             f"reach; C1-C4 cannot vouch for a program "
                             f"that never lowered"),
                    snippet=f"ir-contract {contract.name}")
                d = dataclasses.asdict(f)
                programs_out[contract.name] = {
                    "sources": sorted(contract.sources),
                    "scenarios": [], "coverage": {}, "findings": [d]}
                findings.append(d)

    out = {
        "findings": findings,
        "programs": programs_out,
        "uncontracted": sorted(uncontracted),
        "scenarios_run": ran,
        "elapsed_s": round(time.perf_counter() - t0, 3),
        "env": {"jax": jax.__version__,
                "devices": jax.device_count(),
                "backend": jax.default_backend()},
    }
    text = json.dumps(out)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text + "\n")
    sys.stdout.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
