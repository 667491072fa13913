"""``python -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell. Sets up, warms, measures, checks what
the timed path produced against the plain reference, prints every number
compared beside its limit and, as its last line of standard output, the
contract's one JSON object. Fails (non-zero, no result line) when JAX finds
no TPU, fewer chips than the cell asks for, or a ``device_kind`` that
``peaks.json`` does not hold. Nothing here names a cell.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from . import manifest  # noqa: E402


def log(msg: str) -> None:
    print(f"[benchmark] {msg}", flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="python -m benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU, Pallas interpreted; stamped "
                         "a rehearsal, no device metric is reported")
    ap.add_argument("--control", default="",
                    help="run controls/<name>.json's overrides in the "
                         "program's place; the result is stamped a control")
    return ap.parse_args(argv)


def device_gate(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it, or exit: no CPU fallback."""
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    log(f"jax {jax.__version__}  platform={d.platform}  "
        f"device_kind={d.device_kind}  count={len(devs)}")
    if rehearse:
        return info
    peaks = manifest.load_json("peaks.json")
    if d.platform != "tpu":
        raise SystemExit(f"benchmark: no TPU -- JAX found platform="
                         f"{d.platform!r}; a CPU run measures nothing "
                         "(--rehearse-cpu is the stamped rehearsal)")
    if d.device_kind not in peaks:
        raise SystemExit(f"benchmark: device_kind {d.device_kind!r} is not "
                         "in benchmark/peaks.json; add its published peaks "
                         "with their source")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, JAX "
                         f"found {len(devs)}")
    return info


def run_cell(args, device: dict, hooks=None) -> dict:
    """Everything after the look for a chip: drive the cell, judge it,
    build the result. ``hooks`` replaces the call into the program
    (``update``) -- the tests plant their faults there."""
    cell = manifest.cell(args.workload)
    rehearse = bool(args.rehearse_cpu)
    control = manifest.load_json("controls", args.control + ".json") \
        if args.control else {}
    out_dir = os.path.join(manifest.ROOT, ".bench_out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    ctx = {"log": log, "cell": cell, "seed": int(args.seed),
           "seconds": float(args.seconds), "trace": bool(args.trace),
           "rehearse": rehearse, "control": control, "out_dir": out_dir,
           "t_start": _T_START, "device": device,
           "update": lambda bst: bst.update()}
    ctx.update(hooks or {})
    driver = importlib.import_module(
        "benchmark.drivers." + cell["traffic"]["driver"])
    res = driver.run(ctx)

    from .reference.gbdt_check import judge
    correct, rows = judge(res["numbers"], cell["limits"]["limits"])
    metrics = {}
    if args.trace:
        peaks = manifest.load_json("peaks.json").get(ctx["device"]["kind"])
        view = dict(res, peaks=peaks, cell=cell)
        if res.get("trace") is not None:
            from . import trace_reduce
            view["reduced"] = trace_reduce.reduce(res["trace"])
        for m in cell["per_layer"]:
            reader = importlib.import_module("benchmark.readers." + m["reader"])
            value = reader.read(m, view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": res["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    device = dict(ctx["device"], memory_peak_bytes=res["memory_peak_bytes"])
    result = {"correct": correct, "attempted": res["attempted"],
              "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace and view.get("reduced") is not None:
        red = view["reduced"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = trace_reduce.breakdown(red, cell["per_layer"])
    if rehearse:
        result["rehearsal"] = True
    if args.control:
        result["control"] = args.control
    result["compared"] = {r["name"]: [r["value"], r["limit"]] for r in rows}
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(manifest.ROOT, "lambdagap_tpu")):
        print("benchmark: the system under test (lambdagap_tpu/) is not in "
              "this checkout", file=sys.stderr)
        return 3
    cell = manifest.cell(args.workload)
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    device = device_gate(cell["chips"], args.rehearse_cpu)
    import jax
    from lambdagap_tpu.utils.compile_cache import configure_compile_cache
    # the program places the cache; this entry point asks for EVERY program
    # in it (JAX's default leaves out what compiled in under a second, and
    # the training loop has some thirty such), so that only the first run
    # of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"compile cache: {configure_compile_cache()}")
    result = run_cell(args, device)
    for name, (value, limit) in result["compared"].items():
        print(f"[benchmark] compared {name}: {value!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
