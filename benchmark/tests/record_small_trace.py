"""Record the small device trace the reduction's tests read, on a chip:

    chiprun -- python3 -m benchmark.tests.record_small_trace

One traced boosting iteration at 2^20 x 28 rows, 31 leaves, 255 bins (the
shape of ``data/small_v5e.xplane.pb.gz``, PR 25), after two warm-up
iterations, with the program's telemetry on. Writes
``chiprun_out/small_trace/small_v5e_scopes.xplane.pb.gz`` and, beside it,
``small_v5e_scopes.json``: the traced iteration's record (its ``counts``)
and the device. Copy both into ``benchmark/tests/data/``."""
from __future__ import annotations

import glob
import gzip
import json
import os
import shutil

from .. import manifest
from ..datagen import higgs_like

OUT = os.path.join(manifest.ROOT, "chiprun_out", "small_trace")
NAME = "small_v5e_scopes"


def main() -> int:
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("record_small_trace: no TPU; a CPU trace has no "
                         "device plane")
    import lambdagap_tpu as lgb
    data = higgs_like.generate({"num_features": 28}, 20260926, 1 << 20, 0)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 0,
              "min_sum_hessian_in_leaf": 100, "verbose": -1,
              "telemetry": True}
    bst = lgb.Booster(params, lgb.Dataset(data["X"], label=data["y"],
                                          params=params))
    gb = bst._booster
    for _ in range(2):
        bst.update()
        jax.block_until_ready(gb.scores)
    trace_dir = os.path.join(OUT, "trace")
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("lg_iteration"):
        bst.update()
        jax.block_until_ready(gb.scores)
    jax.profiler.stop_trace()
    gb.telemetry.close()
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    with open(path, "rb") as f, \
            gzip.open(os.path.join(OUT, NAME + ".xplane.pb.gz"), "wb") as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(trace_dir)
    learner = gb.learner
    with open(os.path.join(OUT, NAME + ".json"), "w") as f:
        json.dump({"device": device.device_kind, "jax": jax.__version__,
                   "rows": 1 << 20, "window": learner._window(1 << 20),
                   "learner": [type(learner).__name__, learner.hist_impl,
                               learner.layout],
                   "records": [list(gb.telemetry.records)[-1]]}, f, indent=1)
    print(json.dumps({"ok": True, "out": OUT,
                      "bytes": os.path.getsize(
                          os.path.join(OUT, NAME + ".xplane.pb.gz"))}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
