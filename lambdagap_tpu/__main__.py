"""CLI entry. Multi-process runs must join the distributed runtime BEFORE
the package import touches the JAX backend (module-level jnp constants
initialize it, after which jax.distributed.initialize is rejected) — so a
light argv/config-file peek happens here, pre-import (the analog of the
reference CLI calling Network::Init at application start,
src/application/application.cpp)."""
import sys

# minimal mirror of config.py's alias table for the keys the early init
# needs (the full table lives in the package, which must not be imported
# yet)
_ALIASES = {
    "machine_rank": "machine_rank", "process_id": "machine_rank",
    "rank": "machine_rank",
    "num_machines": "num_machines", "num_machine": "num_machines",
    "machines": "machines", "workers": "machines", "nodes": "machines",
    "machine_list_filename": "machine_list", "machine_list_file":
    "machine_list", "machine_list": "machine_list", "mlist": "machine_list",
    "pre_partition": "pre_partition", "is_pre_partition": "pre_partition",
    "task": "task", "config": "config", "config_file": "config",
}


def _early_distributed_init(argv) -> None:
    params = {}

    def put(k, v):
        canon = _ALIASES.get(k.strip().lower())
        if canon:
            params.setdefault(canon, v.strip())

    config_path = None
    for arg in argv:
        if "=" not in arg:
            continue
        k, v = arg.split("=", 1)
        put(k, v)
    config_path = params.pop("config", None)
    if config_path:
        try:
            with open(config_path) as f:
                for line in f:
                    line = line.split("#", 1)[0].strip()
                    if "=" in line:
                        k, v = line.split("=", 1)
                        put(k, v)
        except OSError:
            return   # the real parser reports the error with context
    try:
        num_machines = int(params.get("num_machines", "1"))
        rank = int(params.get("machine_rank", "-1"))
    except ValueError:
        return       # the real parser reports the error with context
    pre_partition = params.get("pre_partition", "false").lower() in (
        "true", "1", "yes", "on", "+")
    # only training uses the distributed runtime (cli.run_train); a predict
    # reusing a training config must not block waiting for peer ranks
    if params.get("task", "train") != "train":
        return
    if num_machines <= 1 or not pre_partition:
        return
    machines = params.get("machines", "")
    if not machines and params.get("machine_list"):
        try:
            with open(params["machine_list"]) as f:
                machines = ",".join(ln.strip() for ln in f if ln.strip())
        except OSError:
            return
    if not machines or rank < 0:
        return
    import jax
    jax.distributed.initialize(
        coordinator_address=machines.split(",")[0].strip(),
        num_processes=num_machines, process_id=rank)


_early_distributed_init(sys.argv[1:])

from .cli import run  # noqa: E402  (must follow the distributed init)

raise SystemExit(run())
