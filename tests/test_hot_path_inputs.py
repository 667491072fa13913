"""What decides the programs the benchmark times comes from shapes and
configuration, never from the environment (PR 33).

- no module under the six hot-path directories reads an environment
  variable, except the two ``LAMBDAGAP_DEBUG`` re-check switches and the
  whole-environment copy ``parallel/cluster.py`` hands to its workers;
- ``FusedTreeLearner._pick_chunk`` is the one statement of the row window
  W, pinned at the two cells' shapes and at its edges;
- ``train_device`` on the resident path hands ``_train_jit`` the twelve
  positional arguments its compile key is made of.
"""
import ast
import inspect
import os
import types

import numpy as np
import pytest

import lambdagap_tpu as lgb
from lambdagap_tpu.models.fused_learner import FusedTreeLearner
from lambdagap_tpu.parallel.fused_parallel import (
    Fused2DTreeLearner, FusedDataParallelTreeLearner)

PKG = os.path.dirname(os.path.abspath(lgb.__file__))

# (file, variable) an environment READ is allowed at; "*" is the whole
# mapping, copied for a child process
ALLOWED_ENV = {
    "models": {("learner.py", "LAMBDAGAP_DEBUG")},
    "ops": set(),
    "parallel": {("fused_parallel.py", "LAMBDAGAP_DEBUG"),
                 ("cluster.py", "*")},
    "infer": set(),
    "objectives": set(),
    "data": set(),
}


def _env_uses(path: str):
    """Every use of ``os.environ`` / ``os.getenv`` in one source file, as
    the variable's name where it is a literal, "*" where the mapping is
    taken whole, "?" where the name is computed."""
    tree = ast.parse(open(path, encoding="utf-8").read(), path)
    parents = {c: p for p in ast.walk(tree) for c in ast.iter_child_nodes(p)}
    uses = []
    for node in ast.walk(tree):
        is_env = (isinstance(node, ast.Attribute)
                  and node.attr in ("environ", "getenv")
                  and isinstance(node.value, ast.Name)
                  and node.value.id == "os") \
            or (isinstance(node, ast.ImportFrom) and node.module == "os"
                and any(a.name in ("environ", "getenv") for a in node.names))
        if not is_env:
            continue
        up = parents.get(node)
        key = "*"
        if isinstance(node, ast.ImportFrom):
            key = "?"
        elif isinstance(up, ast.Subscript):                  # os.environ[k]
            key = up.slice.value if isinstance(up.slice, ast.Constant) \
                else "?"
        elif isinstance(up, ast.Compare):                    # k in os.environ
            key = up.left.value if isinstance(up.left, ast.Constant) else "?"
        else:
            call = up if node.attr == "getenv" else parents.get(up)
            named = node.attr == "getenv" or (
                isinstance(up, ast.Attribute)
                and up.attr in ("get", "pop", "setdefault"))
            if named and isinstance(call, ast.Call):
                key = call.args[0].value if call.args and isinstance(
                    call.args[0], ast.Constant) else "?"
        uses.append(key)
    return uses


@pytest.mark.parametrize("directory", sorted(ALLOWED_ENV))
def test_hot_path_directory_reads_no_environment(directory):
    found = set()
    root = os.path.join(PKG, directory)
    for dirpath, _dirs, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                found |= {(os.path.relpath(path, root), key)
                          for key in _env_uses(path)}
    assert found == ALLOWED_ENV[directory], (
        f"lambdagap_tpu/{directory}: environment uses {sorted(found)}; a "
        "switch on the hot path is a config field or a constant, not an "
        "environment read")


def _stub(cls, num_data, num_leaves, rows_per_block=4096, n_loc=None):
    cfg = types.SimpleNamespace(tpu_rows_per_block=rows_per_block,
                                num_leaves=num_leaves)
    self = types.SimpleNamespace(num_data=num_data, config=cfg,
                                 min_chunk=cls.min_chunk)
    if n_loc is not None:
        self.n_loc = n_loc
    return cls._pick_chunk(self)


@pytest.mark.parametrize("cls,kwargs,want", [
    # the two cells (PERF.md section 4)
    (FusedTreeLearner, dict(num_data=10_500_000, num_leaves=255), 32_768),
    (FusedTreeLearner, dict(num_data=3_408_630, num_leaves=255), 8_192),
    # a small N resolves the floor; _window then clips it to the rows
    (FusedTreeLearner, dict(num_data=5_000, num_leaves=31), 4_096),
    (FusedTreeLearner, dict(num_data=1, num_leaves=2), 4_096),
    # few leaves count as 8; the cap is 16 row blocks
    (FusedTreeLearner, dict(num_data=10_500_000, num_leaves=2), 65_536),
    (FusedTreeLearner, dict(num_data=10_500_000, num_leaves=255,
                            rows_per_block=1024), 16_384),
    (FusedTreeLearner, dict(num_data=10_500_000, num_leaves=255,
                            rows_per_block=64), 4_096),
    # exactly at a power of two: per_leaf // 2 = 8,192 stays 8,192
    (FusedTreeLearner, dict(num_data=16_384 * 255, num_leaves=255), 8_192),
    (FusedTreeLearner, dict(num_data=16_384 * 255 + 2 * 255,
                            num_leaves=255), 16_384),
    # the learners that shard rows size it off their local rows, floor 1,024
    (FusedDataParallelTreeLearner,
     dict(num_data=10_500_000, num_leaves=255, n_loc=2_625_000), 8_192),
    (Fused2DTreeLearner,
     dict(num_data=4_001, num_leaves=15, n_loc=501), 1_024),
])
def test_pick_chunk_at_the_cells_shapes_and_the_edges(cls, kwargs, want):
    assert _stub(cls, **kwargs) == want


def test_train_device_hands_train_jit_its_twelve_arguments():
    """The resident path's one dispatch a tree: positional arguments in
    the order ``_train_tree_impl`` declares them, ``has_mask`` the only
    keyword. Their shapes and dtypes are the compile key of the program
    both cells time, and every compile is in ``setup_s``."""
    rng = np.random.default_rng(0)
    N, F = 3000, 5
    X = rng.normal(size=(N, F)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 15,
              "tpu_fused_learner": 1, "tree_layout": "sorted",
              "verbose": -1}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    learner = bst._booster.learner
    assert type(learner) is FusedTreeLearner and learner.pack32
    calls = []
    real = learner._train_jit

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return real(*args, **kwargs)

    learner._train_jit = spy
    bst.update()
    (args, kwargs), = calls
    assert kwargs == {"has_mask": False}
    names = ("grad", "hess", "row_mask", "fmask", "x_rows", "x_cols",
             "srows", "gq", "hq", "gs", "hs", "ekey")
    assert len(args) == len(names)
    declared = list(inspect.signature(
        FusedTreeLearner._train_tree_impl).parameters)
    assert declared[1:1 + len(names)] == list(names)
    got = {n: (tuple(a.shape), str(a.dtype)) for n, a in zip(names, args)}
    W = learner._window(N)
    lanes = -(-(learner.hx_rows.shape[1] + 8) // 4)   # bins + g, h as u32
    assert got == {
        "grad": ((N,), "float32"), "hess": ((N,), "float32"),
        "row_mask": ((1,), "bool"), "fmask": ((F,), "bool"),
        "x_rows": ((N, F), "uint8"), "x_cols": ((1, 1), "uint8"),
        "srows": ((lanes, N + W), "uint32"),      # word-major (PR 36)
        "gq": ((1,), "int8"), "hq": ((1,), "int8"),
        "gs": ((), "float32"), "hs": ((), "float32"),
        "ekey": ((2, 2), "uint32"),
    }
    assert args[4] is learner.hx_rows and args[5] is learner.x_cols
