"""Bring-up contracts (ISSUE 21): what must hold for the program to start on
the chip and be judged there — the compile cache can be placed from
outside, a stale native binary can never load, and ``chip_smoke.py`` refuses
to pass without a TPU (its CPU rehearsal runs here, stamped as one)."""
import json
import os
import re
import shutil
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache ------------------------------------------------------
def test_compile_cache_env_wins_and_default_is_the_checkout(monkeypatch):
    from lambdagap_tpu.utils.compile_cache import (DEFAULT_DIR,
                                                   configure_compile_cache)
    before = jax.config.jax_compilation_cache_dir
    try:
        # the variable is JAX's own: when it is set, code sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert configure_compile_cache() == DEFAULT_DIR
        assert DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_one_place_sets_the_compile_cache_dir():
    hits = []
    for root in ("lambdagap_tpu", "tools", "examples"):
        for d, _, files in os.walk(os.path.join(REPO, root)):
            hits += [os.path.join(d, f) for f in files if f.endswith(".py")]
    hits += [os.path.join(REPO, f) for f in os.listdir(REPO)
             if f.endswith(".py")]
    setters = [os.path.relpath(p, REPO) for p in hits
               if re.search(r"""update\(\s*["']jax_compilation_cache_dir""",
                            open(p).read())]
    assert setters == [os.path.join("lambdagap_tpu", "utils",
                                    "compile_cache.py")]


# -- native artefact ----------------------------------------------------
def test_native_artefact_is_keyed_on_source_content(tmp_path, monkeypatch):
    from lambdagap_tpu import native
    for name in native._SOURCES:
        shutil.copy(os.path.join(native._HERE, name), tmp_path / name)
    real = os.path.basename(native.artefact_path())
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    assert os.path.basename(native.artefact_path()) == real
    # a binary left by OTHER sources (here: the old fixed name, newer than
    # every source, and a content-keyed one) is never what the key names
    (tmp_path / "_lg_native.so").write_bytes(b"stale")
    with open(tmp_path / "binner.cpp", "a") as f:
        f.write("\n// edited\n")
    edited = native.artefact_path()
    assert os.path.basename(edited) != real
    assert not os.path.exists(edited)        # so _build_lib must compile


# -- chip_smoke.py ------------------------------------------------------
def _smoke(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)       # one device: the multichip leg skips
    return subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py"),
                           *args], capture_output=True, text=True,
                          cwd=REPO, env=env, timeout=600)


def test_chip_smoke_refuses_without_a_tpu():
    r = _smoke()
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "platform='cpu'" in r.stderr
    # it said what it found, and printed no result
    assert "platform=cpu" in r.stdout and '"ok"' not in r.stdout
    assert "train" not in r.stdout


def test_chip_smoke_cpu_rehearsal():
    r = _smoke("--rehearse-cpu")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    # the last line is the driver's contract: exactly these keys
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
    # the line before it is the summary, stamped as a rehearsal
    tag = "[chip_smoke] summary "
    assert lines[-2].startswith(tag)
    summ = json.loads(lines[-2][len(tag):])
    assert summ["rehearsal"] is True
    assert summ["legs"] == {"kernels": "pass", "train": "pass",
                            "parity": "pass", "predict": "pass",
                            "rank": "pass", "multichip": "not run"}
    assert "multichip: not run (1 device)" in r.stdout
    assert (summ["learner"], summ["hist_impl"], summ["layout"]) == \
        ("FusedTreeLearner", "pallas", "sorted")
    assert list(summ)[-1] == "claim" and summ["claim"] is None
