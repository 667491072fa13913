"""The run refuses to measure where it cannot: no TPU, or a checkout that
holds only BENCHMARK.json and the benchmark's own files. Both exit non-zero
and print no result line."""
import json
import os
import shutil
import subprocess
import sys

from benchmark import manifest

ARGS = ["--workload", "higgs-train", "--seed", "2147483999",
        "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="17")
    return subprocess.run([sys.executable, "-m", "benchmark.run"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def no_result_line(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            assert "correct" not in json.loads(line)
        except ValueError:
            pass


def test_no_tpu_is_an_error_not_a_slower_number():
    proc = run(manifest.ROOT)
    no_result_line(proc)
    assert "no TPU" in proc.stderr


def test_a_checkout_without_the_program_prints_no_result(tmp_path):
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(tmp_path)
    no_result_line(proc)
    assert "lambdagap_tpu" in proc.stderr
