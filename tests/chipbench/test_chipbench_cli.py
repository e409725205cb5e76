"""``chipbench/run.py`` prints no result where it cannot measure: without
a TPU, and in a directory that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = harness.ROOT


def _run(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "walk.kron16", "--seed",
         "3000000001", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    assert '"correct"' not in last


def test_no_tpu_no_result():
    proc = _run(ROOT)
    _no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns(".jax_cache", "traces", "out",
                                                      "__pycache__"))
    _no_result(_run(tmp_path))


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        harness.load_spec("no.such.cell")
