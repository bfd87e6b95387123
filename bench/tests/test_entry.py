"""bench/run.py as its command line runs it, off the chip: it exits non-zero
and prints no result line, both in the checkout and in a directory that
holds only BENCHMARK.json and bench/."""
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.tiny import ROOT

ARGS = ["--workload", "qwen3-1.7b.chat", "--seed", str(2**31 + 11),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("where", ["checkout", "benchmark files only"])
def test_no_chip_no_result(tmp_path, where):
    cwd = ROOT
    if where != "checkout":
        cwd = tmp_path
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        shutil.copytree(ROOT / "bench", tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(cwd)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
