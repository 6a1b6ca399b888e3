"""The command refuses to run, and prints no result, without a TPU or
without the program."""
import json
import os
import shutil
import subprocess
import sys

from chipbench.tests.tiny import REPO

ARGS = ["--workload", "qwen2-0.5b.sim.g50i5.b1x256", "--seed", "7",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(root / "chipbench" / "run.py"),
                           *ARGS], capture_output=True, text=True, env=env,
                          timeout=300, cwd=root)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            assert not isinstance(json.loads(line), dict) or \
                "correct" not in json.loads(line)
        except json.JSONDecodeError:
            pass


def test_no_tpu_exits_nonzero_without_result():
    proc = _run(REPO)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    _no_result(proc)


def test_benchmark_alone_exits_nonzero_without_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    _no_result(proc)
