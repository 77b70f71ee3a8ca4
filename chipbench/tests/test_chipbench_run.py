"""``run.py`` refuses to give a result off the TPU or without the program."""
import os
import shutil
import subprocess
import sys

from chipbench import harness

ARGS = ["--workload", harness.benchmark()["workloads"][0]["name"],
        "--seed", "3", "--seconds", "1", "--trace", "0"]


def _run(root):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=120)


def test_refuses_a_platform_that_is_not_a_tpu():
    p = _run(harness.REPO)
    assert p.returncode == 2
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
    assert p.stdout == ""


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(harness.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode == 2
    assert "program is not beside the benchmark" in p.stderr
    assert p.stdout == ""
