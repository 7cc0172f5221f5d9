"""The harness refuses to run without a TPU, or without the program."""

import os
import shutil
import subprocess
import sys

from benchmarks.chip import layout

CELL = layout.benchmark()["workloads"][0]["name"]
ARGS = ["--workload", CELL, "--seed", "3000000123", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "benchmarks/chip/run.py"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_means_no_result():
    r = _run(layout.ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_without_the_program_it_fails(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(layout.ROOT, "benchmarks", "chip"),
                    root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(layout.ROOT, "BENCHMARK.json"), root)
    # past the look for a chip, the run needs the program under src/
    code = ("import sys; sys.path.insert(0, '.');"
            "from benchmarks.chip import run;"
            f"run.run_cell({CELL!r}, 1, 1.0, False, require_tpu=False)")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "No module named 'repro'" in r.stderr
