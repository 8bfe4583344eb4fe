"""``bench/main.py`` refuses to run without a TPU and without the program."""
import os
import shutil
import subprocess
import sys

from conftest import REPO


def _run(cwd, root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "main.py"),
         "--workload", "olmo-1b.1chip.s32k", "--seed", str(2 ** 33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_the_cpu():
    r = _run(REPO, REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    r = _run(str(tmp_path), str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
