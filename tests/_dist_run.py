"""Run one check of ``_dist_checks.py`` in a subprocess with 8 fake host
devices (device count is locked at first jax import in a process)."""
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def run_check(check: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    res = subprocess.run([sys.executable, os.path.join(HERE, "_dist_checks.py"),
                          check], capture_output=True, text=True,
                         timeout=1200, env=env)
    assert res.returncode == 0, f"{check} failed:\n{res.stdout}\n{res.stderr}"
    assert f"PASS {check}" in res.stdout
