"""A run sees ``correct`` come out false when the timed path is broken
underneath it, and true when it is not (tiny cells on the CPU; the chip
look is skipped)."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _drive(chips, fault):
    r = subprocess.run([sys.executable, os.path.join(HERE, "_drive.py"),
                        str(chips), fault], capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("chips,fault,correct", [
    (1, "none", True),
    (1, "unchanged", False),
    (1, "half_batch", False),
    (4, "none", True),
    (4, "no_exchange", False),
])
def test_fault_is_seen(chips, fault, correct):
    out = _drive(chips, fault)
    assert out["correct"] is correct, out
