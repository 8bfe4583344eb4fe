"""Distributed equivalence tests of attention and the model families — each
runs a subprocess with 8 fake host devices (``_dist_run.py``); the
training system's checks are in ``test_distributed_train.py``, a file of
their own so that the two halves run on separate workers.

Marked ``dist`` so the CI fast tier can deselect the whole suite with
``-m 'not dist'`` instead of relying on ``-x`` ordering luck.
"""
import pytest

from _dist_run import run_check

pytestmark = pytest.mark.dist

_CHECKS = ["attention_grid", "attention_modes", "ring_pallas_path", "ssm",
           "moe", "e2e_loss", "decode_consistency"]


@pytest.mark.parametrize("check", _CHECKS)
def test_distributed(check):
    run_check(check)
