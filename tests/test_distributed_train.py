"""Distributed equivalence tests of the training system (gradient
compression and accumulation, ZeRO placement, packing, elastic checkpoints,
offload) — each runs a subprocess with 8 fake host devices
(``_dist_run.py``).  Marked ``dist``, as ``test_distributed.py``."""
import pytest

from _dist_run import run_check

pytestmark = pytest.mark.dist

_CHECKS = ["grad_compression", "plan_placement", "accum_collectives",
           "packed_parity", "ckpt_elastic", "offload_parity"]


@pytest.mark.parametrize("check", _CHECKS)
def test_distributed(check):
    run_check(check)
