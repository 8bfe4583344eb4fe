"""The control — the reference put in the program's place, in fp8 — fails
where the program passes (a tiny cell on the CPU; the chip readings at the
cells' own size are in PERF.md)."""
import time

import jax

from bench import check, harness
from bench.manifest import Manifest
from bench.peaks import PEAKS
from bench.weights import Dims

from conftest import TINY_CELL, make_tiny_root

#: limits for the tiny cell, between its program's readings (loss 9e-6,
#: grad 1.2e-3, change 6e-4 at this seed) and its control's (7e-5, 1.2e-2,
#: 4e-3)
TINY_LIMITS = {"loss": 3e-5, "grad": 5e-3, "change": 2e-3, "data": 0}
SEED = 2 ** 40 + 3


def test_program_passes_and_control_fails(tmp_path):
    root = make_tiny_root(tmp_path, limits=TINY_LIMITS)
    man = Manifest(root)
    result, _ = harness.run(man, TINY_CELL, SEED, 0.5, False,
                            time.perf_counter(), require_tpu=False,
                            peak=PEAKS["TPU v5 lite"])
    assert result["correct"] is True, result["check"]
    cell = man.cell(TINY_CELL)
    dims, traffic = Dims.from_config(man.config(cell)), man.traffic(cell)
    dev = jax.devices()[:1]
    ref = harness.reference_readings(dims, traffic, SEED, 3, dev)
    control = harness.reference_readings(dims, traffic, SEED, 3, dev,
                                         prec="fp8")
    nums = check.numbers(control, ref)
    assert not check.verdict(nums, TINY_LIMITS), nums
    prog = {k: v["value"] for k, v in result["check"].items()}
    assert any(nums[k] >= 3 * prog[k] for k in ("loss", "grad", "change")), \
        (nums, prog)
