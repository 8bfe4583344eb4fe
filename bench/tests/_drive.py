"""Drive one run of a tiny cell on the CPU, with a fault planted in the
program underneath the timed path; print the run's ``correct`` and checks.

    python bench/tests/_drive.py <chips> <fault>

``fault``: ``none`` or one of ``bench/faults.py``.  With 4 chips the cell
is hp2 x cp2 on four virtual CPU devices.
"""
import json
import os
import sys
import tempfile
import time

if __name__ == "__main__":
    chips, fault = int(sys.argv[1]), sys.argv[2]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                               f"{chips}")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from conftest import TINY_CELL, make_tiny_root

    from bench.faults import plant

    if fault != "none":
        plant(fault)

    from bench import harness
    from bench.manifest import Manifest
    from bench.peaks import PEAKS

    layout = ({"dp": 1, "hp": 2, "cp_outer": 1, "cp_inner": 2}
              if chips == 4 else None)
    root = make_tiny_root(tempfile.mkdtemp(),
                          arch="qwen3-1.7b" if chips == 4 else "olmo-1b",
                          layout=layout, chips=chips)
    result, _ = harness.run(Manifest(root), TINY_CELL, 2 ** 32 + 11, 0.5,
                            False, time.perf_counter(), require_tpu=False,
                            peak=PEAKS["TPU v5 lite"])
    print(json.dumps({"correct": result["correct"],
                      "check": result["check"]}))
