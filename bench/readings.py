"""The readings a cell's limits are set from, on the chip.

    python bench/readings.py --workload <cell> --program-seeds 1 2 ... \\
        --control-seeds 7 8 9

For each program seed: the program's first checked steps against the
reference, as a run makes them (no window); with ``--fault`` the program
carries that fault (``bench/faults.py``).  For each control seed: the
control (the reference in fp8, ``bench/reference.py``) and the fault
"half of the batch left out, the mean over the rest" (planted in the
reference), each against the reference.  One JSON line per reading on
stdout: ``{"seed", "kind", "numbers"}``.  A state left unchanged reads 1
by the measure of ``bench/check.py`` and needs no run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault", default="none",
                    help="plant this fault (bench/faults.py) in the program "
                         "for the program seeds")
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import check, harness
    from bench.manifest import Manifest
    from bench.program import Program
    from bench.weights import Dims

    man = Manifest(ROOT)
    cell = man.cell(args.workload)
    config, traffic = man.config(cell), man.traffic(cell)
    k = int(man.check(cell)["steps"])
    dims = Dims.from_config(config)
    devices = harness.chips_for(cell)
    harness.enable_cache()
    if args.fault != "none":
        from bench.faults import plant
        plant(args.fault)

    def emit(seed, kind, got, ref, t0):
        print(json.dumps({"seed": seed, "kind": kind,
                          "numbers": check.numbers(got, ref),
                          "seconds": time.perf_counter() - t0}), flush=True)

    for seed in args.program_seeds:
        t0 = time.perf_counter()
        prog = Program(config, traffic, dims, devices, seed)
        got, _ = harness.checked_steps(prog, k)
        prog.close()
        got["data"] = prog.data_gaps(k)
        del prog
        gc.collect()
        emit(seed, "program" if args.fault == "none" else args.fault, got,
             harness.reference_readings(dims, traffic, seed, k, devices), t0)
    for seed in args.control_seeds:
        t0 = time.perf_counter()
        ref = harness.reference_readings(dims, traffic, seed, k, devices)
        emit(seed, "control_fp8", harness.reference_readings(
            dims, traffic, seed, k, devices, prec="fp8"), ref, t0)
        t0 = time.perf_counter()
        emit(seed, "fault_half_batch", harness.reference_readings(
            dims, traffic, seed, k, devices, half=True), ref, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
