"""Run one benchmark cell once, on the chips of this machine.

    python bench/main.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Loads the cell named in
``BENCHMARK.json``, sets up, checks the first steps against the plain
reference, measures ``--seconds`` of training and prints one JSON line:
the end-to-end metrics with ``--trace 0``, the per-layer metrics with
``--trace 1``.  Exits 2, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for, or where the checkout lacks the
program.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program under {ROOT}/src", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import main_cli
    return main_cli(args, ROOT, T_START)


if __name__ == "__main__":
    sys.exit(main())
