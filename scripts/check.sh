#!/usr/bin/env bash
# Tiered gate.  Run from anywhere:
#     scripts/check.sh --fast    # tier-1 pytest (single-device tests;
#                                # dist/slow deselected) + docs check +
#                                # PlanTuner enumerate+score smoke
#     scripts/check.sh           # full: all tests + docs check +
#                                # elastic-restart smoke + plan/tune
#                                # smoke + serve smoke + packed train smoke
# The dryrun --plan [--tune] invocations fail fast on ExecutionPlan/
# PlanTuner regressions for production cells of one arch without
# compiling anything.  Speed is measured on the chip by bench/ (see
# PERF.md), not here.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" == "--fast" ]]; then
    python -m pytest -q -m "not dist and not slow"
    python scripts/check_docs.py
    python -m repro.launch.tune --arch qwen3-1.7b --smoke \
        --out /tmp/check_tuned_plan.json
    exit 0
fi

python -m pytest -x -q
python scripts/check_docs.py
python examples/elastic_restart.py
python -m repro.launch.dryrun --plan --arch qwen3-1.7b --shape all
python -m repro.launch.dryrun --plan --tune --arch qwen3-1.7b \
    --shape train_4k
python -m repro.launch.serve --arch qwen3-1.7b --smoke \
    --prompt-len 24 --gen 8 --batch 2 --requests 4
python -m repro.launch.train --arch qwen3-1.7b --smoke --pack --steps 2
