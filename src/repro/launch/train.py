"""Training launcher.

Single-host (CPU/GPU dev) and multi-host SPMD: on a real fleet every host
runs this same script; ``jax.distributed.initialize()`` picks up the
standard cluster env (COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID or
TPU metadata).  All execution decisions — mesh, placement, hybrid ZeRO,
remat, microbatching — are made once by ``build_plan`` and printed via
``plan.describe()``.

    python -m repro.launch.train --arch qwen3-1.7b --steps 100 \
        --seq-len 4096 --global-batch 256 --hp 8 --inner 2 \
        --grad-accum 4 --ckpt-dir /tmp/ckpt --save-every 20 [--smoke]

``--smoke`` swaps in the reduced config + a 1-device mesh — the same code
path end to end, laptop-sized.  Otherwise the grid spans every attached
device: ``--hp`` (default: the config's head parallelism, capped to what
the device count divides) × ``cp = devices / hp`` context ranks, with
``--inner`` ranks on the inner ring (default ``gcd(cp, 4)``).  So

    python -m repro.launch.train --arch qwen3-1.7b --hp 2 --seq-len 8192 \
        --global-batch 4 --steps 3

runs hp2 × cp2 on a four-chip host.  Compiled programs go to the
persistent cache (``repro.runtime.compile_cache``).

Checkpointing (``--ckpt-dir``): async per-shard saves every
``--save-every`` steps through the plan-aware ``CheckpointManager``;
SIGTERM flushes a final checkpoint at the next step boundary
(``PreemptionGuard``), and a relaunch resumes from the latest step —
even under a *different* plan (elastic restore-time resharding).
``--no-resume`` starts fresh.

``--pack`` trains on packed documents (``PackedLM``): variable-length
documents bin-packed into the sequence window with per-document
block-causal masking through the 2D-Attention stack; ``--mean-doc-len``
scales the document-length distribution and the cost model's packing
term (default ``seq_len // 4``).

``--offload-chunks N`` enables FPDT sequence-chunk pipelining: the plan's
memory model charges only the HBM-resident chunk fraction (active + next)
and reports the PCIe wire-time floor plus ``max_seq@budget`` in
``plan.describe()``.  The PlanTuner proposes a depth automatically when
the resident plan does not fit the budget.

PlanTuner integration: ``--plan-file plan.json`` consumes a persisted
``TunedPlan`` (no search — the cached winner supplies dp/hp/cp/placement,
grad-accum, remat and ZeRO); ``--tune`` runs the enumerate+score search
for the attached devices first and, when ``--plan-file`` is also given,
caches the winner there for the next run.
"""
from __future__ import annotations

import argparse
import logging
import math

import jax

from repro.configs import get_config, get_parallel, get_reduced
from repro.core.plan import build_plan
from repro.core.topology import ParallelConfig, factor_cp
from repro.launch import args as launch_args
from repro.launch.args import resolve_tuned   # noqa: F401  (re-export)
from repro.runtime.compile_cache import enable_compile_cache
from repro.train.optimizer import OptConfig
from repro.train.trainer import Trainer, TrainerConfig


def device_grid(base: ParallelConfig, n_devices: int, *,
                hp: int | None = None, inner: int | None = None,
                placement: str | None = None) -> ParallelConfig:
    """hp × cp over ``n_devices`` (dp = 1): ``hp`` defaults to the
    config's head parallelism capped to a divisor of the device count."""
    hp = hp or math.gcd(base.hp, n_devices)
    assert n_devices % hp == 0, f"--hp {hp} does not divide {n_devices} " \
        "devices"
    cp_outer, cp_inner = factor_cp(n_devices // hp, inner)
    return ParallelConfig(hp=hp, cp_outer=cp_outer, cp_inner=cp_inner,
                          placement=placement or base.placement)


def main():
    ap = argparse.ArgumentParser()
    launch_args.add_arch(ap, smoke_help="reduced config on 1 device")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--grad-accum", type=int, default=None,
                    help="microbatches per step (default: 1, or the "
                         "tuned plan's value under --plan-file)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--hp", type=int, default=None)
    ap.add_argument("--inner", type=int, default=None)
    ap.add_argument("--placement", default=None)
    ap.add_argument("--remat", default=None,
                    help="none|full|scpp|auto (default: model config)")
    ap.add_argument("--pack", action="store_true",
                    help="packed-document training: bin-packed variable-"
                         "length documents with per-document block-causal "
                         "masking (PackedLM)")
    ap.add_argument("--mean-doc-len", type=int, default=None,
                    help="expected mean document length of the packed "
                         "stream (default: seq_len // 4); sets the data "
                         "source's length range and the cost model's "
                         "packing term")
    ap.add_argument("--offload-chunks", type=int, default=None,
                    help="FPDT sequence-chunk pipelining: stream the "
                         "sequence through attention in this many chunks "
                         "with inactive K/V staged in host memory "
                         "(default: 1 = fully resident, or the tuned "
                         "plan's value under --plan-file)")
    launch_args.add_plan_source(ap)
    launch_args.add_checkpointing(ap)
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize()")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    if args.distributed:
        jax.distributed.initialize()
    enable_compile_cache()

    if args.smoke:
        cfg = get_reduced(args.arch)
        pc = ParallelConfig()
        devices = jax.devices()[:1]
        seq, gb = min(args.seq_len, 128), min(args.global_batch, 8)
    else:
        cfg = get_config(args.arch)
        pc = device_grid(get_parallel(args.arch, "train_4k", False),
                         len(jax.devices()), hp=args.hp, inner=args.inner,
                         placement=args.placement)
        devices = None
        seq, gb = args.seq_len, args.global_batch

    mean_doc = args.mean_doc_len or max(8, seq // 4)
    tuned = None
    grad_accum = args.grad_accum
    if args.tune or args.plan_file:
        tuned = resolve_tuned(args, cfg, seq=seq, gb=gb, smoke=args.smoke,
                              packing=min(1.0, mean_doc / seq)
                              if args.pack else 1.0)
        pc = tuned.parallel()
        devices = None
        if grad_accum is None and gb % tuned.grad_accum:
            print(f"[train] plan's grad_accum={tuned.grad_accum} does "
                  f"not divide global_batch={gb}; using 1 "
                  f"(pass --grad-accum to choose)")
            grad_accum = 1
    n = pc.num_devices
    assert len(jax.devices()) >= n, \
        f"need {n} devices, have {len(jax.devices())}"

    plan = build_plan(cfg, pc, OptConfig(lr=args.lr,
                                         total_steps=args.steps),
                      devices=devices, grad_accum=grad_accum,
                      remat=args.remat, seq_len=seq, global_batch=gb,
                      packed=args.pack,
                      mean_doc_len=mean_doc if args.pack else None,
                      offload_chunks=args.offload_chunks, tuned=tuned)
    print(plan.describe())
    trainer = Trainer(
        plan, plan.data_config(seq, gb),
        TrainerConfig(num_steps=args.steps, ckpt_dir=args.ckpt_dir,
                      ckpt_every=launch_args.save_every(args),
                      resume=args.resume))
    losses = trainer.run()
    print(f"final loss: {losses[-1]:.4f} "
          f"(median step {trainer.monitor.median:.3f}s)")
    rep = trainer.monitor.report()
    if rep["stragglers"]:
        print(f"stragglers flagged: {rep['stragglers']}")


if __name__ == "__main__":
    main()
