"""Shared launcher argument builders + PlanTuner plan-file resolution.

``train.py``, ``serve.py``, ``dryrun.py`` and ``tune.py`` used to each
re-declare the config / ``--plan-file`` / ``--tune`` flag set (and the
resolution logic behind it) — one builder per flag family ends the
drift, and gives new flags (``--ckpt-dir``/``--resume``/``--save-every``)
a single home.  ``scripts/check_docs.py`` statically unions this
module's ``add_argument`` calls into each importing launcher's known
flag set, so documented commands stay verifiable.
"""
from __future__ import annotations

import os


def add_arch(ap, *, arch_help: str = "architecture id",
             smoke_help: str | None = None):
    """``--arch`` (required) and, when ``smoke_help`` is given,
    ``--smoke`` — the config-selection pair every launcher starts
    with."""
    ap.add_argument("--arch", required=True, help=arch_help)
    if smoke_help is not None:
        ap.add_argument("--smoke", action="store_true", help=smoke_help)


def add_plan_source(ap):
    """``--tune`` / ``--plan-file``: the PlanTuner plan source pair
    consumed by ``resolve_tuned``."""
    ap.add_argument("--tune", action="store_true",
                    help="search the plan space for the attached devices "
                         "first")
    ap.add_argument("--plan-file", default=None,
                    help="TunedPlan JSON: consumed when it exists, "
                         "written by --tune otherwise")


def add_checkpointing(ap):
    """``--ckpt-dir`` / ``--save-every`` / ``--resume``: the trainer's
    checkpoint surface (async sharded saves, elastic resume)."""
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory: per-shard async saves, "
                         "auto-resume from the latest step")
    ap.add_argument("--save-every", type=int, default=None,
                    help="async-save cadence in steps (default 50)")
    ap.add_argument("--resume", dest="resume", action="store_true",
                    default=True,
                    help="resume from the latest checkpoint in --ckpt-dir "
                         "(default)")
    ap.add_argument("--no-resume", dest="resume", action="store_false",
                    help="start fresh even when --ckpt-dir holds "
                         "checkpoints")


def save_every(args) -> int:
    """The effective save cadence: ``--save-every``, default 50."""
    return 50 if args.save_every is None else args.save_every


def resolve_tuned(args, cfg, *, seq: int, gb: int, smoke: bool,
                  packing: float = 1.0, accums=None, page_size=None,
                  tag: str = "train"):
    """--plan-file / --tune resolution shared by train and serve: a
    cached TunedPlan wins; otherwise search (and cache to --plan-file
    when given).

    ``packing`` is the packed-workload fraction (mean_doc_len / seq_len)
    the cost model scores with — 1.0 for unpacked runs.  ``accums``
    restricts the search's grad-accum candidates (serve pins ``(1,)``);
    ``page_size`` is recorded in the persisted plan (serve).
    """
    import jax
    from repro.tune import TunedPlan, tune
    if args.plan_file and os.path.exists(args.plan_file):
        tuned = TunedPlan.load(args.plan_file)
        assert tuned.arch == args.arch, \
            f"{args.plan_file} was tuned for {tuned.arch!r}, " \
            f"not {args.arch!r} — delete it or pass the matching --arch"
        print(f"[{tag}] tuned plan from {args.plan_file}: "
              f"dp{tuned.dp}/hp{tuned.hp}/cp{tuned.cp_outer}x"
              f"{tuned.cp_inner}/{tuned.placement} accum="
              f"{tuned.grad_accum} remat={tuned.remat} "
              f"zero={tuned.zero} (no re-search)")
        if args.tune:
            print(f"[{tag}] --tune ignored: cached plan exists "
                  f"(delete {args.plan_file} to re-search)")
        if (tuned.seq_len, tuned.global_batch) != (seq, gb):
            print(f"[{tag}] note: plan was tuned for seq="
                  f"{tuned.seq_len} gb={tuned.global_batch}, "
                  f"running seq={seq} gb={gb}")
        return tuned
    kw = {}
    if accums is not None:
        kw["accums"] = accums
    result = tune(cfg, num_devices=len(jax.devices()), seq_len=seq,
                  global_batch=gb,
                  memory_budget_gb=1.0 if smoke else 16.0,
                  packing=packing, arch=args.arch, **kw)
    print(result.table())
    tuned = result.tuned_plan(**({"page_size": page_size}
                                 if page_size is not None else {}))
    if args.plan_file:
        tuned.save(args.plan_file)
        print(f"[{tag}] tuned plan cached -> {args.plan_file}")
    return tuned
