"""Chip smoke run: the 2D-Attention training path end to end on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the hp x cp grid across four chips

One chip:

1. Kernel parity.  The Pallas flash kernels, forward and ``jax.grad``, at
   qwen3-1.7b attention widths (L=4096, 16 q / 8 kv heads, d=128, causal,
   bf16): unpacked at B=1 and packed (a per-row doc-start table) at B=2,
   each against the fp32 dense reference of ``repro.kernels.ref``.  The
   compiled programs must hold a ``tpu_custom_call``.
2. Training.  qwen3-1.7b at published widths with depth cut to 8 of 28
   layers (the fp32 masters and AdamW moments of all 28 do not fit one
   16 GB chip), seq 4096, global batch 1, 5 steps through ``build_plan``
   -> ``Trainer.run()``.  The synthetic stream draws its tokens from the
   first 4096 ids of the 151936-token vocabulary: spread uniformly over
   all of it, no id recurs from one batch to the next and 5 steps cannot
   lower the loss.  Losses must be finite and fall from step 1 to step 5.

Four chips (``--chips 4``, nothing else): qwen3-1.7b, all 28 layers, seq
8192, global batch 4, 3 steps on hp2 x cp2 (Double-Ring, inner ring w=2)
and 3 steps on dp4, from the same seed and batches.  First-step loss and
grad-norm must agree to 1e-2 relative, and every chip must show a
nonzero peak of memory in use.

Weights are random, made from ``--seed``.  Compiled programs go to the
persistent cache (``repro.runtime.compile_cache``), so a second run shows
a shorter compile time.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU
the script exits 2 at once; any failed check raises, exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: kernel parity bounds: max |kernel - ref| / max |ref| per tensor
OUT_TOL = 2e-2
GRAD_TOL = 3e-2
#: hp x cp vs dp4: first-step loss and grad-norm, relative
GRID_TOL = 1e-2
#: one-chip training draws its tokens from the first DATA_VOCAB ids
DATA_VOCAB = 4096


def _rel_err(x, ref) -> float:
    import numpy as np
    x = np.asarray(x, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(x - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _check(ok: bool, what) -> None:
    """A failed check: raised even under ``python -O``, which strips
    ``assert``."""
    if not ok:
        raise AssertionError(what)


def _assert_kernel(compiled, what: str):
    """The compiled program holds the Pallas kernel: neither interpreted
    nor replaced by the jnp reference."""
    _check("tpu_custom_call" in compiled.as_text(),
           f"{what}: no tpu_custom_call in the compiled HLO")


def _doc_table(batch: int, seq: int, seed: int):
    """(B, L) int32 per-row document starts: documents of 1/16 to 1/2 of
    the window, packed back to back."""
    import numpy as np
    rng = np.random.default_rng(seed)
    doc = np.zeros((batch, seq), np.int32)
    for b in range(batch):
        start = 0
        while start < seq:
            n = int(rng.integers(seq // 16, seq // 2 + 1))
            doc[b, start:start + n] = start
            start += n
    return doc


def kernel_parity(batch: int, packed: bool, seed: int, *, seq: int = 4096,
                  heads: int = 16, kv_heads: int = 8, head_dim: int = 128):
    """Pallas fwd + grad vs the fp32 dense reference; returns errors."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import flash_attention
    from repro.kernels.ref import attention_ref

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (batch, seq, heads, head_dim), jnp.bfloat16)
    k = jax.random.normal(ks[1], (batch, seq, kv_heads, head_dim),
                          jnp.bfloat16)
    v = jax.random.normal(ks[2], (batch, seq, kv_heads, head_dim),
                          jnp.bfloat16)
    do = jax.random.normal(ks[3], q.shape, jnp.bfloat16)
    doc = jnp.asarray(_doc_table(batch, seq, seed)) if packed else None

    def fwd(q, k, v, doc):
        return flash_attention(q, k, v, causal=True, q_doc_start=doc,
                               impl="pallas")

    def loss(q, k, v, doc, do):
        return jnp.sum(fwd(q, k, v, doc).astype(jnp.float32)
                       * do.astype(jnp.float32))

    t0 = time.perf_counter()
    fwd_c = jax.jit(fwd).lower(q, k, v, doc).compile()
    grad_c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v, doc, do).compile()
    compile_s = time.perf_counter() - t0
    tag = f"{'packed' if packed else 'unpacked'} B={batch}"
    _assert_kernel(fwd_c, f"fwd {tag}")
    _assert_kernel(grad_c, f"grad {tag}")
    out = fwd_c(q, k, v, doc)
    grads = grad_c(q, k, v, doc, do)

    def ref(q, k, v, doc, do):
        f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]

        def attn(q, k, v):
            return attention_ref(q, k, v, causal=True, q_doc_start=doc)[0]

        o, vjp = jax.vjp(attn, *f32[:3])
        return o, vjp(f32[3])

    # dense fp32 reference, one sequence at a time (its score tensors are
    # O(L^2) per head), with fp32-exact matmuls
    ref_j = jax.jit(ref)
    with jax.default_matmul_precision("highest"):
        rows = [ref_j(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                      None if doc is None else doc[b:b + 1], do[b:b + 1])
                for b in range(batch)]
    r_out = jnp.concatenate([r[0] for r in rows])
    r_grads = [jnp.concatenate([r[1][i] for r in rows]) for i in range(3)]
    errs = {"out": _rel_err(out, r_out)}
    for name, g, rg in zip(("dq", "dk", "dv"), grads, r_grads):
        errs[name] = _rel_err(g, rg)
    print(f"[kernel] {tag} L={seq} heads={heads}/{kv_heads} d={head_dim} "
          f"compile={compile_s:.2f}s max-rel-err "
          + " ".join(f"{n}={e:.3e}" for n, e in errs.items()), flush=True)
    _check(errs["out"] <= OUT_TOL, (tag, errs))
    _check(max(errs["dq"], errs["dk"], errs["dv"]) <= GRAD_TOL, (tag, errs))
    return errs


def train(cfg, pc, devices, *, seq: int, global_batch: int, steps: int,
          lr: float, seed: int, tag: str, data_vocab: int | None = None
          ) -> dict:
    """``steps`` training steps through build_plan -> Trainer.run();
    ``data_vocab`` narrows the synthetic stream to the first ids."""
    import jax
    import numpy as np
    from repro.analysis.cost import peak_flops
    from repro.analysis.roofline import count_params, model_flops
    from repro.core.plan import build_plan
    from repro.kernels.ops import resolve_impl
    from repro.train.optimizer import OptConfig
    from repro.train.trainer import Trainer, TrainerConfig

    plan = build_plan(cfg, pc, OptConfig(lr=lr, warmup_steps=1,
                                         total_steps=steps),
                      devices=devices, seq_len=seq,
                      global_batch=global_batch)
    print(f"[{tag}] " + plan.describe().replace("\n", f"\n[{tag}] "),
          flush=True)
    impl = resolve_impl(plan.rt.impl)
    _check(impl == "pallas", f"training attention resolved to {impl!r}")
    data = plan.data_config(seq, global_batch)
    if data_vocab:
        data = dataclasses.replace(data, vocab=data_vocab)
    trainer = Trainer(plan, data,
                      TrainerConfig(num_steps=steps, log_every=1, seed=seed))
    t0 = time.perf_counter()
    compiled = trainer.step_fn.lower(trainer.params, trainer.opt_state,
                                     trainer.data.batch(0)).compile()
    compile_s = time.perf_counter() - t0
    _assert_kernel(compiled, tag)
    trainer.step_fn = compiled
    losses = trainer.run()
    hist = trainer.history
    _check([h["step"] for h in hist] == list(range(steps)), hist)
    _check(all(math.isfinite(x) for x in losses), losses)
    times = trainer.monitor.times
    step_s = float(np.median(times[1:]))           # after the warm-up step
    tokens = seq * global_batch
    kind = devices[0].device_kind
    flops = model_flops(plan.cfg, "train", seq, global_batch,
                        count_params(plan.cfg)[1])
    mfu = flops / step_s / (len(devices) * peak_flops(kind))
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print(f"[{tag}] compile={compile_s:.2f}s", flush=True)
    for h, dt in zip(hist, times):
        print(f"[{tag}] step {h['step'] + 1} loss={h['loss']:.6f} "
              f"grad_norm={h['grad_norm']:.6f} time={dt:.4f}s", flush=True)
    print(f"[{tag}] median step after warm-up={step_s:.4f}s "
          f"tokens/s={tokens / step_s:.1f} "
          f"MFU(6*N*tokens, N={count_params(plan.cfg)[1]} incl. embedding, "
          f"attention excluded; peak {peak_flops(kind):.4g} FLOP/s for "
          f"{kind!r})={mfu:.4f}", flush=True)
    print(f"[{tag}] peak_bytes_in_use " + " ".join(
        f"dev{d.id}={p}" for d, p in zip(devices, peaks)), flush=True)
    return {"losses": losses, "grad_norms": [h["grad_norm"] for h in hist],
            "peaks": peaks, "step_s": step_s, "compile_s": compile_s}


def one_chip(seed: int, devices):
    from repro.configs import get_config
    from repro.core.topology import ParallelConfig

    kernel_parity(1, packed=False, seed=seed)
    kernel_parity(2, packed=True, seed=seed + 1)

    full = get_config("qwen3-1.7b")
    cfg = dataclasses.replace(full, num_layers=8)
    print(f"[train] qwen3-1.7b d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab}; depth cut "
          f"{full.num_layers} -> {cfg.num_layers} layers (one-chip memory "
          f"cap: fp32 masters + AdamW moments of all {full.num_layers} "
          f"layers exceed 16 GB); data ids drawn from the first "
          f"{DATA_VOCAB} (so that 5 steps can show learning)", flush=True)
    r = train(cfg, ParallelConfig(), devices[:1], seq=4096, global_batch=1,
              steps=5, lr=1e-3, seed=seed, tag="train",
              data_vocab=DATA_VOCAB)
    _check(r["losses"][-1] < r["losses"][0], r["losses"])


def four_chips(seed: int, devices):
    from repro.configs import get_config
    from repro.core.topology import ParallelConfig

    cfg = get_config("qwen3-1.7b")
    kw = dict(seq=8192, global_batch=4, steps=3, lr=1e-3, seed=seed)
    grid = train(cfg, ParallelConfig(hp=2, cp_outer=1, cp_inner=2),
                 devices[:4], tag="hp2xcp2", **kw)
    gc.collect()                  # the first run's state leaves the chips
    dp = train(cfg, ParallelConfig(dp=4), devices[:4], tag="dp4", **kw)
    d_loss = abs(grid["losses"][0] - dp["losses"][0]) / abs(dp["losses"][0])
    d_gn = (abs(grid["grad_norms"][0] - dp["grad_norms"][0])
            / abs(dp["grad_norms"][0]))
    print(f"[grid] losses hp2xcp2={grid['losses']} dp4={dp['losses']}",
          flush=True)
    print(f"[grid] first step rel diff: loss={d_loss:.3e} "
          f"grad_norm={d_gn:.3e} (bound {GRID_TOL})", flush=True)
    _check(d_loss <= GRID_TOL and d_gn <= GRID_TOL, (d_loss, d_gn))
    for r in (grid, dp):
        _check(all(p > 0 for p in r["peaks"]), r["peaks"])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernel parity + 8-layer training; 4: the "
                         "hp2 x cp2 vs dp4 comparison only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        sys.exit(2)
    _check(len(devices) >= args.chips, (len(devices), args.chips))

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.runtime.compile_cache import enable_compile_cache
    print(f"[smoke] {devices[0].device_kind} x{len(devices)}; compile "
          f"cache {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(args.seed, devices)
    else:
        four_chips(args.seed, devices)
    print(f"[smoke] done in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
