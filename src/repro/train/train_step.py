"""Jitted train/eval step builders, driven entirely by an ExecutionPlan.

The plan owns the mesh, the hybrid-ZeRO shardings, the remat policy and
the microbatch grid; this module turns it into a jitted step function.

**Microbatched gradient accumulation** (``plan.grad_accum > 1``): the
batch arrives as ``(accum, microbatch, ...)`` and a ``jax.lax.scan``
runs one forward+backward per microbatch.  The gradient carry stays in
the *compute* dtype (bf16 for mixed-precision configs — half the HBM and
wire bytes of an fp32 carry); the in-loop work is pure accumulation.
The fp32 upcast and the AdamW update — where the accumulated grads are
reduced into the ZeRO-sharded optimizer shard (GSPMD's reduce-scatter)
— sit *outside* the loop: one reduction point per step, not one per
microbatch.  That structure (pinned by ``tests/test_plan.py``'s jaxpr
check) is exactly what XLA's while-loop all-reduce code motion needs to
emit a single post-loop reduce-scatter on TPU.  Remat
applies inside each microbatch's forward (``plan.cfg.remat`` —
Selective Checkpoint++ per microbatch), and each microbatch's
activations die with its scan iteration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.plan import ExecutionPlan
from repro.models.model import cast_params_once, forward_loss
from repro.runtime import spans
from repro.train.optimizer import adamw_update


def make_train_step(plan: ExecutionPlan):
    """Mixed-precision step: the model is differentiated w.r.t. the *bf16*
    param tree, so the cross-device gradient reduction runs in bf16 (half
    the wire bytes of an fp32 all-reduce); the fp32→bf16 master cast and
    the bf16→fp32 grad upcast are local.  AdamW updates the fp32 masters.
    fp32-configured models (tests) are bit-identical to the plain path.
    """
    cfg, rt, opt_cfg, accum = plan.cfg, plan.rt, plan.opt, plan.grad_accum

    def step_fn(params, opt_state, batch):
        p_half = cast_params_once(params, cfg)
        grad_of = jax.value_and_grad(
            lambda ph, mb: forward_loss(ph, mb, rt, cfg),
            has_aux=True)
        if accum == 1:
            (_, metrics), grads_half = grad_of(p_half, batch)
        else:
            def micro(g_acc, mb):
                (_, m), g = grad_of(p_half, mb)
                # token-weighted accumulation: each microbatch's grad is
                # of its *mean* loss, so scale by its valid-token count
                # before summing.  With equal counts (SyntheticLM) this
                # reduces to the plain mean over microbatches; with
                # unequal counts (PackedLM bins carry different tail
                # padding) it reproduces the flat large-batch step
                # instead of skewing toward sparsely-filled bins.
                n = m["n_tokens"].astype(cfg.compute_dtype)
                g_acc = jax.tree.map(lambda a, g: a + g * n, g_acc, g)
                return g_acc, m

            grads_half, ms = lax.scan(
                micro, jax.tree.map(jnp.zeros_like, p_half), batch)
            n_total = ms["n_tokens"].sum(0)
            grads_half = jax.tree.map(
                lambda g: g / n_total.astype(g.dtype), grads_half)
            w = ms["n_tokens"] / n_total                # (accum,)
            metrics = {k: (v.sum(0) if k == "n_tokens"
                           else (v * w).sum(0))
                       for k, v in ms.items()}
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads_half,
                             params)
        with jax.named_scope(spans.OPTIMIZER):
            new_params, new_state, om = adamw_update(params, grads,
                                                     opt_state, opt_cfg)
        metrics = dict(metrics)
        metrics.update(om)
        return new_params, new_state, metrics
    return step_fn


def jit_train_step(plan: ExecutionPlan, params, *, donate: bool = True):
    """Returns (jitted_step, param_shardings, opt_state_shardings)."""
    p_sh = plan.param_shardings(params)
    o_sh = plan.opt_shardings(p_sh)
    b_sh = plan.batch_shardings("train")
    fn = jax.jit(
        make_train_step(plan),
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1) if donate else ())
    return fn, p_sh, o_sh
