"""AdamW with warmup+cosine schedule, global-norm clipping, ZeRO-sharded
moments, and optional int8 error-feedback gradient compression.

This module is pure math over pytrees; *where* the state lives is the
ExecutionPlan's decision: ``plan.opt_shardings`` makes the moments
inherit the params' hybrid-ZeRO shardings (at the AMSP extent the plan
chose), so the update is fully sharded — XLA reduce-scatters grads into
the shard and all-gathers updated params at next use.  Under gradient
accumulation the update runs once per step, on the microbatch-mean
grads (train/train_step.py).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def schedule(cfg: OptConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos)


def init_opt_state(params):
    zeros = lambda p: jnp.zeros_like(p)
    return {"m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def decays(path, p) -> bool:
    """Whether decoupled weight decay applies to the leaf at ``path``:
    matrices only, by the leaf's rank within one layer.  Leaves under a
    ``*blocks*`` key are stacked over layers on their leading axis
    (``models/model.py::init_params``), so a stacked norm gain or bias,
    ``(L, d)``, is a vector and does not decay."""
    stacked = any("blocks" in str(getattr(k, "key", "")) for k in path)
    return p.ndim - stacked >= 2


def adamw_update(params, grads, state, cfg: OptConfig):
    """Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-12))
    lr = schedule(cfg, step)
    b1, b2 = cfg.beta1, cfg.beta2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(path, p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / c1
        vh = v / c2
        step_dir = mh / (jnp.sqrt(vh) + cfg.eps)
        if decays(path, p):
            step_dir = step_dir + cfg.weight_decay * p
        return (p - lr * step_dir).astype(p.dtype), m, v

    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_m = tdef.flatten_up_to(state["m"])
    flat_v = tdef.flatten_up_to(state["v"])
    out = [upd(path, p, g, m, v) for (path, p), g, m, v in
           zip(flat, flat_g, flat_m, flat_v)]
    new_p = tdef.unflatten([o[0] for o in out])
    new_m = tdef.unflatten([o[1] for o in out])
    new_v = tdef.unflatten([o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "step": step}, \
        {"grad_norm": gnorm, "lr": lr}


# ---------------------------------------------------------------------------
# int8 error-feedback gradient compression (optional, for DP all-reduce)
# ---------------------------------------------------------------------------

def quantize_int8(x, err):
    """Symmetric per-tensor int8 quantization with error feedback.

    Returns (q int8, scale, new_err).  ``dequantize(q, scale)`` reconstructs;
    the residual is carried into the next step (error feedback keeps the
    long-run bias at zero — property-tested in tests/test_substrates.py).
    """
    xf = x.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    new_err = xf - q.astype(jnp.float32) * scale
    return q, scale, new_err


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum(g, err, axis_name):
    """psum an int8-quantized gradient over ``axis_name`` (inside
    shard_map), with local error feedback.  Returns (g_sum, new_err)."""
    q, scale, new_err = quantize_int8(g, err)
    deq = dequantize_int8(q, scale)                 # simulate int8 wire
    summed = jax.lax.psum(deq, axis_name)
    return summed, new_err
