"""Mixture-of-Experts FFN with expert parallelism over the sp axes.

Expert parallelism is orthogonal to 2D-Attention and reuses its mesh: the
experts are sharded over ``(head, outer, inner)`` (= d_sp ranks per data
group) and tokens are exchanged with a *hierarchical* all-to-all — one
``lax.all_to_all`` per mesh axis, splitting the expert dim and concatenating
the capacity dim.  The composition of the three exchanges is the full
``d_sp``-way dispatch, with the expert-ownership digits (head, outer, inner)
matching the weights' PartitionSpec, and the return path applies the inverse
exchanges in reverse order.

Routing is capacity-based (deterministic shapes for SPMD): top-k with
per-expert capacity ``ceil(T·k/E · cf)``; overflow tokens fall through with
only the shared-expert/residual contribution.  A switch-style load-balance
aux loss is pmean'd across the mesh.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.runtime import Runtime
from repro.core.topology import (AXIS_HP, AXIS_INNER, AXIS_OUTER, BATCH_AXES,
                                 MESH_AXES, SEQ_AXES)
from repro.models.layers import _normal, glu_mlp_apply, init_glu_mlp

EP_AXES = (AXIS_HP, AXIS_OUTER, AXIS_INNER)


@dataclasses.dataclass(frozen=True)
class MoEDims:
    d_model: int
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert intermediate
    n_shared: int = 0              # shared (always-on) experts
    capacity_factor: float = 1.25
    norm_topk: bool = True         # qwen3: renormalize top-k weights
    routed_scale: float = 1.0
    aux_weight: float = 1e-3


def init_moe(key, m: MoEDims):
    ks = jax.random.split(key, 5)
    std = m.d_model ** -0.5
    p = {
        "router": _normal(ks[0], (m.d_model, m.n_experts), std),
        "w1": _normal(ks[1], (m.n_experts, m.d_model, m.d_ff), std),
        "w3": _normal(ks[2], (m.n_experts, m.d_model, m.d_ff), std),
        "w2": _normal(ks[3], (m.n_experts, m.d_ff, m.d_model),
                      m.d_ff ** -0.5),
    }
    if m.n_shared:
        p["shared"] = init_glu_mlp(ks[4], m.d_model, m.d_ff * m.n_shared)
    return p


def _ep_sizes(rt: Runtime):
    pc = rt.pc
    return {AXIS_HP: pc.hp, AXIS_OUTER: pc.cp_outer, AXIS_INNER: pc.cp_inner}


def moe_apply(p, x, rt: Runtime, m: MoEDims, seq_sharded: bool = True):
    """x: (B, S, D) seq-sharded.  Returns (y, aux_loss_scalar).

    ``seq_sharded=False`` is the decode path (S=1 cannot shard over sp):
    tokens are replicated across the sp ranks of each data group, so the
    expert compute is duplicated sp-fold — negligible at decode batch
    sizes; dispatching from a batch-resharded layout would remove it (a
    serving optimization not yet made).
    """
    sizes = _ep_sizes(rt)
    ep = rt.pc.sp
    assert m.n_experts % ep == 0, (m.n_experts, ep)

    def local(x, router, w1, w3, w2):
        b_loc, s_loc, d = x.shape
        t = b_loc * s_loc
        cap = max(4, int(-(-t * m.top_k * m.capacity_factor
                           // m.n_experts)))
        xt = x.reshape(t, d)

        logits = (xt.astype(jnp.float32) @ router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
        topw, topi = lax.top_k(probs, m.top_k)                   # (T, k)
        if m.norm_topk:
            topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
        topw = topw * m.routed_scale

        flat_e = topi.reshape(-1)                                # (T*k,)
        flat_w = topw.reshape(-1)
        tok_ix = jnp.repeat(jnp.arange(t), m.top_k)
        onehot = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)
        pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
        keep = (pos < cap)

        buf = jnp.zeros((m.n_experts, cap, d), x.dtype)
        buf = buf.at[flat_e, jnp.clip(pos, 0, cap - 1)].add(
            jnp.where(keep[:, None], xt[tok_ix], 0.0),
            mode="drop")

        # --- dispatch: expert dim out, capacity dim in ------------------
        for ax in EP_AXES:
            if sizes[ax] > 1:
                buf = lax.all_to_all(buf, ax, 0, 1, tiled=True)
        # buf: (E/ep, cap*ep, D) — this rank's experts, everyone's tokens.

        h1 = jnp.einsum("ecd,edf->ecf", buf, w1.astype(buf.dtype))
        h3 = jnp.einsum("ecd,edf->ecf", buf, w3.astype(buf.dtype))
        hout = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h1) * h3,
                          w2.astype(buf.dtype))

        # --- return path: inverse exchanges, reverse order --------------
        for ax in reversed(EP_AXES):
            if sizes[ax] > 1:
                hout = lax.all_to_all(hout, ax, 1, 0, tiled=True)
        # hout: (E, cap, D)

        gathered = hout[flat_e, jnp.clip(pos, 0, cap - 1)]       # (T*k, D)
        gathered = jnp.where(keep[:, None], gathered, 0.0)
        y = jnp.zeros((t, d), jnp.float32)
        y = y.at[tok_ix].add(gathered.astype(jnp.float32)
                             * flat_w[:, None])
        y = y.reshape(b_loc, s_loc, d).astype(x.dtype)

        # Switch-style load-balance loss (fraction routed × mean prob).
        frac = jnp.mean(
            jnp.sum(jax.nn.one_hot(topi, m.n_experts), axis=1), axis=0)
        mean_p = jnp.mean(probs, axis=0)
        aux = m.n_experts * jnp.sum(frac * mean_p)
        aux = lax.pmean(aux, MESH_AXES)
        return y, aux

    spec_x = P(rt.batch_axes, SEQ_AXES, None) if seq_sharded \
        else P(rt.batch_axes, None, None)
    spec_e = P(EP_AXES, None, None)
    f = jax.shard_map(local, mesh=rt.mesh,
                      in_specs=(spec_x, P(None, None), spec_e, spec_e,
                                spec_e),
                      out_specs=(spec_x, P()), check_vma=False)
    y, aux = f(x, p["router"], p["w1"], p["w3"], p["w2"])

    if m.n_shared:
        y = y + glu_mlp_apply(p["shared"], x, act="silu")
    return y, m.aux_weight * aux
