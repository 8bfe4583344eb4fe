"""Attention blocks: GQA (optionally qk-norm / softcap / sliding window),
DeepSeek MLA, Whisper cross-attention — all running on 2D-Attention.

Train path uses ``attention_2d``; decode paths use flash-decoding style
lse-combines across the context axes (``decode_attention``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.attention2d import (Attn2DConfig, attention_2d,
                                    attn2d_config)
from repro.core.runtime import Runtime
from repro.core.topology import (AXIS_HP, AXIS_INNER, AXIS_OUTER, BATCH_AXES,
                                 SEQ_AXES)
from repro.kernels.ops import flash_fwd_chunk
from repro.kernels.ref import NEG_INF
from repro.models.layers import (apply_rotary, init_linear, init_rmsnorm,
                                 linear_apply, rmsnorm_apply)


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """Per-layer attention behaviour."""
    causal: bool = True
    window: int | None = None     # sliding-window (local) layers
    softcap: float = 0.0
    rope: bool = True
    rope_theta: float = 10000.0


def make_2d_cfg(rt: Runtime, kind: AttnKind, *, zigzag: bool,
                scale: float | None = None) -> Attn2DConfig:
    return attn2d_config(rt.pc, impl=rt.impl, causal=kind.causal,
                         zigzag=zigzag, window=kind.window,
                         softcap=kind.softcap, scale=scale)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def init_gqa(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
             *, qk_norm: bool = False, bias: bool = False):
    ks = jax.random.split(key, 4)
    p = {"wq": init_linear(ks[0], d_model, n_heads * head_dim, bias=bias),
         "wk": init_linear(ks[1], d_model, n_kv_heads * head_dim, bias=bias),
         "wv": init_linear(ks[2], d_model, n_kv_heads * head_dim, bias=bias),
         "wo": init_linear(ks[3], n_heads * head_dim, d_model)}
    if qk_norm:
        p["qn"] = init_rmsnorm(head_dim)
        p["kn"] = init_rmsnorm(head_dim)
    return p


def _project_qkv(p, x, n_heads, n_kv_heads, head_dim, cos, sin,
                 kind: AttnKind, *, qk_norm: bool):
    b, s, _ = x.shape
    q = linear_apply(p["wq"], x).reshape(b, s, n_heads, head_dim)
    k = linear_apply(p["wk"], x).reshape(b, s, n_kv_heads, head_dim)
    v = linear_apply(p["wv"], x).reshape(b, s, n_kv_heads, head_dim)
    if qk_norm:
        q = rmsnorm_apply(p["qn"], q)
        k = rmsnorm_apply(p["kn"], k)
    if kind.rope:
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    return q, k, v


def gqa_apply(p, x, cos, sin, rt: Runtime, kind: AttnKind, *,
              n_heads: int, n_kv_heads: int, head_dim: int,
              qk_norm: bool = False, zigzag: bool = True,
              scale: float | None = None, doc_start=None):
    """x: (B, S, D) -> (B, S, D).  cos/sin: (B, S, head_dim/2).
    ``doc_start``: (B, S) packed-document boundary table (see
    attention_2d)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, cos, sin,
                           kind, qk_norm=qk_norm)
    cfg = make_2d_cfg(rt, kind, zigzag=zigzag, scale=scale)
    out = attention_2d(q, k, v, mesh=rt.mesh, cfg=cfg, doc_start=doc_start)
    return linear_apply(p["wo"], out.reshape(b, s, n_heads * head_dim))


# ---------------------------------------------------------------------------
# DeepSeek-V2 MLA block (latent-compressed KV)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLADims:
    n_heads: int = 16
    kv_lora: int = 512
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128

    @property
    def d_qk(self) -> int:
        return self.d_nope + self.d_rope


def init_mla(key, d_model: int, m: MLADims):
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d_model, m.n_heads * m.d_qk),
        "kv_down": init_linear(ks[1], d_model, m.kv_lora + m.d_rope),
        "kv_norm": init_rmsnorm(m.kv_lora),
        "kv_up": init_linear(ks[2], m.kv_lora,
                             m.n_heads * (m.d_nope + m.d_v)),
        "wo": init_linear(ks[3], m.n_heads * m.d_v, d_model),
    }


def mla_apply(p, x, cos, sin, rt: Runtime, kind: AttnKind, m: MLADims, *,
              zigzag: bool = True, doc_start=None):
    """Training path: up-project the latent, run standard 2D-Attention.

    cos/sin must be built for head_dim = d_rope.
    """
    b, s, _ = x.shape
    q = linear_apply(p["wq"], x).reshape(b, s, m.n_heads, m.d_qk)
    q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
    q_rope = apply_rotary(q_rope, cos, sin)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    ckv = linear_apply(p["kv_down"], x)
    c = rmsnorm_apply(p["kv_norm"], ckv[..., :m.kv_lora])
    k_rope = apply_rotary(ckv[..., None, m.kv_lora:], cos, sin)  # (B,S,1,dr)

    kv = linear_apply(p["kv_up"], c).reshape(b, s, m.n_heads,
                                             m.d_nope + m.d_v)
    k_nope, v = kv[..., :m.d_nope], kv[..., m.d_nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, m.n_heads, m.d_rope))],
        axis=-1)
    # Pad V to the QK head dim so the flash kernel tiles uniformly.
    v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, m.d_qk - m.d_v)))

    cfg = make_2d_cfg(rt, kind, zigzag=zigzag,
                      scale=1.0 / (m.d_qk ** 0.5))
    out = attention_2d(q, k, v_pad, mesh=rt.mesh, cfg=cfg,
                       doc_start=doc_start)[..., :m.d_v]
    return linear_apply(p["wo"], out.reshape(b, s, m.n_heads * m.d_v))


# ---------------------------------------------------------------------------
# Whisper cross-attention (encoder KV is small: gather + head-parallel)
# ---------------------------------------------------------------------------

def init_cross_attn(key, d_model: int, n_heads: int, head_dim: int):
    ks = jax.random.split(key, 4)
    return {"wq": init_linear(ks[0], d_model, n_heads * head_dim, bias=True),
            "wk": init_linear(ks[1], d_model, n_heads * head_dim),
            "wv": init_linear(ks[2], d_model, n_heads * head_dim, bias=True),
            "wo": init_linear(ks[3], n_heads * head_dim, d_model)}


def cross_attn_apply(p, x, enc, rt: Runtime, *, n_heads: int,
                     head_dim: int):
    """x: decoder (B, S_dec, D) seq-sharded; enc: (B, S_enc, D) seq-sharded.

    The encoder context (<=1500 frames) is far too short to ring: gather it
    over the sp axes inside the region and head-parallelize only.
    """
    b, s, _ = x.shape
    q = linear_apply(p["wq"], x).reshape(b, s, n_heads, head_dim)
    k = linear_apply(p["wk"], enc).reshape(b, enc.shape[1], n_heads, head_dim)
    v = linear_apply(p["wv"], enc).reshape(b, enc.shape[1], n_heads, head_dim)

    hp = rt.pc.hp
    impl = rt.impl

    def local(q, k, v):
        if hp > 1:
            q = lax.all_to_all(q, AXIS_HP, 2, 1, tiled=True)
        kf = lax.all_gather(k, SEQ_AXES, axis=1, tiled=True)
        vf = lax.all_gather(v, SEQ_AXES, axis=1, tiled=True)
        if hp > 1:
            h_loc = kf.shape[2] // hp
            h0 = lax.axis_index(AXIS_HP) * h_loc
            kf = lax.dynamic_slice_in_dim(kf, h0, h_loc, axis=2)
            vf = lax.dynamic_slice_in_dim(vf, h0, h_loc, axis=2)
        from repro.kernels.ops import flash_attention
        out = flash_attention(q, kf, vf, causal=False, impl=impl)
        if hp > 1:
            out = lax.all_to_all(out, AXIS_HP, 1, 2, tiled=True)
        return out

    spec = P(BATCH_AXES, SEQ_AXES, None, None)
    out = jax.shard_map(local, mesh=rt.mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)(q, k, v)
    return linear_apply(p["wo"], out.reshape(b, s, n_heads * head_dim))


# ---------------------------------------------------------------------------
# Decode: flash-decoding lse-combine across the context axes
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, pos, rt: Runtime, *,
                     softcap: float = 0.0, window: int | None = None,
                     scale: float | None = None, ring_full=None,
                     kv_replicated: bool = False):
    """One-token attention against a context-sharded KV cache.

    q: (B, 1, H, d) — heads sharded over the head axis by GSPMD.
    k_cache/v_cache: (B, S_max, Hkv, d) — S sharded over (outer, inner),
    heads over the head axis (or replicated when ``kv_replicated`` — the
    MLA latent cache is a single logical head).  ``pos``: current
    length - 1, either a scalar int32 (uniform batch) or a per-request
    ``(B,)`` vector (ragged continuous-batching decode; entries of ``-1``
    mark inactive slots, which see no keys and emit zeros).

    ``ring_full``: for sliding-window ring-buffer caches — the (traced)
    number of live slots (scalar or ``(B,)``); every live slot is
    attendable (no causal band).

    Every context rank computes partial attention over its cache shard with
    a masked valid length, then one pmax+psum pair combines the partials —
    flash-decoding on the 2D grid (no ring needed for q_len = 1).
    """
    cp_axes = (AXIS_OUTER, AXIS_INNER)
    have_full = ring_full is not None
    extras = (jnp.asarray(pos, jnp.int32),)
    if have_full:
        extras += (jnp.asarray(ring_full, jnp.int32),)

    def local(q, kc, vc, *extras_l):
        pos_l = extras_l[0]
        shard_len = kc.shape[1]
        r = lax.axis_index(AXIS_OUTER) * rt.pc.cp_inner + \
            lax.axis_index(AXIS_INNER)
        start = r * shard_len
        if have_full:
            valid = jnp.clip(extras_l[1] - start, 0, shard_len)
            out, lse = flash_fwd_chunk(q, kc, vc, causal=False,
                                       softcap=softcap, scale=scale,
                                       kv_valid_len=valid, impl="ref")
        else:
            # Causal + (optional) window masking in one banded mask: the new
            # token sits at global position ``pos``; this shard's keys start
            # at ``start`` => band offset pos - start (traced => ref path).
            out, lse = flash_fwd_chunk(q, kc, vc, causal=True, window=window,
                                       softcap=softcap, scale=scale,
                                       mask_offset=pos_l - start, impl="ref")
        m = lax.pmax(lse, cp_axes)                       # (b, h, 1)
        m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
        wgt = jnp.exp(lse - m_safe)
        wgt = jnp.where(lse <= NEG_INF / 2, 0.0, wgt)
        w_o = jnp.transpose(wgt, (0, 2, 1))[..., None]   # (b, 1, h, 1)
        num = lax.psum(out.astype(jnp.float32) * w_o, cp_axes)
        den = lax.psum(wgt, cp_axes)
        den = jnp.where(den == 0.0, 1.0, den)
        return (num / jnp.transpose(den, (0, 2, 1))[..., None]).astype(
            q.dtype)

    spec_q = P(rt.batch_axes, None, AXIS_HP, None)
    spec_kv = P(rt.batch_axes, (AXIS_OUTER, AXIS_INNER),
                None if kv_replicated else AXIS_HP, None)
    spec_x = tuple(P(rt.batch_axes) if e.ndim else P() for e in extras)
    return jax.shard_map(local, mesh=rt.mesh,
                         in_specs=(spec_q, spec_kv, spec_kv) + spec_x,
                         out_specs=spec_q, check_vma=False)(
        q, k_cache, v_cache, *extras)
