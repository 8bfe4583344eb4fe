"""Serving: cache construction, prefill, and single-token decode.

* **Attention decode** is flash-decoding on the 2D grid: the KV cache is
  sharded over the context axes (S) and the head axis (KV heads); each
  context rank computes a partial against its shard, one pmax+psum combines
  (``attention_block.decode_attention``).
* **Sliding-window layers** use ring-buffer caches of size ``window`` —
  without this, gemma3's 40 local layers at 500k context would need TBs.
* **MLA decode** runs *absorbed*: the cache stores the compressed latent
  (kv_lora + rope = 576/token instead of materialized 16×2×192 = 6144), and
  the per-head up-projections are folded into q / out — a beyond-paper
  communication/memory win recorded in DESIGN.md.
* **SSM decode** is the O(1)-state recurrence (``ssm.mamba*_decode``).
* Prefill reuses the training forward in *contiguous* (non-zigzag) ring mode
  so collected caches are in natural sequence order.
* **Paged decode** (``PagedLayout``): full-attention K/V (and the MLA
  latent) live in fixed-size block pools; ``decode_step`` scatters the new
  token through per-request block tables and gathers a contiguous view for
  the flash-decoding combine.  ``pos`` may be a per-request ``(B,)`` vector
  (ragged continuous batching); sliding-window layers keep their ring
  buffers (already O(window)) in both modes.  ``prefill_chunk`` is the
  chunked-prefill building block of the serve engine.

Caches mirror the stacked-params structure so decode scans over layers.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.runtime import Runtime
from repro.core.topology import (AXIS_HP, AXIS_INNER, AXIS_OUTER, BATCH_AXES,
                                 MODEL_AXES)
from repro.models.attention_block import (AttnKind, decode_attention,
                                          MLADims)
from repro.models.layers import (apply_rotary, embedding_apply,
                                 gelu_mlp_apply, glu_mlp_apply, linear_apply,
                                 rmsnorm_apply, rotary_cos_sin,
                                 sinusoid_positions)
from repro.models.model import (ModelConfig, apply_norm, build_ropes,
                                cast_params_once, embed_tokens,
                                lm_head_weight, maybe_scan)
from repro.models.moe import moe_apply
from repro.models.ssm import mamba1_decode, mamba2_decode
from repro.kernels.ops import flash_attention, flash_fwd_chunk


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

def kv_cache_spec(batch_axes=BATCH_AXES):
    """PartitionSpec of a (layers, B, S, H, d) stacked KV cache."""
    return P(None, batch_axes, (AXIS_OUTER, AXIS_INNER), AXIS_HP, None)


def _kv_shape(cfg: ModelConfig, b: int, s: int, *, window: int | None):
    s_eff = min(s, window) if window is not None else s
    return (b, s_eff, cfg.n_kv_heads, cfg.hd)


def init_caches(cfg: ModelConfig, b: int, s_max: int):
    """Zero caches (host shapes; the dry-run passes ShapeDtypeStructs)."""
    dt = cfg.compute_dtype
    if cfg.family in ("dense", "moe"):
        if cfg.mla is not None:
            m = cfg.mla
            n = cfg.num_layers
            return {"blocks": [{
                "c": jnp.zeros((n, b, s_max, m.kv_lora), dt),
                "rope": jnp.zeros((n, b, s_max, m.d_rope), dt)}]}
        period = cfg.period
        groups = cfg.num_layers // period
        caches = []
        for slot in range(period):
            kind = cfg.attn_kind(slot)
            shp = _kv_shape(cfg, b, s_max, window=kind.window)
            caches.append({"k": jnp.zeros((groups,) + shp, dt),
                           "v": jnp.zeros((groups,) + shp, dt)})
        return {"blocks": caches}
    if cfg.family == "ssm":
        m = cfg.ssm1
        n = cfg.num_layers
        return {"blocks": {
            "h": jnp.zeros((n, b, m.d_inner, m.d_state), jnp.float32),
            "conv": jnp.zeros((n, b, m.d_conv - 1, m.d_inner), dt)}}
    if cfg.family == "hybrid":
        m = cfg.ssm2
        groups = cfg.num_layers // cfg.attn_every
        rem = cfg.num_layers - groups * cfg.attn_every
        shp = _kv_shape(cfg, b, s_max, window=None)
        caches = {"blocks": {
            "h": jnp.zeros((groups, cfg.attn_every, b, m.n_heads,
                            m.head_dim, m.d_state), jnp.float32),
            "conv": jnp.zeros((groups, cfg.attn_every, b, m.d_conv - 1,
                               m.conv_dim), dt)},
            "shared_attn": {"k": jnp.zeros((groups,) + shp, dt),
                            "v": jnp.zeros((groups,) + shp, dt)}}
        if rem:
            caches["blocks_tail"] = {
                "h": jnp.zeros((rem, b, m.n_heads, m.head_dim, m.d_state),
                               jnp.float32),
                "conv": jnp.zeros((rem, b, m.d_conv - 1, m.conv_dim), dt)}
        return caches
    if cfg.family == "encdec":
        n = cfg.num_layers
        shp = _kv_shape(cfg, b, s_max, window=None)
        enc_shp = (b, cfg.enc_frames, cfg.n_heads, cfg.hd)
        return {"dec_blocks": {"k": jnp.zeros((n,) + shp, dt),
                               "v": jnp.zeros((n,) + shp, dt)},
                "cross": {"k": jnp.zeros((n,) + enc_shp, dt),
                          "v": jnp.zeros((n,) + enc_shp, dt)}}
    raise ValueError(cfg.family)


def grow_caches(cfg: ModelConfig, caches, extra: int):
    """Pad attention caches with ``extra`` free positions along S so decode
    can write past the prefill length (SSM states and full ring buffers are
    size-invariant).  Sliding-window buffers are padded up to ``window``
    when the prompt was shorter than the window.

    Ring-buffer slot math assumes ``window | S_prefill`` when the prompt
    exceeds the window (true for all assigned configs: 1024/4096 | 32k/512k).
    """
    def pad_s(x, target_extra, axis=2):
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, target_extra)
        return jnp.pad(x, pads)

    out = dict(caches)
    if cfg.family in ("dense", "moe"):
        if cfg.mla is not None:
            blk = caches["blocks"][0]
            out["blocks"] = [{k: pad_s(v, extra) for k, v in blk.items()}]
            return out
        new_slots = []
        for slot, blk in enumerate(caches["blocks"]):
            kind = cfg.attn_kind(slot)
            if kind.window is None:
                new_slots.append({k: pad_s(v, extra) for k, v in
                                  blk.items()})
            else:
                cur = blk["k"].shape[2]
                grow = max(0, min(kind.window, cur + extra) - cur)
                new_slots.append({k: pad_s(v, grow) for k, v in
                                  blk.items()})
        out["blocks"] = new_slots
        return out
    if cfg.family == "hybrid":
        out["shared_attn"] = {k: pad_s(v, extra) for k, v in
                              caches["shared_attn"].items()}
        return out
    if cfg.family == "encdec":
        out["dec_blocks"] = {k: pad_s(v, extra) for k, v in
                             caches["dec_blocks"].items()}
        return out
    return out     # ssm: state-only


def cache_shardings(cfg: ModelConfig, caches, mesh, batch_axes=BATCH_AXES):
    """NamedSharding pytree matching init_caches output."""
    def spec_for(path: str, x):
        leaf = path.split("/")[-1]
        if leaf in ("k", "v") and x.ndim == 5:   # KV cache (L,B,S,H,d)
            return kv_cache_spec(batch_axes)
        if leaf == "h":
            if x.ndim == 6:   # hybrid ssm state (G,p,B,nh,hd,N)
                return P(None, None, batch_axes, MODEL_AXES, None, None)
            return P(None, batch_axes, MODEL_AXES, None)  # (L,B,di,N)
        if leaf == "conv":
            if x.ndim == 5:   # hybrid conv tail (G,p,B,K-1,convd)
                return P(None, None, batch_axes, None, MODEL_AXES)
            return P(None, batch_axes, None, MODEL_AXES)  # (L,B,K-1,di)
        if leaf in ("c", "rope"):         # MLA latent (L,B,S,lora)
            return P(None, batch_axes, (AXIS_OUTER, AXIS_INNER), None)
        return P(None, batch_axes) if x.ndim == 2 else \
            P(None, batch_axes, *([None] * (x.ndim - 2)))

    def walk(tree, path=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v, f"{path}/{i}") for i, v in enumerate(tree)]
        return NamedSharding(mesh, spec_for(path, tree))

    return walk(caches)


# ---------------------------------------------------------------------------
# Paged-KV layout: block pools + per-request block tables
# ---------------------------------------------------------------------------

class PagedLayout(NamedTuple):
    """How a paged cache pool maps logical positions to physical blocks.

    Pools are ``(num_blocks, page_size, ...)`` (per layer; stacked pools
    carry a leading layer/group dim).  ``block_tables[b, i]`` is the
    physical block holding request ``b``'s logical positions
    ``[i*page_size, (i+1)*page_size)``; tables are shared across layers
    (every layer's pool uses the same geometry).  Writes for inactive
    slots (``pos < 0``) are routed out of bounds and dropped, so a shared
    physical block is never corrupted by a retired request.
    """
    block_tables: jax.Array        # (B, max_blocks_per_seq) int32
    page_size: int                 # static
    num_blocks: int                # static — pool extent, drop bound


def _paged_write(pool, vals, pos, paged: PagedLayout):
    """Scatter one token per request: pool (NB,Pg,...), vals (B,...),
    pos scalar/(B,) logical positions (< 0 → dropped)."""
    b = paged.block_tables.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    blk = jnp.clip(pos // paged.page_size, 0,
                   paged.block_tables.shape[1] - 1)
    phys = jnp.take_along_axis(paged.block_tables, blk[:, None],
                               axis=1)[:, 0]
    phys = jnp.where(pos >= 0, phys, paged.num_blocks)   # OOB → dropped
    return pool.at[phys, pos % paged.page_size].set(
        vals.astype(pool.dtype), mode="drop")


def _paged_write_chunk(pool, vals, start, valid, paged: PagedLayout):
    """Scatter a prefill chunk: vals (B,Lc,...), positions
    start..start+valid per request (rows ≥ valid dropped)."""
    b, lc = vals.shape[:2]
    t = jnp.arange(lc, dtype=jnp.int32)[None]
    pos = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1, 1),
                           (b, 1)) + t                   # (B, Lc)
    live = t < jnp.asarray(valid, jnp.int32).reshape(-1, 1)
    blk = jnp.clip(pos // paged.page_size, 0,
                   paged.block_tables.shape[1] - 1)
    phys = jnp.take_along_axis(paged.block_tables, blk, axis=1)
    phys = jnp.where(live, phys, paged.num_blocks)       # OOB → dropped
    return pool.at[phys, pos % paged.page_size].set(
        vals.astype(pool.dtype), mode="drop")


def _paged_view(pool, paged: PagedLayout):
    """(NB,Pg,...) -> (B, max_blocks*Pg, ...) gathered through the block
    tables — the contiguous view the flash-decoding combine attends."""
    pages = pool[paged.block_tables]          # (B, MAXB, Pg, ...)
    b, nb, pg = pages.shape[:3]
    return pages.reshape((b, nb * pg) + pages.shape[3:])


# ---------------------------------------------------------------------------
# Per-layer decode helpers
# ---------------------------------------------------------------------------

def _ring_pos_write(cache, new, write):
    """cache (B,S,...), new (B,1,...), write scalar/(B,) slot indices."""
    new = new.astype(cache.dtype)
    write = jnp.asarray(write, jnp.int32)
    if write.ndim:
        return jax.vmap(
            lambda c, n, p: lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
        )(cache, new, jnp.maximum(write, 0))
    return lax.dynamic_update_slice_in_dim(cache, new, write, axis=1)


def _update_cache(cache, new, pos, *, window: int | None,
                  paged: PagedLayout | None = None):
    """cache (B,S,H,d) contiguous / (B,W,H,d) ring / (NB,Pg,H,d) paged
    pool; new (B,1,H,d); pos scalar or per-request (B,).  Ring-buffered
    for window layers (both modes — windows are already O(window))."""
    pos = jnp.asarray(pos, jnp.int32)
    if window is not None:
        return _ring_pos_write(cache, new, pos % cache.shape[1])
    if paged is not None:
        return _paged_write(cache, new[:, 0], pos, paged)
    return _ring_pos_write(cache, new, pos)


def _gqa_decode(p, x, cache, pos, rt, cfg: ModelConfig, kind: AttnKind,
                ropes, paged: PagedLayout | None = None):
    b = x.shape[0]
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = linear_apply(p["wq"], x).reshape(b, 1, h, hd)
    k = linear_apply(p["wk"], x).reshape(b, 1, hkv, hd)
    v = linear_apply(p["wv"], x).reshape(b, 1, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["qn"], q)
        k = rmsnorm_apply(p["kn"], k)
    if kind.rope:
        cos, sin = ropes[kind.rope_theta]
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    use_paged = paged if kind.window is None else None
    k_cache = _update_cache(cache["k"], k, pos, window=kind.window,
                            paged=use_paged)
    v_cache = _update_cache(cache["v"], v, pos, window=kind.window,
                            paged=use_paged)
    if kind.window is not None:
        # Ring buffer: every live slot is inside the window — plain valid-
        # length masking, handled as full attention over min(pos+1, W) keys.
        out = decode_attention(q, k_cache, v_cache,
                               jnp.minimum(pos, k_cache.shape[1] - 1), rt,
                               softcap=kind.softcap, window=None,
                               ring_full=jnp.minimum(pos + 1,
                                                     k_cache.shape[1]))
    else:
        k_att = _paged_view(k_cache, paged) if use_paged else k_cache
        v_att = _paged_view(v_cache, paged) if use_paged else v_cache
        out = decode_attention(q, k_att, v_att, pos, rt,
                               softcap=kind.softcap)
    y = linear_apply(p["wo"], out.reshape(b, 1, h * hd))
    return y, {"k": k_cache, "v": v_cache}


def _mla_decode(p, x, cache, pos, rt, cfg: ModelConfig, ropes,
                paged: PagedLayout | None = None):
    m = cfg.mla
    b = x.shape[0]
    cos, sin = ropes[cfg.rope_theta]
    q = linear_apply(p["wq"], x).reshape(b, 1, m.n_heads, m.d_qk)
    q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
    q_rope = apply_rotary(q_rope, cos, sin)

    ckv = linear_apply(p["kv_down"], x)
    c_t = rmsnorm_apply(p["kv_norm"], ckv[..., :m.kv_lora])
    kr_t = apply_rotary(ckv[..., None, m.kv_lora:], cos, sin)[:, :, 0]

    c_cache = _update_cache(cache["c"], c_t, pos, window=None, paged=paged)
    r_cache = _update_cache(cache["rope"], kr_t, pos, window=None,
                            paged=paged)
    c_att = _paged_view(c_cache, paged) if paged is not None else c_cache
    r_att = _paged_view(r_cache, paged) if paged is not None else r_cache

    # Absorbed attention in latent space (MQA over one 576-dim head).
    w_up = p["kv_up"]["w"].reshape(m.kv_lora, m.n_heads, m.d_nope + m.d_v)
    w_uk = w_up[..., :m.d_nope]                       # (lora, H, d_nope)
    w_uv = w_up[..., m.d_nope:]                       # (lora, H, d_v)
    q_lat = jnp.einsum("bthn,lhn->bthl", q_nope, w_uk.astype(q_nope.dtype))
    q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)  # (B,1,H,lora+rope)
    k_eff = jnp.concatenate([c_att, r_att], axis=-1)[:, :, None]
    v_eff = jnp.pad(c_att[:, :, None],
                    ((0, 0), (0, 0), (0, 0), (0, m.d_rope)))
    out = decode_attention(q_eff, k_eff, v_eff, pos, rt,
                           scale=1.0 / (m.d_qk ** 0.5), kv_replicated=True)
    out_lat = out[..., :m.kv_lora]                    # (B,1,H,lora)
    o = jnp.einsum("bthl,lhv->bthv", out_lat, w_uv.astype(out_lat.dtype))
    y = linear_apply(p["wo"], o.reshape(b, 1, m.n_heads * m.d_v))
    return y, {"c": c_cache, "rope": r_cache}


def _cross_decode(p, x, cache, rt, cfg: ModelConfig):
    """Cross-attention against the (small, replicated-S) encoder cache."""
    b = x.shape[0]
    h, hd = cfg.n_heads, cfg.hd
    q = linear_apply(p["wq"], x).reshape(b, 1, h, hd)

    def local(q, k, v):
        return flash_attention(q, k, v, causal=False, impl="ref")

    spec_q = P(rt.batch_axes, None, AXIS_HP, None)
    spec_kv = P(rt.batch_axes, None, AXIS_HP, None)
    out = jax.shard_map(local, mesh=rt.mesh,
                        in_specs=(spec_q, spec_kv, spec_kv),
                        out_specs=spec_q, check_vma=False)(
        q, cache["k"], cache["v"])
    return linear_apply(p["wo"], out.reshape(b, 1, h * hd))


# ---------------------------------------------------------------------------
# Decode step (one new token)
# ---------------------------------------------------------------------------

def decode_step(params, caches, tokens, pos, rt: Runtime, cfg: ModelConfig,
                paged: PagedLayout | None = None):
    """tokens: (B, 1) int32; pos: scalar int32 or per-request (B,) int32
    (ragged continuous batching — entries of -1 mark inactive slots).
    ``paged``: when given, full-attention K/V (and MLA latent) caches are
    block pools gathered through per-request block tables (dense/moe
    families).  -> (logits, new_caches)."""
    b = tokens.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    assert paged is None or cfg.family in ("dense", "moe"), cfg.family
    params = cast_params_once(params, cfg)
    x = embed_tokens(params, tokens, cfg)
    positions = pos[:, None] if pos.ndim else jnp.full((b, 1), pos,
                                                       jnp.int32)
    positions = jnp.maximum(positions, 0)     # inactive slots: dummy rope
    ropes = build_ropes(cfg, positions) if cfg.rope else {}
    new_caches = {}

    if cfg.family in ("dense", "moe"):
        period = cfg.period
        kinds = [cfg.attn_kind(i) for i in range(period)]
        if cfg.mla is not None:
            def body(x, xs):
                lp, cache = xs
                h = apply_norm(cfg, lp["ln1"], x)
                h, cache = _mla_decode(lp["attn"], h, cache, pos, rt, cfg,
                                       ropes, paged=paged)
                x = x + h
                h = apply_norm(cfg, lp["ln2"], x)
                if cfg.family == "moe":
                    h, _ = moe_apply(lp["moe"], h, rt, cfg.moe,
                                     seq_sharded=False)
                else:
                    h = glu_mlp_apply(lp["mlp"], h, act=cfg.act)
                return x + h, cache
            x, ncache = maybe_scan(body, x, (params["blocks"][0],
                                      caches["blocks"][0]),
                                   cfg.unroll_loops)
            new_caches["blocks"] = [ncache]
        else:
            def body(x, xs):
                lps, slot_caches = xs
                new_slots = []
                for slot in range(period):
                    lp = lps[slot]
                    cache = slot_caches[slot]
                    h = apply_norm(cfg, lp["ln1"], x)
                    h, cache = _gqa_decode(lp["attn"], x=h, cache=cache,
                                           pos=pos, rt=rt, cfg=cfg,
                                           kind=kinds[slot], ropes=ropes,
                                           paged=paged)
                    if cfg.post_norms:
                        h = apply_norm(cfg, lp["pn1"], h)
                    x = x + h
                    h = apply_norm(cfg, lp["ln2"], x)
                    if cfg.family == "moe":
                        h, _ = moe_apply(lp["moe"], h, rt, cfg.moe,
                                         seq_sharded=False)
                    else:
                        h = glu_mlp_apply(lp["mlp"], h, act=cfg.act)
                    if cfg.post_norms:
                        h = apply_norm(cfg, lp["pn2"], h)
                    x = x + h
                    new_slots.append(cache)
                return x, new_slots
            x, ncaches = maybe_scan(body, x,
                                    (params["blocks"], caches["blocks"]),
                                    cfg.unroll_loops)
            new_caches["blocks"] = ncaches

    elif cfg.family == "ssm":
        def body(x, xs):
            lp, cache = xs
            h = apply_norm(cfg, lp["ln"], x)
            h, cache = mamba1_decode(lp["mix"], h, cache, cfg.ssm1)
            return x + h, cache
        x, ncache = maybe_scan(body, x, (params["blocks"], caches["blocks"]),
                               cfg.unroll_loops)
        new_caches["blocks"] = ncache

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        kind = cfg.attn_kind(0)

        def body(x, xs):
            lps, ssm_cache, attn_cache = xs
            new_ssm = []
            for i in range(cfg.attn_every):
                lp = jax.tree.map(lambda t: t[i], lps)
                sc = jax.tree.map(lambda t: t[i], ssm_cache)
                h = apply_norm(cfg, lp["ln"], x)
                h, sc = mamba2_decode(lp["mix"], h, sc, cfg.ssm2)
                x = x + h
                new_ssm.append(sc)
            h = apply_norm(cfg, shared["ln1"], x)
            h, attn_cache = _gqa_decode(shared["attn"], x=h,
                                        cache=attn_cache, pos=pos, rt=rt,
                                        cfg=cfg, kind=kind, ropes=ropes)
            x = x + h
            h = glu_mlp_apply(shared["mlp"],
                              apply_norm(cfg, shared["ln2"], x),
                              act=cfg.act)
            x = x + h
            new_ssm = jax.tree.map(lambda *t: jnp.stack(t), *new_ssm)
            return x, (new_ssm, attn_cache)

        x, (nssm, nattn) = maybe_scan(
            body, x, (params["blocks"], caches["blocks"],
                      caches["shared_attn"]), cfg.unroll_loops)
        new_caches["blocks"] = nssm
        new_caches["shared_attn"] = nattn
        if "blocks_tail" in params:
            def tail(x, xs):
                lp, cache = xs
                h = apply_norm(cfg, lp["ln"], x)
                h, cache = mamba2_decode(lp["mix"], h, cache, cfg.ssm2)
                return x + h, cache
            x, ntail = maybe_scan(tail, x, (params["blocks_tail"],
                                            caches["blocks_tail"]),
                                  cfg.unroll_loops)
            new_caches["blocks_tail"] = ntail

    elif cfg.family == "encdec":
        kind = AttnKind(causal=True, rope=False)
        x = x + embedding_apply(
            params["dec_pos"],
            jnp.minimum(positions, cfg.max_positions - 1), dtype=x.dtype)

        def body(x, xs):
            lp, cache, xcache = xs
            h = apply_norm(cfg, lp["ln1"], x)
            h, cache = _gqa_decode(lp["attn"], x=h, cache=cache, pos=pos,
                                   rt=rt, cfg=cfg, kind=kind, ropes=ropes)
            x = x + h
            x = x + _cross_decode(lp["cross"],
                                  apply_norm(cfg, lp["lnx"], x), xcache, rt,
                                  cfg)
            h = gelu_mlp_apply(lp["mlp"], apply_norm(cfg, lp["ln2"], x))
            return x + h, cache

        x, ncache = maybe_scan(body, x, (params["dec_blocks"],
                                         caches["dec_blocks"],
                                         caches["cross"]), cfg.unroll_loops)
        new_caches["dec_blocks"] = ncache
        new_caches["cross"] = caches["cross"]
    else:
        raise ValueError(cfg.family)

    x = apply_norm(cfg, params["final_norm"], x)
    w = lm_head_weight(params, cfg)
    logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
    # Keep logits vocab-sharded so the LM head never gathers its weight.
    logits = jax.lax.with_sharding_constraint(
        logits, NamedSharding(rt.mesh, P(BATCH_AXES, None, MODEL_AXES)))
    return logits, new_caches


# ---------------------------------------------------------------------------
# Prefill: run the prompt through the trunk, collecting caches
# ---------------------------------------------------------------------------

def _pref_kind(kind: AttnKind) -> AttnKind:
    return kind


def _gqa_prefill(p, x, ropes, rt: Runtime, cfg: ModelConfig,
                 kind: AttnKind):
    """Returns (y, (k, v)) with k/v rotary-applied, contiguous order."""
    from repro.models.attention_block import (_project_qkv, make_2d_cfg)
    from repro.core.attention2d import attention_2d
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                           ropes.get(kind.rope_theta, (None, None))[0],
                           ropes.get(kind.rope_theta, (None, None))[1],
                           kind, qk_norm=cfg.qk_norm)
    cfg2d = make_2d_cfg(rt, kind, zigzag=False)
    out = attention_2d(q, k, v, mesh=rt.mesh, cfg=cfg2d)
    y = linear_apply(p["wo"], out.reshape(b, s, cfg.n_heads * cfg.hd))
    if kind.window is not None:
        k, v = k[:, -kind.window:], v[:, -kind.window:]
    return y, (k, v)


def prefill(params, batch, rt: Runtime, cfg: ModelConfig):
    """batch: {tokens (B,S)[, frames]} (contiguous order, no zigzag).

    Returns (last-token logits (B, 1, V), caches ready for decode_step at
    pos = S).
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    params = cast_params_once(params, cfg)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                 (b, s))
    x = embed_tokens(params, tokens, cfg)
    x = rt.constrain(x, None)
    ropes = build_ropes(cfg, positions) if cfg.rope else {}
    caches = {}

    if cfg.family in ("dense", "moe"):
        period = cfg.period
        kinds = [cfg.attn_kind(i) for i in range(period)]
        if cfg.mla is not None:
            m = cfg.mla

            def body(x, lp):
                h = apply_norm(cfg, lp["ln1"], x)
                # latent cache entries
                ckv = linear_apply(lp["attn"]["kv_down"], h)
                c_t = rmsnorm_apply(lp["attn"]["kv_norm"],
                                    ckv[..., :m.kv_lora])
                cos, sin = ropes[cfg.rope_theta]
                kr = apply_rotary(ckv[..., None, m.kv_lora:], cos,
                                  sin)[:, :, 0]
                from repro.models.attention_block import mla_apply
                kind = AttnKind(causal=True, rope=True,
                                rope_theta=cfg.rope_theta)
                h2 = mla_apply(lp["attn"], h, cos, sin, rt, kind, m,
                               zigzag=False)
                x = x + h2
                h = apply_norm(cfg, lp["ln2"], x)
                if cfg.family == "moe":
                    h, _ = moe_apply(lp["moe"], h, rt, cfg.moe)
                else:
                    h = glu_mlp_apply(lp["mlp"], h, act=cfg.act)
                return x + h, {"c": c_t, "rope": kr}

            x, ncache = maybe_scan(body, x, params["blocks"][0], cfg.unroll_loops)
            caches["blocks"] = [ncache]
        else:
            def body(x, lps):
                slot_caches = []
                for slot in range(period):
                    lp = lps[slot]
                    h = apply_norm(cfg, lp["ln1"], x)
                    h, kv = _gqa_prefill(lp["attn"], h, ropes, rt, cfg,
                                         kinds[slot])
                    if cfg.post_norms:
                        h = apply_norm(cfg, lp["pn1"], h)
                    x = x + h
                    h = apply_norm(cfg, lp["ln2"], x)
                    if cfg.family == "moe":
                        h, _ = moe_apply(lp["moe"], h, rt, cfg.moe)
                    else:
                        h = glu_mlp_apply(lp["mlp"], h, act=cfg.act)
                    if cfg.post_norms:
                        h = apply_norm(cfg, lp["pn2"], h)
                    x = x + h
                    slot_caches.append({"k": kv[0], "v": kv[1]})
                return x, slot_caches

            x, ncaches = maybe_scan(body, x, params["blocks"], cfg.unroll_loops)
            caches["blocks"] = ncaches

    elif cfg.family == "ssm":
        from repro.models.ssm import mamba1_apply

        def body(x, lp):
            h = apply_norm(cfg, lp["ln"], x)
            h, st = mamba1_apply(lp["mix"], h, rt, cfg.ssm1,
                                 return_state=True)
            return x + h, st
        x, st = maybe_scan(body, x, params["blocks"], cfg.unroll_loops)
        caches["blocks"] = st

    elif cfg.family == "hybrid":
        from repro.models.ssm import mamba2_apply
        shared = params["shared_attn"]
        kind = cfg.attn_kind(0)

        def body(x, lps):
            states = []
            for i in range(cfg.attn_every):
                lp = jax.tree.map(lambda t: t[i], lps)
                h = apply_norm(cfg, lp["ln"], x)
                h, st = mamba2_apply(lp["mix"], h, rt, cfg.ssm2,
                                     return_state=True)
                x = x + h
                states.append(st)
            h = apply_norm(cfg, shared["ln1"], x)
            h, kv = _gqa_prefill(shared["attn"], h, ropes, rt, cfg, kind)
            x = x + h
            h = glu_mlp_apply(shared["mlp"],
                              apply_norm(cfg, shared["ln2"], x), act=cfg.act)
            x = x + h
            states = jax.tree.map(lambda *t: jnp.stack(t), *states)
            return x, (states, {"k": kv[0], "v": kv[1]})

        x, (nssm, nattn) = maybe_scan(body, x, params["blocks"], cfg.unroll_loops)
        caches["blocks"] = nssm
        caches["shared_attn"] = nattn
        if "blocks_tail" in params:
            def tail(x, lp):
                h = apply_norm(cfg, lp["ln"], x)
                h, st = mamba2_apply(lp["mix"], h, rt, cfg.ssm2,
                                     return_state=True)
                return x + h, st
            x, st = maybe_scan(tail, x, params["blocks_tail"], cfg.unroll_loops)
            caches["blocks_tail"] = st

    elif cfg.family == "encdec":
        from repro.models.model import whisper_encoder
        enc = whisper_encoder(params, batch["frames"], rt, cfg)
        kind = AttnKind(causal=True, rope=False)
        x = x + embedding_apply(
            params["dec_pos"],
            jnp.minimum(positions, cfg.max_positions - 1), dtype=x.dtype)

        def body(x, lp):
            h = apply_norm(cfg, lp["ln1"], x)
            h, kv = _gqa_prefill(lp["attn"], h, ropes, rt, cfg, kind)
            x = x + h
            xk = linear_apply(lp["cross"]["wk"], enc).reshape(
                enc.shape[0], enc.shape[1], cfg.n_heads, cfg.hd)
            xv = linear_apply(lp["cross"]["wv"], enc).reshape(
                enc.shape[0], enc.shape[1], cfg.n_heads, cfg.hd)
            from repro.models.attention_block import cross_attn_apply
            x = x + cross_attn_apply(lp["cross"],
                                     apply_norm(cfg, lp["lnx"], x), enc, rt,
                                     n_heads=cfg.n_heads, head_dim=cfg.hd)
            h = gelu_mlp_apply(lp["mlp"], apply_norm(cfg, lp["ln2"], x))
            return x + h, ({"k": kv[0], "v": kv[1]},
                           {"k": xk, "v": xv})

        x, (selfc, crossc) = maybe_scan(body, x, params["dec_blocks"],
                                    cfg.unroll_loops)
        caches["dec_blocks"] = selfc
        caches["cross"] = crossc
    else:
        raise ValueError(cfg.family)

    x = apply_norm(cfg, params["final_norm"], x)
    x_last = x[:, -1:]
    w = lm_head_weight(params, cfg)
    logits = (x_last @ w.astype(x_last.dtype)).astype(jnp.float32)
    logits = jax.lax.with_sharding_constraint(
        logits, NamedSharding(rt.mesh, P(BATCH_AXES, None, MODEL_AXES)))
    return logits, caches


# ---------------------------------------------------------------------------
# Chunked prefill against a paged cache (serve-engine building block)
# ---------------------------------------------------------------------------

def prefill_chunk(params, caches, tokens, start, valid, rt: Runtime,
                  cfg: ModelConfig, paged: PagedLayout):
    """One prefill chunk against the paged cache (dense/moe families).

    tokens (B, Lc) int32 — a bucketed chunk (rows ≥ ``valid`` are padding);
    start scalar/(B,) int32 — logical position of ``tokens[:, 0]``;
    valid scalar/(B,) int32 — real tokens in this chunk (≤ Lc).

    Full-attention layers write the chunk's K/V through the block tables,
    then attend the gathered pages with a ``start``-anchored causal band
    capped at ``start + valid`` visible keys.  Sliding-window layers
    require single-chunk prefill (``start == 0`` covering the whole
    prompt): chunk-local banded attention is exact there, and the ring
    buffer is seeded with the last ``min(window, valid)`` positions.  MLA
    runs absorbed against the gathered latent pages.  Masks are ragged
    (per-request offsets) => ref attention path.

    Returns (logits of token ``valid - 1`` per request (B, 1, V),
    new_caches).
    """
    assert cfg.family in ("dense", "moe"), cfg.family
    b, lc = tokens.shape
    params = cast_params_once(params, cfg)
    x = embed_tokens(params, tokens, cfg)
    start = jnp.asarray(start, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    start_c = start.reshape(-1, 1)
    valid_c = valid.reshape(-1, 1)
    positions = jnp.broadcast_to(
        jnp.maximum(start_c + jnp.arange(lc, dtype=jnp.int32)[None], 0),
        (b, lc))
    ropes = build_ropes(cfg, positions) if cfg.rope else {}
    period = cfg.period
    kinds = [cfg.attn_kind(i) for i in range(period)]

    from repro.models.attention_block import _project_qkv

    def gqa_chunk(p, h, cache, kind: AttnKind):
        cos, sin = ropes.get(kind.rope_theta, (None, None))
        q, k, v = _project_qkv(p, h, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                               cos, sin, kind, qk_norm=cfg.qk_norm)
        if kind.window is not None:
            w = kind.window
            out, _ = flash_fwd_chunk(q, k, v, causal=True, window=w,
                                     softcap=kind.softcap,
                                     kv_valid_len=valid, impl="ref")
            # Seed the ring buffer with the last min(w, valid) positions —
            # each lands in its decode slot ``pos % w``; the rest (and the
            # padded rows) are routed out of bounds and dropped.
            t = jnp.arange(lc, dtype=jnp.int32)[None]
            keep = (t < valid_c) & (t >= valid_c - w)
            slot = jnp.where(keep, t % w, w)
            bidx = jnp.arange(b)[:, None]
            kc = cache["k"].at[bidx, slot].set(
                k.astype(cache["k"].dtype), mode="drop")
            vc = cache["v"].at[bidx, slot].set(
                v.astype(cache["v"].dtype), mode="drop")
        else:
            kc = _paged_write_chunk(cache["k"], k, start, valid, paged)
            vc = _paged_write_chunk(cache["v"], v, start, valid, paged)
            out, _ = flash_fwd_chunk(q, _paged_view(kc, paged),
                                     _paged_view(vc, paged), causal=True,
                                     softcap=kind.softcap,
                                     mask_offset=start,
                                     kv_valid_len=start + valid,
                                     impl="ref")
        y = linear_apply(p["wo"], out.reshape(b, lc, cfg.n_heads * cfg.hd))
        return y, {"k": kc, "v": vc}

    def mla_chunk(p, h, cache):
        m = cfg.mla
        cos, sin = ropes[cfg.rope_theta]
        q = linear_apply(p["wq"], h).reshape(b, lc, m.n_heads, m.d_qk)
        q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
        q_rope = apply_rotary(q_rope, cos, sin)
        ckv = linear_apply(p["kv_down"], h)
        c_t = rmsnorm_apply(p["kv_norm"], ckv[..., :m.kv_lora])
        kr_t = apply_rotary(ckv[..., None, m.kv_lora:], cos, sin)[:, :, 0]
        cc = _paged_write_chunk(cache["c"], c_t, start, valid, paged)
        rc = _paged_write_chunk(cache["rope"], kr_t, start, valid, paged)
        c_att = _paged_view(cc, paged)
        r_att = _paged_view(rc, paged)
        w_up = p["kv_up"]["w"].reshape(m.kv_lora, m.n_heads,
                                       m.d_nope + m.d_v)
        q_lat = jnp.einsum("bthn,lhn->bthl", q_nope,
                           w_up[..., :m.d_nope].astype(q_nope.dtype))
        q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)
        k_eff = jnp.concatenate([c_att, r_att], axis=-1)[:, :, None]
        v_eff = jnp.pad(c_att[:, :, None],
                        ((0, 0), (0, 0), (0, 0), (0, m.d_rope)))
        out, _ = flash_fwd_chunk(q_eff, k_eff, v_eff, causal=True,
                                 scale=1.0 / (m.d_qk ** 0.5),
                                 mask_offset=start,
                                 kv_valid_len=start + valid, impl="ref")
        out_lat = out[..., :m.kv_lora]
        o = jnp.einsum("bthl,lhv->bthv", out_lat,
                       w_up[..., m.d_nope:].astype(out_lat.dtype))
        return linear_apply(p["wo"], o.reshape(b, lc, m.n_heads * m.d_v)), \
            {"c": cc, "rope": rc}

    if cfg.mla is not None:
        def body(x, xs):
            lp, cache = xs
            h = apply_norm(cfg, lp["ln1"], x)
            h, cache = mla_chunk(lp["attn"], h, cache)
            x = x + h
            h = apply_norm(cfg, lp["ln2"], x)
            if cfg.family == "moe":
                h, _ = moe_apply(lp["moe"], h, rt, cfg.moe,
                                 seq_sharded=False)
            else:
                h = glu_mlp_apply(lp["mlp"], h, act=cfg.act)
            return x + h, cache

        x, ncache = maybe_scan(body, x, (params["blocks"][0],
                                         caches["blocks"][0]),
                               cfg.unroll_loops)
        new_caches = {"blocks": [ncache]}
    else:
        def body(x, xs):
            lps, slot_caches = xs
            new_slots = []
            for slot in range(period):
                lp, cache = lps[slot], slot_caches[slot]
                h = apply_norm(cfg, lp["ln1"], x)
                h, cache = gqa_chunk(lp["attn"], h, cache, kinds[slot])
                if cfg.post_norms:
                    h = apply_norm(cfg, lp["pn1"], h)
                x = x + h
                h = apply_norm(cfg, lp["ln2"], x)
                if cfg.family == "moe":
                    h, _ = moe_apply(lp["moe"], h, rt, cfg.moe,
                                     seq_sharded=False)
                else:
                    h = glu_mlp_apply(lp["mlp"], h, act=cfg.act)
                if cfg.post_norms:
                    h = apply_norm(cfg, lp["pn2"], h)
                x = x + h
                new_slots.append(cache)
            return x, new_slots

        x, ncaches = maybe_scan(body, x, (params["blocks"],
                                          caches["blocks"]),
                                cfg.unroll_loops)
        new_caches = {"blocks": ncaches}

    x = apply_norm(cfg, params["final_norm"], x)
    idx = jnp.clip(jnp.broadcast_to(valid.reshape(-1), (b,)) - 1, 0, lc - 1)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    w = lm_head_weight(params, cfg)
    logits = (x_last @ w.astype(x_last.dtype)).astype(jnp.float32)
    logits = jax.lax.with_sharding_constraint(
        logits, NamedSharding(rt.mesh, P(rt.batch_axes, None, MODEL_AXES)))
    return logits, new_caches
