"""Model assembly: config + init + train/prefill forward for the model zoo.

Families:
* ``dense``  — decoder-only GQA transformer (qwen3, gemma2/3, olmo,
               chameleon): optional qk-norm, logit softcaps, sliding-window/
               global layer patterns, post-norms.
* ``moe``    — dense skeleton with MoE FFN (qwen3-moe) and optionally MLA
               attention (deepseek-v2-lite).
* ``ssm``    — attention-free Mamba1 stack (falcon-mamba).
* ``hybrid`` — Mamba2 stack with a shared transformer block every
               ``attn_every`` layers (zamba2).
* ``encdec`` — Whisper: conv-frontend-stubbed encoder (non-causal 2D-Attn)
               + causal decoder with cross-attention.

Layers are grouped into *periods* (the window/global pattern length) and
scanned with ``lax.scan`` over stacked params — compile time stays flat in
depth.  Each scan body is wrapped in ``jax.checkpoint`` with the configured
policy; Selective Checkpoint++ saves only the attention residuals the
backward reads, named where the attention ``custom_vjp`` forward rules make
them (``spans.ATTN_OUT``, ``spans.ATTN_LSE``).

The cross-entropy is computed in token chunks inside a rematerialized scan so
the (tokens × vocab) logits never materialize (critical for gemma3's 262k
vocab at 1M-token global batches).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.runtime import Runtime
from repro.core.topology import BATCH_AXES, SEQ_AXES
from repro.models.attention_block import (AttnKind, MLADims, cross_attn_apply,
                                          gqa_apply, init_cross_attn,
                                          init_gqa, init_mla, mla_apply)
from repro.models.layers import (embedding_apply, gelu_mlp_apply,
                                 glu_mlp_apply, init_embedding, init_gelu_mlp,
                                 init_glu_mlp, init_layernorm, init_linear,
                                 init_rmsnorm, layernorm_apply,
                                 layernorm_nonparametric, linear_apply,
                                 rmsnorm_apply, rotary_cos_sin, softcap,
                                 sinusoid_positions)
from repro.models.moe import MoEDims, init_moe, moe_apply
from repro.models.ssm import (Mamba1Dims, Mamba2Dims, init_mamba1,
                              init_mamba2, mamba1_apply, mamba2_apply)
from repro.runtime import spans


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec
    num_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 => d_model // n_heads
    # attention flavour
    qk_norm: bool = False
    rope: bool = True
    rope_theta: float = 10000.0
    rope_theta_local: float = 10000.0
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    window: int | None = None
    window_pattern: int = 0      # period p: layer i is global iff i%p==p-1
    attn_bias: bool = False
    post_norms: bool = False     # gemma2/3 post-block norms
    # norms / mlp
    norm: str = "rms"            # rms | ln | ln_np
    act: str = "silu"
    # embeddings
    embed_scale: bool = False    # gemma: x *= sqrt(d_model)
    tie_embeddings: bool = True
    # family extras
    moe: MoEDims | None = None
    mla: MLADims | None = None
    ssm1: Mamba1Dims | None = None
    ssm2: Mamba2Dims | None = None
    attn_every: int = 0          # hybrid: shared attn block period
    encoder_layers: int = 0
    enc_frames: int = 1536       # stub conv-frontend output length (padded)
    max_positions: int = 4096    # whisper learned decoder positions
    # execution
    dtype: str = "bfloat16"
    remat: str = "scpp"          # none | full | scpp
    zigzag: bool = True
    loss_chunk: int = 512
    init_std: float = 0.02
    #: python-unroll every layer/chunk loop.  Dry-runs set this: XLA's
    #: cost_analysis counts a while body ONCE, so looped lowering would
    #: undercount FLOPs/collective-bytes by ~num_layers.
    unroll_loops: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def attn_kind(self, layer_in_period: int) -> AttnKind:
        """Attention kind for position ``layer_in_period`` of the pattern."""
        if self.window is not None and self.window_pattern:
            is_global = layer_in_period % self.window_pattern == \
                self.window_pattern - 1
        else:
            is_global = True
        return AttnKind(
            causal=True,
            window=None if is_global else self.window,
            softcap=self.attn_softcap,
            rope=self.rope,
            rope_theta=self.rope_theta if is_global
            else self.rope_theta_local)

    @property
    def period(self) -> int:
        if self.family in ("dense", "moe"):
            return self.window_pattern or 1
        return 1


def remat_policy(name: str):
    if name == "none":
        return "none"
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "scpp":
        return jax.checkpoint_policies.save_only_these_names(
            spans.ATTN_OUT, spans.ATTN_LSE)
    raise ValueError(name)


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def maybe_scan(body, init, xs, unroll: bool):
    """lax.scan, or a python-unrolled equivalent (for dry-run costing)."""
    if not unroll:
        return lax.scan(body, init, xs)
    carry = init
    ys = []
    n = jax.tree.leaves(xs)[0].shape[0]
    for i in range(n):
        x_i = jax.tree.map(lambda t: t[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *t: jnp.stack(t), *ys)
    else:
        ys = None
    return carry, ys


# ---------------------------------------------------------------------------
# Norm helpers
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: int):
    if cfg.norm == "rms":
        return init_rmsnorm(dim)
    if cfg.norm == "ln":
        return init_layernorm(dim)
    if cfg.norm == "ln_np":
        return {}
    raise ValueError(cfg.norm)


def apply_norm(cfg: ModelConfig, p, x):
    if cfg.norm == "rms":
        return rmsnorm_apply(p, x)
    if cfg.norm == "ln":
        return layernorm_apply(p, x)
    return layernorm_nonparametric(x)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def init_transformer_block(key, cfg: ModelConfig, *, moe_layer: bool):
    ks = jax.random.split(key, 4)
    p = {"ln1": init_norm(cfg, cfg.d_model),
         "ln2": init_norm(cfg, cfg.d_model)}
    if cfg.mla is not None:
        p["attn"] = init_mla(ks[0], cfg.d_model, cfg.mla)
    else:
        p["attn"] = init_gqa(ks[0], cfg.d_model, cfg.n_heads,
                             cfg.n_kv_heads, cfg.hd, qk_norm=cfg.qk_norm,
                             bias=cfg.attn_bias)
    if moe_layer:
        p["moe"] = init_moe(ks[1], cfg.moe)
    else:
        p["mlp"] = init_glu_mlp(ks[1], cfg.d_model, cfg.d_ff)
    if cfg.post_norms:
        p["pn1"] = init_norm(cfg, cfg.d_model)
        p["pn2"] = init_norm(cfg, cfg.d_model)
    return p


def apply_transformer_block(p, x, ropes, rt: Runtime, cfg: ModelConfig,
                            kind: AttnKind, *, moe_layer: bool,
                            doc_start=None):
    """Returns (x, aux_loss)."""
    cos, sin = ropes[kind.rope_theta]
    h = apply_norm(cfg, p["ln1"], x)
    with jax.named_scope(spans.ATTN):
        if cfg.mla is not None:
            h = mla_apply(p["attn"], h, cos, sin, rt, kind, cfg.mla,
                          zigzag=cfg.zigzag, doc_start=doc_start)
        else:
            h = gqa_apply(p["attn"], h, cos, sin, rt, kind,
                          n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                          head_dim=cfg.hd, qk_norm=cfg.qk_norm,
                          zigzag=cfg.zigzag, doc_start=doc_start)
    if cfg.post_norms:
        h = apply_norm(cfg, p["pn1"], h)
    x = x + h
    h = apply_norm(cfg, p["ln2"], x)
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope(spans.MLP):
        if moe_layer:
            h, aux = moe_apply(p["moe"], h, rt, cfg.moe)
        else:
            h = glu_mlp_apply(p["mlp"], h, act=cfg.act)
    if cfg.post_norms:
        h = apply_norm(cfg, p["pn2"], h)
    return x + h, aux


def init_mamba_block(key, cfg: ModelConfig, kind: str):
    p = {"ln": init_norm(cfg, cfg.d_model)}
    if kind == "mamba1":
        p["mix"] = init_mamba1(key, cfg.ssm1)
    else:
        p["mix"] = init_mamba2(key, cfg.ssm2)
    return p


def apply_mamba_block(p, x, rt: Runtime, cfg: ModelConfig, kind: str):
    h = apply_norm(cfg, p["ln"], x)
    if kind == "mamba1":
        h = mamba1_apply(p["mix"], h, rt, cfg.ssm1)
    else:
        h = mamba2_apply(p["mix"], h, rt, cfg.ssm2)
    return x + h


# ---------------------------------------------------------------------------
# Rope table
# ---------------------------------------------------------------------------

def build_ropes(cfg: ModelConfig, positions):
    """{theta: (cos, sin)} for every theta the layer pattern uses."""
    thetas = {cfg.rope_theta}
    if cfg.window is not None and cfg.window_pattern:
        thetas.add(cfg.rope_theta_local)
    dt = cfg.compute_dtype
    return {th: rotary_cos_sin(positions, cfg.hd if cfg.mla is None
                               else cfg.mla.d_rope, theta=th, dtype=dt)
            for th in sorted(thetas)}


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> dict:
    ks = iter(jax.random.split(key, 4 * cfg.num_layers + 64))
    params: dict[str, Any] = {
        "embed": init_embedding(next(ks), cfg.vocab, cfg.d_model,
                                std=cfg.init_std)}
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(next(ks), cfg.d_model, cfg.vocab,
                                        std=cfg.init_std)
    params["final_norm"] = init_norm(cfg, cfg.d_model)

    if cfg.family in ("dense", "moe"):
        period = cfg.period
        n_groups = cfg.num_layers // period
        assert cfg.num_layers % period == 0, (cfg.num_layers, period)
        groups = []
        for _ in range(n_groups):
            groups.append([init_transformer_block(
                next(ks), cfg, moe_layer=cfg.family == "moe")
                for _ in range(period)])
        # stack: list over period slots, each stacked over groups
        params["blocks"] = [_stack([g[slot] for g in groups])
                            for slot in range(period)]
    elif cfg.family == "ssm":
        params["blocks"] = _stack([init_mamba_block(next(ks), cfg, "mamba1")
                                   for _ in range(cfg.num_layers)])
    elif cfg.family == "hybrid":
        period = cfg.attn_every
        n_groups = cfg.num_layers // period
        rem = cfg.num_layers - n_groups * period
        params["blocks"] = _stack(
            [_stack([init_mamba_block(next(ks), cfg, "mamba2")
                     for _ in range(period)]) for _ in range(n_groups)])
        if rem:
            params["blocks_tail"] = _stack(
                [init_mamba_block(next(ks), cfg, "mamba2")
                 for _ in range(rem)])
        params["shared_attn"] = init_transformer_block(next(ks), cfg,
                                                       moe_layer=False)
    elif cfg.family == "encdec":
        params["enc_blocks"] = _stack(
            [init_whisper_block(next(ks), cfg, cross=False)
             for _ in range(cfg.encoder_layers)])
        params["dec_blocks"] = _stack(
            [init_whisper_block(next(ks), cfg, cross=True)
             for _ in range(cfg.num_layers)])
        params["enc_norm"] = init_norm(cfg, cfg.d_model)
        params["dec_pos"] = init_embedding(next(ks), cfg.max_positions,
                                           cfg.d_model, std=cfg.init_std)
    else:
        raise ValueError(cfg.family)
    return params


def init_whisper_block(key, cfg: ModelConfig, *, cross: bool):
    ks = jax.random.split(key, 3)
    p = {"ln1": init_norm(cfg, cfg.d_model),
         "attn": init_gqa(ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                          cfg.hd, bias=True),
         "ln2": init_norm(cfg, cfg.d_model),
         "mlp": init_gelu_mlp(ks[1], cfg.d_model, cfg.d_ff)}
    if cross:
        p["lnx"] = init_norm(cfg, cfg.d_model)
        p["cross"] = init_cross_attn(ks[2], cfg.d_model, cfg.n_heads, cfg.hd)
    return p


# ---------------------------------------------------------------------------
# Forward (training / prefill trunk)
# ---------------------------------------------------------------------------

def _scan_blocks(body, x, stacked, policy, collect: bool = False,
                 unroll: bool = False):
    """scan with per-step remat.  body(x, layer_params) -> (x, aux[, ys])."""
    if policy == "none":
        wrapped = body
    else:
        wrapped = jax.checkpoint(body, policy=policy, prevent_cse=False)

    def step(carry, lp):
        x, aux = carry
        out = wrapped(x, lp)
        if collect:
            x, a, ys = out
            return (x, aux + a), ys
        x, a = out
        return (x, aux + a), None

    (x, aux), ys = maybe_scan(step, (x, jnp.zeros((), jnp.float32)),
                              stacked, unroll)
    return x, aux, ys


def backbone(params, x, ropes, rt: Runtime, cfg: ModelConfig,
             doc_start=None):
    """Embedded input -> final hidden states.  Returns (x, aux).

    ``doc_start`` (packed documents) reaches only the attention blocks;
    SSM mixing layers are sequence-recurrent and have no packed mode
    (their state would need per-document resets) — packing is gated to
    attention families by the ExecutionPlan.
    """
    aux_total = jnp.zeros((), jnp.float32)
    policy = remat_policy(cfg.remat)

    if cfg.family in ("dense", "moe"):
        period = cfg.period
        kinds = [cfg.attn_kind(i) for i in range(period)]

        def body(x, lps):
            aux = jnp.zeros((), jnp.float32)
            for slot in range(period):
                x, a = apply_transformer_block(
                    lps[slot], x, ropes, rt, cfg, kinds[slot],
                    moe_layer=cfg.family == "moe", doc_start=doc_start)
                aux = aux + a
            return x, aux

        x, aux_total, _ = _scan_blocks(body, x, params["blocks"], policy,
                                       unroll=cfg.unroll_loops)

    elif cfg.family == "ssm":
        def body(x, lp):
            return apply_mamba_block(lp, x, rt, cfg, "mamba1"), \
                jnp.zeros((), jnp.float32)
        x, aux_total, _ = _scan_blocks(body, x, params["blocks"], policy,
                                       unroll=cfg.unroll_loops)

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        kind = cfg.attn_kind(0)

        def body(x, lps):
            for i in range(cfg.attn_every):
                x = apply_mamba_block(
                    jax.tree.map(lambda t: t[i], lps), x, rt, cfg, "mamba2")
            x, a = apply_transformer_block(shared, x, ropes, rt, cfg, kind,
                                           moe_layer=False)
            return x, a

        x, aux_total, _ = _scan_blocks(body, x, params["blocks"], policy,
                                       unroll=cfg.unroll_loops)
        if "blocks_tail" in params:
            def tail(x, lp):
                return apply_mamba_block(lp, x, rt, cfg, "mamba2"), \
                    jnp.zeros((), jnp.float32)
            x, _, _ = _scan_blocks(tail, x, params["blocks_tail"], policy,
                                   unroll=cfg.unroll_loops)
    else:
        raise ValueError(cfg.family)
    return x, aux_total


def whisper_encoder(params, frames, rt: Runtime, cfg: ModelConfig):
    """frames: (B, T_enc, D) stubbed conv-frontend output."""
    dt = cfg.compute_dtype
    x = frames.astype(dt) + sinusoid_positions(frames.shape[1], cfg.d_model,
                                               dt)[None]
    policy = remat_policy(cfg.remat)
    kind = AttnKind(causal=False, rope=False)

    def body(x, lp):
        h = apply_norm(cfg, lp["ln1"], x)
        with jax.named_scope(spans.ATTN):
            h = gqa_apply(lp["attn"], h, None, None, rt, kind,
                          n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                          head_dim=cfg.hd, zigzag=False)
        x = x + h
        h = apply_norm(cfg, lp["ln2"], x)
        with jax.named_scope(spans.MLP):
            h = gelu_mlp_apply(lp["mlp"], h)
        return x + h, jnp.zeros((), jnp.float32)

    x, _, _ = _scan_blocks(body, x, params["enc_blocks"], policy,
                           unroll=cfg.unroll_loops)
    return apply_norm(cfg, params["enc_norm"], x)


def whisper_decoder(params, x, enc_out, ropes, rt: Runtime,
                    cfg: ModelConfig, positions):
    policy = remat_policy(cfg.remat)
    kind = AttnKind(causal=True, rope=False)
    x = x + embedding_apply(params["dec_pos"],
                            jnp.minimum(positions, cfg.max_positions - 1),
                            dtype=x.dtype)

    def body(x, lp):
        h = apply_norm(cfg, lp["ln1"], x)
        with jax.named_scope(spans.ATTN):
            h = gqa_apply(lp["attn"], h, None, None, rt, kind,
                          n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                          head_dim=cfg.hd, zigzag=cfg.zigzag)
        x = x + h
        h = apply_norm(cfg, lp["lnx"], x)
        with jax.named_scope(spans.ATTN):
            h = cross_attn_apply(lp["cross"], h, enc_out, rt,
                                 n_heads=cfg.n_heads, head_dim=cfg.hd)
        x = x + h
        h = apply_norm(cfg, lp["ln2"], x)
        with jax.named_scope(spans.MLP):
            h = gelu_mlp_apply(lp["mlp"], h)
        return x + h, jnp.zeros((), jnp.float32)

    x, _, _ = _scan_blocks(body, x, params["dec_blocks"], policy,
                           unroll=cfg.unroll_loops)
    return x


# ---------------------------------------------------------------------------
# Loss (chunked, never materializes tokens × vocab)
# ---------------------------------------------------------------------------

def chunked_xent(x, w_head, labels, rt: Runtime, cfg: ModelConfig):
    """x: (B, S, D); w_head: (D, V); labels: (B, S) int32 (-1 = pad).

    Returns (loss_sum, n_valid) — both replicated scalars.
    """
    cap = cfg.final_softcap

    @jax.named_scope(spans.LM_HEAD)
    def local(x, w, labels):
        b_loc, s_loc, d = x.shape
        t = b_loc * s_loc
        chunk = min(cfg.loss_chunk, t)
        while t % chunk:
            chunk -= 1
        xt = x.reshape(t, d)
        lt = labels.reshape(t)

        def chunk_fn(carry, xs):
            xc, lc = xs
            logits = (xc @ w.astype(xc.dtype)).astype(jnp.float32)
            if cap:
                logits = softcap(logits, cap)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(
                logits, jnp.maximum(lc, 0)[:, None], axis=1)[:, 0]
            valid = (lc >= 0)
            loss = jnp.where(valid, lse - ll, 0.0)
            return (carry[0] + loss.sum(),
                    carry[1] + valid.sum().astype(jnp.float32)), None

        xs = (xt.reshape(t // chunk, chunk, d),
              lt.reshape(t // chunk, chunk))
        zero = jnp.zeros((), jnp.float32)
        (loss_sum, n_valid), _ = maybe_scan(
            jax.checkpoint(chunk_fn), (zero, zero), xs, cfg.unroll_loops)
        loss_sum = lax.psum(loss_sum, BATCH_AXES + SEQ_AXES)
        n_valid = lax.psum(n_valid, BATCH_AXES + SEQ_AXES)
        return loss_sum, n_valid

    spec_x = P(BATCH_AXES, SEQ_AXES, None)
    spec_l = P(BATCH_AXES, SEQ_AXES)
    f = jax.shard_map(local, mesh=rt.mesh,
                      in_specs=(spec_x, P(None, None), spec_l),
                      out_specs=(P(), P()), check_vma=False)
    return f(x, w_head, labels)


def cast_params_once(params, cfg: ModelConfig):
    """Cast matrix params to the compute dtype *once*, before any use.

    Without this, XLA gathers ZeRO-sharded fp32 masters and converts after
    the all-gather — 2× the gather wire bytes.  A single up-front convert
    keeps every gather in bf16 (numerics identical: the same cast happened
    per-use before).  Precision-critical leaves (A_log: exp() of it drives
    SSM decay) stay fp32.
    """
    dt = cfg.compute_dtype
    if dt == jnp.float32:
        return params

    def cast(path, x):
        name = jax.tree_util.keystr(path)
        if "A_log" in name or x.ndim < 2 or \
                not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return x.astype(dt)

    return jax.tree_util.tree_map_with_path(cast, params)


def lm_head_weight(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"]["table"].T
    return params["lm_head"]["w"]


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg: ModelConfig):
    scale = cfg.d_model ** 0.5 if cfg.embed_scale else None
    return embedding_apply(params["embed"], tokens,
                           dtype=cfg.compute_dtype, scale=scale)


def forward_loss(params, batch, rt: Runtime, cfg: ModelConfig):
    """batch: {tokens, labels, positions[, frames]} -> (loss, metrics)."""
    tokens = batch["tokens"]
    positions = batch["positions"]
    doc_start = batch.get("doc_start")       # packed documents (PackedLM)
    params = cast_params_once(params, cfg)
    x = embed_tokens(params, tokens, cfg)
    x = rt.constrain(x, None)
    ropes = build_ropes(cfg, positions) if cfg.rope else {}

    if cfg.family == "encdec":
        enc = whisper_encoder(params, batch["frames"], rt, cfg)
        x = whisper_decoder(params, x, enc, ropes, rt, cfg, positions)
        aux = jnp.zeros((), jnp.float32)
    else:
        x, aux = backbone(params, x, ropes, rt, cfg, doc_start=doc_start)

    x = apply_norm(cfg, params["final_norm"], x)
    x = rt.constrain(x, None)
    loss_sum, n_valid = chunked_xent(x, lm_head_weight(params, cfg),
                                     batch["labels"], rt, cfg)
    loss = loss_sum / jnp.maximum(n_valid, 1.0) + aux
    return loss, {"loss": loss, "xent": loss_sum / jnp.maximum(n_valid, 1.0),
                  "aux": aux, "n_tokens": n_valid}
