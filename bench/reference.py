"""Plain reference of the dense decoders the benchmark runs, and of AdamW.

Straight ``jax.numpy``: no kernel, no cache, no sharding rule of the
program; it imports nothing of the program and takes nothing it made.
The weights come from ``bench/weights.py`` and the seed, the tokens from
``bench/traffic.py``.

Model (OLMo-1B and Qwen3 as published): tied embedding; per layer
``x += Wo·attn(norm(x))`` then ``x += W2·(silu(W1·h) * W3·h)`` with
``h = norm(x)``; causal softmax attention with rotary positions (halves
convention, positions 0..S-1) and query head ``h`` reading kv head
``h // (Hq / Hkv)``; Qwen3 RMS-normalizes q and k per head before the
rotation.  OLMo's norm is LayerNorm without scale or bias (eps 1e-5),
Qwen3's RMSNorm with a gain (eps 1e-6).  The loss is the mean token
cross-entropy of the tied head's logits.

Precision: ``"fp32"`` runs every matmul in float32 at ``HIGHEST``.  The
control, ``"fp8"``, rounds the operands of every matmul to float8 e4m3 and
the gradient that reaches a matmul's output to e5m2, each scaled per
tensor: the step below the bf16 the configurations state.

Memory: the gradient is taken layer by layer (``Model``), attention runs
in query blocks against the causal prefix of keys (grouped into a few key
lengths), the MLP and the loss in token chunks, and the moments wait in
host memory, so that 32k-token rows fit one chip; over a mesh each layer
is split by heads and MLP width.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.weights import Dims, init, leaf_norms

HIGHEST = lax.Precision.HIGHEST
#: query rows per attention block; number of key-length groups; tokens
#: per chunk of the MLP and of the loss
Q_BLOCK = 256
KEY_GROUPS = 8
MLP_CHUNK = 4096
LOSS_CHUNK = 1024


@dataclasses.dataclass(frozen=True)
class Opt:
    lr: float
    beta1: float
    beta2: float
    eps: float
    weight_decay: float
    clip_norm: float
    warmup_steps: int
    total_steps: int
    min_lr_ratio: float


def _round(x, dtype, top):
    """``x`` rounded to ``dtype``, scaled per tensor so that its largest
    magnitude maps to ``top``."""
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8_operand(x):
    return _round(x, jnp.float8_e4m3fn, 448.0)


_fp8_operand.defvjp(lambda x: (_fp8_operand(x), None),
                    lambda _, ct: (ct,))


@jax.custom_vjp
def _fp8_grad(y):
    return y


_fp8_grad.defvjp(lambda y: (y, None),
                 lambda _, ct: (_round(ct, jnp.float8_e5m2, 57344.0),))


def _einsum(spec, a, b, prec):
    """A matmul in float32 at HIGHEST; the control ("fp8") rounds its
    operands to e4m3 and, backward, the incoming gradient to e5m2, each
    scaled per tensor, as fp8 training does."""
    if prec == "fp8":
        return _fp8_grad(jnp.einsum(spec, _fp8_operand(a), _fp8_operand(b),
                                    precision=HIGHEST,
                                    preferred_element_type=jnp.float32))
    if prec != "fp32":
        raise ValueError(prec)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _norm(dims: Dims, x, gain):
    if dims.norm == "rmsnorm":
        return _rms(x, gain, dims.norm_eps)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    return xc * lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True)
                          + dims.norm_eps)


def _rope(x, cos, sin):
    """x (S, H, hd); cos/sin (S, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rope_tables(dims: Dims, seq: int):
    half = dims.head_dim // 2
    freqs = 1.0 / (dims.rope_theta ** (np.arange(half) * 2.0 / dims.head_dim))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)
    return jnp.cos(ang), jnp.sin(ang)


def _attention(q, k, v, prec):
    """Causal attention; q (S, Hq, hd), k/v (S, Hkv, hd) -> (S, Hq, hd)."""
    s, hq, hd = q.shape
    group = hq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    bq = min(Q_BLOCK, s)
    nb = s // bq
    groups = min(KEY_GROUPS, nb)
    per = -(-nb // groups)
    scale = 1.0 / np.sqrt(hd)
    outs = []
    for g0 in range(0, nb, per):
        g1 = min(nb, g0 + per)
        kend = g1 * bq                       # keys this group can see
        kg, vg = k[:kend], v[:kend]

        @jax.checkpoint
        def block(qb, start, kg=kg, vg=vg, kend=kend):
            sc = _einsum("qhd,khd->hqk", qb, kg, prec) * scale
            rows = start + jnp.arange(bq)[:, None]
            sc = jnp.where(jnp.arange(kend)[None, :] <= rows, sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            return _einsum("hqk,khd->qhd", p, vg, prec)

        qs = q[g0 * bq:g1 * bq].reshape(g1 - g0, bq, hq, hd)
        starts = jnp.arange(g0, g1) * bq
        outs.append(lax.map(lambda a: block(*a), (qs, starts)))
    return jnp.concatenate(outs, axis=0).reshape(s, hq, hd)


def _attn_delta(dims: Dims, prec, cos, sin, x, lw):
    """What the attention half of a layer adds to the residual ``x``."""
    s = x.shape[0]
    hq, hkv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    h = _norm(dims, x, lw.get("attn_norm"))
    q = _einsum("sd,de->se", h, lw["wq"], prec).reshape(s, hq, hd)
    k = _einsum("sd,de->se", h, lw["wk"], prec).reshape(s, hkv, hd)
    v = _einsum("sd,de->se", h, lw["wv"], prec).reshape(s, hkv, hd)
    if dims.qk_norm:
        q = _rms(q, lw["q_norm"], dims.norm_eps)
        k = _rms(k, lw["k_norm"], dims.norm_eps)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    o = _attention(q, k, v, prec).reshape(s, hq * hd)
    return _einsum("se,ed->sd", o, lw["wo"], prec)


def _mlp_delta(dims: Dims, prec, x, lw):
    """What the MLP half of a layer adds to the residual ``x``."""
    s, d = x.shape
    c = min(MLP_CHUNK, s)

    @jax.checkpoint
    def chunk(xc):
        h = _norm(dims, xc, lw.get("mlp_norm"))
        a = jax.nn.silu(_einsum("sd,df->sf", h, lw["w1"], prec))
        a = a * _einsum("sd,df->sf", h, lw["w3"], prec)
        return _einsum("sf,fd->sd", a, lw["w2"], prec)

    return lax.map(chunk, x.reshape(s // c, c, d)).reshape(s, d)


#: how a layer's weights split over the devices of a mesh (axis "t"):
#: heads and the MLP's width, Megatron style; gains whole on every device
_SPLIT = {"wq": P(None, "t"), "wk": P(None, "t"), "wv": P(None, "t"),
          "wo": P("t", None), "w1": P(None, "t"), "w3": P(None, "t"),
          "w2": P("t", None)}


def weight_shardings(dims: Dims, mesh):
    """Shardings of the weights (and moments) over ``mesh``: layer
    matrices split as ``_SPLIT``, everything else whole on every device."""
    whole = NamedSharding(mesh, P())
    out = {"embed": whole, "layers": {
        k: NamedSharding(mesh, P(None, *_SPLIT[k])) if k in _SPLIT else whole
        for k in dims.shapes()["layers"]}}
    if dims.norm == "rmsnorm":
        out["final_norm"] = whole
    return out


def _half(delta, dims: Dims, prec, mesh):
    """``x + delta(x)`` for one half of a layer; over a mesh, each device
    computes the part of its heads or MLP columns and the parts are
    summed."""
    if mesh is None:
        f = functools.partial(delta, dims, prec)
        return lambda *a: a[-2] + f(*a)
    n = mesh.size
    if dims.n_kv_heads % n or dims.d_ff % n:
        raise ValueError(f"{dims.n_kv_heads} kv heads and width {dims.d_ff} "
                         f"do not split over {n} devices")
    local = dataclasses.replace(dims, n_heads=dims.n_heads // n,
                                n_kv_heads=dims.n_kv_heads // n,
                                d_ff=dims.d_ff // n)
    specs = {k: _SPLIT.get(k, P()) for k in dims.shapes()["layers"]}

    def part(*a):
        return lax.psum(delta(local, prec, *a), "t")

    def run(*a):
        f = jax.shard_map(part, mesh=mesh,
                          in_specs=(P(),) * (len(a) - 1) + (specs,),
                          out_specs=P())
        return a[-2] + f(*a)
    return run


def _head_loss_sum(dims: Dims, prec, x, embed, final_norm, labels):
    """Summed cross-entropy of the tied head's logits, in token chunks."""
    s = x.shape[0]
    x = _norm(dims, x, final_norm)
    c = min(LOSS_CHUNK, s)

    @jax.checkpoint
    def chunk(xc, lc):
        logits = _einsum("cd,vd->cv", xc, embed, prec)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[:, None],
                                 axis=1)[:, 0]
        return jnp.sum(jnp.where(lc >= 0, lse - ll, 0.0))

    parts = lax.map(lambda a: chunk(*a),
                    (x.reshape(s // c, c, -1), labels.reshape(s // c, c)))
    return jnp.sum(parts)


class Model:
    """The model's loss and gradient, one layer at a time.

    The forward keeps each layer's input; the backward runs layer by layer
    from the last, each layer one compiled program that recomputes the
    layer's attention once and keeps its blocks' inputs only, so that no
    more than one layer's activations are live."""

    def __init__(self, dims: Dims, prec: str, mesh=None):
        """``mesh`` (optional, axis "t"): split each layer's heads and MLP
        width over its devices, and keep the saved layer inputs split
        along the sequence, so that a model too large for one chip fits."""
        self.dims = dims
        attn = _half(_attn_delta, dims, prec, mesh)
        mlp = _half(_mlp_delta, dims, prec, mesh)
        head = functools.partial(_head_loss_sum, dims, prec)
        if mesh is None:
            self.stash = self.unstash = lambda x: x
        else:
            self.stash = jax.jit(lambda x: x, out_shardings=NamedSharding(
                mesh, P("t", None)))
            self.unstash = jax.jit(lambda x: x, out_shardings=NamedSharding(
                mesh, P()))
        self.tables = jax.jit(functools.partial(_rope_tables, dims),
                              static_argnums=0)
        self.layer = jax.jit(lambda layers, i: jax.tree.map(
            lambda a: a[i], layers))
        self.attn = jax.jit(attn)
        self.mlp = jax.jit(mlp)

        def layer_vjp(c, s, x, lw, dx):
            mid, attn_back = jax.vjp(lambda x, lw: attn(c, s, x, lw), x, lw)
            dmid, d_mlp = jax.vjp(mlp, mid, lw)[1](dx)
            dx, d_attn = attn_back(dmid)
            return dx, d_mlp, d_attn
        self.layer_vjp = jax.jit(layer_vjp)
        def head_vjp(x, e, f, lab, scale):
            out, back = jax.vjp(lambda x, e, f: head(x, e, f, lab), x, e, f)
            return back(scale) + (out,)
        self.head = jax.jit(head_vjp)
        self.embed_vjp = jax.jit(lambda g, tok, dx: g.at[tok].add(dx),
                                 donate_argnums=0)
        self.put = jax.jit(lambda g, i, d1, d2: jax.tree.map(
            lambda a, b, c: a.at[i].add(b + c), g, d1, d2),
            donate_argnums=0)

    def loss_and_grad(self, w, tokens, labels):
        """Mean token cross-entropy over the valid labels (``>= 0``) and its
        gradient; tokens/labels (B, S) int32 in logical order."""
        b, s = tokens.shape
        cos, sin = self.tables(s)
        n_valid = int((labels >= 0).sum())
        scale = jnp.float32(1.0 / n_valid)
        g = jax.tree.map(jnp.zeros_like, w)
        total = 0.0
        for r in range(b):
            tok, lab = jnp.asarray(tokens[r]), jnp.asarray(labels[r])
            x = jnp.take(w["embed"], tok, axis=0)
            xs = []
            for i in range(self.dims.layers):
                lw = self.layer(w["layers"], i)
                xs.append(self.stash(x))
                x = self.mlp(self.attn(cos, sin, x, lw), lw)
            dx, d_embed, d_final, part = self.head(
                x, w["embed"], w.get("final_norm"), lab, scale)
            total += float(part)
            g["embed"] = g["embed"] + d_embed
            if "final_norm" in g:
                g["final_norm"] = g["final_norm"] + d_final
            for i in reversed(range(self.dims.layers)):
                lw = self.layer(w["layers"], i)
                dx, d_mlp, d_attn = self.layer_vjp(
                    cos, sin, self.unstash(xs.pop()), lw, dx)
                g["layers"] = self.put(g["layers"], i, d_mlp, d_attn)
            g["embed"] = self.embed_vjp(g["embed"], tok, dx)
        return total / n_valid, g


def loss_fn(dims: Dims, prec, w, tokens, labels):
    """``Model.loss_and_grad``'s loss as one differentiable function (for
    small sizes and tests)."""
    total = 0.0
    for r in range(tokens.shape[0]):
        cos, sin = _rope_tables(dims, tokens.shape[1])
        x = jnp.take(w["embed"], tokens[r], axis=0)
        for i in range(dims.layers):
            lw = jax.tree.map(lambda a: a[i], w["layers"])
            x = x + _attn_delta(dims, prec, cos, sin, x, lw)
            x = x + _mlp_delta(dims, prec, x, lw)
        total = total + _head_loss_sum(dims, prec, x, w["embed"],
                                       w.get("final_norm"), labels[r])
    return total / jnp.sum(labels >= 0)


def lr_at(opt: Opt, step):
    """Linear warm-up to ``lr``, cosine decay to ``min_lr_ratio * lr``."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(opt.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - opt.warmup_steps)
                    / max(opt.total_steps - opt.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return opt.lr * warm * (opt.min_lr_ratio + (1 - opt.min_lr_ratio) * cos)


def _is_gain(path) -> bool:
    name = jax.tree_util.keystr(path)
    return "norm" in name


def adamw(opt: Opt, w, g, m, v, step: int):
    """One AdamW step (1-based ``step``): global-norm clipping, bias
    correction, decoupled weight decay on matrices (not on norm gains)."""
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt.clip_norm / (gnorm + 1e-12))
    lr = lr_at(opt, step)
    step = jnp.asarray(step, jnp.float32)
    c1 = 1.0 - opt.beta1 ** step
    c2 = 1.0 - opt.beta2 ** step

    def upd(path, p, gi, mi, vi):
        gi = gi * scale
        mi = opt.beta1 * mi + (1 - opt.beta1) * gi
        vi = opt.beta2 * vi + (1 - opt.beta2) * gi * gi
        d = (mi / c1) / (jnp.sqrt(vi / c2) + opt.eps)
        if not _is_gain(path):
            d = d + opt.weight_decay * p
        return p - lr * d, mi, vi

    out = jax.tree_util.tree_map_with_path(upd, w, g, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), gnorm


def train(dims: Dims, opt: Opt, key, batches, prec: str = "fp32",
          mesh=None) -> dict:
    """Train ``len(batches)`` steps from the weights of ``key``.

    ``batches``: list of (tokens, labels), each (B, S) int32.  Returns
    {"loss": per-step losses, "grad": {leaf: norm} of the first step's
    unclipped gradient, "grad_norm": its global norm, "change": {leaf:
    norm} of the weights' change after the last step}.  The moments wait
    in host memory while the gradient is computed.  ``mesh``: see
    ``Model``.
    """
    sh = None if mesh is None else weight_shardings(dims, mesh)
    make = jax.jit(functools.partial(init, dims), out_shardings=sh)
    w = make(key)
    m = v = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), w)
    model = Model(dims, prec, mesh)
    step_fn = jax.jit(functools.partial(adamw, opt),
                      donate_argnums=(0, 2, 3))
    norms = jax.jit(leaf_norms)
    out = {"loss": []}
    for i, (tokens, labels) in enumerate(batches):
        loss, g = model.loss_and_grad(w, tokens, labels)
        out["loss"].append(loss)
        if i == 0:
            out["grad"] = {k: float(x) for k, x in norms(g).items()}
        w, m, v, gnorm = step_fn(w, g, jax.device_put(m, sh),
                                 jax.device_put(v, sh), jnp.int32(i + 1))
        m, v = jax.device_get((m, v))
        if i == 0:
            out["grad_norm"] = float(gnorm)
    # the key is an argument, not a constant: one program for every seed
    change = jax.jit(lambda w, k: norms(jax.tree.map(jnp.subtract, w,
                                                     make(k))))(w, key)
    out["change"] = {k: float(x) for k, x in change.items()}
    return out
