"""Mamba1 / Mamba2 blocks with context-parallel chunked selective scan.

LoongTrain's 2D-Attention does not apply to attention-free layers (DESIGN.md
§Arch-applicability), but its *context* dimension does: the sequence stays
sharded over all sp axes and the recurrence crosses shard boundaries through
a tiny state hand-off:

* the per-chunk cumulative decay has a closed form (``exp(A · ΣΔ)`` — A is
  diagonal for Mamba1, scalar-per-head for Mamba2), so
* each rank runs its local scan from ``h0 = 0``, all ranks ``all_gather``
  their ``(chunk_decay, chunk_state)`` pair (a few MB), every rank computes
  its exclusive prefix locally, and a second local scan runs with the
  corrected ``h0``.  The rescan costs < 2 % extra FLOPs (the scan is ~N/D of
  the block's work) and avoids materializing (S, d_inner, N) corrections.

The causal depthwise conv crosses shards with a (d_conv-1)-token halo
ppermute (no wraparound: rank 0 sees zeros, which is the causal pad).

Memory: the intra-chunk scan runs segment-wise (``lax.scan`` over segments
of an ``associative_scan``), bounding backward residuals to one state per
segment instead of one per timestep.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.runtime import Runtime
from repro.core.topology import BATCH_AXES, SEQ_AXES
from repro.models.layers import (init_linear, init_rmsnorm, linear_apply,
                                 rmsnorm_apply)


# ---------------------------------------------------------------------------
# Scan machinery
# ---------------------------------------------------------------------------

def _assoc_combine(left, right):
    a_l, u_l = left
    a_r, u_r = right
    return a_l * a_r, u_l * a_r + u_r


def _assoc_fold(a, u, axis: int = 1):
    """Associative pair-fold of (decay, increment) along ``axis`` — the
    final state only, in 2× the tensor's traffic (vs log-n sweeps of an
    associative_scan).  Used by the summary pass of the chunked CP scan.
    """
    while a.shape[axis] > 1:
        n = a.shape[axis]
        if n % 2:
            # fold the odd tail into its neighbour first
            a_last = jnp.take(a, jnp.array([n - 1]), axis=axis)
            u_last = jnp.take(u, jnp.array([n - 1]), axis=axis)
            a_prev = jnp.take(a, jnp.array([n - 2]), axis=axis)
            u_prev = jnp.take(u, jnp.array([n - 2]), axis=axis)
            a2, u2 = _assoc_combine((a_prev, u_prev), (a_last, u_last))
            a = jnp.concatenate(
                [jax.lax.slice_in_dim(a, 0, n - 2, axis=axis), a2], axis)
            u = jnp.concatenate(
                [jax.lax.slice_in_dim(u, 0, n - 2, axis=axis), u2], axis)
            n -= 1
        even = jax.lax.slice_in_dim(a, 0, n, 2, axis=axis), \
            jax.lax.slice_in_dim(u, 0, n, 2, axis=axis)
        odd = jax.lax.slice_in_dim(a, 1, n, 2, axis=axis), \
            jax.lax.slice_in_dim(u, 1, n, 2, axis=axis)
        a, u = _assoc_combine(even, odd)
    return jnp.squeeze(a, axis), jnp.squeeze(u, axis)


def _segmented_scan(h0, seg_fn, xs, n_seg: int):
    """lax.scan over segments with rematerialized bodies.

    ``seg_fn(h, seg_xs) -> (h_next, y_seg)``; residual storage is one carry
    per segment boundary.
    """
    body = jax.checkpoint(seg_fn)
    h_fin, ys = lax.scan(body, h0, xs)
    return h_fin, ys


def _halo_exchange(x, halo: int, axes, n_ranks: int):
    """Bring the previous sequence shard's last ``halo`` tokens in front.

    x: (B, S_loc, C).  Rank 0 receives zeros (the causal pad).

    Implementation note: ``lax.ppermute`` flattens multi-axis names in *mesh*
    order (not listed order), so a combined-axis ring shift is unsafe; the
    halo is a few tokens, so an all_gather + dynamic pick is cheap & exact.
    """
    tail = x[:, -halo:]
    if n_ranks == 1:
        return jnp.concatenate([jnp.zeros_like(tail), x], axis=1)
    tails = lax.all_gather(tail, axes)               # (R, B, halo, C)
    r = _linear_rank(axes)
    prev = lax.dynamic_index_in_dim(tails, jnp.maximum(r - 1, 0), 0,
                                    keepdims=False)
    prev = jnp.where(r > 0, prev, jnp.zeros_like(prev))
    return jnp.concatenate([prev, x], axis=1)


def _causal_conv(x, w, b, halo_x):
    """Depthwise causal conv.  x: (B, S+K-1, C) pre-padded; w: (K, C)."""
    k = w.shape[0]
    s = x.shape[1] - (k - 1)
    y = jnp.zeros((x.shape[0], s, x.shape[2]), jnp.float32)
    for i in range(k):
        y = y + x[:, i:i + s].astype(jnp.float32) * w[i].astype(jnp.float32)
    return (y + b.astype(jnp.float32)).astype(halo_x)


def _cross_rank_state(d_tot, h_fin, axes, n_ranks: int):
    """Exclusive prefix of (decay, state) pairs across sequence shards.

    d_tot/h_fin: local chunk decay & final state (from the h0=0 pass).
    Returns (h0, h_global_final): this rank's initial state
    ``h0 = sum_{r'<r} (prod_{r'<m<r} D_m) h_{r'}`` and the state after the
    full sequence (identical on every rank — the decode cache seed).
    """
    if n_ranks == 1:
        return jnp.zeros_like(h_fin), h_fin
    ds = lax.all_gather(d_tot, axes)       # (R, ...)
    hs = lax.all_gather(h_fin, axes)
    prefixes = [jnp.zeros_like(h_fin)]
    for r in range(n_ranks):
        prefixes.append(prefixes[-1] * ds[r] + hs[r])
    stacked = jnp.stack(prefixes[:-1])     # (R, ...)
    idx = _linear_rank(axes)
    h0 = lax.dynamic_index_in_dim(stacked, idx, axis=0, keepdims=False)
    return h0, prefixes[-1]


def _linear_rank(axes):
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# Mamba1 (falcon-mamba)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Mamba1Dims:
    d_model: int
    d_inner: int
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0          # 0 => ceil(d_model / 16)
    seg: int = 64             # intra-chunk scan segment length

    def __post_init__(self):
        if self.dt_rank == 0:
            object.__setattr__(self, "dt_rank",
                               (self.d_model + 15) // 16)


def init_mamba1(key, m: Mamba1Dims):
    ks = jax.random.split(key, 6)
    a = jnp.tile(jnp.arange(1, m.d_state + 1, dtype=jnp.float32),
                 (m.d_inner, 1))
    return {
        "in_proj": init_linear(ks[0], m.d_model, 2 * m.d_inner),
        "conv_w": 0.1 * jax.random.normal(ks[1], (m.d_conv, m.d_inner)),
        "conv_b": jnp.zeros((m.d_inner,), jnp.float32),
        "x_proj": init_linear(ks[2], m.d_inner, m.dt_rank + 2 * m.d_state),
        "dt_proj": init_linear(ks[3], m.dt_rank, m.d_inner, bias=True),
        "A_log": jnp.log(a),
        "D": jnp.ones((m.d_inner,), jnp.float32),
        "out_proj": init_linear(ks[4], m.d_inner, m.d_model),
    }


def _mamba1_scan_local(delta, bmat, cmat, x_in, a_diag, h0, seg: int):
    """delta/x_in: (B,S,di); bmat/cmat: (B,S,N); a_diag: (di,N) (negative).

    Returns y (B,S,di) f32, h_fin (B,di,N), d_tot (B,di,N).
    """
    b, s, di = delta.shape
    n = bmat.shape[-1]
    seg = min(seg, s)
    n_seg = s // seg
    assert s % seg == 0, (s, seg)

    def seg_fn(h, xs):
        d_s, b_s, c_s, x_s = xs                     # (B,seg,...)
        a = jnp.exp(d_s[..., None] * a_diag)        # (B,seg,di,N)
        u = (d_s * x_s)[..., None] * b_s[:, :, None, :]
        a_cum, u_cum = lax.associative_scan(_assoc_combine, (a, u), axis=1)
        h_t = a_cum * h[:, None] + u_cum            # (B,seg,di,N)
        y = jnp.einsum("bsdn,bsn->bsd", h_t, c_s)
        return h_t[:, -1], y

    xs = tuple(x.reshape(b, n_seg, seg, *x.shape[2:]).swapaxes(0, 1)
               for x in (delta, bmat, cmat, x_in))
    h_fin, ys = _segmented_scan(h0, seg_fn, xs, n_seg)
    y = ys.swapaxes(0, 1).reshape(b, s, di)
    d_tot = jnp.exp(jnp.sum(delta, axis=1)[..., None] * a_diag)
    return y, h_fin, d_tot


def mamba1_apply(p, x, rt: Runtime, m: Mamba1Dims,
                 return_state: bool = False):
    """x: (B, S, d_model) seq-sharded -> same (+ final state for prefill)."""
    xz = linear_apply(p["in_proj"], x)
    x_in, z = jnp.split(xz, 2, axis=-1)

    n_ranks = rt.pc.sp

    def conv_local(x_in):
        xp = _halo_exchange(x_in, m.d_conv - 1, SEQ_AXES, n_ranks)
        return jax.nn.silu(_causal_conv(xp, p["conv_w"], p["conv_b"],
                                        x_in.dtype))

    spec = P(BATCH_AXES, SEQ_AXES, None)
    x_conv = jax.shard_map(conv_local, mesh=rt.mesh, in_specs=(spec,),
                           out_specs=spec, check_vma=False)(x_in)

    dbc = linear_apply(p["x_proj"], x_conv)
    dt = jax.nn.softplus(
        linear_apply(p["dt_proj"], dbc[..., :m.dt_rank]).astype(jnp.float32))
    bmat = dbc[..., m.dt_rank:m.dt_rank + m.d_state].astype(jnp.float32)
    cmat = dbc[..., m.dt_rank + m.d_state:].astype(jnp.float32)
    a_diag = -jnp.exp(p["A_log"])

    def scan_local(dt, bmat, cmat, x_conv):
        bsz = dt.shape[0]
        xf = x_conv.astype(jnp.float32)
        # ONE local scan from h0=0; the cross-rank initial state enters as
        # a closed-form affine correction (h_t is affine in h0 and the
        # cumulative decay exp(A·cumsum(Δ)) needs no scan) — half the scan
        # traffic of the two-pass formulation.
        y0, h_fin, d_tot = _mamba1_scan_local(dt, bmat, cmat, xf, a_diag,
                                              jnp.zeros((bsz, m.d_inner,
                                                         m.d_state),
                                                        jnp.float32), m.seg)
        if n_ranks == 1:
            return y0.astype(x_conv.dtype), h_fin
        h_init, h_last = _cross_rank_state(d_tot, h_fin, SEQ_AXES, n_ranks)
        cum = jnp.cumsum(dt, axis=1)                      # (B,S,di)
        # corr_t[d] = sum_n C_t[n] · h0[d,n] · exp(A[d,n]·cumΔ_t[d])
        decay = jnp.exp(cum[..., None] * a_diag)          # (B,S,di,N)
        corr = jnp.einsum("bsdn,bdn,bsn->bsd", decay, h_init, cmat)
        return (y0 + corr).astype(x_conv.dtype), h_last

    y, h_last = jax.shard_map(
        scan_local, mesh=rt.mesh, in_specs=(spec,) * 4,
        out_specs=(spec, P(BATCH_AXES, None, None)),
        check_vma=False)(dt, bmat, cmat, x_conv)
    y = y + x_conv * p["D"].astype(x_conv.dtype)
    y = y * jax.nn.silu(z)
    out = linear_apply(p["out_proj"], y)
    if return_state:
        return out, {"h": h_last, "conv": x_in[:, -(m.d_conv - 1):]}
    return out


def mamba1_decode(p, x, state, m: Mamba1Dims):
    """Single-token step.  x: (B, 1, d_model).

    state: {"h": (B, di, N) f32, "conv": (B, d_conv-1, di)}.
    Returns (y (B,1,d_model), new_state).
    """
    xz = linear_apply(p["in_proj"], x)
    x_in, z = jnp.split(xz, 2, axis=-1)
    conv_buf = jnp.concatenate([state["conv"], x_in], axis=1)
    x_conv = jax.nn.silu(_causal_conv(conv_buf, p["conv_w"], p["conv_b"],
                                      x_in.dtype))
    dbc = linear_apply(p["x_proj"], x_conv)
    dt = jax.nn.softplus(
        linear_apply(p["dt_proj"], dbc[..., :m.dt_rank]).astype(jnp.float32))
    bmat = dbc[..., m.dt_rank:m.dt_rank + m.d_state].astype(jnp.float32)
    cmat = dbc[..., m.dt_rank + m.d_state:].astype(jnp.float32)
    a_diag = -jnp.exp(p["A_log"])
    a = jnp.exp(dt[:, 0, :, None] * a_diag)                 # (B,di,N)
    u = (dt[:, 0] * x_conv[:, 0].astype(jnp.float32))[..., None] \
        * bmat[:, 0, None, :]
    h = state["h"] * a + u
    y = jnp.einsum("bdn,bn->bd", h, cmat[:, 0])[:, None]
    y = y.astype(x.dtype) + x_conv * p["D"].astype(x.dtype)
    y = y * jax.nn.silu(z)
    return linear_apply(p["out_proj"], y), {"h": h,
                                            "conv": conv_buf[:, 1:]}


# ---------------------------------------------------------------------------
# Mamba2 (zamba2)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Mamba2Dims:
    d_model: int
    d_inner: int
    d_state: int = 64
    d_conv: int = 4
    head_dim: int = 64
    seg: int = 32

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.d_state


def init_mamba2(key, m: Mamba2Dims):
    ks = jax.random.split(key, 4)
    return {
        "in_proj": init_linear(
            ks[0], m.d_model,
            2 * m.d_inner + 2 * m.d_state + m.n_heads),
        "conv_w": 0.1 * jax.random.normal(ks[1], (m.d_conv, m.conv_dim)),
        "conv_b": jnp.zeros((m.conv_dim,), jnp.float32),
        "A_log": jnp.zeros((m.n_heads,), jnp.float32),
        "D": jnp.ones((m.n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((m.n_heads,), jnp.float32),
        "norm": init_rmsnorm(m.d_inner),
        "out_proj": init_linear(ks[2], m.d_inner, m.d_model),
    }


def _mamba2_scan_local(dt, bmat, cmat, x_h, a_head, h0, seg: int):
    """dt: (B,S,nh); bmat/cmat: (B,S,N); x_h: (B,S,nh,hd); a_head: (nh,).

    Returns y (B,S,nh,hd) f32, h_fin (B,nh,hd,N), d_tot (B,nh,1,1).
    """
    b, s, nh = dt.shape
    seg = min(seg, s)
    n_seg = s // seg

    def seg_fn(h, xs):
        d_s, b_s, c_s, x_s = xs
        a = jnp.exp(d_s * a_head)[..., None, None]          # (B,seg,nh,1,1)
        u = (d_s[..., None] * x_s)[..., None] \
            * b_s[:, :, None, None, :]                      # (B,seg,nh,hd,N)
        a_cum, u_cum = lax.associative_scan(_assoc_combine, (a, u), axis=1)
        h_t = a_cum * h[:, None] + u_cum
        y = jnp.einsum("bshdn,bsn->bshd", h_t, c_s)
        return h_t[:, -1], y

    xs = tuple(x.reshape(b, n_seg, seg, *x.shape[2:]).swapaxes(0, 1)
               for x in (dt, bmat, cmat, x_h))
    h_fin, ys = _segmented_scan(h0, seg_fn, xs, n_seg)
    y = ys.swapaxes(0, 1).reshape(b, s, *ys.shape[3:])
    d_tot = jnp.exp(jnp.sum(dt, axis=1) * a_head)[..., None, None]
    return y, h_fin, d_tot


def mamba2_apply(p, x, rt: Runtime, m: Mamba2Dims,
                 return_state: bool = False):
    """x: (B, S, d_model) seq-sharded -> same (+ final state for prefill)."""
    zxbcdt = linear_apply(p["in_proj"], x)
    z = zxbcdt[..., :m.d_inner]
    xbc_pre = zxbcdt[..., m.d_inner:m.d_inner + m.conv_dim]
    dt_raw = zxbcdt[..., m.d_inner + m.conv_dim:]

    n_ranks = rt.pc.sp
    spec3 = P(BATCH_AXES, SEQ_AXES, None)

    def conv_local(xbc):
        xp = _halo_exchange(xbc, m.d_conv - 1, SEQ_AXES, n_ranks)
        return jax.nn.silu(_causal_conv(xp, p["conv_w"], p["conv_b"],
                                        xbc.dtype))

    xbc = jax.shard_map(conv_local, mesh=rt.mesh, in_specs=(spec3,),
                        out_specs=spec3, check_vma=False)(xbc_pre)
    x_in = xbc[..., :m.d_inner]
    bmat = xbc[..., m.d_inner:m.d_inner + m.d_state].astype(jnp.float32)
    cmat = xbc[..., m.d_inner + m.d_state:].astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    a_head = -jnp.exp(p["A_log"])

    def scan_local(dt, bmat, cmat, x_in):
        bsz, s_loc, _ = x_in.shape
        x_h = x_in.reshape(bsz, s_loc, m.n_heads,
                           m.head_dim).astype(jnp.float32)
        y0, h_fin, d_tot = _mamba2_scan_local(
            dt, bmat, cmat, x_h, a_head,
            jnp.zeros((bsz, m.n_heads, m.head_dim, m.d_state),
                      jnp.float32), m.seg)
        if n_ranks == 1:
            return (y0.reshape(bsz, s_loc, m.d_inner).astype(x_in.dtype),
                    h_fin)
        h_init, h_last = _cross_rank_state(d_tot, h_fin, SEQ_AXES, n_ranks)
        # scalar-per-head decay => the correction is one small einsum
        decay = jnp.exp(jnp.cumsum(dt, axis=1) * a_head)  # (B,S,nh)
        corr = jnp.einsum("bsh,bhdn,bsn->bshd", decay, h_init, cmat)
        y = y0 + corr
        return (y.reshape(bsz, s_loc, m.d_inner).astype(x_in.dtype), h_last)

    y, h_last = jax.shard_map(
        scan_local, mesh=rt.mesh, in_specs=(spec3,) * 4,
        out_specs=(spec3, P(BATCH_AXES, None, None, None)),
        check_vma=False)(dt, bmat, cmat, x_in)
    d_rep = jnp.repeat(p["D"], m.head_dim).astype(x_in.dtype)
    y = y + x_in * d_rep
    y = rmsnorm_apply(p["norm"], y * jax.nn.silu(z))
    out = linear_apply(p["out_proj"], y)
    if return_state:
        # conv state: last (K-1) pre-activation conv inputs, global order
        return out, {"h": h_last, "conv": xbc_pre[:, -(m.d_conv - 1):]}
    return out


def mamba2_decode(p, x, state, m: Mamba2Dims):
    """Single-token step.  state: {"h": (B,nh,hd,N), "conv": (B,K-1,convd)}."""
    zxbcdt = linear_apply(p["in_proj"], x)
    z = zxbcdt[..., :m.d_inner]
    xbc = zxbcdt[..., m.d_inner:m.d_inner + m.conv_dim]
    dt_raw = zxbcdt[..., m.d_inner + m.conv_dim:]
    conv_buf = jnp.concatenate([state["conv"], xbc], axis=1)
    xbc = jax.nn.silu(_causal_conv(conv_buf, p["conv_w"], p["conv_b"],
                                   x.dtype))
    x_in = xbc[..., :m.d_inner]
    bmat = xbc[..., m.d_inner:m.d_inner + m.d_state].astype(jnp.float32)
    cmat = xbc[..., m.d_inner + m.d_state:].astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))[:, 0]  # (B,nh)
    a = jnp.exp(dt * -jnp.exp(p["A_log"]))[..., None, None]
    x_h = x_in[:, 0].reshape(x.shape[0], m.n_heads,
                             m.head_dim).astype(jnp.float32)
    u = (dt[..., None] * x_h)[..., None] * bmat[:, 0, None, None, :]
    h = state["h"] * a + u
    y = jnp.einsum("bhdn,bn->bhd", h, cmat[:, 0])
    y = y.reshape(x.shape[0], 1, m.d_inner).astype(x.dtype)
    y = y + x_in * jnp.repeat(p["D"], m.head_dim).astype(x.dtype)
    y = rmsnorm_apply(p["norm"], y * jax.nn.silu(z))
    return linear_apply(p["out_proj"], y), {"h": h, "conv": conv_buf[:, 1:]}
