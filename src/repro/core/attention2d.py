"""2D-Attention: head-parallel × context-parallel distributed attention.

The paper's core mechanism (LoongTrain §4), TPU-native:

* **SeqAlltoAll** (Ulysses): ``jax.lax.all_to_all`` over the ``head`` mesh
  axis redistributes Q/K/V from ``(S/d_sp sequence, all heads)`` to
  ``(S/d_cp sequence, H/d_hp heads)`` and back.
* **KV replication** (paper §4.2): when ``d_hp > H_kv`` the KV heads are
  replicated *before* the all-to-all; the replica-gradient aggregation of the
  backward pass falls out of JAX's transpose of ``jnp.repeat``.
* **Double-Ring-Attention** (paper §4.3, Algorithm 2): the context group is
  factored into ``outer × inner`` mesh axes.  KV chunks rotate with
  ``jax.lax.ppermute`` — inner ring every micro-step, outer ring once per
  outer step, issued *before* the inner loop so XLA's latency-hiding
  scheduler overlaps it with the whole inner round (the paper's prefetch).
  Two concurrent ppermutes on distinct mesh axes travel on distinct ICI
  torus dimensions — the TPU analogue of "use all NICs".
* **Zigzag causal load balance**: context rank ``i`` owns logical sequence
  chunks ``(i, 2·cp−1−i)`` (the data pipeline pre-permutes tokens, paper
  §4.4's loader post-processing).  Every ring step then computes exactly two
  C×C sub-blocks per rank:

      j < i : whole-Q × K_lo        (both full)
      j = i : causal diagonal       (two causal halves + one full)
      j > i : Q_hi × whole-K        (both full)

  so per-step FLOPs are balanced and ≈ useful FLOPs.  All three cases are
  *one* kernel call parameterized by the scalar pair ``(i, j)`` through a
  ``BandMask``: the kernel's logical-position masking plus block-skip
  reproduces the case split internally, so there is no ``lax.cond`` branch
  pair, no duplicated branch HLO, and no zero-padding/concatenate traffic
  around the half-chunk cases.
* The ring is one ``jax.custom_vjp`` unit: forward accumulates (out, lse)
  with the flash combine rule; backward re-runs the ring, accumulating dq
  locally while dk/dv ride around the rings *with* their KV chunk and arrive
  home after a full cycle.

Everything here is the *per-shard* program (runs under ``shard_map``);
``attention_2d`` is the global-array entry point.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.core.topology import (AXIS_HP, AXIS_INNER, AXIS_OUTER, BATCH_AXES,
                                 SEQ_AXES)
from repro.core.zigzag import from_zigzag, to_zigzag
from repro.kernels.ops import flash_attention, flash_bwd_chunk, flash_fwd_chunk
from repro.kernels.ref import BandMask, combine_pair
from repro.runtime import spans


class Attn2DConfig(NamedTuple):
    """Static 2D-Attention configuration (hashable)."""
    hp: int = 1
    n_out: int = 1            # outer ring size (d_cp / w)
    w: int = 1                # inner ring size (paper's w)
    causal: bool = True
    zigzag: bool = True       # False: contiguous chunks (hybrid/SSM models)
    window: int | None = None
    softcap: float = 0.0
    scale: float | None = None
    impl: str = "auto"
    axis_hp: str = AXIS_HP
    axis_outer: str = AXIS_OUTER
    axis_inner: str = AXIS_INNER

    @property
    def cp(self) -> int:
        return self.n_out * self.w


def attn2d_config(pc, *, impl: str, causal: bool = True,
                  zigzag: bool = True, window: int | None = None,
                  softcap: float = 0.0,
                  scale: float | None = None) -> Attn2DConfig:
    """The one place a ``ParallelConfig`` becomes an ``Attn2DConfig``
    (used by ``core/plan.py`` and the model attention blocks)."""
    return Attn2DConfig(hp=pc.hp, n_out=pc.cp_outer, w=pc.cp_inner,
                        causal=causal, zigzag=zigzag, window=window,
                        softcap=softcap, scale=scale, impl=impl)


class RingConfig(NamedTuple):
    """Static ring configuration (the custom_vjp nondiff arg)."""
    n_out: int
    w: int
    causal: bool
    zigzag: bool
    window: int | None
    softcap: float
    scale: float
    impl: str
    axis_outer: str
    axis_inner: str

    @property
    def cp(self) -> int:
        return self.n_out * self.w


def _shift(x, axis: str, size: int):
    """Ring ppermute: every rank sends to (r+1) % size, receives from r-1."""
    if size == 1:
        return x
    return lax.ppermute(x, axis, [(r, (r + 1) % size) for r in range(size)])


def _ring_indices(cfg: RingConfig):
    i_out = lax.axis_index(cfg.axis_outer)
    i_in = lax.axis_index(cfg.axis_inner)
    return i_out, i_in, i_out * cfg.w + i_in


def _visiting(cfg: RingConfig, i_out, i_in, o: int, t: int):
    """Global cp index of the KV chunk visiting this rank at step (o, t)."""
    j_out = (i_out - o) % cfg.n_out
    j_in = (i_in - t) % cfg.w
    return j_out * cfg.w + j_in


def _kw(cfg: RingConfig):
    return dict(softcap=cfg.softcap, scale=cfg.scale, impl=cfg.impl)


# ---------------------------------------------------------------------------
# Ring forward
# ---------------------------------------------------------------------------

def _step_band(cfg: RingConfig, i, j, s_loc: int, qb=0, kb=0) -> BandMask:
    """The (i, j) ring-step mask as a BandMask over the full local shapes.

    ``i``/``j`` are traced rank indices; the offsets land in the kernels as
    scalar-prefetch operands, so the case split (j<i full, j=i diagonal,
    j>i empty/half) happens inside one kernel call via logical-position
    masking + block skip — no ``lax.cond`` branch pair.

    ``qb``/``kb`` are global sequence-chunk bases (the FPDT chunk pipeline
    runs this same ring once per chunk pair; each side's logical positions
    shift by its chunk start).  The resident path passes 0/0.
    """
    if cfg.zigzag:
        band = BandMask.zigzag(i, j, s_loc // 2, cfg.cp)
    else:
        # Contiguous chunks (no causal load balance): chunk r = cp rank r.
        # Used by hybrid/SSM models whose recurrent layers need contiguous
        # sequence shards; the paper's balanced layout needs the zigzag data
        # permutation which those layers cannot tolerate.  Absolute offsets
        # on both sides (not the relative ``(i-j)·s_loc`` single-sided form)
        # keep packed-document doc-start comparisons — global positions —
        # correct; causal/window masking only sees the difference, which is
        # unchanged.
        band = BandMask(i * s_loc, i * s_loc, j * s_loc, j * s_loc, 0, 0)
    if isinstance(qb, int) and isinstance(kb, int) and qb == 0 and kb == 0:
        return band           # resident path: skip the no-op adds
    return band._replace(q_off_lo=band.q_off_lo + qb,
                         q_off_hi=band.q_off_hi + qb,
                         k_off_lo=band.k_off_lo + kb,
                         k_off_hi=band.k_off_hi + kb)


def _step_fwd(q, kc, vc, doc, o: int, t: int, i_out, i_in, i,
              cfg: RingConfig, qb=0, kb=0):
    """Partial (out, lse) of local q against the visiting KV chunk pair.

    ``doc`` (packed documents) is the *local* per-row doc-start table: it
    is q-side data, so it stays put while KV rotates — the band supplies
    the visiting chunk's logical positions, and the kernel compares them
    against the stationary doc starts.  No per-step translation needed.
    """
    kw = _kw(cfg)
    if not cfg.causal:
        return flash_fwd_chunk(q, kc, vc, causal=False, **kw)
    j = _visiting(cfg, i_out, i_in, o, t)
    return flash_fwd_chunk(q, kc, vc, causal=True, window=cfg.window,
                           band=_step_band(cfg, i, j, q.shape[1], qb, kb),
                           q_doc_start=doc, **kw)


@jax.named_scope(spans.RING)
def _ring_fwd(q, k, v, doc, cfg: RingConfig, qb=0, kb=0):
    i_out, i_in, i = _ring_indices(cfg)
    acc_o = None
    acc_l = None
    k0, v0 = k, v
    for o in range(cfg.n_out):
        nxt_outer = None
        if o < cfg.n_out - 1:
            # Outer prefetch (Alg. 2 line 3): issued before the inner loop so
            # it overlaps the whole inner round.
            nxt_outer = (_shift(k0, cfg.axis_outer, cfg.n_out),
                         _shift(v0, cfg.axis_outer, cfg.n_out))
        kc, vc = k0, v0
        for t in range(cfg.w):
            nxt_inner = None
            if t < cfg.w - 1:
                nxt_inner = (_shift(kc, cfg.axis_inner, cfg.w),
                             _shift(vc, cfg.axis_inner, cfg.w))
            po, pl_ = _step_fwd(q, kc, vc, doc, o, t, i_out, i_in, i, cfg,
                                qb, kb)
            if acc_o is None:
                acc_o, acc_l = po.astype(jnp.float32), pl_
            else:
                acc_o, acc_l = combine_pair(acc_o, acc_l, po, pl_)
            if nxt_inner is not None:
                kc, vc = nxt_inner
        if nxt_outer is not None:
            k0, v0 = nxt_outer
    return acc_o.astype(q.dtype), acc_l


# ---------------------------------------------------------------------------
# Ring backward
# ---------------------------------------------------------------------------

def _step_bwd(q, kc, vc, out, lse, do, doc, o: int, t: int, i_out, i_in, i,
              cfg: RingConfig, qb=0, kb=0):
    """(dq_part, dk_part, dv_part) for the KV chunk visiting at (o, t).

    ``out``/``lse`` are the final combined values (global softmax), so each
    step's contribution is exact and linear.
    """
    kw = _kw(cfg)
    if not cfg.causal:
        return flash_bwd_chunk(q, kc, vc, out, lse, do, causal=False, **kw)
    j = _visiting(cfg, i_out, i_in, o, t)
    return flash_bwd_chunk(q, kc, vc, out, lse, do, causal=True,
                           window=cfg.window,
                           band=_step_band(cfg, i, j, q.shape[1], qb, kb),
                           q_doc_start=doc, **kw)


@jax.named_scope(spans.RING)
def _ring_bwd(q, k, v, out, lse, do, doc, cfg: RingConfig, qb=0, kb=0):
    i_out, i_in, i = _ring_indices(cfg)
    dq = jnp.zeros(q.shape, jnp.float32)
    k0, v0 = k, v
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    for o in range(cfg.n_out):
        kc, vc, dkc, dvc = k0, v0, dk0, dv0
        for t in range(cfg.w):
            dq_p, dk_p, dv_p = _step_bwd(q, kc, vc, out, lse, do, doc, o, t,
                                         i_out, i_in, i, cfg, qb, kb)
            dq = dq + dq_p.astype(jnp.float32)
            dkc = dkc + dk_p.astype(jnp.float32)
            dvc = dvc + dv_p.astype(jnp.float32)
            # dk/dv ride the inner ring with their chunk; the last rotation
            # completes the inner cycle so the chunk grads are home (within
            # this outer round) before the outer hop.
            last = (t == cfg.w - 1) and (o == cfg.n_out - 1)
            if not last:
                kc = _shift(kc, cfg.axis_inner, cfg.w)
                vc = _shift(vc, cfg.axis_inner, cfg.w)
            dkc = _shift(dkc, cfg.axis_inner, cfg.w)
            dvc = _shift(dvc, cfg.axis_inner, cfg.w)
        # Outer hop: the visiting set (with its accumulated grads) moves on;
        # after n_out hops every chunk's grads are back at their owner.
        if o < cfg.n_out - 1:
            k0 = _shift(kc, cfg.axis_outer, cfg.n_out)
            v0 = _shift(vc, cfg.axis_outer, cfg.n_out)
        dk0 = _shift(dkc, cfg.axis_outer, cfg.n_out)
        dv0 = _shift(dvc, cfg.axis_outer, cfg.n_out)
    return dq.astype(q.dtype), dk0.astype(k.dtype), dv0.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def ring_attention(q, k, v, doc, cfg: RingConfig):
    """Double-ring zigzag attention over the local (post-AlltoAll) shards.

    q: (b, S/cp, Hq/hp, d);  k/v: (b, S/cp, Hkv_eff/hp, d);
    doc: None, or the local (b, S/cp) int32 per-row doc-start table
    (packed documents — integer data, zero cotangent).
    """
    out, _ = _ring_fwd(q, k, v, doc, cfg)
    return out


def _ring_vjp_fwd(q, k, v, doc, cfg: RingConfig):
    # Named for Selective Checkpoint++ (models/model.py::remat_policy): the
    # backward reads these, so saving them keeps the ring forward out of
    # the recompute.  ``lse`` (b, h, s) is already lane-dense.
    out, lse = _ring_fwd(q, k, v, doc, cfg)
    out = checkpoint_name(out, spans.ATTN_OUT)
    lse = checkpoint_name(lse, spans.ATTN_LSE)
    return out, (q, k, v, doc, out, lse)


def _ring_vjp_bwd(cfg: RingConfig, res, do):
    q, k, v, doc, out, lse = res
    dq, dk, dv = _ring_bwd(q, k, v, out, lse, do, doc, cfg)
    d_doc = None if doc is None else np.zeros(doc.shape, jax.dtypes.float0)
    return dq, dk, dv, d_doc


ring_attention.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


# ---------------------------------------------------------------------------
# SeqAlltoAll + public API
# ---------------------------------------------------------------------------

def attention_2d_local(q, k, v, cfg: Attn2DConfig, doc_start=None):
    """Per-shard 2D-Attention (call under shard_map).

    q: (b, S/d_sp, Hq, d);  k/v: (b, S/d_sp, Hkv, d).  Returns q-shaped out.

    ``doc_start``: local (b, S/d_sp) int32 per-row doc-start table for
    packed documents.  The SeqAlltoAll redistributes *heads*, so the
    boundary table has nothing to split — it is all-gathered over the
    head axis along the sequence dim (int32/token: ~0.25% of one tensor's
    a2a bytes), after which every cp rank holds the table for exactly the
    S/d_cp rows its post-AlltoAll q holds.
    """
    b, s_loc, hq, dh = q.shape
    hkv = k.shape[2]
    scale = cfg.scale if cfg.scale is not None else 1.0 / (dh ** 0.5)
    if doc_start is not None:
        assert cfg.causal, "packed documents require causal attention"

    if cfg.hp > hkv:
        # Paper §4.2: replicate KV heads to d_hp before the SeqAlltoAll.
        assert cfg.hp % hkv == 0, (cfg.hp, hkv)
        rep = cfg.hp // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    if cfg.hp > 1:
        assert hq % cfg.hp == 0, (hq, cfg.hp)
        with jax.named_scope(spans.ULYSSES_A2A):
            q = lax.all_to_all(q, cfg.axis_hp, 2, 1, tiled=True)
            k = lax.all_to_all(k, cfg.axis_hp, 2, 1, tiled=True)
            v = lax.all_to_all(v, cfg.axis_hp, 2, 1, tiled=True)
            if doc_start is not None:
                doc_start = lax.all_gather(doc_start, cfg.axis_hp, axis=1,
                                           tiled=True)

    if cfg.cp == 1:
        out = flash_attention(q, k, v, causal=cfg.causal, window=cfg.window,
                              softcap=cfg.softcap, scale=scale,
                              q_doc_start=doc_start, impl=cfg.impl)
    else:
        rcfg = RingConfig(n_out=cfg.n_out, w=cfg.w, causal=cfg.causal,
                          zigzag=cfg.zigzag and cfg.causal,
                          window=cfg.window, softcap=cfg.softcap,
                          scale=scale, impl=cfg.impl,
                          axis_outer=cfg.axis_outer,
                          axis_inner=cfg.axis_inner)
        out = ring_attention(q, k, v, doc_start, rcfg)

    if cfg.hp > 1:
        with jax.named_scope(spans.ULYSSES_A2A):
            out = lax.all_to_all(out, cfg.axis_hp, 1, 2, tiled=True)
    return out


def attention_2d(q, k, v, *, mesh, cfg: Attn2DConfig, doc_start=None):
    """Global-array 2D-Attention: q (B, S, Hq, d), k/v (B, S, Hkv, d).

    B is sharded over the batch axes, S over the sp axes (the zigzag
    data-layout contract — see data/pipeline.py).  ``doc_start``
    (optional, (B, S) int32): per-token logical document starts in the
    same physical layout as q — packed-document block-causal masking.
    """
    spec = P(BATCH_AXES, SEQ_AXES, None, None)
    if doc_start is None:
        f = jax.shard_map(functools.partial(attention_2d_local, cfg=cfg),
                          mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec, check_vma=False)
        return f(q, k, v)
    spec_d = P(BATCH_AXES, SEQ_AXES)
    f = jax.shard_map(
        lambda q, k, v, d: attention_2d_local(q, k, v, cfg, doc_start=d),
        mesh=mesh, in_specs=(spec, spec, spec, spec_d), out_specs=spec,
        check_vma=False)
    return f(q, k, v, jnp.asarray(doc_start, jnp.int32))


# ---------------------------------------------------------------------------
# Sequence-chunk pipelining with host KV offload (FPDT, arxiv 2408.16978)
# ---------------------------------------------------------------------------
#
# The resident path above holds the entire local sequence in HBM, so max
# trainable context is capped by device memory regardless of mesh size.
# The chunked path splits the *global* sequence into C chunks, keeps only
# the active (and prefetched) chunks in HBM via an OffloadManager, and
# runs the same double-ring/Ulysses machinery once per causal chunk pair
# (i, j<=i).  The pair kernels are the resident ones: the only change is
# that each side's BandMask logical positions shift by its chunk base
# (qb = i·Sc, kb = j·Sc), so zigzag, packed-document doc starts (global
# positions — boundaries straddling chunk edges included), GQA folding
# and block skip all fall out unchanged.  Per-pair FLOPs match the causal
# half at chunk granularity: pair j<i is all-visible, j=i is the ordinary
# zigzag diagonal.
#
# Host staging is opaque to jax.grad (tracers cannot cross np.asarray), so
# the driver is an explicit forward + manual vjp: a host Python loop over
# two jitted shard_map programs (one forward pair, one backward pair),
# qb/kb passed as traced int32 scalars so a single compile serves every
# pair.  Forward accumulates (out, lse) partials with the flash combine
# rule; backward accumulates dq on device and sends dk/dv home to host
# fp32 accumulators chunk by chunk.

def _chunk_ring_cfg(cfg: Attn2DConfig, dh: int) -> RingConfig:
    scale = cfg.scale if cfg.scale is not None else 1.0 / (dh ** 0.5)
    return RingConfig(n_out=cfg.n_out, w=cfg.w, causal=True,
                      zigzag=cfg.zigzag, window=None, softcap=cfg.softcap,
                      scale=scale, impl=cfg.impl, axis_outer=cfg.axis_outer,
                      axis_inner=cfg.axis_inner)


def _chunk_pair_fwd_local(q, k, v, doc, qb, kb, cfg: Attn2DConfig):
    """Per-shard (out, lse) of q-chunk (base qb) against kv-chunk (kb)."""
    dh = q.shape[-1]
    hkv = k.shape[2]
    rcfg = _chunk_ring_cfg(cfg, dh)
    if cfg.hp > hkv:
        rep = cfg.hp // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if cfg.hp > 1:
        with jax.named_scope(spans.ULYSSES_A2A):
            q = lax.all_to_all(q, cfg.axis_hp, 2, 1, tiled=True)
            k = lax.all_to_all(k, cfg.axis_hp, 2, 1, tiled=True)
            v = lax.all_to_all(v, cfg.axis_hp, 2, 1, tiled=True)
            if doc is not None:
                doc = lax.all_gather(doc, cfg.axis_hp, axis=1, tiled=True)
    out, lse = _ring_fwd(q, k, v, doc, rcfg, qb, kb)
    if cfg.hp > 1:
        with jax.named_scope(spans.ULYSSES_A2A):
            out = lax.all_to_all(out, cfg.axis_hp, 1, 2, tiled=True)
            lse = lax.all_to_all(lse, cfg.axis_hp, 2, 1, tiled=True)
    return out, lse


def _chunk_pair_bwd_local(q, k, v, out, lse, do, doc, qb, kb,
                          cfg: Attn2DConfig):
    """Per-shard (dq, dk, dv) contribution of one (q-chunk, kv-chunk) pair.

    ``out``/``lse`` are the chunk's *final* combined values, so every
    pair's contribution is exact and linear (same argument as the ring
    backward's per-step decomposition).
    """
    dh = q.shape[-1]
    hkv = k.shape[2]
    rcfg = _chunk_ring_cfg(cfg, dh)
    rep = cfg.hp // hkv if cfg.hp > hkv else 1
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if cfg.hp > 1:
        with jax.named_scope(spans.ULYSSES_A2A):
            q, k, v, out, do = (lax.all_to_all(x, cfg.axis_hp, 2, 1,
                                               tiled=True)
                                for x in (q, k, v, out, do))
            lse = lax.all_to_all(lse, cfg.axis_hp, 1, 2, tiled=True)
            if doc is not None:
                doc = lax.all_gather(doc, cfg.axis_hp, axis=1, tiled=True)
    dq, dk, dv = _ring_bwd(q, k, v, out, lse, do, doc, rcfg, qb, kb)
    if cfg.hp > 1:
        with jax.named_scope(spans.ULYSSES_A2A):
            dq, dk, dv = (lax.all_to_all(x, cfg.axis_hp, 1, 2, tiled=True)
                          for x in (dq, dk, dv))
    if rep > 1:
        bb, ss, _, dd = dk.shape
        # jnp.repeat is consecutive, so replica grads group-sum by reshape.
        dk = dk.reshape(bb, ss, hkv, rep, dd).sum(3)
        dv = dv.reshape(bb, ss, hkv, rep, dd).sum(3)
    return dq, dk, dv


@functools.lru_cache(maxsize=32)
def _chunk_pair_fns(mesh, cfg: Attn2DConfig, has_doc: bool):
    """(fwd, bwd) jitted global-array pair programs for (mesh, cfg).

    One compile serves all (i, j) pairs: the chunk bases ride in as traced
    int32 scalars (they land in the kernels as scalar-prefetch operands,
    exactly like the ring's rank indices)."""
    spec = P(BATCH_AXES, SEQ_AXES, None, None)
    spec_l = P(BATCH_AXES, None, SEQ_AXES)
    spec_d = P(BATCH_AXES, SEQ_AXES)
    sc = P()
    if has_doc:
        fwd = jax.shard_map(
            lambda q, k, v, d, qb, kb:
                _chunk_pair_fwd_local(q, k, v, d, qb, kb, cfg),
            mesh=mesh, in_specs=(spec, spec, spec, spec_d, sc, sc),
            out_specs=(spec, spec_l), check_vma=False)
        bwd = jax.shard_map(
            lambda q, k, v, o, l, g, d, qb, kb:
                _chunk_pair_bwd_local(q, k, v, o, l, g, d, qb, kb, cfg),
            mesh=mesh,
            in_specs=(spec, spec, spec, spec, spec_l, spec, spec_d, sc, sc),
            out_specs=(spec, spec, spec), check_vma=False)
    else:
        fwd = jax.shard_map(
            lambda q, k, v, qb, kb:
                _chunk_pair_fwd_local(q, k, v, None, qb, kb, cfg),
            mesh=mesh, in_specs=(spec, spec, spec, sc, sc),
            out_specs=(spec, spec_l), check_vma=False)
        bwd = jax.shard_map(
            lambda q, k, v, o, l, g, qb, kb:
                _chunk_pair_bwd_local(q, k, v, o, l, g, None, qb, kb, cfg),
            mesh=mesh, in_specs=(spec, spec, spec, spec, spec_l, spec, sc, sc),
            out_specs=(spec, spec, spec), check_vma=False)
    return jax.jit(fwd), jax.jit(bwd)


@jax.jit
def _combine_chunks(oa, la, ob, lb):
    return combine_pair(oa, la, ob, lb)


@jax.jit
def _acc(a, b):
    return a + b


class ChunkedAttention:
    """FPDT-style sequence-chunk pipelined 2D-Attention with KV offload.

    Inputs and outputs are in *logical* token order over the full
    sequence; the per-chunk zigzag layout is applied internally (each
    chunk is independently balanced over the cp ranks, so the resident
    ring kernels apply per pair unchanged).  Causal, full-context only
    (``window`` needs no offload — its KV footprint is already bounded).

    The manager's HBM budget covers staged chunk residency; with the
    double-buffer schedule the peak is the active pair plus the
    prefetched next K/V (≈ q + 2·(k+v) chunk shards on the forward,
    plus out/lse/do on the backward).

    Usage::

        ca = ChunkedAttention(mesh, cfg, chunks=8)
        out = ca.forward(q, k, v)          # logical order
        dq, dk, dv = ca.vjp(d_out)         # manual vjp (host loop is
                                           # opaque to jax.grad)
    """

    def __init__(self, mesh, cfg: Attn2DConfig, *, chunks: int,
                 offload=None):
        assert cfg.causal, "chunk pipelining is causal-only"
        assert cfg.window is None, \
            "sliding-window KV is already bounded; no offload needed"
        assert chunks >= 1, chunks
        if offload is None:
            from repro.runtime.offload import OffloadManager
            offload = OffloadManager()
        self.mesh, self.cfg, self.chunks = mesh, cfg, chunks
        self.mgr = offload
        self._docs = None
        self._sc = None
        self._dtypes = None

    # -- layout helpers ----------------------------------------------------

    def _lay(self, x):
        return to_zigzag(x, self.cfg.cp) if self.cfg.zigzag else x

    def _unlay(self, x):
        return from_zigzag(x, self.cfg.cp) if self.cfg.zigzag else x

    def _stage(self, name: str, x, sc: int):
        """Slice ``x`` into chunks, per-chunk zigzag, snapshot to host."""
        for i in range(self.chunks):
            self.mgr.put((name, i), self._lay(x[:, i * sc:(i + 1) * sc]))

    # -- forward -----------------------------------------------------------

    def forward(self, q, k, v, doc_start=None):
        C, cp = self.chunks, self.cfg.cp
        S = q.shape[1]
        assert S % C == 0, (S, C)
        sc = S // C
        if self.cfg.zigzag and cp > 1:
            assert sc % (2 * cp) == 0, \
                f"chunk len {sc} must split into 2·cp={2 * cp} zigzag " \
                f"sub-chunks"
        self._sc = sc
        self._dtypes = (q.dtype, k.dtype, v.dtype)
        fwd, _ = _chunk_pair_fns(self.mesh, self.cfg, doc_start is not None)
        for name, x in (("q", q), ("k", k), ("v", v)):
            self._stage(name, x, sc)
        self._docs = None
        if doc_start is not None:
            d = jnp.asarray(doc_start, jnp.int32)
            self._docs = [self._lay(d[:, i * sc:(i + 1) * sc])
                          for i in range(C)]
        mgr, outs = self.mgr, []
        for i in range(C):
            mgr.prefetch(("q", i))
            qi = mgr.get(("q", i))
            di = () if self._docs is None else (self._docs[i],)
            mgr.prefetch(("k", 0))
            mgr.prefetch(("v", 0))
            acc_o = acc_l = None
            for j in range(i + 1):
                if j < i:   # double buffer: next fetch overlaps this pair
                    mgr.prefetch(("k", j + 1))
                    mgr.prefetch(("v", j + 1))
                kj, vj = mgr.get(("k", j)), mgr.get(("v", j))
                po, pl_ = fwd(qi, kj, vj, *di,
                              jnp.asarray(i * sc, jnp.int32),
                              jnp.asarray(j * sc, jnp.int32))
                if acc_o is None:
                    acc_o, acc_l = po.astype(jnp.float32), pl_
                else:
                    acc_o, acc_l = _combine_chunks(acc_o, acc_l, po, pl_)
                mgr.release(("k", j))
                mgr.release(("v", j))
            out_i = acc_o.astype(q.dtype)
            mgr.put(("o", i), out_i)       # saved residuals for the vjp
            mgr.put(("l", i), acc_l)
            mgr.release(("q", i))
            outs.append(self._unlay(out_i))
        return jnp.concatenate(outs, axis=1)

    # -- backward ----------------------------------------------------------

    def vjp(self, do):
        """(dq, dk, dv) in logical order given the output cotangent."""
        assert self._sc is not None, "forward() first"
        C, sc = self.chunks, self._sc
        qdt, kdt, vdt = self._dtypes
        _, bwd = _chunk_pair_fns(self.mesh, self.cfg, self._docs is not None)
        mgr = self.mgr
        self._stage("g", do, sc)
        dqs = []
        for i in range(C):
            for key in (("q", i), ("g", i), ("o", i), ("l", i)):
                mgr.prefetch(key)
            qi, gi = mgr.get(("q", i)), mgr.get(("g", i))
            oi, li = mgr.get(("o", i)), mgr.get(("l", i))
            di = () if self._docs is None else (self._docs[i],)
            mgr.prefetch(("k", 0))
            mgr.prefetch(("v", 0))
            dq_i = None
            for j in range(i + 1):
                if j < i:
                    mgr.prefetch(("k", j + 1))
                    mgr.prefetch(("v", j + 1))
                kj, vj = mgr.get(("k", j)), mgr.get(("v", j))
                dq_p, dk_p, dv_p = bwd(qi, kj, vj, oi, li, gi, *di,
                                       jnp.asarray(i * sc, jnp.int32),
                                       jnp.asarray(j * sc, jnp.int32))
                dq_i = dq_p if dq_i is None else _acc(dq_i, dq_p)
                # dk/dv come home chunk by chunk: host fp32 accumulation.
                mgr.accumulate(("dk", j), dk_p)
                mgr.accumulate(("dv", j), dv_p)
                mgr.release(("k", j))
                mgr.release(("v", j))
            dqs.append(self._unlay(dq_i))
            for key in (("q", i), ("g", i), ("o", i), ("l", i)):
                mgr.release(key)
        dq = jnp.concatenate(dqs, axis=1).astype(qdt)
        dk = jnp.concatenate(
            [self._unlay(jnp.asarray(mgr.host_array(("dk", j))))
             for j in range(C)], axis=1).astype(kdt)
        dv = jnp.concatenate(
            [self._unlay(jnp.asarray(mgr.host_array(("dv", j))))
             for j in range(C)], axis=1).astype(vdt)
        return dq, dk, dv


def chunked_attention_2d(q, k, v, *, mesh, cfg: Attn2DConfig, chunks: int,
                         doc_start=None, offload=None):
    """Forward + manual-vjp entry point for the chunk pipeline.

    Returns ``(out, vjp_fn)`` with ``vjp_fn(d_out) -> (dq, dk, dv)``; all
    arrays in logical token order.  ``offload`` (an ``OffloadManager``)
    carries the residency budget and telemetry; a fresh unbounded manager
    is used when omitted.
    """
    ca = ChunkedAttention(mesh, cfg, chunks=chunks, offload=offload)
    out = ca.forward(q, k, v, doc_start=doc_start)
    return out, ca.vjp
