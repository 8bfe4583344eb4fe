"""Public attention ops: impl dispatch, layout/padding plumbing.

Three entry points:

* ``flash_attention``          — differentiable single-call attention
                                 (custom_vjp Pallas path or jnp ref path).
* ``flash_fwd_chunk``          — non-differentiable (out, lse) for one KV
                                 chunk; the ring-attention building block.
* ``flash_bwd_chunk``          — chunk backward given global (out, lse).

Layout everywhere: ``q (B, Lq, Hq, D)``, ``k/v (B, Lk, Hkv, D)``.

Impl dispatch
-------------
``impl`` picks the compute path; ``resolve_impl`` maps ``"auto"`` to the
backend default:

================== =========================================================
``impl``           what runs
================== =========================================================
``"auto"``         ``"pallas"`` on TPU; ``"flashref"`` elsewhere (CPU
                   dry-run/compile keeps attention as plain einsums XLA
                   can cost).
``"pallas"``       compiled Pallas kernel (TPU).  Traced ``mask_offset`` /
                   ``band`` values ride in as scalar-prefetch operands, so
                   **every Double-Ring step stays on the fused kernel** —
                   there is no downgrade for dynamic offsets.
``"pallas_interpret"`` same kernels, interpreted on CPU (tests/benches).
``"flashref"``     q-chunked pure-jnp oracle (flash memory semantics).
``"ref"``          dense pure-jnp oracle.
================== =========================================================

Masking
-------
``mask_offset`` (scalar, possibly traced) sets the bottom-right band
``kj <= qi + mask_offset``; ``band`` (a ``ref.BandMask``) generalizes it to
the segmented zigzag layout, letting one kernel call cover any ring-step
pair (diagonal, j<i, j>i).  Both are honored identically by every impl.

Tiling
------
``block_q`` / ``block_k`` left at ``None`` take the tiles
``choose_blocks`` picks from the lengths (docs/KERNELS.md, "Tiling");
sizes a caller passes are used as given.

GQA
---
The Pallas forward and dq kernels fold the head group into the K/V index
maps; the dk/dv kernel folds it into its sequential grid dimension and
group-sums in VMEM scratch.  No path materializes ``group×``-expanded K/V
or gradients.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.kernels import ref as ref_mod
from repro.kernels.flash_attention import (FlashParams, _bwd,
                                           _flash_folded, _flash_folded_doc,
                                           _fwd, _round_up)
from repro.kernels.ref import BandMask
from repro.runtime import spans

NEG_INF = ref_mod.NEG_INF

#: doc-start sentinel for padded q rows: larger than any logical position,
#: so padding rows see no keys (their outputs are dropped by _unfold).
DOC_PAD = 1 << 30


def resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "flashref"
    return impl


#: Cap of the tiles the flash grid takes when the caller passes none, on
#: both axes (on-chip sweep at B 1, 16 heads, D 128, seq 32768:
#: docs/KERNELS.md, "Tiling").
BLOCK_CAP = 1024


def choose_blocks(lq: int, lk: int, block_q: int | None = None,
                  block_k: int | None = None) -> tuple[int, int]:
    """``(block_q, block_k)`` of the flash grid for lengths ``lq``, ``lk``.

    An explicit size is honoured, cut to the length padded to 8.  Without
    one, a length under 128 is one tile of ``round_up(L, 8)``; a longer
    one takes the largest multiple of 128, up to ``BLOCK_CAP``, that
    divides ``round_up(L, 128)``: it pads no more than 128-tiles would,
    and the grid has up to ``(BLOCK_CAP / 128)**2`` times fewer steps.
    """
    def one(length: int, block: int | None) -> int:
        if block is not None:
            return min(block, _round_up(length, 8))
        if length < 128:
            return _round_up(length, 8)
        padded = _round_up(length, 128)
        return max(t for t in range(128, BLOCK_CAP + 1, 128)
                   if padded % t == 0)

    return one(lq, block_q), one(lk, block_k)


def _fold_pad(x, block_l: int, d_pad: int):
    """(B, L, H, D) -> (B*H, L_pad, D_pad)."""
    b, l, h, d = x.shape
    x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, l, d)
    l_pad = _round_up(l, block_l)
    if l_pad != l or d_pad != d:
        x = jnp.pad(x, ((0, 0), (0, l_pad - l), (0, d_pad - d)))
    return x


def _unfold(x, b: int, h: int, l: int, d: int):
    """(B*H, L_pad, D_pad) -> (B, L, H, D)."""
    x = x[:, :l, :d].reshape(b, h, l, d)
    return jnp.transpose(x, (0, 2, 1, 3))


def _make_params(q, k, *, causal, window, softcap, scale, kv_valid_len,
                 block_q, block_k, interpret, q_seg=0, k_seg=0,
                 packed=False, doc_skip=True):
    _, lq, _, d = q.shape
    _, lk, _, _ = k.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bq, bk = choose_blocks(lq, lk, block_q, block_k)
    lk_valid = lk if kv_valid_len is None else kv_valid_len
    return FlashParams(causal=causal, window=window, softcap=float(softcap),
                       scale=float(scale), lq_valid=int(lq),
                       lk_valid=int(lk_valid),
                       block_q=bq, block_k=bk, interpret=interpret,
                       q_seg=int(q_seg), k_seg=int(k_seg),
                       delta=int(lk - lq), packed=bool(packed),
                       doc_skip=bool(doc_skip)), bq, bk


def _pad_doc(q_doc_start, lq: int, block_q: int):
    """(B, Lq) int32 doc-start table -> the kernels' (B, Lq_pad, 1), q rows
    padded with ``DOC_PAD`` (the padded rows attend nothing; their outputs
    are dropped)."""
    doc = jnp.asarray(q_doc_start, jnp.int32)
    assert doc.ndim == 2 and doc.shape[1] == lq, (doc.shape, lq)
    lq_pad = _round_up(lq, block_q)
    if lq_pad != lq:
        doc = jnp.pad(doc, ((0, 0), (0, lq_pad - lq)),
                      constant_values=DOC_PAD)
    return doc[:, :, None]


def _band_scalars(band, mask_offset, lq: int, lk: int, kv_valid_len,
                  *, causal, window):
    """(int32 (5,) scalar-prefetch vector, q_seg, k_seg).

    Offsets are in *unpadded* physical coordinates — padding appends rows,
    so real rows keep their indices; padded keys are cut by ``kv_valid``.
    """
    if band is not None and not causal and window is None:
        raise ValueError("band only shifts the causal/window band anchors; "
                         "passing one with causal=False and window=None "
                         "would be silently ignored")
    if band is None:
        off = (lk - lq) if mask_offset is None else mask_offset
        band = BandMask.uniform(off)
    kv_valid = lk if kv_valid_len is None else kv_valid_len
    scalars = jnp.stack([jnp.asarray(x, jnp.int32) for x in
                         (band.q_off_lo, band.q_off_hi,
                          band.k_off_lo, band.k_off_hi, kv_valid)])
    return scalars, band.q_seg, band.k_seg


def flash_attention(q, k, v, *, causal: bool = False,
                    window: int | None = None, softcap: float = 0.0,
                    scale: float | None = None,
                    kv_valid_len: int | None = None,
                    q_doc_start=None, doc_skip: bool = True,
                    impl: str = "auto",
                    block_q: int | None = None,
                    block_k: int | None = None):
    """Differentiable attention.  Returns out (B, Lq, Hq, D).

    ``q_doc_start``: packed-document block-causal masking — a (B, Lq)
    int32 table of each q row's logical document start (see ref.py).
    Requires ``causal=True``; on the Pallas path, K blocks entirely below
    a q block's doc start are *skipped* (``doc_skip=False`` keeps the
    element-wise mask but disables the skip — the dense-masked baseline
    the packing bench measures against).
    """
    impl = resolve_impl(impl)
    if q_doc_start is not None and not causal:
        raise ValueError("q_doc_start requires causal=True")
    # The jnp paths' output carries the name the Pallas path's vjp gives
    # its saved ``out``, so Selective Checkpoint++ saves it on every impl.
    if impl == "flashref":
        out, _ = ref_mod.attention_ref_chunked(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, kv_valid_len=kv_valid_len,
            q_doc_start=q_doc_start)
        return checkpoint_name(out, spans.ATTN_OUT)
    if impl == "ref":
        out, _ = ref_mod.attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, kv_valid_len=kv_valid_len,
            q_doc_start=q_doc_start)
        return checkpoint_name(out, spans.ATTN_OUT)
    interpret = impl == "pallas_interpret"
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    p, bq, bk = _make_params(q, k, causal=causal, window=window,
                             softcap=softcap, scale=scale,
                             kv_valid_len=kv_valid_len, block_q=block_q,
                             block_k=block_k, interpret=interpret,
                             packed=q_doc_start is not None,
                             doc_skip=doc_skip)
    d_pad = _round_up(d, 128)
    qf = _fold_pad(q, bq, d_pad)
    kf = _fold_pad(k, bk, d_pad)
    vf = _fold_pad(v, bk, d_pad)
    if q_doc_start is not None:
        doc = _pad_doc(q_doc_start, lq, bq)
        out = _flash_folded_doc(qf, kf, vf, doc, p)
    else:
        out = _flash_folded(qf, kf, vf, p)
    return _unfold(out, b, hq, lq, d)


def flash_fwd_chunk(q, k, v, *, causal: bool = False,
                    window: int | None = None, softcap: float = 0.0,
                    scale: float | None = None,
                    kv_valid_len: int | None = None, kv_start=None,
                    mask_offset=None, band: BandMask | None = None,
                    q_doc_start=None, doc_skip: bool = True,
                    impl: str = "auto",
                    block_q: int | None = None,
                    block_k: int | None = None):
    """Non-differentiable (out, lse) — ring / decode building block.

    out (B, Lq, Hq, D);  lse (B, Hq, Lq) fp32.

    ``mask_offset`` / ``band`` may be traced: the Pallas path threads them
    into the kernel as scalar-prefetch operands and keeps its block-skip
    logic (no downgrade to the jnp path).  ``q_doc_start`` (packed
    documents, (B, Lq) int32 per-row doc starts) rides in as a blocked
    VMEM operand the same way — cross-document K blocks are skipped
    unless ``doc_skip=False``.  Per-request ``(B,)`` ragged offsets
    (``mask_offset`` / ``kv_valid_len`` / ``kv_start`` — the
    continuous-batching decode case) are ref-path only.
    """
    impl = resolve_impl(impl)
    if q_doc_start is not None and not causal:
        raise ValueError("q_doc_start requires causal=True")
    ragged = any(isinstance(x, jax.Array) and x.ndim >= 1
                 for x in (mask_offset, kv_valid_len, kv_start))
    if kv_start is not None or ragged:
        if impl not in ("ref", "flashref"):
            raise NotImplementedError(
                "per-request ragged masks (kv_start / batched offsets) are "
                f"only lowered on the ref paths, got impl={impl!r}")
    if impl == "flashref":
        return ref_mod.attention_ref_chunked(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, kv_valid_len=kv_valid_len, kv_start=kv_start,
            mask_offset=mask_offset, band=band, q_doc_start=q_doc_start)
    if impl == "ref":
        return ref_mod.attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, kv_valid_len=kv_valid_len, kv_start=kv_start,
            mask_offset=mask_offset, band=band, q_doc_start=q_doc_start)
    interpret = impl == "pallas_interpret"
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    scalars, q_seg, k_seg = _band_scalars(band, mask_offset, lq, lk,
                                          kv_valid_len, causal=causal,
                                          window=window)
    p, bq, bk = _make_params(q, k, causal=causal, window=window,
                             softcap=softcap, scale=scale,
                             kv_valid_len=None, block_q=block_q,
                             block_k=block_k, interpret=interpret,
                             q_seg=q_seg, k_seg=k_seg,
                             packed=q_doc_start is not None,
                             doc_skip=doc_skip)
    d_pad = _round_up(d, 128)
    qf = _fold_pad(q, bq, d_pad)
    kf = _fold_pad(k, bk, d_pad)
    vf = _fold_pad(v, bk, d_pad)
    doc = None if q_doc_start is None else _pad_doc(q_doc_start, lq, bq)
    out, lse = _fwd(qf, kf, vf, p, band=scalars, doc=doc)
    out = _unfold(out, b, hq, lq, d)
    lse = lse[:, :lq, 0].reshape(b, hq, lq)
    return out, lse


def flash_bwd_chunk(q, k, v, out, lse, do, *, causal: bool = False,
                    window: int | None = None, softcap: float = 0.0,
                    scale: float | None = None,
                    kv_valid_len: int | None = None,
                    mask_offset=None, band: BandMask | None = None,
                    q_doc_start=None, doc_skip: bool = True,
                    impl: str = "auto",
                    block_q: int | None = None,
                    block_k: int | None = None):
    """Chunk backward given global (out, lse).  Returns (dq, dk, dv).

    GQA gradients are group-summed inside the dk/dv kernel — no
    ``group×``-expanded K/V is allocated on any path.
    """
    impl = resolve_impl(impl)
    if q_doc_start is not None and not causal:
        raise ValueError("q_doc_start requires causal=True")
    if impl == "flashref":
        return ref_mod.attention_bwd_ref_chunked(
            q, k, v, out, lse, do, causal=causal, window=window,
            softcap=softcap, scale=scale, kv_valid_len=kv_valid_len,
            mask_offset=mask_offset, band=band, q_doc_start=q_doc_start)
    if impl == "ref":
        return ref_mod.attention_bwd_ref(
            q, k, v, out, lse, do, causal=causal, window=window,
            softcap=softcap, scale=scale, kv_valid_len=kv_valid_len,
            mask_offset=mask_offset, band=band, q_doc_start=q_doc_start)
    interpret = impl == "pallas_interpret"
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    scalars, q_seg, k_seg = _band_scalars(band, mask_offset, lq, lk,
                                          kv_valid_len, causal=causal,
                                          window=window)
    p, bq, bk = _make_params(q, k, causal=causal, window=window,
                             softcap=softcap, scale=scale,
                             kv_valid_len=None, block_q=block_q,
                             block_k=block_k, interpret=interpret,
                             q_seg=q_seg, k_seg=k_seg,
                             packed=q_doc_start is not None,
                             doc_skip=doc_skip)
    d_pad = _round_up(d, 128)
    qf = _fold_pad(q, bq, d_pad)
    kf = _fold_pad(k, bk, d_pad)
    vf = _fold_pad(v, bk, d_pad)
    outf = _fold_pad(out, bq, d_pad)
    dof = _fold_pad(do, bq, d_pad)
    lq_pad = qf.shape[1]
    lsef = lse.reshape(b * hq, lq, 1)
    if lq_pad != lq:
        lsef = jnp.pad(lsef, ((0, 0), (0, lq_pad - lq), (0, 0)))
    doc = None if q_doc_start is None else _pad_doc(q_doc_start, lq, bq)
    dqf, dkf, dvf = _bwd(qf, kf, vf, outf, lsef, dof, p, band=scalars,
                         doc=doc)
    dq = _unfold(dqf, b, hq, lq, d)
    dk = _unfold(dkf, b, hkv, lk, d).astype(k.dtype)
    dv = _unfold(dvf, b, hkv, lk, d).astype(v.dtype)
    return dq, dk, dv
