"""Pallas TPU flash-attention kernel (forward + backward).

TPU-native adaptation of FlashAttention-2 for the LoongTrain reproduction:

* ``pl.pallas_call`` with explicit ``BlockSpec`` VMEM tiling; MXU-aligned
  (multiples-of-128) Q/K blocks; fp32 accumulators in VMEM scratch.
* Masking is driven by a *scalar-prefetch* band operand
  (``pltpu.PrefetchScalarGridSpec``): an int32 ``(5,)`` vector
  ``[q_off_lo, q_off_hi, k_off_lo, k_off_hi, kv_valid]`` carrying the
  piecewise logical-position offsets of ``ref.BandMask``.  The offsets may
  be traced (``lax.axis_index`` functions on the ring path), yet the
  bottom-right-aligned causal + sliding-window *block-skip* logic still
  runs inside the kernel: fully-masked K blocks are skipped via ``pl.when``
  on predicates computed from the prefetched scalars, so the compiled
  FLOPs of a causal call stay ~half of the dense product on every Double
  Ring step — not just the static diagonal.
* Sliding-window (local) masking, Gemma-style logit softcap, GQA via
  index-map head folding in *both* directions: the forward and dq kernels
  read KV block ``b // group``; the dk/dv kernel folds the query-head
  group into its (sequential) innermost grid dimension and accumulates the
  group-summed gradients in VMEM scratch, so replicated KV is never
  materialized anywhere.
* **Packed documents** (``FlashParams.packed``): a per-q-row int32
  doc-start table arrives as one more blocked ``(1, block_q, 1)`` VMEM
  operand (shared by all folded heads of a sequence); keys below a row's
  document start are masked, and K blocks entirely below a q block's
  first-row doc start are *skipped* at grid level (``doc_skip``).  The
  full contract is written down in docs/KERNELS.md.

* Per-row vectors (``lse``, ``dsum``, the doc table, the running max and
  sum) carry a trailing singleton axis, ``(rows, 1)``: the TPU lowering
  takes a block whose last two dimensions are multiples of (8, 128) or
  equal to the array's, so ``(block_q, 1)`` is legal at any batch size
  where a ``(1, block_q)`` slice of a 2-D ``(B·H, L)`` array is not.

Validated on CPU with ``interpret=True`` against ``ref.py`` (see
``tests/test_kernels.py``); ``tests/test_tpu_compile.py`` compiles the
kernels for a described v5e chip.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import _logical_pos
from repro.runtime import spans

NEG_INF = -1e30


class FlashParams(NamedTuple):
    """Static kernel configuration (hashable => usable as nondiff arg)."""
    causal: bool
    window: int | None
    softcap: float
    scale: float
    lq_valid: int          # number of real (unpadded) queries
    lk_valid: int          # attendable keys (kv_valid_len cut, else Lk)
    block_q: int
    block_k: int
    interpret: bool
    q_seg: int = 0         # physical row where the q hi-offset segment starts
    k_seg: int = 0         # (0 => unsplit: every row uses the hi offset)
    delta: int = 0         # default causal anchor: full Lk - Lq (the oracle
                           # anchors bottom-right at the full key length;
                           # kv_valid_len only cuts, it does not re-anchor)
    packed: bool = False   # packed documents: a per-q-row doc-start table
                           # (logical positions) arrives as one more blocked
                           # operand; keys before a row's doc start are
                           # masked (block-causal within each document)
    doc_skip: bool = True  # skip K blocks entirely below the q block's doc
                           # start (False: mask in-tile only — the dense-
                           # masked baseline the packing bench compares to)


def _default_band(p: FlashParams) -> jax.Array:
    """Band scalars for the classic bottom-right-aligned static mask."""
    return jnp.array([p.delta, p.delta, 0, 0, p.lk_valid], jnp.int32)


def _q_log(r, band_ref, p: FlashParams):
    """Logical sequence position of physical q row(s) ``r``."""
    return _logical_pos(r, band_ref[0], band_ref[1], p.q_seg)


def _k_log(c, band_ref, p: FlashParams):
    """Logical sequence position of physical k column(s) ``c``."""
    return _logical_pos(c, band_ref[2], band_ref[3], p.k_seg)


def _run_predicate(q_start, k_start, band_ref, p: FlashParams,
                   doc_ref=None):
    """Whole-block skip test.  Logical positions are nondecreasing in the
    physical index (the BandMask contract), so block extrema sit at the
    block edges even when a block straddles the segment boundary.

    Packed documents add a second skip direction: the doc-start table is
    nondecreasing in the physical q row (documents are contiguous logical
    intervals and rows are logically ordered), so the q block's smallest
    doc start sits at its first row; K blocks whose last logical position
    is below it are entirely cross-document and skipped."""
    run = k_start < band_ref[4]
    if p.causal:
        run = jnp.logical_and(
            run,
            _k_log(k_start, band_ref, p)
            <= _q_log(q_start + p.block_q - 1, band_ref, p))
    if p.window is not None:
        run = jnp.logical_and(
            run,
            _k_log(k_start + p.block_k - 1, band_ref, p)
            >= _q_log(q_start, band_ref, p) - (p.window - 1))
    if p.packed and p.doc_skip:
        run = jnp.logical_and(
            run,
            _k_log(k_start + p.block_k - 1, band_ref, p) >= doc_ref[0, 0, 0])
    return run


def _tile_mask(q_start, k_start, band_ref, p: FlashParams, doc_ref=None):
    """Elementwise (block_q, block_k) visibility mask."""
    qi = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (p.block_q, p.block_k), 0)
    kj = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (p.block_q, p.block_k), 1)
    mask = kj < band_ref[4]
    if p.causal or p.window is not None:
        q_log = _q_log(qi, band_ref, p)
        k_log = _k_log(kj, band_ref, p)
        if p.causal:
            mask &= k_log <= q_log
        if p.packed:
            mask &= k_log >= doc_ref[0]
        if p.window is not None:
            mask &= k_log >= q_log - (p.window - 1)
    return mask


#: Scoped VMEM a Mosaic kernel gets unless its ``CompilerParams`` ask for
#: more (16 MiB on v5e, of the core's 128 MiB).
_SCOPED_VMEM = 16 << 20
#: ``(block_q, block_k)`` fp32 temporaries each grid step may hold at once.
_FWD_TILES = 4
_BWD_TILES = 6


def _vmem_bytes(p: FlashParams, d: int, n_tiles: int) -> int:
    """Upper reckoning of one kernel's VMEM: every blocked operand
    double-buffered at 4 bytes with its last axis padded to 128 lanes (at
    most 6 q-side and 4 k-side), the fp32 scratch (at most 3 q-side
    rows), and ``n_tiles`` fp32 score-sized temporaries."""
    lanes = _round_up(d, 128)
    blocks = 2 * 4 * lanes * (6 * p.block_q + 4 * p.block_k)
    scratch = 4 * lanes * 3 * p.block_q
    return blocks + scratch + n_tiles * 4 * p.block_q * p.block_k


def _compiler_params(p: FlashParams, d: int, *, n_tiles: int):
    """Grid semantics, and a raised VMEM limit where the reckoning of the
    kernel's tiles passes the scoped default."""
    need = _vmem_bytes(p, d, n_tiles)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=need if need > _SCOPED_VMEM else None)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(band_ref, *refs, p: FlashParams, nk: int):
    if p.packed:
        q_ref, k_ref, v_ref, doc_ref = refs[:4]
    else:
        (q_ref, k_ref, v_ref), doc_ref = refs[:3], None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs[-5:]
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * p.block_q
    k_start = jk * p.block_k

    @pl.when(_run_predicate(q_start, k_start, band_ref, p, doc_ref))
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * p.scale
        if p.softcap:
            s = p.softcap * jnp.tanh(s / p.softcap)

        mask = _tile_mask(q_start, k_start, band_ref, p, doc_ref)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                          # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # fully-masked-so-far rows: keep shift at 0 to avoid exp(inf) traps
        shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        pmat = jnp.exp(s - shift)
        pmat = jnp.where(mask, pmat, 0.0)
        alpha = jnp.exp(jnp.where(m_prev <= NEG_INF / 2, NEG_INF,
                                  m_prev - shift))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pmat, axis=1,
                                                  keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            pmat, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        m = m_ref[...]
        shift = jnp.where(m <= NEG_INF / 2, 0.0, m)
        lse_ref[0] = jnp.where(l == 0.0, NEG_INF, shift + jnp.log(l_safe))


def _fwd(q, k, v, p: FlashParams, band=None, doc=None):
    """q: (B*Hq, Lq, D); k/v: (B*Hkv, Lk, D), heads folded major-to-minor.

    GQA is handled in the K/V index maps (kv row = q row // group), so the
    replicated KV is never materialized.  ``band``: optional int32 (5,)
    scalar-prefetch vector (see module docstring); defaults to the static
    bottom-right band.  ``doc``: optional (B, Lq, 1) int32 per-row
    doc-start table (``p.packed`` must be set) — blocked over q, shared
    across the folded heads of each sequence.  Returns out (BH, Lq, D),
    lse (BH, Lq, 1) fp32.
    """
    bh, lq, d = q.shape
    bhkv, lk, _ = k.shape
    assert bh % bhkv == 0, (bh, bhkv)
    assert (doc is not None) == p.packed, (doc is None, p.packed)
    group = bh // bhkv
    nq = lq // p.block_q
    nk = lk // p.block_k
    if band is None:
        band = _default_band(p)

    kernel = functools.partial(_fwd_kernel, p=p, nk=nk)
    in_specs = [
        pl.BlockSpec((1, p.block_q, d), lambda b, i, j, s: (b, i, 0)),
        pl.BlockSpec((1, p.block_k, d),
                     lambda b, i, j, s: (b // group, j, 0)),
        pl.BlockSpec((1, p.block_k, d),
                     lambda b, i, j, s: (b // group, j, 0)),
    ]
    operands = (q, k, v)
    if p.packed:
        q_mult = bh // doc.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, p.block_q, 1), lambda b, i, j, s: (b // q_mult, i, 0)))
        operands = (q, k, v, doc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, p.block_q, d), lambda b, i, j, s: (b, i, 0)),
            pl.BlockSpec((1, p.block_q, 1), lambda b, i, j, s: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((p.block_q, d), jnp.float32),
            pltpu.VMEM((p.block_q, 1), jnp.float32),
            pltpu.VMEM((p.block_q, 1), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(p, d, n_tiles=_FWD_TILES),
        interpret=p.interpret,
        name=spans.FLASH_FWD,
    )(band, *operands)
    return out, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _recompute_p(q, k, q_start, k_start, band_ref, p: FlashParams,
                 doc_ref=None):
    """Recompute softcapped+masked scores; returns (s_capped, mask, s_raw)."""
    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * p.scale
    s = p.softcap * jnp.tanh(s_raw / p.softcap) if p.softcap else s_raw
    mask = _tile_mask(q_start, k_start, band_ref, p, doc_ref)
    return s, mask, s_raw


def _ds_from_dp(dp, pmat, s_capped, s_raw, p: FlashParams):
    """dS wrt pre-scale logits, including softcap chain rule; returns
    d(logits)/scale factor applied (i.e. gradient wrt q@k.T before *scale)."""
    ds = pmat * dp
    if p.softcap:
        ds = ds * (1.0 - (s_capped / p.softcap) ** 2)
    return ds * p.scale


def _dq_kernel(band_ref, *refs, p: FlashParams, nk: int):
    if p.packed:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, doc_ref = refs[:7]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref = refs[:6]
        doc_ref = None
    dq_ref, dq_acc = refs[-2:]
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = iq * p.block_q
    k_start = jk * p.block_k

    @pl.when(_run_predicate(q_start, k_start, band_ref, p, doc_ref))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                             # (bq, 1)
        dsum = dsum_ref[0]

        s, mask, s_raw = _recompute_p(q, k, q_start, k_start, band_ref, p,
                                      doc_ref)
        shift = jnp.where(lse <= NEG_INF / 2, 0.0, lse)
        pmat = jnp.where(mask, jnp.exp(s - shift), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = _ds_from_dp(dp - dsum, pmat, s, s_raw, p)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(band_ref, *refs, p: FlashParams, nq: int, group: int):
    """dk/dv for one KV head.  The innermost (sequential) grid dimension
    runs over ``group * nq`` steps — all q blocks of every query head in
    this KV head's group — so the group-summed gradients accumulate in the
    VMEM scratch without ever materializing group-expanded K/V."""
    if p.packed:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, doc_ref = refs[:7]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref = refs[:6]
        doc_ref = None
    dk_ref, dv_ref, dk_acc, dv_acc = refs[-4:]
    jk = pl.program_id(1)
    ig = pl.program_id(2)            # ig = g * nq + iq

    @pl.when(ig == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = jax.lax.rem(ig, nq) * p.block_q
    k_start = jk * p.block_k

    @pl.when(_run_predicate(q_start, k_start, band_ref, p, doc_ref))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                             # (bq, 1)
        dsum = dsum_ref[0]

        s, mask, s_raw = _recompute_p(q, k, q_start, k_start, band_ref, p,
                                      doc_ref)
        shift = jnp.where(lse <= NEG_INF / 2, 0.0, lse)
        pmat = jnp.where(mask, jnp.exp(s - shift), 0.0)
        dv_acc[...] += jax.lax.dot_general(
            pmat, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = _ds_from_dp(dp - dsum, pmat, s, s_raw, p)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ig == group * nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q, k, v, out, lse, do, p: FlashParams, band=None, doc=None):
    """Backward in the folded layout.  k/v may have fewer (KV) heads than
    q (GQA); dk/dv come back at the KV head count, group-summed."""
    if band is None:
        band = _default_band(p)
    dsum = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1, keepdims=True)  # (BH, Lq, 1)
    operands = (q, k, v, do, lse, dsum) + ((doc,) if p.packed else ())
    dq = _bwd_dq(band, *operands, p=p)
    dk, dv = _bwd_dkv(band, *operands, p=p)
    return dq, dk, dv


def _bwd_dq(band, q, k, v, do, lse, dsum, doc=None, *, p: FlashParams):
    """The dq kernel: grid (B·Hq, nq, nk), K blocks sequential."""
    bh, lq, d = q.shape
    bhkv, lk, _ = k.shape
    assert bh % bhkv == 0, (bh, bhkv)
    assert (doc is not None) == p.packed, (doc is None, p.packed)
    group = bh // bhkv
    nq = lq // p.block_q
    nk = lk // p.block_k
    in_specs = [
        pl.BlockSpec((1, p.block_q, d), lambda b, i, j, s: (b, i, 0)),
        pl.BlockSpec((1, p.block_k, d),
                     lambda b, i, j, s: (b // group, j, 0)),
        pl.BlockSpec((1, p.block_k, d),
                     lambda b, i, j, s: (b // group, j, 0)),
        pl.BlockSpec((1, p.block_q, d), lambda b, i, j, s: (b, i, 0)),
        pl.BlockSpec((1, p.block_q, 1), lambda b, i, j, s: (b, i, 0)),
        pl.BlockSpec((1, p.block_q, 1), lambda b, i, j, s: (b, i, 0)),
    ]
    operands = (q, k, v, do, lse, dsum)
    if p.packed:
        q_mult = bh // doc.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, p.block_q, 1), lambda b, i, j, s: (b // q_mult, i, 0)))
        operands = operands + (doc,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, p.block_q, d),
                               lambda b, i, j, s: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((p.block_q, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_dq_kernel, p=p, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        compiler_params=_compiler_params(p, d, n_tiles=_BWD_TILES),
        interpret=p.interpret,
        name=spans.FLASH_DQ,
    )(band, *operands)


def _bwd_dkv(band, q, k, v, do, lse, dsum, doc=None, *, p: FlashParams):
    """The dk/dv kernel: grid (B·Hkv, nk, group·nq), q blocks of every
    query head of the group sequential."""
    bh, lq, d = q.shape
    bhkv, lk, _ = k.shape
    assert bh % bhkv == 0, (bh, bhkv)
    assert (doc is not None) == p.packed, (doc is None, p.packed)
    group = bh // bhkv
    nq = lq // p.block_q
    nk = lk // p.block_k
    # Query-side operands walk b*group + ig//nq: for a fixed KV head, the
    # sequential dimension visits each group member's q blocks in turn.
    in_specs = [
        pl.BlockSpec((1, p.block_q, d),
                     lambda b, j, g, s: (b * group + g // nq,
                                         g % nq, 0)),
        pl.BlockSpec((1, p.block_k, d), lambda b, j, g, s: (b, j, 0)),
        pl.BlockSpec((1, p.block_k, d), lambda b, j, g, s: (b, j, 0)),
        pl.BlockSpec((1, p.block_q, d),
                     lambda b, j, g, s: (b * group + g // nq,
                                         g % nq, 0)),
        pl.BlockSpec((1, p.block_q, 1),
                     lambda b, j, g, s: (b * group + g // nq, g % nq, 0)),
        pl.BlockSpec((1, p.block_q, 1),
                     lambda b, j, g, s: (b * group + g // nq, g % nq, 0)),
    ]
    operands = (q, k, v, do, lse, dsum)
    if p.packed:
        q_mult = bh // doc.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, p.block_q, 1),
            lambda b, j, g, s: ((b * group + g // nq) // q_mult,
                                g % nq, 0)))
        operands = operands + (doc,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bhkv, nk, group * nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, p.block_k, d), lambda b, j, g, s: (b, j, 0)),
            pl.BlockSpec((1, p.block_k, d), lambda b, j, g, s: (b, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((p.block_k, d), jnp.float32),
            pltpu.VMEM((p.block_k, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_dkv_kernel, p=p, nq=nq, group=group),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bhkv, lk, d), k.dtype),
            jax.ShapeDtypeStruct((bhkv, lk, d), v.dtype),
        ],
        compiler_params=_compiler_params(p, d, n_tiles=_BWD_TILES),
        interpret=p.interpret,
        name=spans.FLASH_DKV,
    )(band, *operands)


# ---------------------------------------------------------------------------
# custom_vjp plumbing (head-folded layout)
# ---------------------------------------------------------------------------
#
# The forward rules name the residuals the backward reads (``out`` and
# ``lse``), so that Selective Checkpoint++ saves them and the backward of a
# checkpointed layer does not rerun the forward kernel.  ``out`` is the
# primal output itself: whatever the caller does with it recomputes from
# the saved copy.  ``lse`` is saved as ``(BH, L)``: a ``(BH, L, 1)`` array
# pads its size-1 minor axis to 128 lanes in TPU HBM.

def _named_residuals(out, lse):
    """``(out, lse)`` under their checkpoint names, ``lse`` lane-dense."""
    return (checkpoint_name(out, spans.ATTN_OUT),
            checkpoint_name(lse[..., 0], spans.ATTN_LSE))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_folded(q, k, v, p: FlashParams):
    out, _ = _fwd(q, k, v, p)
    return out


def _flash_fwd_rule(q, k, v, p: FlashParams):
    out, lse = _named_residuals(*_fwd(q, k, v, p))
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(p: FlashParams, res, do):
    q, k, v, out, lse = res
    # GQA dk/dv are group-summed inside the dkv kernel (the query group is
    # folded into its sequential grid dimension) — no KV expansion here.
    return _bwd(q, k, v, out, lse[..., None], do, p)


_flash_folded.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash_folded_doc(q, k, v, doc, p: FlashParams):
    """Packed-document variant: ``doc`` is the (B, Lq_pad, 1) int32 per-row
    doc-start table (integer data — its cotangent is float0)."""
    out, _ = _fwd(q, k, v, p, doc=doc)
    return out


def _flash_doc_fwd_rule(q, k, v, doc, p: FlashParams):
    out, lse = _named_residuals(*_fwd(q, k, v, p, doc=doc))
    return out, (q, k, v, doc, out, lse)


def _flash_doc_bwd_rule(p: FlashParams, res, do):
    q, k, v, doc, out, lse = res
    dq, dk, dv = _bwd(q, k, v, out, lse[..., None], do, p, doc=doc)
    return dq, dk, dv, np.zeros(doc.shape, jax.dtypes.float0)


_flash_folded_doc.defvjp(_flash_doc_fwd_rule, _flash_doc_bwd_rule)
