"""Pallas flash-attention kernel vs pure-jnp oracle: shape/dtype sweeps,
gradient checks, and hypothesis property tests on the combine rule."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def t(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


SWEEP = [
    # b, lq, lk, hq, hkv, d, causal, window, softcap
    (2, 64, 64, 4, 4, 32, True, None, 0.0),
    (1, 48, 80, 4, 2, 24, True, None, 0.0),      # GQA + rectangular + pad
    (1, 33, 100, 6, 3, 40, True, None, 0.0),     # odd lengths
    (2, 16, 96, 4, 4, 32, True, None, 0.0),      # ring-like short q
    (1, 32, 32, 2, 2, 16, False, None, 30.0),    # softcap, non-causal
    (2, 64, 64, 4, 1, 32, True, 16, 0.0),        # MQA + sliding window
    (1, 64, 64, 8, 2, 64, True, 8, 25.0),        # window + softcap + GQA
    (1, 128, 128, 2, 2, 128, True, None, 0.0),   # MXU-aligned
]


@pytest.mark.parametrize("case", SWEEP, ids=[str(i) for i in range(len(SWEEP))])
def test_fwd_matches_oracle(case):
    b, lq, lk, hq, hkv, d, causal, window, cap = case
    q, k, v = t((b, lq, hq, d)), t((b, lk, hkv, d)), t((b, lk, hkv, d))
    o_ref, lse_ref = ref.attention_ref(q, k, v, causal=causal,
                                       window=window, softcap=cap)
    o_p, lse_p = ops.flash_fwd_chunk(q, k, v, causal=causal, window=window,
                                     softcap=cap, impl="pallas_interpret",
                                     block_q=32, block_k=32)
    np.testing.assert_allclose(o_p, o_ref, atol=2e-5, rtol=2e-5)
    mask = lse_ref > ref.NEG_INF / 2
    np.testing.assert_allclose(np.where(mask, lse_p, 0.0),
                               np.where(mask, lse_ref, 0.0),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", SWEEP[:6],
                         ids=[str(i) for i in range(6)])
def test_bwd_matches_oracle(case):
    b, lq, lk, hq, hkv, d, causal, window, cap = case
    q, k, v = t((b, lq, hq, d)), t((b, lk, hkv, d)), t((b, lk, hkv, d))

    def loss_ref(q, k, v):
        return (ref.attention_ref(q, k, v, causal=causal, window=window,
                                  softcap=cap)[0] ** 2).sum()

    def loss_pal(q, k, v):
        return (ops.flash_attention(q, k, v, causal=causal, window=window,
                                    softcap=cap, impl="pallas_interpret",
                                    block_q=32, block_k=32) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_pal = jax.grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_pal, g_ref):
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtypes(dtype):
    q, k, v = (t((1, 64, 4, 32), dtype) for _ in range(3))
    o_ref, _ = ref.attention_ref(q, k, v, causal=True)
    o_p, _ = ops.flash_fwd_chunk(q, k, v, causal=True,
                                 impl="pallas_interpret",
                                 block_q=32, block_k=32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_p, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=tol, rtol=tol)


def test_chunk_bwd_matches_ref():
    q, k, v = t((1, 32, 4, 16)), t((1, 48, 2, 16)), t((1, 48, 2, 16))
    out, lse = ref.attention_ref(q, k, v, causal=True)
    do = t(out.shape)
    a = ops.flash_bwd_chunk(q, k, v, out, lse, do, causal=True,
                            impl="pallas_interpret", block_q=16, block_k=16)
    b = ref.attention_bwd_ref(q, k, v, out, lse, do, causal=True)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(lk1=st.integers(1, 24), lk2=st.integers(1, 24),
       seed=st.integers(0, 2 ** 16))
def test_combine_equals_joint(lk1, lk2, seed):
    """Attention over concat(K1, K2) == lse-combine of the two partials —
    the invariant ring attention and flash-decoding rely on."""
    rng = np.random.default_rng(seed)
    b, lq, h, d = 1, 8, 2, 8
    q = jnp.asarray(rng.standard_normal((b, lq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, lk1 + lk2, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, lk1 + lk2, h, d)), jnp.float32)
    o_joint, lse_joint = ref.attention_ref(q, k, v)
    p1 = ref.attention_ref(q, k[:, :lk1], v[:, :lk1])
    p2 = ref.attention_ref(q, k[:, lk1:], v[:, lk1:])
    o_c, lse_c = ref.combine_attention([p1, p2])
    np.testing.assert_allclose(o_c, o_joint, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(lse_c, lse_joint, atol=1e-5, rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 5), seed=st.integers(0, 2 ** 16))
def test_combine_order_invariance(n, seed):
    """The combine is associative/commutative over KV chunks."""
    rng = np.random.default_rng(seed)
    b, lq, h, d, lk = 1, 4, 1, 8, 6
    q = jnp.asarray(rng.standard_normal((b, lq, h, d)), jnp.float32)
    parts = []
    for _ in range(n):
        k = jnp.asarray(rng.standard_normal((b, lk, h, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, lk, h, d)), jnp.float32)
        parts.append(ref.attention_ref(q, k, v))
    fwd = ref.combine_attention(parts)
    rev = ref.combine_attention(parts[::-1])
    np.testing.assert_allclose(fwd[0], rev[0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(fwd[1], rev[1], atol=1e-5, rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 16), window=st.integers(1, 20))
def test_window_is_band_subset(seed, window):
    """Sliding-window output == dense attention with a banded mask."""
    rng = np.random.default_rng(seed)
    b, l, h, d = 1, 16, 2, 8
    q = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
    o_win, _ = ref.attention_ref(q, k, v, causal=True, window=window)
    # manual band mask via bias
    qi = np.arange(l)[:, None]
    kj = np.arange(l)[None, :]
    bias = np.where((kj <= qi) & (kj >= qi - window + 1), 0.0, -1e30)
    o_bias, _ = ref.attention_ref(q, k, v,
                                  bias=jnp.asarray(bias)[None, None])
    np.testing.assert_allclose(o_win, o_bias, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Scalar-prefetch band masks: traced offsets stay on the Pallas kernel
# ---------------------------------------------------------------------------

BAND_SWEEP = [
    # b, lq, lk, hq, hkv, d, window, softcap
    (1, 32, 48, 4, 4, 16, None, 0.0),
    (1, 32, 48, 4, 2, 16, None, 0.0),     # GQA
    (2, 24, 40, 4, 1, 24, None, 0.0),     # MQA + padding
    (1, 32, 48, 4, 2, 16, 12, 0.0),       # sliding window
    (1, 32, 48, 4, 2, 16, None, 20.0),    # softcap
    (1, 32, 48, 6, 3, 16, 10, 25.0),      # window + softcap + GQA
]


@pytest.mark.parametrize("case", BAND_SWEEP,
                         ids=[str(i) for i in range(len(BAND_SWEEP))])
def test_fwd_chunk_traced_mask_offset(case):
    """A *traced* mask_offset must dispatch to the Pallas kernel (no
    flashref downgrade) and match the oracle."""
    b, lq, lk, hq, hkv, d, window, cap = case
    q, k, v = t((b, lq, hq, d)), t((b, lk, hkv, d)), t((b, lk, hkv, d))

    @jax.jit
    def f(off):
        return ops.flash_fwd_chunk(q, k, v, causal=True, window=window,
                                   softcap=cap, mask_offset=off,
                                   impl="pallas_interpret",
                                   block_q=16, block_k=16)

    for off in (16, 0, 40):
        o_p, lse_p = f(jnp.int32(off))
        o_ref, lse_ref = ref.attention_ref(q, k, v, causal=True,
                                           window=window, softcap=cap,
                                           mask_offset=off)
        np.testing.assert_allclose(o_p, o_ref, atol=1e-4, rtol=1e-4)
        mask = lse_ref > ref.NEG_INF / 2
        assert ((np.asarray(lse_p) > ref.NEG_INF / 2) == mask).all()
        np.testing.assert_allclose(np.where(mask, lse_p, 0.0),
                                   np.where(mask, lse_ref, 0.0),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("case", BAND_SWEEP,
                         ids=[str(i) for i in range(len(BAND_SWEEP))])
def test_bwd_chunk_traced_mask_offset(case):
    b, lq, lk, hq, hkv, d, window, cap = case
    q, k, v = t((b, lq, hq, d)), t((b, lk, hkv, d)), t((b, lk, hkv, d))
    out, lse = ref.attention_ref(q, k, v, causal=True, window=window,
                                 softcap=cap, mask_offset=16)
    do = t(out.shape)

    @jax.jit
    def g(off):
        return ops.flash_bwd_chunk(q, k, v, out, lse, do, causal=True,
                                   window=window, softcap=cap,
                                   mask_offset=off, impl="pallas_interpret",
                                   block_q=16, block_k=16)

    g_p = g(jnp.int32(16))
    g_ref = ref.attention_bwd_ref(q, k, v, out, lse, do, causal=True,
                                  window=window, softcap=cap, mask_offset=16)
    for a, b_ in zip(g_p, g_ref):
        assert a.shape == b_.shape
        np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("window,cap", [(None, 0.0), (6, 0.0), (None, 20.0)])
def test_zigzag_band_all_step_pairs(window, cap):
    """One kernel call per ring step: the zigzag BandMask must reproduce
    every (i, j) case — diagonal, past, future — for fwd and bwd."""
    from repro.kernels.ref import BandMask
    c, cp = 8, 4
    q, k, v = t((1, 16, 4, 16)), t((1, 16, 2, 16)), t((1, 16, 2, 16))

    @jax.jit
    def f(i, j):
        return ops.flash_fwd_chunk(q, k, v, causal=True, window=window,
                                   softcap=cap,
                                   band=BandMask.zigzag(i, j, c, cp),
                                   impl="pallas_interpret",
                                   block_q=8, block_k=8)

    @jax.jit
    def g(i, j, out, lse, do):
        return ops.flash_bwd_chunk(q, k, v, out, lse, do, causal=True,
                                   window=window, softcap=cap,
                                   band=BandMask.zigzag(i, j, c, cp),
                                   impl="pallas_interpret",
                                   block_q=8, block_k=8)

    for i in range(cp):
        for j in range(cp):
            band = BandMask.zigzag(i, j, c, cp)
            o_ref, lse_ref = ref.attention_ref(q, k, v, causal=True,
                                               window=window, softcap=cap,
                                               band=band)
            o_p, lse_p = f(jnp.int32(i), jnp.int32(j))
            np.testing.assert_allclose(o_p, o_ref, atol=1e-4, rtol=1e-4,
                                       err_msg=f"fwd i={i} j={j}")
            mask = np.asarray(lse_ref) > ref.NEG_INF / 2
            assert ((np.asarray(lse_p) > ref.NEG_INF / 2) == mask).all(), \
                (i, j)
            do = t(o_ref.shape)
            g_p = g(jnp.int32(i), jnp.int32(j), o_ref, lse_ref, do)
            g_ref = ref.attention_bwd_ref(q, k, v, o_ref, lse_ref, do,
                                          causal=True, window=window,
                                          softcap=cap, band=band)
            for a, b_ in zip(g_p, g_ref):
                np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4,
                                           err_msg=f"bwd i={i} j={j}")


# ---------------------------------------------------------------------------
# Tiles chosen from the shape
# ---------------------------------------------------------------------------

TILE_CASES = [
    # lq, lk, block_q, block_k, seg (a zigzag half the tile must divide)
    (1, 1, None, None, None),
    (33, 100, None, None, None),            # under 128: round_up(L, 8)
    (127, 128, None, None, None),
    (129, 200, None, None, None),           # padded to 256
    (384, 640, None, None, None),           # 384 | 384; 640 takes 128
    (1000, 1024, None, None, None),
    (4096, 4096, None, None, 2048),         # staged grid under hp2
    (16384, 16384, None, None, 8192),       # staged grid: cp2 local chunk
    (32768, 32768, None, None, None),       # the one-chip cell
    (32769, 300, None, None, None),
    (4096, 4096, 128, 128, None),           # explicit sizes win
    (64, 48, 32, 16, None),
    (20, 20, 32, 64, None),                 # explicit, cut to round_up(L, 8)
    (32768, 32768, 256, None, None),        # one explicit, one chosen
]


@pytest.mark.parametrize("case", TILE_CASES,
                         ids=[str(i) for i in range(len(TILE_CASES))])
def test_choose_blocks(case):
    """The tile rule: explicit sizes as given (cut to the 8-padded length);
    else under 128 one tile of round_up(L, 8), else the largest multiple
    of 128 up to the cap that divides round_up(L, 128), so it pads no
    more than 128-tiles do."""
    lq, lk, block_q, block_k, seg = case
    chosen = ops.choose_blocks(lq, lk, block_q, block_k)
    cap = ops.BLOCK_CAP
    for length, block, tile in ((lq, block_q, chosen[0]),
                                (lk, block_k, chosen[1])):
        if block is not None:
            assert tile == min(block, -(-length // 8) * 8)
            continue
        if length < 128:
            assert tile == -(-length // 8) * 8
            continue
        padded = -(-length // 128) * 128
        assert tile % 128 == 0 and tile <= cap and padded % tile == 0
        assert -(-length // tile) * tile == padded     # no extra padding
        assert not any(padded % t == 0
                       for t in range(tile + 128, cap + 1, 128))
        if seg is not None:
            assert seg % tile == 0


@pytest.mark.parametrize("packed", [False, True], ids=["causal", "packed"])
def test_default_tiles_match_oracle(packed):
    """At L=1536 the rule picks 768-tiles: a 2×2 grid whose block above
    the diagonal is skipped, and, packed, whose first k block lies wholly
    before the second q block's first document (doc-skipped).  Fwd and
    bwd at those default tiles match the oracle."""
    b, l, h, d = 1, 1536, 2, 128
    assert ops.choose_blocks(l, l) == (768, 768)
    q, k, v = t((b, l, h, d)), t((b, l, h, d)), t((b, l, h, d))
    doc = None
    if packed:
        starts = np.repeat([0, 500, 768, 1200], [500, 268, 432, 336])
        doc = jnp.asarray(starts[None], jnp.int32)
    o_ref, lse_ref = ref.attention_ref(q, k, v, causal=True, q_doc_start=doc)
    o_p, lse_p = ops.flash_fwd_chunk(q, k, v, causal=True, q_doc_start=doc,
                                     impl="pallas_interpret")
    np.testing.assert_allclose(o_p, o_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse_p, lse_ref, atol=2e-5, rtol=2e-5)
    do = t(o_ref.shape)
    g_p = ops.flash_bwd_chunk(q, k, v, o_ref, lse_ref, do, causal=True,
                              q_doc_start=doc, impl="pallas_interpret")
    g_ref = ref.attention_bwd_ref(q, k, v, o_ref, lse_ref, do, causal=True,
                                  q_doc_start=doc)
    for a, b_ in zip(g_p, g_ref):
        np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)


def test_bwd_gqa_no_expanded_kv():
    """The GQA backward must not allocate group-expanded K/V: no
    intermediate of shape (B*Hq, Lk_pad, D_pad) may appear in the jaxpr."""
    b, lq, lk, hq, hkv, d = 1, 32, 48, 4, 2, 16
    q, k, v = t((b, lq, hq, d)), t((b, lk, hkv, d)), t((b, lk, hkv, d))
    out, lse = ref.attention_ref(q, k, v, causal=True)
    do = t(out.shape)

    def g(q, k, v, out, lse, do):
        return ops.flash_bwd_chunk(q, k, v, out, lse, do, causal=True,
                                   impl="pallas_interpret",
                                   block_q=16, block_k=16)

    jaxpr = jax.make_jaxpr(g)(q, k, v, out, lse, do)
    lk_pad, d_pad = 48, 128
    expanded = (b * hq, lk_pad, d_pad)      # what jnp.repeat used to make

    def shapes(jp):
        for eqn in jp.eqns:
            for var in eqn.outvars:
                yield tuple(getattr(var.aval, "shape", ()))
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    yield from shapes(sub.jaxpr)

    assert expanded not in set(shapes(jaxpr.jaxpr))
    dq, dk, dv = g(q, k, v, out, lse, do)
    assert dk.shape == k.shape and dv.shape == v.shape
