"""The main path's Pallas flash kernels compiled for a described TPU v5e.

Nothing runs: each case lowers and compiles for a chip that the TPU
compiler describes (``v5e:2x2``) without one attached, and asserts that
the program holds the kernel (``tpu_custom_call``).  This catches what
interpret mode cannot: block shapes the TPU lowering refuses, VMEM over-
use, a kernel that cannot be partitioned.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, so describing it while the file is
collected would break every other pytest worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ops import (choose_blocks, flash_attention,
                               flash_bwd_chunk, flash_fwd_chunk)
from repro.kernels.ref import BandMask
from repro.runtime import spans

#: qwen3-1.7b attention widths
L, HQ, HKV, D = 4096, 16, 8, 128


@pytest.fixture(scope="module")
def chip():
    """A ``SingleDeviceSharding`` on one described v5e chip; the persistent
    compile cache is off meanwhile (entries compiled for a described chip
    cannot be read back without one)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _qkv(chip, batch, seq=L, hq=HQ, hkv=HKV):
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)
    return (sds(batch, seq, hq, D), sds(batch, seq, hkv, D),
            sds(batch, seq, hkv, D))


def _loss(q, k, v, doc=None):
    return flash_attention(q, k, v, causal=True, q_doc_start=doc,
                           impl="pallas").astype(jnp.float32).sum()


def test_fwd_qwen3_widths(chip):
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             impl="pallas"),
             *_qkv(chip, 1))


def test_gqa_fwd_bwd(chip):
    _compile(jax.grad(_loss, argnums=(0, 1, 2)), *_qkv(chip, 1))


def test_fwd_bwd_kernel_names(chip):
    """The three kernels carry the names the benchmark's trace readers
    and the breakdown show (``runtime/spans.py``)."""
    from bench.trace import kernel_names
    compiled = _compile(jax.grad(_loss, argnums=(0, 1, 2)), *_qkv(chip, 1))
    assert set(kernel_names(compiled.as_text()).values()) == {
        spans.FLASH_FWD, spans.FLASH_DQ, spans.FLASH_DKV}


@pytest.mark.parametrize("seq,heads,dim", [(32768, 16, 128), (8192, 8, 256)],
                         ids=["olmo-1b-cell", "head-dim-256"])
def test_fwd_bwd_default_tiles(chip, seq, heads, dim):
    """Fwd and bwd at the tiles the rule picks from the length (1024 at
    these lengths): the one-chip benchmark cell's attention (olmo-1b: B 1,
    seq 32768, 16 MHA heads, D 128, causal), and a head dim of 256
    (gemma3's 240, padded), whose kernels pass the default scoped VMEM
    at these tiles and must ask for more."""
    assert choose_blocks(seq, seq) == (1024, 1024)
    q, k, v = (jax.ShapeDtypeStruct((1, seq, heads, dim), jnp.bfloat16,
                                    sharding=chip) for _ in range(3))
    _compile(jax.grad(_loss, argnums=(0, 1, 2)), q, k, v)


def test_packed_fwd_bwd_batch2(chip):
    doc = jax.ShapeDtypeStruct((2, L), jnp.int32, sharding=chip)
    _compile(jax.grad(_loss, argnums=(0, 1, 2)), *_qkv(chip, 2), doc)


def test_ring_step_traced_band(chip):
    """One Double-Ring step of a cp=2, hp=2 shard: zigzag band offsets
    from traced rank indices ride into both kernels as scalar prefetch."""
    cp, s_loc = 2, L // 2
    q, k, v = _qkv(chip, 1, seq=s_loc, hq=HQ // 2, hkv=HKV // 2)
    rank = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)

    def step(q, k, v, i, j):
        band = BandMask.zigzag(i, j, s_loc // 2, cp)
        out, lse = flash_fwd_chunk(q, k, v, causal=True, band=band,
                                   impl="pallas")
        grads = flash_bwd_chunk(q, k, v, out, lse, out, causal=True,
                                band=band, impl="pallas")
        return out, lse, grads

    _compile(step, q, k, v, rank, rank)


def test_scpp_saves_fwd_residuals_olmo_cell(chip, capsys):
    """Selective Checkpoint++ at the one-chip cell's attention (olmo-1b:
    seq 32768, 16 MHA heads, D 128): the gradient of a checkpointed scan of
    two layers holds one ``flash_fwd`` kernel, the backward's recompute
    none, and the saved ``lse`` has no size-1 minor axis, which a TPU would
    pad to 128 lanes in HBM.  (One layer would not do: XLA drops a loop of
    one trip and merges the recompute's kernel into the forward's.)"""
    from bench.trace import kernel_names
    from repro.models.model import _scan_blocks, remat_policy
    seq, heads, dim = 32768, 16, 128
    width = heads * dim

    def body(x, lp):
        q, k, v = ((x @ lp[n]).reshape(1, seq, heads, dim)
                   for n in ("wq", "wk", "wv"))
        o = flash_attention(q, k, v, causal=True, impl="pallas")
        return x + o.reshape(1, seq, width) @ lp["wo"], jnp.zeros(())

    def loss(x, p):
        y = _scan_blocks(body, x, p, remat_policy("scpp"))[0]
        return y.astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((1, seq, width), jnp.bfloat16, sharding=chip)
    params = {n: jax.ShapeDtypeStruct((2, width, width), jnp.bfloat16,
                                      sharding=chip)
              for n in ("wq", "wk", "wv", "wo")}
    compiled = _compile(jax.grad(loss, argnums=1), x, params)
    kernels = list(kernel_names(compiled.as_text()).values())
    assert kernels.count(spans.FLASH_FWD) == 1, kernels

    layer = {n: jax.ShapeDtypeStruct((width, width), jnp.bfloat16)
             for n in params}
    jax.ad_checkpoint.print_saved_residuals(
        jax.checkpoint(body, policy=remat_policy("scpp")),
        jax.ShapeDtypeStruct(x.shape, x.dtype), layer)
    saved = [r for r in capsys.readouterr().out.splitlines()
             if "from the argument" not in r]
    # the kernel's folded out, and lse as (B·H, L)
    assert [r.split()[0] for r in saved] == [
        f"bf16[{heads},{seq},{dim}]", f"f32[{heads},{seq}]"], saved
    assert f"named '{spans.ATTN_LSE}'" in saved[1], saved
