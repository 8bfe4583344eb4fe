"""Per-architecture smoke tests (reduced configs, 1 CPU device):
forward/train step with shape + finiteness asserts, prefill/decode paths,
decode == incremental-forward consistency, and a short learning check."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import all_arch_names, get_config, get_reduced
from repro.configs.common import SHAPES, applicable_shapes
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.decode import (decode_step, grow_caches,
                                 init_caches, prefill)
from repro.models.model import forward_loss, init_params

ARCHS = all_arch_names()


def _batch(cfg, b, s, key=0):
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=s,
                                  global_batch=b, cp=1, zigzag=False,
                                  seed=key), cfg)
    return {k: jnp.asarray(v) for k, v in data.batch(0).items()}


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch, single_runtime):
    rt = single_runtime
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, 2, 32)
    with rt.mesh:
        (loss, metrics), grads = jax.jit(jax.value_and_grad(
            lambda p: forward_loss(p, batch, rt, cfg),
            has_aux=True))(params)
    assert np.isfinite(float(loss)), arch
    assert float(metrics["n_tokens"]) == 64
    gnorm = np.sqrt(sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
                        for g in jax.tree.leaves(grads)))
    assert np.isfinite(gnorm) and gnorm > 0, arch
    # every param leaf matches its grad leaf's shape
    for p, g in zip(jax.tree.leaves(params), jax.tree.leaves(grads)):
        assert p.shape == g.shape


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_decode(arch, single_runtime):
    rt = single_runtime
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    pf = {"tokens": batch["tokens"]}
    if cfg.family == "encdec":
        pf["frames"] = batch["frames"]
    with rt.mesh:
        logits, caches = prefill(params, pf, rt, cfg)
        assert logits.shape == (B, 1, cfg.vocab)
        assert np.isfinite(np.asarray(logits)).all(), arch
        nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        lg2, caches2 = decode_step(params, caches, nxt, jnp.int32(S), rt,
                                   cfg)
        assert lg2.shape == (B, 1, cfg.vocab)
        assert np.isfinite(np.asarray(lg2)).all(), arch
        # cache pytree structure is stable across steps
        assert jax.tree.structure(caches) == jax.tree.structure(caches2)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "gemma2-2b",
                                  "deepseek-v2-lite-16b",
                                  "falcon-mamba-7b", "zamba2-7b",
                                  "whisper-small"])
def test_decode_matches_incremental_forward(arch, single_runtime):
    """Greedy continuation via decode_step == re-running prefill on the
    extended prompt (KV caches, ring buffers and SSM states are exact)."""
    rt = single_runtime
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S, T = 1, 16, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + T), 0,
                                cfg.vocab)
    pf = {"tokens": tokens[:, :S]}
    full = {"tokens": tokens}
    if cfg.family == "encdec":
        frames = jax.random.normal(jax.random.PRNGKey(2),
                                   (B, cfg.enc_frames, cfg.d_model))
        pf["frames"] = frames
        full["frames"] = frames
    with rt.mesh:
        # decode path: prefill on S tokens then feed the known next tokens
        _, caches = prefill(params, pf, rt, cfg)
        caches = grow_caches(cfg, caches, T)
        dec_logits = []
        for t in range(T):
            lg, caches = decode_step(params, caches, tokens[:, S + t:S + t + 1],
                                     jnp.int32(S + t), rt, cfg)
            dec_logits.append(np.asarray(lg[:, 0]))
        # oracle: prefill over the full prompt gives the last-token logits
        lg_full, _ = prefill(params, full, rt, cfg)
    np.testing.assert_allclose(dec_logits[-1], np.asarray(lg_full[:, 0]),
                               atol=2e-3, rtol=2e-3)


def test_full_configs_match_assignment():
    """The full-size configs carry the exact assigned dims."""
    spec = {
        "whisper-small": (12, 768, 12, 12, 3072, 51865),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "gemma2-2b": (26, 2304, 8, 4, 9216, 256000),
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 1408, 102400),
        "chameleon-34b": (48, 8192, 64, 8, 22016, 65536),
        "falcon-mamba-7b": (64, 4096, 1, 1, 0, 65024),
    }
    for arch, (L, d, h, kv, ff, v) in spec.items():
        cfg = get_config(arch)
        assert cfg.num_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.n_heads == h, arch
        assert cfg.n_kv_heads == kv, arch
        assert cfg.d_ff == ff, arch
        assert cfg.vocab == v, arch
    assert get_config("qwen3-moe-30b-a3b").moe.n_experts == 128
    assert get_config("qwen3-moe-30b-a3b").moe.top_k == 8
    assert get_config("deepseek-v2-lite-16b").moe.n_experts == 64
    assert get_config("deepseek-v2-lite-16b").moe.top_k == 6
    assert get_config("deepseek-v2-lite-16b").mla.kv_lora == 512
    assert get_config("zamba2-7b").ssm2.d_state == 64
    assert get_config("falcon-mamba-7b").ssm1.d_state == 16


def test_shape_applicability():
    assert "long_500k" not in applicable_shapes("qwen3-1.7b")
    assert "long_500k" in applicable_shapes("falcon-mamba-7b")
    assert "long_500k" in applicable_shapes("zamba2-7b")
    assert "long_500k" in applicable_shapes("gemma3-12b")
    assert "decode_32k" in applicable_shapes("whisper-small")
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["long_500k"].seq_len == 524288


def test_selective_checkpoint_changes_residuals(single_runtime):
    """SC++ ('scpp') vs full remat produce identical losses/grads but
    different saved-residual sets (sanity that the policy is wired)."""
    import dataclasses
    rt = single_runtime
    base = get_reduced("qwen3-1.7b")
    batch = _batch(base, 2, 32)
    results = {}
    for remat in ("none", "full", "scpp"):
        cfg = dataclasses.replace(base, remat=remat)
        params = init_params(cfg, jax.random.PRNGKey(0))
        with rt.mesh:
            loss, grads = jax.value_and_grad(
                lambda p: forward_loss(p, batch, rt, cfg)[0])(params)
        results[remat] = (float(loss), grads)
    for a in ("full", "scpp"):
        assert abs(results[a][0] - results["none"][0]) < 1e-5
        for g1, g2 in zip(jax.tree.leaves(results[a][1]),
                          jax.tree.leaves(results["none"][1])):
            np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("packed", [False, True], ids=["flash", "flash_doc"])
def test_scpp_saves_flash_residuals(packed):
    """Selective Checkpoint++ saves the flash kernel's ``out`` and ``lse``
    (named in its ``custom_vjp`` forward rule): the grad of a scan of two
    checkpointed attention layers holds one ``flash_fwd`` call for the
    scan body, where ``full`` remat reruns it in the backward, and its
    grads equal those of no remat."""
    from _dist_checks import kernel_calls
    from repro.kernels.ops import flash_attention
    from repro.models.model import _scan_blocks, remat_policy
    from repro.runtime import spans

    B, S, H, D, L = 1, 256, 2, 64, 2
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    params = {n: 0.1 * jax.random.normal(k, (L, H * D, H * D))
              for n, k in zip(("wq", "wk", "wv", "wo"), keys)}
    x = jax.random.normal(keys[4], (B, S, H * D))
    doc = (jnp.where(jnp.arange(S) < 100, 0, 100)[None]
           if packed else None)

    def body(x, lp):
        q, k, v = ((x @ lp[n]).reshape(B, S, H, D) for n in ("wq", "wk",
                                                              "wv"))
        o = flash_attention(q, k, v, causal=True, q_doc_start=doc,
                            impl="pallas_interpret", block_q=128,
                            block_k=128)
        return x + o.reshape(B, S, H * D) @ lp["wo"], jnp.zeros(())

    def grad(remat):
        def loss(p):
            return (_scan_blocks(body, x, p, remat_policy(remat))[0]
                    ** 2).sum()
        return jax.grad(loss)

    grads = {}
    for remat, n_fwd in (("scpp", 1), ("full", 2), ("none", 1)):
        names = kernel_calls(jax.make_jaxpr(grad(remat))(params).jaxpr)
        assert names.count(spans.FLASH_FWD) == n_fwd, (remat, names)
        grads[remat] = grad(remat)(params)
    for n in params:
        np.testing.assert_allclose(grads["scpp"][n], grads["none"][n],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.dist
def test_scpp_ring_saves_kernel_residuals():
    """``tests/_dist_checks.py::check_scpp_ring_saves_kernel_residuals``:
    the same on the hp2 x cp2 ring, in a process with four devices."""
    from _dist_run import run_check
    run_check("scpp_ring_saves_kernel_residuals")
