"""The plain reference: its layer-by-layer gradient, its attention, and the
traffic it is fed."""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as R
from bench.traffic import Stream
from bench.weights import Dims, init, seed_key

from conftest import REPO


def _tiny(arch):
    with open(os.path.join(REPO, "bench", "configs", arch + ".json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, vocab_size=512,
               num_key_value_heads=2 if cfg.get("qk_norm") else 4)
    if "head_dim" in cfg:
        cfg["head_dim"] = 16
    return Dims.from_config(cfg)


def _weights(dims):
    w = jax.jit(functools.partial(init, dims))(seed_key(5))
    # move the gains off 1 so that every leaf takes part
    return jax.tree.map(lambda a: a + 0.05 * jax.random.normal(
        jax.random.PRNGKey(1), a.shape), w)


def _dense_loss(dims, w, tokens, labels):
    """Causal attention over the whole (S, S) score matrix, one row."""
    s = tokens.shape[0]
    cos, sin = R._rope_tables(dims, s)
    x = w["embed"][tokens]
    g = dims.n_heads // dims.n_kv_heads
    for i in range(dims.layers):
        lw = jax.tree.map(lambda a: a[i], w["layers"])
        h = R._norm(dims, x, lw.get("attn_norm"))
        q = (h @ lw["wq"]).reshape(s, dims.n_heads, -1)
        k = (h @ lw["wk"]).reshape(s, dims.n_kv_heads, -1)
        v = (h @ lw["wv"]).reshape(s, dims.n_kv_heads, -1)
        if dims.qk_norm:
            q = R._rms(q, lw["q_norm"], dims.norm_eps)
            k = R._rms(k, lw["k_norm"], dims.norm_eps)
        q, k = R._rope(q, cos, sin), R._rope(k, cos, sin)
        k, v = jnp.repeat(k, g, 1), jnp.repeat(v, g, 1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
        sc = jnp.where(np.tril(np.ones((s, s), bool)), sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        x = x + o.reshape(s, -1) @ lw["wo"]
        h = R._norm(dims, x, lw.get("mlp_norm"))
        x = x + (jax.nn.silu(h @ lw["w1"]) * (h @ lw["w3"])) @ lw["w2"]
    x = R._norm(dims, x, w.get("final_norm"))
    lg = x @ w["embed"].T
    return jnp.mean(jax.nn.logsumexp(lg, -1)
                    - jnp.take_along_axis(lg, labels[:, None], 1)[:, 0])


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(R, "Q_BLOCK", 64)
    monkeypatch.setattr(R, "MLP_CHUNK", 128)
    monkeypatch.setattr(R, "LOSS_CHUNK", 128)


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen3-1.7b"])
def test_layerwise_gradient_equals_autodiff_and_dense(arch):
    dims = _tiny(arch)
    w = _weights(dims)
    tokens, labels = Stream({"kind": "affine_stream", "seq_len": 512,
                             "global_batch": 2, "noise": 0.1},
                            512, 3).logical(0)
    loss, g = R.Model(dims, "fp32").loss_and_grad(w, tokens, labels)
    with jax.default_matmul_precision("highest"):
        l_ad, g_ad = jax.value_and_grad(functools.partial(
            R.loss_fn, dims, "fp32"))(w, jnp.asarray(tokens),
                                      jnp.asarray(labels))
        dense = np.mean([float(_dense_loss(dims, w, jnp.asarray(tokens[r]),
                                           jnp.asarray(labels[r])))
                         for r in range(2)])
    assert abs(loss - float(l_ad)) < 1e-5 * abs(float(l_ad))
    assert abs(loss - dense) < 1e-5 * abs(dense)
    for a, b in zip(jax.tree.leaves(g_ad), jax.tree.leaves(g)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(
            jnp.max(jnp.abs(a)))


def test_masked_labels_leave_the_mean():
    dims = _tiny("olmo-1b")
    w = _weights(dims)
    tokens, labels = Stream({"kind": "affine_stream", "seq_len": 256,
                             "global_batch": 1, "noise": 0.1},
                            512, 4).logical(0)
    half = labels.copy()
    half[:, 128:] = -1
    model = R.Model(dims, "fp32")
    loss_half, _ = model.loss_and_grad(w, tokens, half)
    loss_first, _ = R.Model(dims, "fp32").loss_and_grad(
        w, tokens[:, :128], labels[:, :128])
    assert abs(loss_half - loss_first) < 1e-5 * loss_first


def test_stream_equals_the_programs_synthetic_stream():
    from repro.data.pipeline import DataConfig, SyntheticLM
    for vocab, seq, batch, seed in [(512, 300, 2, 3),
                                    (151936, 2048, 1, 2 ** 40 + 7)]:
        st = Stream({"kind": "affine_stream", "seq_len": seq,
                     "global_batch": batch, "noise": 0.1}, vocab, seed)
        syn = SyntheticLM(DataConfig(vocab=vocab, seq_len=seq,
                                     global_batch=batch, seed=seed,
                                     zigzag=False))
        for step in (0, 5):
            tokens, labels = st.logical(step)
            b = syn.batch(step)
            np.testing.assert_array_equal(tokens, b["tokens"])
            np.testing.assert_array_equal(labels, b["labels"])


def test_seed_wider_than_32_bits():
    a, b = seed_key(2 ** 31 + 5), seed_key(2 ** 31 + 6)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(b))
    np.testing.assert_array_equal(jax.random.key_data(a),
                                  jax.random.key_data(seed_key(2 ** 31 + 5)))


def test_reference_split_over_four_devices_trains_alike():
    import subprocess
    import sys
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "bench", "tests",
                                     "_mesh_reference.py")],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    gap = json.loads(r.stdout.strip().splitlines()[-1])["gap"]
    assert gap < 1e-4, gap
