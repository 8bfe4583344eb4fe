"""Substrate tests: zigzag layout, ZeRO sharding rules, checkpoint/restore
(incl. elastic reshard), optimizer, compression, resilience utilities."""
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.zigzag import (from_zigzag, to_zigzag, zigzag_indices,
                               zigzag_inverse)
from repro.core.topology import ParallelConfig, make_mesh
from repro.core.zero import leaf_spec, zero_shardings
from repro.runtime import checkpoint as ckpt
from repro.runtime.resilience import StepMonitor, elastic_plan
from repro.train.optimizer import (OptConfig, adamw_update, dequantize_int8,
                                   global_norm, init_opt_state,
                                   quantize_int8, schedule)


# ---------------------------------------------------------------------------
# zigzag
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(cp=st.sampled_from([1, 2, 4, 8, 16]), mult=st.integers(1, 4))
def test_zigzag_inverse_property(cp, mult):
    s = 2 * cp * mult
    idx = zigzag_indices(s, cp)
    inv = zigzag_inverse(s, cp)
    assert (idx[inv] == np.arange(s)).all()
    assert sorted(idx.tolist()) == list(range(s))


def test_zigzag_balanced_ownership():
    """rank r owns logical chunks (r, 2cp-1-r)."""
    s, cp = 32, 4
    c = s // (2 * cp)
    idx = zigzag_indices(s, cp)
    for r in range(cp):
        block = idx[r * 2 * c:(r + 1) * 2 * c]
        chunks = sorted(set(b // c for b in block))
        assert chunks == [r, 2 * cp - 1 - r]


def test_zigzag_roundtrip_array():
    x = jnp.arange(2 * 48).reshape(2, 48)
    y = from_zigzag(to_zigzag(x, 4), 4)
    np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# ZeRO sharding rules
# ---------------------------------------------------------------------------

def test_zero_leaf_rules():
    # leaf_spec only reads mesh.shape — an abstract 8-way mesh suffices
    names = ("pod", "data", "head", "outer", "inner")
    sizes = (1, 2, 2, 1, 2)
    mesh = jax.sharding.AbstractMesh(sizes, names)
    # big leaf divisible by full group (8) -> sharded on largest dim
    spec = leaf_spec((128, 512), mesh)
    assert spec[1] is not None
    # tiny leaf -> replicated
    assert leaf_spec((8,), mesh) == jax.sharding.PartitionSpec()
    # divisible only by dp (2-way) -> falls back to a smaller group
    spec = leaf_spec((100002, 7), mesh)
    assert spec != jax.sharding.PartitionSpec()


def test_zero_shardings_cover_params(single_runtime):
    from repro.configs import get_reduced
    from repro.models.model import init_params
    cfg = get_reduced("qwen3-1.7b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    sh = zero_shardings(params, single_runtime.mesh)
    assert jax.tree.structure(sh) == jax.tree.structure(params)


# ---------------------------------------------------------------------------
# checkpoint / elastic restore
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip():
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32)}}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(tree, 7, d)
        assert ckpt.list_steps(d) == [7]
        restored, step = ckpt.restore(tree, d)
        assert step == 7
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(a, b)


def test_checkpoint_atomicity_tmp_invisible():
    tree = {"a": jnp.zeros((4,))}
    with tempfile.TemporaryDirectory() as d:
        os.makedirs(os.path.join(d, "step_00000009.tmp"))
        assert ckpt.list_steps(d) == []          # half-written is invisible
        ckpt.save(tree, 9, d)
        assert ckpt.list_steps(d) == [9]


def test_elastic_restore_resharding():
    """Save under one sharding, restore under another — the elastic path."""
    pc = ParallelConfig(dp=1)
    mesh = make_mesh(pc, devices=jax.devices()[:1])
    x = {"w": jnp.arange(64.0).reshape(8, 8)}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(x, 0, d)
        sh = zero_shardings(x, mesh)
        restored, _ = ckpt.restore(x, d, shardings=sh)
        np.testing.assert_array_equal(restored["w"], x["w"])


# ---------------------------------------------------------------------------
# optimizer + compression
# ---------------------------------------------------------------------------

def test_adamw_decreases_quadratic():
    p = {"w": jnp.array([3.0, -2.0])}
    s = init_opt_state(p)
    cfg = OptConfig(lr=0.1, warmup_steps=0, total_steps=1000,
                    weight_decay=0.0, clip_norm=1e9)
    for _ in range(200):
        g = {"w": 2 * p["w"]}
        p, s, _ = adamw_update(p, g, s, cfg)
    assert float(jnp.abs(p["w"]).max()) < 0.2


def test_schedule_shape():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100,
                    min_lr_ratio=0.1)
    lrs = [float(schedule(cfg, jnp.int32(s))) for s in range(101)]
    assert lrs[0] < lrs[9] <= 1.0
    assert abs(lrs[10] - 1.0) < 1e-6
    assert lrs[100] == pytest.approx(0.1, abs=1e-5)


def test_grad_clip():
    p = {"w": jnp.zeros((2,))}
    s = init_opt_state(p)
    cfg = OptConfig(lr=1.0, warmup_steps=0, clip_norm=1.0,
                    weight_decay=0.0)
    _, _, m = adamw_update(p, {"w": jnp.array([30.0, 40.0])}, s, cfg)
    assert float(m["grad_norm"]) == pytest.approx(50.0, rel=1e-5)


@pytest.mark.parametrize("path,decayed", [
    (("blocks", 0, "ln1", "w"), False),         # stacked (L, d) gain
    (("blocks", 0, "attn", "qn", "w"), False),  # stacked (L, hd) QK-norm
    (("final_norm", "w"), False),               # (d,) gain
    (("blocks", 0, "attn", "wq", "b"), False),  # stacked (L, e) bias
    (("blocks", 0, "attn", "wq", "w"), True),   # stacked (L, d, e) matrix
    (("embed", "table"), True),                 # (V, d) matrix
])
def test_weight_decay_on_matrices_only(path, decayed):
    """With a zero gradient the step is the decay alone: a matrix shrinks
    by ``lr * weight_decay``; a norm gain, stacked over layers or not, is
    left as it was, and so is a bias."""
    p = {"embed": {"table": jnp.ones((8, 4))},
         "final_norm": {"w": jnp.ones((4,))},
         "blocks": [{"ln1": {"w": jnp.ones((2, 4))},
                     "attn": {"qn": {"w": jnp.ones((2, 3))},
                              "wq": {"w": jnp.ones((2, 4, 6)),
                                     "b": jnp.ones((2, 6))}}}]}
    cfg = OptConfig(lr=1.0, warmup_steps=0, total_steps=10 ** 6,
                    weight_decay=0.1)
    new, _, _ = adamw_update(p, jax.tree.map(jnp.zeros_like, p),
                             init_opt_state(p), cfg)
    leaf = new
    for key in path:
        leaf = leaf[key]
    want = 1.0 - 0.1 * float(schedule(cfg, jnp.int32(1))) if decayed else 1.0
    np.testing.assert_allclose(np.asarray(leaf), want, rtol=1e-6)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2 ** 16), steps=st.integers(5, 30))
def test_int8_error_feedback_unbiased(seed, steps):
    """Error feedback: the *cumulative* quantized sum tracks the exact sum
    to within one quantization step (not O(steps) drift)."""
    rng = np.random.default_rng(seed)
    err = jnp.zeros((32,))
    acc_q = np.zeros((32,))
    acc_x = np.zeros((32,))
    max_scale = 0.0
    for s in range(steps):
        x = jnp.asarray(rng.standard_normal(32), jnp.float32)
        q, scale, err = quantize_int8(x, err)
        acc_q += np.asarray(dequantize_int8(q, scale))
        acc_x += np.asarray(x)
        max_scale = max(max_scale, float(scale))
    assert np.abs(acc_q - acc_x).max() <= max_scale * 1.01 + 1e-6


# ---------------------------------------------------------------------------
# resilience
# ---------------------------------------------------------------------------

def test_step_monitor_flags_stragglers():
    m = StepMonitor(window=20, threshold=1.5)
    for i in range(20):
        m.record(i, 1.0)
    m.record(20, 5.0)
    assert len(m.flagged) == 1
    assert m.report()["stragglers"][0][0] == 20


def test_elastic_plan_valid():
    for chips in (256, 128, 64, 48, 17, 8, 1):
        pc = elastic_plan(chips, kv_heads=8, n_heads=16)
        assert pc.num_devices <= chips
        assert 16 % pc.hp == 0 or pc.hp == 1


def test_data_determinism_and_layout():
    from repro.data.pipeline import DataConfig, SyntheticLM
    d1 = SyntheticLM(DataConfig(vocab=100, seq_len=16, global_batch=2,
                                cp=4, zigzag=True, seed=3))
    d2 = SyntheticLM(DataConfig(vocab=100, seq_len=16, global_batch=2,
                                cp=4, zigzag=True, seed=3))
    b1, b2 = d1.batch(5), d2.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # positions are the zigzag permutation itself
    np.testing.assert_array_equal(b1["positions"][0],
                                  zigzag_indices(16, 4))


def test_global_norm():
    assert float(global_norm({"a": jnp.array([3.0]),
                              "b": jnp.array([4.0])})) == pytest.approx(5.0)
