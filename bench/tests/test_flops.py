"""The FLOP and byte counts against the hand counts of the two cells."""
import json
import os

from bench.flops import matmul_params, step_work
from bench.weights import Dims

from conftest import REPO


def _dims(name, **over):
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(over)
    return Dims.from_config(cfg)


def test_olmo_4_layers_hand_count():
    # 6 * 371.4M * 32768 = 73.0 TFLOP of matmuls, 3 * 17.6 TFLOP of
    # causal attention: 125.8 TFLOP per step
    d = _dims("olmo-1b", num_hidden_layers=4)
    assert matmul_params(d) == 4 * (4 * 2048 ** 2 + 3 * 2048 * 8192) \
        + 50304 * 2048
    w = step_work(d, 32768, 1)
    assert abs(w.matmul_flops / 1e12 - 73.0) < 0.05
    assert abs((w.attn_fwd_flops + w.attn_bwd_flops) / 1e12 - 52.8) < 0.05
    assert abs(w.flops / 1e12 - 125.8) < 0.1


def test_qwen3_28_layers_hand_count():
    # 338.3 TFLOP of matmuls + 369.4 of attention = 707.7 per step
    d = _dims("qwen3-1.7b")
    w = step_work(d, 32768, 1)
    assert abs(w.matmul_flops / 1e12 - 338.3) < 0.1
    assert abs((w.attn_fwd_flops + w.attn_bwd_flops) / 1e12 - 369.4) < 0.1
    assert abs(w.flops / 1e12 - 707.7) < 0.2


def test_attention_bytes_and_backward():
    d = _dims("olmo-1b", num_hidden_layers=1)
    w = step_work(d, 1024, 1)
    elems = 1024 * 16 * 128
    assert w.attn_fwd_bytes == 2 * 4 * elems + 4 * 1024 * 16
    assert w.attn_bwd_bytes == 2 * 8 * elems + 8 * 1024 * 16
    assert w.attn_bwd_flops == 2 * w.attn_fwd_flops
    assert w.attn_fwd_flops == 4 * 128 * 16 * 1024 * 1025 / 2
