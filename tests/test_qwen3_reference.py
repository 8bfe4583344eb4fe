"""Qwen3 through the program's normal path (``build_plan`` -> ``Trainer``)
trains like the benchmark's plain fp32 reference (``bench/reference.py``).

One CPU device, small widths that keep what makes the block Qwen3: QK-norm,
GQA group 2 (8 q / 4 kv heads), RMSNorm gains and the tied head.  Both
sides start from the same seeded weights and see the same two batches; the
comparison is the chip cell's (``bench/check.py``): each step's loss, the
first step's gradient and the weights' change, per leaf and per layer.
"""
import json
import os

import jax
import pytest

from bench import check, harness
from bench.program import Program
from bench.weights import Dims

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 33 + 15
STEPS = 2

#: ``bench/check.py``'s numbers and the most each may read here.  With
#: float32 compute the CPU runs both sides' matmuls in float32, so what is
#: left is the order of summation (flash-style blocks, chunked loss,
#: per-layer backward against autodiff).  Each limit also lies below what
#: a broken program reads (measured at this seed): no QK-norm reads loss
#: 7.1e-4, grad 0.98; norm gains ignored read grad 0.15, change 0.13; bf16
#: compute reads grad 1.1e-3.
LIMITS = {
    "loss": 1e-5,      # a mean over 128 tokens: reads 7.6e-8, float32's
                       # last digits
    "grad": 1e-4,      # worst leaf, per layer: reads 3.2e-7
    "change": 1e-3,    # reads 3.7e-4: the reference keeps a gain near 1
                       # and the program keeps gain - 1 near 0, so a
                       # gain's change of ~7e-5 carries float32's 6e-8
                       # steps at 1 (~4e-4 of the median leaf's change)
    "data": 0,         # the same tokens, exactly
}


def _dims():
    with open(os.path.join(REPO, "bench", "configs", "qwen3-1.7b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=8, num_key_value_heads=4, head_dim=16,
               vocab_size=512)
    cfg["precision"] = dict(cfg["precision"], compute="float32")
    return cfg, Dims.from_config(cfg)


@pytest.fixture(scope="module")
def readings():
    cfg, dims = _dims()
    with open(os.path.join(REPO, "bench", "traffic", "s32k.json")) as f:
        traffic = json.load(f)
    traffic["seq_len"] = 128
    devices = jax.devices()[:1]
    prog = Program(cfg, traffic, dims, devices, SEED)
    got, _ = harness.checked_steps(prog, STEPS)
    prog.close()
    got["data"] = prog.data_gaps(STEPS)
    ref = harness.reference_readings(dims, traffic, SEED, STEPS, devices)
    return got, ref


def test_every_leaf_is_compared(readings):
    got, ref = readings
    assert set(got["grad"]) == set(ref["grad"])
    kinds = {k.split(".")[0] for k in ref["grad"]}
    assert {"q_norm", "k_norm", "attn_norm", "mlp_norm",
            "final_norm", "embed"} <= kinds
    assert len(got["loss"]) == len(ref["loss"]) == STEPS


@pytest.mark.parametrize("number", sorted(LIMITS))
def test_program_trains_like_the_reference(readings, number):
    nums = check.numbers(*readings)
    assert nums[number] <= LIMITS[number], nums
