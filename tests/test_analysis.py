"""Analysis-layer units: HLO collective parser, wire model, roofline terms,
report rendering — these numbers are the §Roofline deliverable, so they get
their own oracle tests."""
import numpy as np
import pytest

from repro.analysis.hlo import (_parse_def, _participants, _shape_bytes,
                                _wire_multiplier, parse_collective_bytes)
from repro.analysis.roofline import (RooflineTerms, terms_from_record,
                                     model_flops)

HLO = """
HloModule jit_step

ENTRY %main (p0: f32[16,128]) -> f32[16,128] {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ag = f32[256,128]{1,0} all-gather(%p0), channel_id=1, replica_groups=[16,16]<=[256], dimensions={0}
  %ar = f32[16,128]{1,0} all-reduce(%p0), channel_id=2, replica_groups=[1,256]<=[256], to_apply=%add
  %cp = f32[16,128]{1,0} collective-permute(%p0), channel_id=3, source_target_pairs={{0,1},{1,0}}
  %a2a = (f32[4,128]{1,0}, f32[4,128]{1,0}) all-to-all(%p0, %p0), channel_id=4, replica_groups=[64,4]<=[256]
  ROOT %out = f32[16,128]{1,0} add(%ar, %cp)
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[16,128]{1,0}") == 16 * 128 * 4
    assert _shape_bytes("bf16[8]") == 16
    assert _shape_bytes("(f32[2,2]{1,0}, bf16[4]{0})") == 16 + 8
    assert _shape_bytes("pred[100]") == 100
    assert _shape_bytes("token[]") == 0


def test_parse_def_variants():
    name, shape, op, operands = _parse_def(
        "  %all-gather.93 = f32[2048]{0} all-gather(%x.1), channel_id=2")
    assert name == "all-gather.93" and op == "all-gather"
    assert "%x.1" in operands
    # tuple-shaped with comments
    name, shape, op, _ = _parse_def(
        "  %a = (f32[1]{0}, /*index=1*/f32[1]{0}) all-to-all(%b, %c), x=1")
    assert op == "all-to-all" and _shape_bytes(shape) == 8


def test_participants():
    assert _participants("replica_groups=[16,16]<=[256]") == 16
    assert _participants("replica_groups={{0,1,2,3}}") == 4
    assert _participants("no groups here") == 2


def test_wire_multipliers():
    assert _wire_multiplier("all-reduce", 2) == pytest.approx(1.0)
    assert _wire_multiplier("all-reduce", 256) == pytest.approx(2 * 255 / 256)
    assert _wire_multiplier("all-gather", 16) == 15.0
    assert _wire_multiplier("reduce-scatter", 4) == pytest.approx(0.75)
    assert _wire_multiplier("collective-permute", 8) == 1.0
    assert _wire_multiplier("all-reduce", 1) == 0.0


def test_parse_collective_bytes_end_to_end():
    r = parse_collective_bytes(HLO)
    sz = 16 * 128 * 4
    assert r["by_op"]["all-gather"] == sz
    assert r["by_op"]["all-reduce"] == sz
    assert r["by_op"]["collective-permute"] == sz
    assert r["by_op"]["all-to-all"] == 2 * sz
    assert r["counts"] == {"all-gather": 1, "all-reduce": 1,
                           "collective-permute": 1, "all-to-all": 1}
    # wire: ag over 16 => (16-1)*sz; ar over 256 => 2*255/256*sz
    assert r["wire_by_op"]["all-gather"] == 15 * sz
    assert r["wire_by_op"]["all-reduce"] == int(2 * 255 / 256 * sz)


def test_roofline_terms_and_dominance():
    rec = {"chips": 256,
           "cost": {"flops": 197e12, "bytes_accessed": 819e9 * 2},
           "collectives": {"total": 1, "wire_total": 50e9 * 0.5},
           "model_flops": 197e12 * 256 * 0.5}
    t = terms_from_record(rec)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(2.0)
    assert t.collective_s == pytest.approx(0.5)
    assert t.dominant == "memory"
    assert t.useful_ratio == pytest.approx(0.5)
    # roofline fraction = (useful/total compute) * compute / bound
    assert t.roofline_fraction == pytest.approx(0.5 * 1.0 / 2.0)


def test_model_flops_kinds():
    class Cfg:
        moe = None
    assert model_flops(Cfg, "train", 1024, 8, 1_000_000) == \
        6.0 * 1_000_000 * 1024 * 8
    assert model_flops(Cfg, "prefill", 1024, 8, 10) == 2.0 * 10 * 8192
    assert model_flops(Cfg, "decode", 1024, 8, 10) == 2.0 * 10 * 8


def test_count_params_moe_active():
    from repro.analysis.roofline import count_params
    from repro.configs import get_reduced
    cfg = get_reduced("qwen3-moe-30b-a3b")
    total, active = count_params(cfg)
    assert active < total  # experts discounted by top_k / n_experts
    dense_total, dense_active = count_params(get_reduced("olmo-1b"))
    assert dense_total == dense_active


def test_peak_flops_by_device_kind():
    from repro.analysis.cost import PEAK_BF16_FLOPS, V5E, peak_flops
    assert peak_flops("TPU v5 lite") == V5E.peak == 197e12
    assert set(PEAK_BF16_FLOPS) == {"TPU v5 lite"}
    with pytest.raises(KeyError, match="TPU v9"):
        peak_flops("TPU v9")
