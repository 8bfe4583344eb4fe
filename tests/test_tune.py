"""PlanTuner: search-space feasibility, the paper's placement-crossover
ordering, winner optimality, TunedPlan round-trip through build_plan, and
the shared cost-model surface."""
import json

import jax
import pytest

from repro.analysis.cost import (AttnCase, CostConstants, V5E,
                                 attention_op_time, end_to_end_mfu)
from repro.configs import get_reduced
from repro.core.plan import build_plan, plan_memory
from repro.core.topology import ParallelConfig
from repro.tune import TunedPlan, enumerate_space, tune
from repro.tune.space import hp_choices, seq_ok


class FakeDev:
    def __init__(self, i):
        self.id = i

    def __repr__(self):
        return f"d{self.id}"


def _fake_devs(n):
    return [FakeDev(i) for i in range(n)]


CFG = get_reduced("qwen3-1.7b")          # 4 q-heads, 2 kv-heads


# ---------------------------------------------------------------------------
# stage 1: enumeration respects the hard constraints + the memory model
# ---------------------------------------------------------------------------

def test_space_is_feasible():
    """Every enumerated candidate validates, divides, shards the batch,
    and fits the plan memory model — no infeasible point reaches
    scoring (and so none can win)."""
    cands = enumerate_space(CFG, num_devices=8, seq_len=256,
                            global_batch=8, memory_budget_gb=1.0)
    assert cands
    for c in cands:
        c.pc.validate()
        pc = c.pc
        assert pc.num_devices == 8
        assert CFG.n_heads % pc.hp == 0
        if pc.hp > CFG.n_kv_heads:
            assert pc.hp % CFG.n_kv_heads == 0
        assert 256 % pc.sp == 0
        if pc.cp > 1:
            assert (256 // pc.cp) % 2 == 0          # zigzag half-chunks
        assert 8 % c.grad_accum == 0
        assert (8 // c.grad_accum) % (pc.pods * pc.dp) == 0
        assert c.remat in ("none", "scpp", "full")
        assert c.mem["fits"], c.tag
        # the candidate's memory verdict is *the* build_plan model
        _, _, _, mem = plan_memory(
            CFG, pc, grad_accum=c.grad_accum, remat=c.remat, zero=c.zero,
            memory_budget_gb=1.0, seq_len=256, global_batch=8)
        assert mem["total_dev"] == c.mem["total_dev"]


def test_space_contains_the_degenerate_corners():
    """DeepSpeed-Ulysses (hp=sp) and Megatron-CP (cp=sp) are corners of
    the enumerated space, not separate systems."""
    cands = enumerate_space(CFG, num_devices=8, seq_len=256,
                            global_batch=8, dp=2, memory_budget_gb=1.0)
    splits = {(c.pc.hp, c.pc.cp) for c in cands}
    assert (4, 1) in splits                  # Ulysses corner (hp=sp=4)
    assert (1, 4) in splits                  # Megatron-CP corner
    assert (2, 2) in splits                  # a true 2D point


def test_hp_choices_respect_gqa_replication():
    import dataclasses
    # heads=4, kv=2: hp=4 needs 4 % 2 == 0 (KV replication) -> allowed;
    # a 3-way split never divides the head count.
    assert hp_choices(CFG, 4) == [1, 2, 4]
    assert 3 not in hp_choices(CFG, 6)
    # below H_kv the KV heads shard over hp: 6 kv heads cannot split 4
    # ways even though 24 q heads can.
    odd = dataclasses.replace(CFG, n_heads=24, n_kv_heads=6)
    assert 4 not in hp_choices(odd, 4)
    assert hp_choices(odd, 12) == [1, 2, 3, 6, 12]


def test_seq_divisibility_gates_zigzag():
    assert seq_ok(CFG, 4, 4, 256)
    assert not seq_ok(CFG, 3, 3, 256)        # 256 % 3 != 0
    assert not seq_ok(CFG, 256, 256, 256)    # per-rank chunk of 1: no halves


def test_degenerate_placement_deduped():
    """hp==1 / cp==1 grids have one physical device order; only the
    canonical placement is enumerated there."""
    cands = enumerate_space(CFG, num_devices=8, seq_len=256,
                            global_batch=8, dp=2, memory_budget_gb=1.0)
    for c in cands:
        if c.pc.cp == 1:
            assert c.pc.placement == "head_first"
        elif c.pc.hp == 1:
            assert c.pc.placement == "context_first"


# ---------------------------------------------------------------------------
# stage 2: the analytic ranking reproduces the paper's placement analysis
# ---------------------------------------------------------------------------

def _attn_time(h_kv, s, hp, placement, sp=64):
    c = AttnCase(s=s, h_kv=h_kv, sp=sp, hp=hp, placement=placement)
    return attention_op_time(c) + attention_op_time(c, backward=True)


def test_placement_crossover_head_vs_context_first():
    """The §4.4 analysis, executable: at 128k MHA on 64-way SP,
    context-first wins the ring-dominated low-hp points and head-first
    wins once the SeqAlltoAll dominates (hp >= 8) — the crossover the
    paper's Table 3 placement columns show."""
    for hp in (2, 4):
        assert _attn_time(32, 131072, hp, "context_first") < \
            _attn_time(32, 131072, hp, "head_first"), hp
    for hp in (8, 16, 32):
        assert _attn_time(32, 131072, hp, "head_first") < \
            _attn_time(32, 131072, hp, "context_first"), hp
    # GQA's small KV chunks never let the rings dominate: head-first
    # wins the whole hp sweep (the paper's GQA rows).
    for hp in (2, 4, 8, 16, 32):
        assert _attn_time(8, 131072, hp, "head_first") < \
            _attn_time(8, 131072, hp, "context_first"), hp


def test_interior_2d_point_beats_both_corners():
    """Table-2 shape: MHA at 128k on 32-way SP — a 2D split (hp=4)
    out-MFUs both DeepSpeed-Ulysses (hp=sp) and pure ring-CP (hp=1)."""
    mfu = {hp: end_to_end_mfu(AttnCase(s=131072, h_kv=32, sp=32, hp=hp))
           for hp in (1, 4, 32)}
    assert mfu[4] > mfu[1]
    assert mfu[4] > mfu[32]


def test_winner_is_the_analytic_minimum():
    r = tune(CFG, num_devices=8, seq_len=256, global_batch=8,
             memory_budget_gb=1.0)
    assert r.ranked
    assert r.winner.score_s == min(s.score_s for s in r.ranked)
    assert r.winner.cand.mem["fits"]
    assert r.space_size == len(r.ranked)


def test_calibrated_constants_rescale_not_reorder():
    """A uniform bandwidth/flops rescale must not change the placement
    ordering (the trade-off is a bw *ratio*)."""
    const = CostConstants(peak=V5E.peak / 50, hbm=V5E.hbm / 50,
                          ici=V5E.ici / 50, source="test")
    c_hf = AttnCase(s=131072, h_kv=32, sp=64, hp=2,
                    placement="head_first")
    c_cf = AttnCase(s=131072, h_kv=32, sp=64, hp=2,
                    placement="context_first")
    assert attention_op_time(c_cf, const=const) < \
        attention_op_time(c_hf, const=const)


# ---------------------------------------------------------------------------
# TunedPlan round-trip through build_plan
# ---------------------------------------------------------------------------

def test_tuned_plan_roundtrips_through_build_plan(tmp_path):
    r = tune(CFG, num_devices=8, seq_len=256, global_batch=8,
             memory_budget_gb=1.0)
    tp = r.tuned_plan()
    path = tp.save(str(tmp_path / "plan.json"))
    loaded = TunedPlan.load(path)
    assert loaded == tp

    devs = _fake_devs(8)
    via_tuned = build_plan(CFG, devices=devs, tuned=loaded)
    explicit = build_plan(CFG, loaded.parallel(), devices=devs,
                          grad_accum=loaded.grad_accum,
                          remat=loaded.remat, zero=loaded.zero,
                          seq_len=loaded.seq_len,
                          global_batch=loaded.global_batch)
    assert via_tuned.pc == explicit.pc == tp.parallel()
    assert via_tuned.grad_accum == explicit.grad_accum == tp.grad_accum
    assert via_tuned.cfg.remat == explicit.cfg.remat == tp.remat
    assert via_tuned.zero_mode == explicit.zero_mode
    assert via_tuned.zero_groups == explicit.zero_groups
    assert via_tuned.mem == explicit.mem
    assert via_tuned.seq_len == tp.seq_len


def test_tuned_plan_defaults_lose_to_explicit_args(tmp_path):
    tp = TunedPlan(arch="x", num_devices=4, seq_len=256, global_batch=8,
                   dp=2, hp=2, grad_accum=2, remat="full", zero="dp")
    plan = build_plan(CFG, devices=_fake_devs(4), tuned=tp,
                      grad_accum=4, remat="none", seq_len=128,
                      global_batch=16)
    assert plan.grad_accum == 4              # explicit beats tuned
    assert plan.cfg.remat == "none"
    assert plan.seq_len == 128 and plan.global_batch == 16
    assert plan.pc == tp.parallel()          # pc still from the file
    # explicitly passing the library default (1 / "auto") also wins
    plan1 = build_plan(CFG, devices=_fake_devs(4), tuned=tp,
                       grad_accum=1, zero="auto", seq_len=256,
                       global_batch=8)
    assert plan1.grad_accum == 1
    assert plan1.zero_mode == "replica"      # auto on a tiny model


def test_tuned_plan_json_is_versioned_and_forward_safe(tmp_path):
    tp = TunedPlan(arch="x", num_devices=1, seq_len=64, global_batch=4)
    d = tp.to_json()
    d["some_future_field"] = 123             # unknown keys are dropped
    assert TunedPlan.from_json(d) == tp
    with open(tmp_path / "future.json", "w") as f:
        json.dump({**d, "version": 99}, f)
    with pytest.raises(AssertionError):
        TunedPlan.load(str(tmp_path / "future.json"))


# ---------------------------------------------------------------------------
# shared cost model surface
# ---------------------------------------------------------------------------

def test_attncase_from_plan():
    pc = ParallelConfig(dp=2, hp=2, cp_outer=1, cp_inner=2)
    plan = build_plan(CFG, pc, devices=_fake_devs(8), seq_len=256,
                      global_batch=8)
    c = AttnCase.from_plan(plan)
    assert (c.s, c.d, c.h, c.h_kv) == (256, CFG.d_model, CFG.n_heads,
                                       CFG.n_kv_heads)
    assert (c.sp, c.hp, c.w, c.placement) == (4, 2, 2, "head_first")
    assert c.cp == 2


def test_roofline_constants_are_the_cost_models():
    from repro.analysis import cost, roofline
    assert roofline.PEAK_FLOPS == cost.PEAK
    assert roofline.ICI_BW == cost.ICI


# ---------------------------------------------------------------------------
# stage 3: live measurement (1-device, reduced config — cheap)
# ---------------------------------------------------------------------------

def test_measure_top_reranks_with_wall_clock():
    r = tune(CFG, num_devices=1, seq_len=64, global_batch=2,
             memory_budget_gb=1.0, measure_top_k=1, measure_steps=1,
             accums=(1,), remats=("none",), zeros=("replica",))
    w = r.winner
    assert w.measured_s is not None and w.measured_s > 0
    assert r.ranked[0] is w                  # re-ranked measured-first
    tp = r.tuned_plan()
    assert tp.measured_s == w.measured_s
    # a measured winner still builds + runs
    plan = build_plan(CFG, devices=jax.devices()[:1], tuned=tp)
    assert plan.pc == tp.parallel()


# ---------------------------------------------------------------------------
# FPDT chunk-offload candidates
# ---------------------------------------------------------------------------

def test_chunks_ok_divisibility_and_zigzag():
    from repro.tune.space import chunks_ok
    pc = ParallelConfig(dp=1, hp=2, cp_outer=2, cp_inner=2)   # sp=8, cp=4
    assert chunks_ok(CFG, pc, 1024, 4)        # sc=256 shards fine
    assert not chunks_ok(CFG, pc, 1024, 3)    # 1024 % 3 != 0
    assert not chunks_ok(CFG, pc, 1024, 256)  # sc=4 < sp
    # zigzag needs an even per-cp-rank sub-chunk: sc=6 over cp=2 is odd
    pc2 = ParallelConfig(dp=1, hp=1, cp_outer=2, cp_inner=1)
    assert not chunks_ok(CFG, pc2, 24, 4)
    assert chunks_ok(CFG, pc2, 32, 4)


def test_offload_candidates_only_when_resident_infeasible():
    """Offload points appear exactly where they help: the resident twin
    must not fit (activations over budget) while the state does — and a
    short-sequence, ample-budget space stays fully resident."""
    cands = enumerate_space(CFG, num_devices=8, seq_len=131072,
                            global_batch=8, memory_budget_gb=0.05)
    offs = [c for c in cands if c.offload_chunks > 1]
    assert offs, "no offload candidates at the infeasible long-seq point"
    for c in offs:
        assert c.mem["fits"] and c.mem["fits_state"]
        assert c.tag.endswith(f".off{c.offload_chunks}")
        _, _, _, mem_r = plan_memory(
            CFG, c.pc, grad_accum=c.grad_accum, remat=c.remat,
            zero=c.zero, memory_budget_gb=0.05, seq_len=131072,
            global_batch=8)
        assert not mem_r["fits"], c.tag       # the resident twin does not fit
    easy = enumerate_space(CFG, num_devices=8, seq_len=256,
                           global_batch=8, memory_budget_gb=1.0)
    assert easy and all(c.offload_chunks == 1 for c in easy)


def test_tuner_prefers_offload_when_resident_infeasible():
    r = tune(CFG, num_devices=8, seq_len=131072, global_batch=8,
             memory_budget_gb=0.05)
    w = r.winner.cand
    assert w.offload_chunks > 1
    tp = r.tuned_plan()
    assert tp.offload_chunks == w.offload_chunks
    plan = build_plan(CFG, devices=_fake_devs(8), tuned=tp,
                      seq_len=131072, global_batch=8,
                      memory_budget_gb=0.05)
    assert plan.offload_chunks == tp.offload_chunks


def test_tuner_stays_resident_when_it_fits():
    r = tune(CFG, num_devices=8, seq_len=256, global_batch=8,
             memory_budget_gb=1.0)
    assert r.winner.cand.offload_chunks == 1
    assert r.tuned_plan().offload_chunks == 1


def test_tuned_plan_v1_file_loads_with_resident_default(tmp_path):
    tp = TunedPlan(arch="x", num_devices=4, seq_len=256, global_batch=8)
    d = tp.to_json()
    d.pop("offload_chunks")                   # a pre-offload (v1) file
    d["version"] = 1
    with open(tmp_path / "v1.json", "w") as f:
        json.dump(d, f)
    loaded = TunedPlan.load(str(tmp_path / "v1.json"))
    assert loaded.version == 1
    assert loaded.offload_chunks == 1         # defaults to resident
