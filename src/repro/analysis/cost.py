"""LoongTrain §4.5 cost model — the one shared implementation.

The paper evaluates on A100 + 4×HDR nodes; we target a v5e pod, so the
model is re-based on ICI:

* peak = 197 TF/s bf16/chip;  per-link ICI = 50 GB/s.
* "intra-node NVLINK" ≙ collectives over the ICI-*minor* mesh axis
  (single-hop neighbours): full link bw.
* "inter-node NIC"    ≙ collectives over major axes: modelled at half
  effective bw (multi-hop average on the torus) — the placement trade-off
  of §4.4 survives with the same structure.
* Double ring: inner ring uses one torus dimension, outer the other; both
  can run concurrently (the "use all NICs" insight).

Consumers: the PlanTuner (``repro/tune``) scores candidate
``ExecutionPlan``s with it, the roofline (``repro/analysis/roofline.py``)
shares its hardware constants, and the paper-table benches
(``benchmarks/run.py`` t2–t5) print it.  The formulas are *models*, cross-checked against dry-run
collective bytes (see EXPERIMENTS.md §Roofline); the ``CostConstants``
α factors are calibrated by on-host microbenchmarks
(``repro/tune/calibrate.py``) and persisted, so predicted step times land
in the measured ballpark on whatever host runs the tuner.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class CostConstants:
    """Hardware constants + calibration factors for the §4.5 model.

    The defaults are nominal TPU v5e.  ``repro/tune/calibrate.py``
    rescales them from measured microbenchmarks (matmul, HBM copy,
    collective round-trip) so absolute predictions track the host the
    tuner runs on; the *relative* placement trade-offs are bandwidth
    ratios and survive any uniform rescale.
    """
    peak: float = 197e12          # bf16 FLOP/s per chip
    hbm: float = 819e9            # HBM B/s per chip
    ici: float = 50e9             # B/s per ICI link
    pcie: float = 16e9            # host↔device B/s (chunk-offload wire)
    major_penalty: float = 0.5    # effective bw multiplier, ICI-major axes
    bytes_per_el: int = 2         # bf16
    #: measured/nominal efficiency factors (calibration output)
    alpha_flops: float = 1.0      # achieved matmul FLOP/s / peak
    alpha_p2p: float = 1.0        # achieved ring p2p bw / nominal
    alpha_a2a: float = 1.0        # achieved AlltoAll bw / nominal
    alpha_rsag: float = 1.0       # achieved RS/AG bw / nominal
    alpha_pcie: float = 1.0       # achieved host↔device bw / nominal
    source: str = "v5e-nominal"

    @property
    def flops(self) -> float:
        return self.peak * self.alpha_flops


V5E = CostConstants()

#: Published bf16 peak FLOP/s of one chip, keyed by
#: ``jax.Device.device_kind`` (Google Cloud documentation, "TPU v5e":
#: 197 TFLOP/s bf16).  Utilization figures divide by this table only.
PEAK_BF16_FLOPS = {"TPU v5 lite": V5E.peak}


def peak_flops(device_kind: str) -> float:
    """bf16 peak of one chip of ``device_kind``; a kind missing from
    ``PEAK_BF16_FLOPS`` is an error, not a default."""
    if device_kind not in PEAK_BF16_FLOPS:
        raise KeyError(f"no published bf16 peak for device kind "
                       f"{device_kind!r}; add it to PEAK_BF16_FLOPS")
    return PEAK_BF16_FLOPS[device_kind]

# Module-level aliases — single source of truth for every consumer that
# previously duplicated these numbers (analysis/roofline.py and the
# now-deprecated benchmarks/analytic.py shim).
PEAK = V5E.peak
HBM_BW = V5E.hbm
ICI = V5E.ici
MAJOR_PENALTY = V5E.major_penalty
BYTES = V5E.bytes_per_el


@dataclasses.dataclass(frozen=True)
class AttnCase:
    s: int                 # sequence length
    d: int = 4096          # hidden
    h: int = 32            # query heads
    h_kv: int = 32         # kv heads (MHA: == h)
    sp: int = 64           # total sequence-parallel degree
    hp: int = 1
    w: int = 4             # inner ring size
    placement: str = "head_first"
    causal: bool = True
    #: packed-document fraction of the causal band that is attendable
    #: (≈ mean_doc_len / seq_len; Σlᵢ²/S² exactly).  Scales the attention
    #: FLOPs only — the KV chunks still rotate whole, so packing shifts
    #: the compute/communication balance the tuner ranks on.  The kernel
    #: realizes the reduction via doc-aware block skipping.
    packing: float = 1.0
    #: FPDT chunk pipeline: sequence chunks streamed through attention
    #: with inactive K/V in host memory (1 = fully resident).  Adds the
    #: PCIe wire term ``offload_wire_time`` that the tuner trades
    #: against the HBM the offload frees.
    offload_chunks: int = 1

    @property
    def cp(self) -> int:
        return self.sp // self.hp

    @property
    def hd(self) -> int:
        return self.d // self.h

    @classmethod
    def from_plan(cls, plan, *, seq_len: int | None = None) -> "AttnCase":
        """Cost-model case straight from an ``ExecutionPlan`` — the tuner
        and roofline query one object instead of re-deriving dims."""
        cfg, pc = plan.cfg, plan.pc
        s = seq_len or plan.seq_len
        assert s is not None, "plan has no seq_len; pass seq_len="
        return cls(s=s, d=cfg.d_model, h=cfg.n_heads,
                   h_kv=cfg.n_kv_heads, sp=pc.sp, hp=pc.hp,
                   w=pc.cp_inner, placement=pc.placement,
                   packing=getattr(plan, "packing_frac", 1.0),
                   offload_chunks=getattr(plan, "offload_chunks", 1))


def attn_flops_per_device(c: AttnCase) -> float:
    """Useful attention FLOPs per device per layer fwd (causal halved;
    packed streams scale by the attendable fraction)."""
    full = 4.0 * c.s * c.s * c.d          # QK^T + PV, MACs×2
    if c.causal:
        full *= 0.5 * c.packing
    return full / c.sp


def comp_time_fwd(c: AttnCase, const: CostConstants = V5E) -> float:
    """One ring micro-step of compute (paper: α S²D/(cp·sp))."""
    per_step = attn_flops_per_device(c) / c.cp
    return per_step / const.flops


def kv_chunk_bytes(c: AttnCase, const: CostConstants = V5E) -> float:
    """Paper §4.5.3: Size(kv) = max(Hkv, hp)/H × (2 tensors)·S·D/sp ·bytes."""
    h_eff = max(c.h_kv, c.hp)
    return h_eff / c.h * 2.0 * c.s * c.d / c.sp * const.bytes_per_el


def p2p_time(c: AttnCase, *, inner: bool, const: CostConstants = V5E) -> float:
    bw = const.ici * const.alpha_p2p
    # context-first: inner ring is ICI-minor (full bw); head-first: the head
    # axis is minor, pushing rings to major axes.
    if c.placement == "context_first":
        if not inner:
            bw *= const.major_penalty
    else:
        bw *= const.major_penalty
    return kv_chunk_bytes(c, const) / bw


def alltoall_time(c: AttnCase, const: CostConstants = V5E) -> float:
    """Paper §4.5.4: Σ_{q,k,v,out} size × (hp-1)/hp, over the hp axis."""
    if c.hp == 1:
        return 0.0
    # Size(q) el = 2SD/sp
    q = out = 2.0 * c.s * c.d / c.sp * const.bytes_per_el / 2
    kv = kv_chunk_bytes(c, const)                        # K and V together
    vol = (q + out + kv) * (c.hp - 1) / c.hp
    bw = const.ici if c.placement == "head_first" \
        else const.ici * const.major_penalty
    return vol * (1.0 / (bw * const.alpha_a2a))


def attention_op_time(c: AttnCase, *, backward: bool = False,
                      const: CostConstants = V5E) -> float:
    """Paper's overlap model: T = T_a2a + (cp/w)·[A(w-1) + B]."""
    t_comp = comp_time_fwd(c, const) * (3.0 if backward else 1.0)
    t_inner = p2p_time(c, inner=True, const=const) * (2.0 if backward
                                                      else 1.0)
    t_outer = p2p_time(c, inner=False, const=const) * (2.0 if backward
                                                       else 1.0)
    w = min(c.w, c.cp)
    n_outer = c.cp // w
    a = max(t_comp, t_inner)
    b = max(t_comp, t_outer)
    ring = n_outer * (a * (w - 1) + b)
    return alltoall_time(c, const) * (2.0 if backward else 1.0) + ring


def offload_wire_time(c: AttnCase, const: CostConstants = V5E) -> float:
    """Per-layer host↔device wire seconds of the FPDT chunk pipeline.

    With C chunks, KV chunk j is re-fetched from host for every q-chunk
    i ≥ j — ≈ (C+1)/2 copies of the local K+V per direction (forward and
    backward each run the full causal pair schedule) — plus ~4 q-sized
    one-shot tensors (q/out/lse staging forward, do + grads home on the
    backward).  The copies are double-buffered against ring steps, so
    this is a *floor* the attention time is maxed against, not an
    additive serial term.
    """
    if c.offload_chunks <= 1:
        return 0.0
    kv = kv_chunk_bytes(c, const)
    q = 2.0 * c.s * c.d / c.sp * const.bytes_per_el
    refetch = (c.offload_chunks + 1) / 2.0
    wire = 2.0 * refetch * kv + 4.0 * q
    return wire / (const.pcie * const.alpha_pcie)


def layer_linear_flops(d: int, d_ff: int, s: int, h: int, hd: int,
                       h_kv: int) -> float:
    qkvo = 2.0 * s * d * (h * hd + 2 * h_kv * hd + h * hd)
    mlp = 2.0 * s * d * d_ff * 3
    return qkvo + mlp


def layer_step_time(c: AttnCase, *, d_ff: int = 11008,
                    remat: str = "scpp",
                    const: CostConstants = V5E) -> dict:
    """Per-layer modelled wall seconds of one train step (fwd + bwd),
    split into terms.  ``remat`` mirrors the model stack's policies:

    * ``none`` — nothing recomputed;
    * ``scpp`` — Selective Checkpoint++ (§5.2): linear fwd recomputed,
      attention saved;
    * ``full`` — full-layer checkpointing: linear *and* attention fwd
      recomputed during backward.
    """
    lin_flops = layer_linear_flops(c.d, d_ff, c.s, c.h, c.hd, c.h_kv) / c.sp
    t_lin = lin_flops * 3.0 / const.flops
    if remat in ("scpp", "full"):
        t_lin += lin_flops / const.flops
    t_attn = attention_op_time(c, const=const) \
        + attention_op_time(c, backward=True, const=const)
    if remat == "full":
        t_attn += attention_op_time(c, const=const)
    t_wire = offload_wire_time(c, const)
    # chunk H2D/D2H copies are double-buffered against ring steps: the
    # pipeline runs at whichever of compute or wire is slower
    t_attn = max(t_attn, t_wire)
    return {"linear_s": t_lin, "attn_s": t_attn,
            "offload_s": t_wire,
            "lin_flops": lin_flops,
            "attn_flops": attn_flops_per_device(c)}


def zero_collective_time(n_params: int, extent: int, *,
                         const: CostConstants = V5E) -> float:
    """Per-step hybrid-ZeRO wire time: one grad reduce-scatter + one
    param all-gather over the sharding group — ring-algorithm wire bytes
    ``2·(g-1)/g·N·bytes`` (AMSP's latency argument: smaller extents move
    marginally fewer bytes but far fewer hops; we fold hops into the
    same (g-1)/g factor, which preserves the smaller-is-cheaper order).
    """
    if extent <= 1:
        # grads still all-reduce over dp in spirit, but that cost is
        # extent-independent; the *differential* term is what the tuner
        # ranks on, so replica contributes zero.
        return 0.0
    wire = 2.0 * (extent - 1) / extent * n_params * const.bytes_per_el
    return wire / (const.ici * const.alpha_rsag)


#: fixed per-microbatch dispatch/loop overhead charged by the step-time
#: model — grad-accum trades activation memory for this (small) serial
#: cost, so the tuner prefers the smallest feasible accum.
ACCUM_OVERHEAD_S = 20e-6


def train_step_time(c: AttnCase, *, d_ff: int = 11008, n_layers: int = 32,
                    remat: str = "scpp", seqs_per_group: float = 1.0,
                    n_params: int = 0, zero_extent: int = 1,
                    grad_accum: int = 1,
                    const: CostConstants = V5E) -> dict:
    """Modelled wall seconds of one full train step.

    ``seqs_per_group`` — sequences each sp group processes per step
    (``global_batch / (pods·dp)``); the attention/linear terms scale with
    it, the ZeRO collectives and accum overhead do not.
    """
    layer = layer_step_time(c, d_ff=d_ff, remat=remat, const=const)
    t_math = (layer["linear_s"] + layer["attn_s"]) * n_layers \
        * seqs_per_group
    t_zero = zero_collective_time(n_params, zero_extent, const=const)
    t_accum = ACCUM_OVERHEAD_S * max(grad_accum - 1, 0)
    return {"total_s": t_math + t_zero + t_accum,
            "math_s": t_math, "zero_s": t_zero, "accum_s": t_accum,
            "linear_s": layer["linear_s"] * n_layers * seqs_per_group,
            "attn_s": layer["attn_s"] * n_layers * seqs_per_group,
            "offload_s": layer["offload_s"] * n_layers * seqs_per_group}


def end_to_end_mfu(c: AttnCase, *, d_ff: int = 11008, n_layers: int = 32,
                   sc_pp: bool = True, const: CostConstants = V5E) -> float:
    """Modelled training MFU for a LLaMA-7B-like stack on sp devices.

    Non-attention compute is assumed perfectly overlapped/balanced (it has
    no sequence-length-dependent communication under hybrid ZeRO);
    attention uses the overlap model above.  Without SC++, the attention
    forward is recomputed during backward (full-layer gradient
    checkpointing); with SC++ it is not (the paper's §5.2 point).
    """
    # full-layer remat recomputes the linear fwd either way (activation
    # memory at 1M tokens forces checkpointing; SC++ only spares attention)
    layer = layer_step_time(c, d_ff=d_ff,
                            remat="scpp" if sc_pp else "full", const=const)
    useful = (layer["lin_flops"] + layer["attn_flops"]) * 3.0  # fwd + 2×bwd
    t_total = layer["linear_s"] + layer["attn_s"]
    return useful / (t_total * const.flops)
