"""ExecutionPlan: the single planning layer for every cross-layer decision.

LoongTrain's system contribution is the *composition* of head×context
placement (§4.4), hybrid ZeRO (§5.1), and Selective Checkpoint++ (§5.2)
tuned together per workload.  ``build_plan`` makes all of those choices
once, from ``(ParallelConfig, ModelConfig, OptConfig, memory budget)``,
and every entry point — launchers, trainer, dry-run, examples — consumes
the resulting ``ExecutionPlan`` instead of re-deriving mesh/sharding
facts:

* **mesh** — the 5-axis LoongTrain mesh (built from a flat device list or
  refined from a production ``(pod, data, model)`` grid) with the
  head-first / context-first placement strategy.
* **hybrid ZeRO** — the sharding extent (Full-Replica / dp / sp / dp×sp,
  AMSP's three modes) is chosen from a parameter+optimizer memory model:
  the *least* sharded extent whose state fits the per-device budget wins,
  minimizing collective latency (the seed hardcoded most-sharded-first).
* **remat** — ``none | full | scpp`` from an activation estimate when
  asked for ``"auto"``; the decision lands in ``cfg.remat`` so the model
  stack reads one source of truth.
* **gradient accumulation** — ``grad_accum`` microbatches per step; the
  plan owns the ``(accum, microbatch, ...)`` batch layout and shardings.
* **Attn2DConfig / batch / param / opt shardings** — derived here only.

``plan.describe()`` prints the whole story as one table, so train, serve
and dry-run all report identically.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.attention2d import Attn2DConfig, attn2d_config
from repro.core.runtime import Runtime
from repro.core.topology import (AXIS_DATA, AXIS_POD, BATCH_AXES, MESH_AXES,
                                 MODEL_AXES, SEQ_AXES, ParallelConfig,
                                 make_mesh, refine_mesh)
from repro.core.zero import (_group_size, leaf_extent, tp_shardings,
                             zero_shardings)

if TYPE_CHECKING:                                  # avoid core -> models
    from repro.models.model import ModelConfig     # import at runtime
    from repro.train.optimizer import OptConfig

#: per-parameter state bytes: fp32 master + Adam m + Adam v
STATE_BYTES_PER_PARAM = 12
#: transient bf16 compute copy of the (matrix) params
HALF_BYTES_PER_PARAM = 2
#: assumed device→host snapshot bandwidth for the checkpoint-stall
#: estimate (PCIe-gen4-ish); the disk side is hidden by the async writer
CKPT_D2H_BYTES_PER_S = 16e9
#: rough live activation width per token per layer, in units of
#: d_model × 2 bytes: hidden + norms + q/k/v/o + gate/up intermediates
#: when nothing is rematerialized; the saved-residual footprint per layer
#: under full / SC++ checkpointing.
ACT_UNITS = {"none": 14, "scpp": 2, "full": 1}
#: fraction of the device budget the optimizer/param state may occupy —
#: the rest is headroom for activations, grads and XLA workspace.
STATE_BUDGET_FRAC = 0.6

#: serve mode: fraction of the device budget available to bf16 weights +
#: the paged-KV block pool (the rest is activation/workspace headroom).
SERVE_BUDGET_FRAC = 0.8

#: assumed host↔device wire bandwidth for the chunk-offload traffic model
#: (PCIe-gen4-ish, matching the checkpoint snapshot path)
OFFLOAD_WIRE_BYTES_PER_S = 16e9


def offload_resident_frac(chunks: int) -> float:
    """HBM-resident fraction of a chunk-pipelined tensor: the active
    chunk plus the prefetched next one (the double-buffer schedule the
    ``OffloadManager`` runs).  1.0 when not chunked."""
    if chunks <= 1:
        return 1.0
    return min(1.0, 2.0 / chunks)


def offload_split(total_bytes: float, chunks: int) -> tuple[float, float]:
    """``(device_bytes, host_bytes)`` of a chunk-pipelined tensor.

    The single split rule shared by the train activation model and the
    serve KV-pool model, so a byte lives on exactly one side of the
    accounting — never device-counted *and* host-counted."""
    dev = total_bytes * offload_resident_frac(chunks)
    return dev, total_bytes - dev

#: AMSP sharding modes, smallest extent first (Full-Replica → dp-only →
#: sp-only → full dp×sp).  ``build_plan`` picks the first that fits.
ZERO_MODES = (
    ("replica", ()),
    ("dp", (AXIS_DATA,)),
    ("sp", MODEL_AXES),
    ("dp_sp", (AXIS_DATA,) + MODEL_AXES),
    ("pod_dp_sp", (AXIS_POD, AXIS_DATA) + MODEL_AXES),
)


@functools.lru_cache(maxsize=64)
def _params_struct(cfg):
    """Abstract param tree for a (hashable) ModelConfig — cached so the
    memory model and describe()/leaf_extents() trace the model once."""
    from repro.models.model import init_params
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def _param_count(cfg) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(_params_struct(cfg)))


def _fmt_bytes(b: float) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if abs(b) < 1024 or unit == "GB":
            return f"{b:.1f}{unit}" if unit != "B" else f"{int(b)}B"
        b /= 1024
    return f"{b:.1f}GB"


class _ShapeOnlyMesh:
    """Duck-typed stand-in for a ``Mesh`` where only ``.shape`` is read
    (``choose_zero_mode`` / ``_group_size``).  Lets the memory model run
    from a ``ParallelConfig`` alone — the PlanTuner prunes thousands of
    candidate points without constructing a device mesh per point."""

    def __init__(self, pc: ParallelConfig):
        self.shape = {AXIS_POD: pc.pods, AXIS_DATA: pc.dp,
                      MODEL_AXES[0]: pc.hp, MODEL_AXES[1]: pc.cp_outer,
                      MODEL_AXES[2]: pc.cp_inner}


def choose_zero_mode(n_params: int, mesh, budget_bytes: float,
                     *, include_pod: bool = False):
    """AMSP mode selection from the param+optimizer memory model.

    Returns ``(mode_name, group, groups)`` where ``groups`` is the
    preference order handed to ``leaf_spec``: the chosen group first,
    then every smaller extent as a fallback for leaves the chosen group
    cannot divide (after ``leaf_spec``'s own sub-group dropping).
    """
    state = n_params * (STATE_BYTES_PER_PARAM + HALF_BYTES_PER_PARAM)
    modes = [(name, grp) for name, grp in ZERO_MODES
             if include_pod or AXIS_POD not in grp]
    sized = sorted(((name, grp, _group_size(mesh, grp)) for name, grp
                    in modes), key=lambda t: t[2])
    chosen = sized[-1]                 # largest extent if nothing fits
    for name, grp, g in sized:
        if state / max(g, 1) <= budget_bytes * STATE_BUDGET_FRAC:
            chosen = (name, grp, g)
            break
    fallbacks = tuple(grp for _, grp, g in reversed(sized)
                      if g < chosen[2] and grp)
    groups = ((chosen[1],) if chosen[1] else ()) + fallbacks
    return chosen[0], chosen[1], groups


def choose_remat(cfg, budget_bytes: float, state_dev: float,
                 tokens_dev: float) -> str:
    """Pick ``none | full | scpp`` from the activation estimate: the
    cheapest-recompute policy whose saved activations fit the headroom."""
    headroom = budget_bytes - state_dev
    for policy in ("none", "scpp", "full"):
        saved = (tokens_dev * cfg.d_model * 2
                 * ACT_UNITS[policy] * cfg.num_layers)
        if policy != "none":           # + one layer recompute peak
            saved += tokens_dev * cfg.d_model * 2 * ACT_UNITS["none"]
        if saved <= headroom:
            return policy
    return "full"


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """Geometry of the paged-KV serve engine, chosen by the memory model.

    Field names match ``repro.serve.engine.EngineConfig`` so a spec can be
    handed straight to ``ServeEngine``.
    """
    page_size: int
    num_blocks: int              # physical blocks in the shared pool
    max_blocks_per_seq: int      # block-table width (longest request)
    max_batch: int               # engine decode slots
    prefill_chunk: int
    paged_bytes_per_token: int   # KV bytes/token across paged layers
    window_bytes: int            # fixed ring-buffer bytes per slot


def serve_kv_bytes(cfg) -> tuple[int | None, int]:
    """(paged bytes/token, fixed window-ring bytes per slot) for a config;
    (None, 0) when the family has no paged decode path (ssm state is
    O(1), encdec caches are bounded by max_positions)."""
    if cfg.family not in ("dense", "moe"):
        return None, 0
    itemsize = cfg.compute_dtype.itemsize
    if cfg.mla is not None:
        m = cfg.mla
        return (m.kv_lora + m.d_rope) * itemsize * cfg.num_layers, 0
    groups = cfg.num_layers // cfg.period
    per_tok, win = 0, 0
    for slot in range(cfg.period):
        kind = cfg.attn_kind(slot)
        kv = 2 * cfg.n_kv_heads * cfg.hd * itemsize * groups
        if kind.window is None:
            per_tok += kv
        else:
            win += kv * kind.window
    return per_tok, win


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Every cross-layer execution decision, made once.

    Consumers read decisions from here: ``plan.cfg`` (remat already
    resolved), ``plan.rt`` (mesh + impl + batch axes), the sharding
    factories, and ``plan.grad_accum``.
    """
    cfg: "ModelConfig"               # remat already resolved
    pc: ParallelConfig
    opt: "OptConfig"
    mesh: Mesh
    rt: Runtime
    grad_accum: int = 1
    zero_mode: str = "replica"
    zero_groups: tuple = ()
    memory_budget: float = 16e9      # bytes / device
    #: workload shape the memory model used (None when not supplied)
    seq_len: int | None = None
    global_batch: int | None = None
    #: packed-document training: batches carry a doc_start boundary table
    #: and attention is block-causal per document
    packed: bool = False
    #: expected mean document length of the packed stream (the cost
    #: model's ``packing`` term; None => seq_len, i.e. no packing win)
    mean_doc_len: int | None = None
    #: FPDT chunk pipeline: sequence chunks streamed through attention
    #: with inactive chunks in host memory (1 = fully resident)
    offload_chunks: int = 1
    mem: dict = dataclasses.field(default_factory=dict)

    # -- sharding factories -------------------------------------------------

    def param_shardings(self, params):
        """Hybrid-ZeRO NamedShardings at the chosen extent."""
        return zero_shardings(params, self.mesh, groups=self.zero_groups)

    def opt_shardings(self, param_sh):
        """Optimizer state inherits the param shardings (ZeRO-1/2)."""
        return {"m": param_sh, "v": param_sh,
                "step": NamedSharding(self.mesh, P())}

    def state_shardings(self, state):
        """NamedShardings for a trainer state dict: ``params``/``opt``
        get the hybrid-ZeRO layout, anything else replicates.  This is
        both the layout checkpoints are sharded by on save and the
        target spec ``CheckpointManager.restore`` reshards through."""
        out = {}
        for key, sub in state.items():
            if key == "params":
                out[key] = self.param_shardings(sub)
            elif key == "opt":
                out[key] = self.opt_shardings(self.param_shardings(sub["m"]))
            else:
                out[key] = jax.tree.map(
                    lambda _: NamedSharding(self.mesh, P()), sub)
        return out

    def serve_shardings(self, params):
        """Weight-stationary (inference-TP) shardings for serving."""
        return tp_shardings(params, self.mesh)

    def serve_spec(self, *, page_size: int = 16, max_batch: int = 8,
                   max_seq_len: int | None = None,
                   prefill_chunk: int = 64,
                   offload_chunks: int | None = None) -> ServeSpec | None:
        """Paged-serving geometry from the memory model: bf16 weights and
        per-slot window rings are charged against the budget first; the
        paged block pool takes what's left, capped at the usable maximum
        ``max_batch × max_blocks_per_seq`` (blocks beyond every slot's
        worst case can never be handed out).  None for families without a
        paged decode path.

        Chunk offload (``offload_chunks``, default: the plan's) reuses
        ``offload_split``: only the resident fraction of a block is
        charged against HBM — the same rule the train activation model
        applies, so a KV byte is accounted device-side *or* host-side,
        never both."""
        per_tok, win_bytes = serve_kv_bytes(self.cfg)
        if per_tok is None:
            return None
        chunks = self.offload_chunks if offload_chunks is None \
            else offload_chunks
        max_seq = max_seq_len or self.seq_len or 4096
        max_blocks_per_seq = -(-max_seq // page_size)
        headroom = (self.memory_budget * SERVE_BUDGET_FRAC
                    - self.mem.get("n_params", 0) * HALF_BYTES_PER_PARAM
                    - max_batch * win_bytes)
        cap = max_batch * max_blocks_per_seq
        block_dev, _ = offload_split(per_tok * page_size, chunks)
        fit = int(headroom // max(block_dev, 1))
        num_blocks = max(min(fit, cap), max_blocks_per_seq)
        return ServeSpec(page_size=page_size, num_blocks=num_blocks,
                         max_blocks_per_seq=max_blocks_per_seq,
                         max_batch=max_batch, prefill_chunk=prefill_chunk,
                         paged_bytes_per_token=per_tok,
                         window_bytes=win_bytes)

    @property
    def packing_frac(self) -> float:
        """Fraction of the full causal band a packed stream attends
        (≈ mean_doc_len / seq_len) — the §4.5 cost model's ``packing``
        term.  1.0 when not packed (or shapes unknown)."""
        if not self.packed or not self.seq_len:
            return 1.0
        mean = self.mean_doc_len or self.seq_len
        return min(1.0, max(mean / self.seq_len, 1e-6))

    def batch_shardings(self, kind: str = "train"):
        """NamedShardings for a step's batch dict.  Train batches carry a
        leading (replicated) accumulation axis when ``grad_accum > 1``;
        packed plans add the ``doc_start`` boundary table (token-like)."""
        mesh, rt = self.mesh, self.rt
        lead = (None,) if kind == "train" and self.grad_accum > 1 else ()
        if kind == "decode":
            return {"tokens": NamedSharding(mesh, P(rt.batch_axes, None))}
        tok = NamedSharding(mesh, P(*lead, rt.batch_axes, SEQ_AXES))
        out = {"tokens": tok}
        if kind == "train":
            out["labels"] = out["positions"] = tok
            if self.packed:
                out["doc_start"] = tok
        if self.cfg.family == "encdec":
            out["frames"] = NamedSharding(
                mesh, P(*lead, rt.batch_axes, SEQ_AXES, None))
        return out

    def attn2d(self, *, causal: bool = True, zigzag: bool | None = None,
               window: int | None = None, softcap: float = 0.0,
               scale: float | None = None) -> Attn2DConfig:
        """The 2D-Attention grid config implied by this plan."""
        return attn2d_config(self.pc, impl=self.rt.impl, causal=causal,
                             zigzag=self.cfg.zigzag if zigzag is None
                             else zigzag, window=window, softcap=softcap,
                             scale=scale)

    def data_config(self, seq_len: int, global_batch: int,
                    zigzag: bool | None = None, **kw):
        """DataConfig consistent with this plan (cp, zigzag layout,
        microbatch grid) — the loader-side §4.4 post-processing.
        ``zigzag`` defaults to the plan's model-family decision.  Packed
        plans fill ``doc_len_range`` around ``mean_doc_len``."""
        from repro.data.pipeline import DataConfig
        cfg = self.cfg
        if zigzag is None:
            zigzag = cfg.zigzag and cfg.family in ("dense", "moe", "encdec")
        if self.packed and "doc_len_range" not in kw \
                and self.mean_doc_len is not None:
            # clamp: a plan tuned for a longer sequence may be reused at
            # a shorter one (resolve_tuned permits it with a note)
            m = min(self.mean_doc_len, seq_len)
            kw["doc_len_range"] = (max(2, m // 2), min(seq_len, 2 * m))
        return DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                          global_batch=global_batch, cp=self.pc.cp,
                          zigzag=zigzag, grad_accum=self.grad_accum, **kw)

    def data_source(self, seq_len: int, global_batch: int, **kw):
        """The plan's data source: ``PackedLM`` for packed plans,
        ``SyntheticLM`` otherwise."""
        from repro.data.pipeline import PackedLM, SyntheticLM
        src = PackedLM if self.packed else SyntheticLM
        return src(self.data_config(seq_len, global_batch, **kw), self.cfg)

    # -- reporting ----------------------------------------------------------

    def leaf_extents(self) -> dict:
        """{top-level param key: sorted unique (extent, axes)} — the ZeRO
        degree actually applied per leaf class."""
        struct = _params_struct(self.cfg)
        out: dict[str, set] = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(struct)[0]:
            key = str(getattr(path[0], "key", path[0]))
            ext = leaf_extent(leaf.shape, self.mesh, self.zero_groups) \
                if self.zero_groups else (1, ())
            out.setdefault(key, set()).add(ext)
        return {k: sorted(v) for k, v in sorted(out.items())}

    def describe(self) -> str:
        """One table: mesh, placement, ZeRO extent per leaf class, remat,
        accumulation, per-device memory estimate."""
        cfg, pc, m = self.cfg, self.pc, self.mem
        minor = "head" if pc.placement == "head_first" else "inner"
        shape = "×".join(str(self.mesh.shape[a]) for a in MESH_AXES)
        lines = [
            f"ExecutionPlan: {cfg.name} [{cfg.family}] on "
            f"{self.mesh.size} devices",
            f"  mesh        {'×'.join(MESH_AXES)} = {shape}  "
            f"placement={pc.placement} ({minor} minor)",
            f"  parallel    dp={pc.dp} pods={pc.pods} hp={pc.hp} "
            f"cp={pc.cp} (outer={pc.cp_outer} × inner={pc.cp_inner})  "
            f"sp={pc.sp}",
            f"  batch       global_batch={self.global_batch} "
            f"seq_len={self.seq_len} grad_accum={self.grad_accum} "
            f"microbatch={m.get('microbatch')}",
            f"  attention   impl={self.rt.impl} zigzag={cfg.zigzag} "
            f"hp={pc.hp}×cp={pc.cp} 2D grid",
            f"  packing     {'on' if self.packed else 'off'}"
            + (f" mean_doc={self.mean_doc_len} "
               f"frac={self.packing_frac:.3f}" if self.packed else ""),
            f"  remat       {cfg.remat}",
            f"  zero        mode={self.zero_mode} "
            f"extent={m.get('zero_extent', 1)} "
            f"axes={self.zero_groups[0] if self.zero_groups else ()}",
        ]
        ext = self.leaf_extents()
        if ext:
            per = " ".join(
                f"{k}={'/'.join(str(e) for e, _ in v)}"
                for k, v in ext.items())
            lines.append(f"    leaf extents: {per}")
        lines.append(
            f"  memory/dev  params+opt={_fmt_bytes(m.get('state_dev', 0))} "
            f"bf16-copy={_fmt_bytes(m.get('half_dev', 0))} "
            f"acts≈{_fmt_bytes(m.get('act_dev', 0))} "
            f"total≈{_fmt_bytes(m.get('total_dev', 0))} "
            f"/ budget {_fmt_bytes(self.memory_budget)}")
        max_seq = m.get("max_seq_at_budget")
        lines.append(
            f"  offload     chunks={self.offload_chunks} "
            f"resident={offload_resident_frac(self.offload_chunks):.2f} "
            f"act_host={_fmt_bytes(m.get('act_host', 0))} "
            f"wire≈{m.get('offload_wire_s', 0) * 1e3:.1f}ms/step "
            f"max_seq@budget≈"
            f"{max_seq if max_seq is not None else 'n/a'}")
        lines.append(
            f"  ckpt        bytes/host="
            f"{_fmt_bytes(m.get('ckpt_bytes_host', 0))} "
            f"(state/{m.get('zero_extent', 1)}) "
            f"snapshot-stall≈{m.get('ckpt_stall_s', 0) * 1e3:.1f}ms "
            f"(write async)")
        sv = self.serve_spec()
        if sv is None:
            lines.append(f"  serve       paged=n/a (family={cfg.family})")
        else:
            pool = sv.num_blocks * sv.page_size * sv.paged_bytes_per_token
            lines.append(
                f"  serve       page={sv.page_size} "
                f"blocks={sv.num_blocks} "
                f"(pool={_fmt_bytes(pool)} kv/token="
                f"{_fmt_bytes(sv.paged_bytes_per_token)}) "
                f"max_batch={sv.max_batch} "
                f"max_seq={sv.max_blocks_per_seq * sv.page_size} "
                f"prefill_chunk={sv.prefill_chunk}")
        return "\n".join(lines)


def plan_memory(cfg, pc: ParallelConfig, *, grad_accum: int = 1,
                remat: str | None = None, zero: str = "auto",
                memory_budget_gb: float = 16.0,
                include_pod: bool = False,
                seq_len: int | None = None,
                global_batch: int | None = None,
                offload_chunks: int = 1,
                mesh=None):
    """The param+optimizer+activation memory model behind ``build_plan``.

    Runnable without devices: with ``mesh=None`` group extents come from
    the ``ParallelConfig`` shape alone (``_ShapeOnlyMesh``), which is how
    the PlanTuner (``repro/tune``) prunes candidate configurations at
    enumeration scale.  Returns ``(remat_policy, zero_mode, groups, mem)``
    where ``mem`` carries the per-device estimates plus the feasibility
    verdicts ``fits_state`` / ``fits``.

    ``offload_chunks > 1`` applies the FPDT chunk-pipeline split: only
    ``offload_resident_frac`` of the sequence-extensive bytes stay in HBM
    (``act_dev``; the rest is ``act_host``), in exchange for the PCIe
    wire time ``offload_wire_s`` of streaming chunks back per step.
    ``max_seq_at_budget`` is the longest trainable sequence the remaining
    headroom admits at this residency fraction (monotone in the budget).
    """
    pc.validate()
    assert grad_accum >= 1
    if global_batch is not None:
        assert global_batch % grad_accum == 0, (global_batch, grad_accum)
    shape = mesh if mesh is not None else _ShapeOnlyMesh(pc)

    budget = memory_budget_gb * 1e9
    n_params = _param_count(cfg)

    # hybrid-ZeRO extent from the param+optimizer memory model
    if zero == "auto":
        zero_mode, group, groups = choose_zero_mode(
            n_params, shape, budget, include_pod=include_pod)
    else:
        by_name = dict(ZERO_MODES)
        assert zero in by_name, (zero, sorted(by_name))
        zero_mode, group = zero, by_name[zero]
        smaller = tuple(g for _, g in ZERO_MODES
                        if g and _group_size(shape, g) <
                        max(_group_size(shape, group), 1))
        groups = ((group,) if group else ()) + tuple(reversed(smaller))
    extent = max(_group_size(shape, group), 1)
    state_dev = n_params * STATE_BYTES_PER_PARAM / extent
    half_dev = n_params * HALF_BYTES_PER_PARAM / extent

    # batch shardability + per-device tokens for the activation model
    assert offload_chunks >= 1, offload_chunks
    n_batch_dev = pc.pods * pc.dp
    batch_shardable = True
    microbatch = tokens_dev = None
    tokens_per_seq_unit = None
    if global_batch is not None:
        microbatch = global_batch // grad_accum
        batch_shardable = microbatch % n_batch_dev == 0
        div = (n_batch_dev if batch_shardable else 1) * pc.sp
        tokens_per_seq_unit = microbatch / div
        if seq_len is not None:
            tokens_dev = microbatch * seq_len / div

    # remat policy
    if remat == "auto":
        policy = choose_remat(cfg, budget, state_dev + half_dev,
                              tokens_dev) if tokens_dev is not None \
            else cfg.remat
    else:
        policy = remat or cfg.remat

    act_total = (tokens_dev or 0) * cfg.d_model * 2 \
        * ACT_UNITS[policy] * cfg.num_layers
    act_dev, act_host = offload_split(act_total, offload_chunks)

    # chunk-pipeline wire time: KV chunk j is re-fetched for every
    # q-chunk i >= j, so a full fwd (and again bwd) round streams
    # ≈ (C+1)/2 copies of the local K+V; q/out/lse/do staging adds ~4
    # one-shot tensors.  Copies overlap ring steps, but the wire bytes
    # are a hard PCIe floor the cost model trades against HBM freed.
    offload_wire_s = 0.0
    if offload_chunks > 1 and tokens_dev:
        kv_bytes = tokens_dev * cfg.d_model * 2 * 2          # K+V, bf16
        refetch = (offload_chunks + 1) / 2
        wire = (2 * refetch * kv_bytes + 4 * tokens_dev * cfg.d_model * 2) \
            * cfg.num_layers
        offload_wire_s = wire / OFFLOAD_WIRE_BYTES_PER_S

    total_dev = state_dev + half_dev + act_dev
    # longest trainable sequence the activation headroom admits at this
    # residency fraction (per device, at the plan's microbatch layout)
    max_seq_at_budget = None
    if tokens_per_seq_unit:
        per_seq_unit = tokens_per_seq_unit * cfg.d_model * 2 \
            * ACT_UNITS[policy] * cfg.num_layers \
            * offload_resident_frac(offload_chunks)
        headroom = max(budget - state_dev - half_dev, 0.0)
        max_seq_at_budget = int(headroom / max(per_seq_unit, 1e-9))
    # sharded-checkpoint footprint: each host serializes only its shards
    # of the fp32 master + Adam moments, so bytes/host (and the blocking
    # device→host snapshot stall) shrink with the ZeRO extent
    ckpt_host = n_params * STATE_BYTES_PER_PARAM / extent
    mem = {"n_params": n_params, "state_dev": state_dev,
           "half_dev": half_dev, "act_dev": act_dev,
           "act_host": act_host,
           "total_dev": total_dev,
           "offload_chunks": offload_chunks,
           "offload_wire_s": offload_wire_s,
           "max_seq_at_budget": max_seq_at_budget,
           "ckpt_bytes_host": ckpt_host,
           "ckpt_stall_s": ckpt_host / CKPT_D2H_BYTES_PER_S,
           "zero_extent": extent, "microbatch": microbatch,
           "batch_shardable": batch_shardable,
           "fits_state": state_dev + half_dev
           <= budget * STATE_BUDGET_FRAC,
           "fits": (state_dev + half_dev <= budget * STATE_BUDGET_FRAC
                    and total_dev <= budget)}
    return policy, zero_mode, groups, mem


def build_plan(cfg, pc: ParallelConfig | None = None, opt=None, *,
               devices=None, base_mesh: Mesh | None = None,
               impl: str | None = None, grad_accum: int | None = None,
               remat: str | None = None, zero: str | None = None,
               memory_budget_gb: float = 16.0,
               include_pod: bool = False,
               seq_len: int | None = None,
               global_batch: int | None = None,
               packed: bool = False,
               mean_doc_len: int | None = None,
               offload_chunks: int | None = None,
               tuned=None) -> ExecutionPlan:
    """Build the ExecutionPlan — the only place these decisions are made.

    * ``devices`` / ``base_mesh`` — flat device list (tests, single-host)
      or a production ``(pod, data, model)`` mesh to refine.
    * ``impl`` — attention impl; ``None`` picks the compiled Pallas
      kernels on TPU and the dense jnp reference elsewhere.
    * ``remat`` — ``None`` keeps ``cfg.remat``; ``"auto"`` decides from
      the activation memory model (needs ``seq_len``+``global_batch``);
      an explicit policy overrides.
    * ``zero`` — ``None``/``"auto"`` picks the AMSP mode from the memory
      model; or force ``replica | dp | sp | dp_sp | pod_dp_sp``.
    * ``packed`` — packed-document training (``PackedLM`` batches with a
      ``doc_start`` boundary table, block-causal attention masking);
      attention families only.  ``mean_doc_len`` feeds the cost model's
      packing term and the data source's document-length range.
    * ``tuned`` — a ``repro.tune.TunedPlan`` (or any object with its
      fields): fills every knob the caller left unset (``None``) —
      ``pc``, ``grad_accum``, ``zero``, ``remat``, ``seq_len``,
      ``global_batch`` — so a persisted tuner winner rebuilds the exact
      plan with zero re-search.  Any explicitly passed value wins over
      the file.
    """
    from repro.train.optimizer import OptConfig
    if tuned is not None:
        if pc is None:
            pc = ParallelConfig(dp=tuned.dp, hp=tuned.hp,
                                cp_outer=tuned.cp_outer,
                                cp_inner=tuned.cp_inner, pods=tuned.pods,
                                placement=tuned.placement)
        if grad_accum is None:
            grad_accum = tuned.grad_accum
        if remat is None:
            remat = tuned.remat
        if zero is None:
            zero = tuned.zero
        if seq_len is None:
            seq_len = tuned.seq_len
        if global_batch is None:
            global_batch = tuned.global_batch
        if offload_chunks is None:
            offload_chunks = getattr(tuned, "offload_chunks", 1)
    grad_accum = 1 if grad_accum is None else grad_accum
    offload_chunks = 1 if offload_chunks is None else offload_chunks
    zero = zero or "auto"
    pc = pc or ParallelConfig()
    opt = opt or OptConfig()
    pc.validate()
    if packed:
        assert cfg.family in ("dense", "moe"), \
            f"packed training needs an attention family, got {cfg.family} " \
            "(SSM state has no per-document reset)"

    mesh = refine_mesh(base_mesh, pc) if base_mesh is not None \
        else make_mesh(pc, devices=devices)
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"

    policy, zero_mode, groups, mem = plan_memory(
        cfg, pc, grad_accum=grad_accum, remat=remat, zero=zero,
        memory_budget_gb=memory_budget_gb, include_pod=include_pod,
        seq_len=seq_len, global_batch=global_batch,
        offload_chunks=offload_chunks, mesh=mesh)
    if policy != cfg.remat:
        cfg = dataclasses.replace(cfg, remat=policy)

    rt = Runtime(mesh=mesh, pc=pc, impl=impl,
                 batch_axes=BATCH_AXES if mem["batch_shardable"] else ())
    return ExecutionPlan(cfg=cfg, pc=pc, opt=opt, mesh=mesh, rt=rt,
                         grad_accum=grad_accum, zero_mode=zero_mode,
                         zero_groups=groups,
                         memory_budget=memory_budget_gb * 1e9,
                         seq_len=seq_len, global_batch=global_batch,
                         packed=packed, mean_doc_len=mean_doc_len,
                         offload_chunks=offload_chunks, mem=mem)
