"""The work a training step requires, from the cell's shapes.

Counts what the algorithm needs, not what a kernel happened to compute:
matmuls are ``6 * N * tokens`` (forward 2, backward 4) with ``N`` the
matmul weights (the tied head once; the embedding lookup is no matmul);
causal attention's forward is ``4 * hd * Hq`` FLOPs per visible
(query, key) pair, ``S (S + 1) / 2`` pairs per row and layer, and its
backward twice that.  Recomputation is not counted.  Bytes are the
kernel's operands read and written once, in bf16, with the per-row
log-sum-exp (and, backward, the row sums of ``dO * O``) in fp32.
"""
from __future__ import annotations

import dataclasses

from bench.weights import Dims

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class StepWork:
    matmul_flops: float
    attn_fwd_flops: float
    attn_fwd_bytes: float
    attn_bwd_flops: float
    attn_bwd_bytes: float

    @property
    def flops(self) -> float:
        """All the FLOPs one step requires."""
        return self.matmul_flops + self.attn_fwd_flops + self.attn_bwd_flops


def matmul_params(d: Dims) -> int:
    q, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    per_layer = d.d_model * (2 * q + 2 * kv) + 3 * d.d_model * d.d_ff
    return d.layers * per_layer + d.vocab * d.d_model


def step_work(d: Dims, seq: int, batch: int) -> StepWork:
    tokens = seq * batch
    pairs = batch * seq * (seq + 1) / 2 * d.layers
    fwd = 4.0 * d.head_dim * d.n_heads * pairs
    q_elems = tokens * d.n_heads * d.head_dim * d.layers
    kv_elems = tokens * d.n_kv_heads * d.head_dim * d.layers
    rows = tokens * d.n_heads * d.layers
    # fwd: read q, k, v; write o and lse
    fwd_bytes = BF16 * (2 * q_elems + 2 * kv_elems) + F32 * rows
    # bwd: read q, k, v, o, dO, lse, delta; write dq, dk, dv
    bwd_bytes = BF16 * (4 * q_elems + 4 * kv_elems) + 2 * F32 * rows
    return StepWork(matmul_flops=6.0 * matmul_params(d) * tokens,
                    attn_fwd_flops=fwd, attn_fwd_bytes=fwd_bytes,
                    attn_bwd_flops=2.0 * fwd, attn_bwd_bytes=bwd_bytes)


def least_time(flops: float, nbytes: float, peak) -> float:
    """The least time one chip could take: the larger of compute and
    memory bounds (``peak``: a ``bench.peaks.Peak``)."""
    return max(flops / peak.bf16_flops, nbytes / peak.hbm_bytes_per_s)
