"""The names the program writes into a profiler trace.

Any ``jax.profiler`` capture of a training run (``jax.profiler.trace``,
``start_trace``/``stop_trace``, or xprof's remote capture) shows them; no
other tracing system is involved and nothing is recorded without one.

* Host spans (``jax.profiler.TraceAnnotation``, on the host plane, on the
  device trace's clock), opened by ``train/trainer.py::Trainer.run``:
  one ``STEP`` step annotation per training step (``StepTraceAnnotation``,
  with ``step_num``), and inside it ``DATA`` (the batch fetch from the
  data source), ``DISPATCH`` (the step call, which includes the batch's
  host-to-device copy), ``SYNC`` (the ``log_every`` sync and the final
  fetch of the losses) and ``CKPT`` (checkpoint saves and flushes).
* Name scopes (``jax.named_scope``): compile-time only, they land in each
  HLO op's ``op_name`` metadata (``jit(step_fn)/.../attn/...``).
* Pallas kernel names (``pallas_call(name=...)``): the kernel's Mosaic
  module and the last entry of its custom call's ``op_name``.

JAX adds its own entries to an ``op_name``: the backward recomputes what
the remat policy did not save under ``rematted_computation``.

The checkpoint names (``jax.ad_checkpoint.checkpoint_name``) live here
too, though no trace shows them: they mark the attention residuals that
the flash kernel's and the ring's ``custom_vjp`` forward rules make, which
Selective Checkpoint++ saves (``models/model.py::remat_policy``).
"""

# host spans of Trainer.run
STEP = "train"
DATA = "train.data"
DISPATCH = "train.dispatch"
SYNC = "train.sync"
CKPT = "train.ckpt"

# name scopes of the training step
ATTN = "attn"               # projections, RoPE, attention, out-projection
MLP = "mlp"                 # dense or MoE feed-forward
LM_HEAD = "lm_head"         # chunked cross-entropy over the vocabulary
OPTIMIZER = "optimizer"     # AdamW
ULYSSES_A2A = "ulysses_a2a"  # the head <-> sequence all-to-alls
RING = "ring"               # the Double-Ring passes, kernels included

# Pallas kernels of kernels/flash_attention.py
FLASH_FWD = "flash_fwd"
FLASH_DQ = "flash_dq"
FLASH_DKV = "flash_dkv"

# checkpoint names of the attention residuals the backward reads
ATTN_OUT = "attn_out"       # attention output, as the vjp keeps it
ATTN_LSE = "attn_lse"       # fp32 row log-sum-exp, no size-1 minor axis

#: the step's top-level scopes, in the order a by-scope table lists them
LAYER_SCOPES = (ATTN, MLP, LM_HEAD, OPTIMIZER)
