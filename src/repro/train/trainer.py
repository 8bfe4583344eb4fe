"""End-to-end trainer: data -> jitted step -> metrics, with checkpointing,
preemption flush, deterministic resume, and straggler monitoring.

The trainer consumes an ``ExecutionPlan`` — it makes no mesh/sharding/
remat decisions of its own.  The hot loop is *sync-free*: metrics stay on
device and are only materialized (forcing a host sync) at ``log_every``
boundaries, so step dispatch pipelines ahead of execution instead of
blocking on ``float(loss)`` every iteration.
"""
from __future__ import annotations

import dataclasses
import logging

import jax

from repro.core.plan import ExecutionPlan
from repro.data.pipeline import DataConfig, PackedLM, SyntheticLM
from repro.models.model import init_params
from repro.runtime import checkpoint as ckpt
from repro.runtime import spans
from repro.runtime.resilience import PreemptionGuard, StepMonitor
from repro.train.optimizer import init_opt_state
from repro.train.train_step import jit_train_step

log = logging.getLogger("repro.trainer")


@dataclasses.dataclass
class TrainerConfig:
    num_steps: int = 100
    ckpt_dir: str | None = None
    ckpt_every: int = 50      # async-save cadence (steps)
    log_every: int = 10
    seed: int = 0
    resume: bool = True       # auto-restore the latest step in ckpt_dir


class Trainer:
    def __init__(self, plan: ExecutionPlan, data_cfg: DataConfig,
                 tcfg: TrainerConfig):
        self.plan, self.tcfg = plan, tcfg
        self.cfg, self.rt = plan.cfg, plan.rt
        if data_cfg.grad_accum != plan.grad_accum:
            data_cfg = dataclasses.replace(data_cfg,
                                           grad_accum=plan.grad_accum)
        # packed plans pull document batches (with doc_start boundary
        # tables) — the step function's batch pytree must match
        # plan.batch_shardings("train"), which adds doc_start iff packed
        self.data = (PackedLM if plan.packed else SyntheticLM)(
            data_cfg, plan.cfg)
        self.monitor = StepMonitor()
        self.guard = PreemptionGuard()
        self.guard.install()

        # params and moments are created in place at their ZeRO shardings
        # (never whole on one device first: at published widths the fp32
        # masters plus moments exceed one chip's memory)
        key = jax.random.PRNGKey(tcfg.seed)
        with plan.mesh:
            shapes = jax.eval_shape(lambda: init_params(plan.cfg, key))
            self.step_fn, self.p_sh, self.o_sh = jit_train_step(plan,
                                                                shapes)
            self.params = jax.jit(lambda: init_params(plan.cfg, key),
                                  out_shardings=self.p_sh)()
            self.opt_state = jax.jit(init_opt_state,
                                     out_shardings=self.o_sh)(self.params)
        #: ``{"step", "loss", "grad_norm"}`` at every ``log_every`` sync
        self.history: list[dict] = []
        self.start_step = 0
        self.ckpter = None
        if tcfg.ckpt_dir:
            self.ckpter = ckpt.CheckpointManager(tcfg.ckpt_dir, plan=plan)
            if tcfg.resume and self.ckpter.latest_step() is not None:
                self.restore()

    def restore(self, step: int | None = None):
        """Restore (latest step by default) through *this* run's plan:
        the manager reassembles the saved shards and reshards them onto
        the current layout — a checkpoint saved under a different
        dp/ZeRO extent resumes here without migration."""
        state = {"params": self.params, "opt": self.opt_state}
        state, step = self.ckpter.restore(state, step=step)
        self.params, self.opt_state = state["params"], state["opt"]
        self.start_step = step
        log.info("restored checkpoint at step %d", step)

    def save(self, step: int):
        if self.ckpter is None:
            return
        with jax.profiler.TraceAnnotation(spans.CKPT):
            self.ckpter.save_async({"params": self.params,
                                    "opt": self.opt_state}, step)

    def _flush(self):
        if self.ckpter:
            with jax.profiler.TraceAnnotation(spans.CKPT):
                self.ckpter.flush()

    def run(self):
        """Steps ``start_step .. num_steps - 1``; returns their losses.

        Under a ``jax.profiler`` capture each step shows as a ``train``
        step annotation holding the ``train.data``, ``train.dispatch``,
        ``train.sync`` and ``train.ckpt`` spans (``runtime/spans.py``)."""
        losses = []                    # device scalars until the end
        pending = 0                    # steps dispatched since last sync
        remaining = self.tcfg.num_steps - self.start_step
        # deterministic resume: the source indexes by step, so a restored
        # run *skips* to start_step instead of replaying
        batches = self.data.iter_batches(self.start_step, remaining)
        with self.plan.mesh:
            self.monitor.start()
            for step in range(self.start_step, self.tcfg.num_steps):
                with jax.profiler.StepTraceAnnotation(spans.STEP,
                                                      step_num=step):
                    with jax.profiler.TraceAnnotation(spans.DATA):
                        _, batch = next(batches)
                    with jax.profiler.TraceAnnotation(spans.DISPATCH):
                        self.params, self.opt_state, metrics = self.step_fn(
                            self.params, self.opt_state, batch)
                    pending += 1
                    if step % self.tcfg.log_every == 0:
                        self._log(step, pending, metrics)
                        pending = 0
                    losses.append(metrics["loss"])
                    if self.ckpter and (step + 1) % self.tcfg.ckpt_every == 0:
                        self.save(step + 1)
                    if self.guard.requested:
                        # SIGTERM landed: flush a final checkpoint at this
                        # step boundary and stop cleanly
                        log.warning("preemption requested: flushing "
                                    "checkpoint at step %d", step + 1)
                        self.save(step + 1)
                        self._flush()
                        break
            with jax.profiler.TraceAnnotation(spans.SYNC):
                losses = [float(x) for x in jax.device_get(losses)]
            if pending:                # attribute the synced tail
                self.monitor.lap(pending)
        self._flush()
        return losses

    def _log(self, step: int, pending: int, metrics):
        """The only in-loop host sync; step time is amortized over the
        ``pending`` steps dispatched since the previous sync."""
        with jax.profiler.TraceAnnotation(spans.SYNC):
            jax.block_until_ready((self.params, self.opt_state, metrics))
            n_flagged = len(self.monitor.flagged)
            self.monitor.lap(pending)
            loss = float(metrics["loss"])
            gnorm = float(metrics["grad_norm"])
        self.history.append({"step": step, "loss": loss,
                             "grad_norm": gnorm})
        log.info("step %d loss %.4f gnorm %.3f (%.2fs/step)",
                 step, loss, gnorm, self.monitor.median)
        for s, dt, med in self.monitor.flagged[n_flagged:]:
            log.warning("straggler flagged at step %d: "
                        "%.3fs vs median %.3fs", s, dt, med)
