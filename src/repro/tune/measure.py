"""PlanTuner stage 3: measure the top-K candidates live.

Each candidate is rebuilt as a *real* ExecutionPlan on the attached
devices (the enumeration/scoring stages never touch device state), its
train step jitted, and a few steps timed after a warmup.  Measured step
times re-rank the analytic top-K and land in the ``TunedPlan`` as the
predicted-vs-measured record.

Candidates whose device count exceeds what is attached are skipped with
a note — measurement is an opt-in refinement, never a requirement
(the acceptance path is enumerate+score on fake devices).
"""
from __future__ import annotations

import dataclasses
import logging
import time

log = logging.getLogger("repro.tune")


def measure_plan(plan, *, steps: int = 3, warmup: int = 1) -> float:
    """Median-free simple measurement: best of ``steps`` timed jitted
    train steps (best-of is robust to host jitter at this scale)."""
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import init_params
    from repro.train.optimizer import init_opt_state
    from repro.train.train_step import jit_train_step

    assert plan.seq_len and plan.global_batch, \
        "measurement needs the workload shape on the plan"
    data = SyntheticLM(plan.data_config(plan.seq_len, plan.global_batch),
                       plan.cfg)
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    with plan.mesh:
        params = init_params(plan.cfg, jax.random.PRNGKey(0))
        step, _, _ = jit_train_step(plan, params, donate=False)
        opt = init_opt_state(params)
        for _ in range(warmup):
            jax.block_until_ready(step(params, opt, batch))
        best = float("inf")
        for _ in range(steps):
            t0 = time.perf_counter()
            jax.block_until_ready(step(params, opt, batch))
            best = min(best, time.perf_counter() - t0)
    return best


def measure_top(cfg, result, *, k: int = 3, steps: int = 3,
                impl: str | None = None):
    """Measure the analytic top-``k`` of a ``TuneResult`` in place
    (returns the result with ``measured_s`` attached and re-ranked
    measured-first)."""
    import jax
    from repro.core.plan import build_plan

    n_dev = len(jax.devices())
    ranked = list(result.ranked)
    for i, s in enumerate(ranked[:k]):
        pc = s.cand.pc
        if pc.num_devices > n_dev:
            log.warning("skip measuring %s: needs %d devices, have %d",
                        s.tag, pc.num_devices, n_dev)
            continue
        plan = build_plan(cfg, pc, impl=impl,
                          grad_accum=s.cand.grad_accum,
                          remat=s.cand.remat, zero=s.cand.zero,
                          memory_budget_gb=result.memory_budget_gb,
                          seq_len=result.seq_len,
                          global_batch=result.global_batch)
        t = measure_plan(plan, steps=steps)
        ranked[i] = dataclasses.replace(s, measured_s=t)
        log.info("measured %s: %.1f ms (predicted %.1f ms)",
                 s.tag, t * 1e3, s.score_s * 1e3)
    # re-rank: measured candidates first by wall clock, the unmeasured
    # tail keeps its analytic order (stable sort on the bucket key)
    ranked.sort(key=lambda s: (0, s.measured_s) if s.measured_s
                is not None else (1, 0.0))
    return dataclasses.replace(result, ranked=ranked)
