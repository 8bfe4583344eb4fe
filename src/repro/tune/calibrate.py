"""Calibrate the §4.5 cost-model constants from on-device microbenchmarks.

Three measurements, run once and persisted (``experiments/calibration.json``
by default) so every later tuner invocation reuses them:

* **matmul** — achieved FLOP/s of a jitted ``dot`` → ``peak``;
* **copy** — achieved B/s of a jitted array copy → ``hbm``;
* **collective** — achieved B/s of a ``ppermute`` ring step over the
  local devices (the ring-attention KV hop) → ``ici``.  On a single-device
  host there is no wire to measure, so ``ici`` is rescaled by the same
  factor as the memory bandwidth — ratios between comm terms (the §4.4
  placement trade-off) are preserved exactly, and absolute predictions
  stay in the ballpark of what this host can actually execute.

The result is a :class:`repro.analysis.cost.CostConstants` whose α
factors fold the measured/nominal ratios; ``source`` records provenance
so plan files and bench JSON say which calibration scored them.
"""
from __future__ import annotations

import json
import os
import time

from repro.analysis.cost import V5E, CostConstants

CALIBRATION_VERSION = 1
DEFAULT_PATH = os.path.join("experiments", "calibration.json")


def _time_best(fn, reps: int = 5) -> float:
    """Best-of-N wall time of ``fn()`` (already warmed)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run_microbenchmarks(n: int = 1024) -> dict:
    """Measure (matmul FLOP/s, copy B/s, collective B/s) on this host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    x = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)),
                    jnp.float32)
    mm = jax.jit(lambda a: a @ a)
    jax.block_until_ready(mm(x))
    t = _time_best(lambda: jax.block_until_ready(mm(x)))
    flops = 2.0 * n ** 3 / t

    big = jnp.zeros((64, n, n), jnp.float32)
    cp = jax.jit(lambda a: a + 1.0)
    jax.block_until_ready(cp(big))
    t = _time_best(lambda: jax.block_until_ready(cp(big)))
    copy_bw = 2.0 * big.size * 4 / t          # read + write

    coll_bw = None
    devs = jax.devices()
    if len(devs) > 1:
        mesh = jax.make_mesh((len(devs),), ("ring",))
        from jax.sharding import PartitionSpec as P

        def hop(a):
            pairs = [(r, (r + 1) % len(devs)) for r in range(len(devs))]
            return jax.lax.ppermute(a, "ring", pairs)

        chunk = jnp.zeros((len(devs), n, n), jnp.float32)
        f = jax.jit(jax.shard_map(hop, mesh=mesh, in_specs=(P("ring"),),
                                  out_specs=P("ring"), check_vma=False))
        jax.block_until_ready(f(chunk))
        t = _time_best(lambda: jax.block_until_ready(f(chunk)))
        coll_bw = n * n * 4 / t               # per-device chunk over wire
    return {"matmul_flops": flops, "copy_bw": copy_bw,
            "collective_bw": coll_bw, "n": n,
            "backend": jax.default_backend(), "devices": len(devs)}


def constants_from_raw(raw: dict) -> CostConstants:
    hbm_scale = raw["copy_bw"] / V5E.hbm
    ici = raw["collective_bw"] if raw.get("collective_bw") \
        else V5E.ici * hbm_scale
    return CostConstants(
        peak=raw["matmul_flops"], hbm=raw["copy_bw"], ici=ici,
        source=f"calibrated-{raw.get('backend', '?')}"
               f"x{raw.get('devices', 1)}")


def calibrate(path: str | None = DEFAULT_PATH, *,
              force: bool = False) -> CostConstants:
    """Load the persisted calibration, or run the microbenchmarks once
    and persist them.  ``path=None`` measures without persisting."""
    if path and not force and os.path.exists(path):
        with open(path) as f:
            saved = json.load(f)
        if saved.get("version") == CALIBRATION_VERSION:
            return constants_from_raw(saved["raw"])
    raw = run_microbenchmarks()
    if path:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"version": CALIBRATION_VERSION, "raw": raw},
                      f, indent=2)
    return constants_from_raw(raw)
