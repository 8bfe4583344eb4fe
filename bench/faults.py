"""Faults planted in the program underneath the timed path, to show that
``correct`` catches them and to read their numbers for the limits.

``plant(name)`` patches the program in this process:

* ``unchanged`` — the step returns its state as given;
* ``half_batch`` — the second half of every row's tokens is left out of
  the loss, the mean taken over the rest;
* ``no_exchange`` — the Double-Ring's ``ppermute`` between chips is left
  out (each rank keeps its own K/V block).
"""
from __future__ import annotations

import jax.numpy as jnp

FAULTS = ("unchanged", "half_batch", "no_exchange")


def plant(name: str) -> None:
    from repro.core import attention2d
    from repro.train import train_step

    if name == "unchanged":
        def adamw_update(params, grads, state, cfg):
            return params, state, {"grad_norm": jnp.float32(1.0),
                                   "lr": jnp.float32(0.0)}
        train_step.adamw_update = adamw_update
    elif name == "half_batch":
        forward_loss = train_step.forward_loss

        def half(params, batch, rt, cfg):
            labels = batch["labels"]
            labels = labels.at[..., labels.shape[-1] // 2:].set(-1)
            return forward_loss(params, dict(batch, labels=labels), rt, cfg)
        train_step.forward_loss = half
    elif name == "no_exchange":
        attention2d._shift = lambda x, axis, size: x
    else:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
