"""Runtime context threaded through model code: mesh + parallel layout."""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.topology import BATCH_AXES, SEQ_AXES, ParallelConfig


@dataclasses.dataclass(frozen=True)
class Runtime:
    mesh: Mesh
    pc: ParallelConfig
    impl: str = "auto"          # attention kernel impl (auto/pallas/ref/...)
    #: axes the batch dim shards over; () when global_batch < dp (e.g. the
    #: B=1 long-context decode shape)
    batch_axes: tuple = BATCH_AXES

    def act_spec(self, *trailing) -> P:
        """(B, S, ...) activation spec: B over batch axes, S over sp axes."""
        return P(self.batch_axes, SEQ_AXES, *trailing)

    def constrain(self, x, *trailing):
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, self.act_spec(*trailing)))
