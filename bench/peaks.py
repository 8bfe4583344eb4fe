"""Published peaks of each accelerator kind, keyed by ``device_kind``.

A kind that is not in the table is an error, never a default: a roofline
or utilization share computed against a guessed peak means nothing.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float      # FLOP/s of one chip, dense bf16 matmul
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16e9,
                        source="Google Cloud TPU v5e documentation"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
