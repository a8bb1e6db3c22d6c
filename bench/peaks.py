"""Published peaks of the chips the benchmark may run on, by the
``device_kind`` JAX reports. A kind that is not here is an error."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float              # bf16 dense matrix operations per second
    bytes_per_s: float        # HBM bandwidth
    memory_bytes: float       # HBM capacity
    source: str


PEAKS = {
    "TPU v5 lite": Peak(flops=197e12, bytes_per_s=819e9, memory_bytes=16e9,
                        source='Google Cloud documentation, "TPU v5e"'),
}


def peak(kind: str) -> Peak:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
