"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip): 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect.
JAX reports the chip's ``device_kind`` as "TPU v5 lite".  A device kind that
is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float          # FLOP/s (bf16 matrix unit)
    int8_ops: float       # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_bits_per_s: float
    source: str


_V5E = Peaks(
    flops=197e12,
    int8_ops=393e12,
    hbm_bytes_per_s=819e9,
    hbm_bytes=16e9,
    ici_bits_per_s=1600e9,
    source='Google Cloud documentation, "TPU v5e"',
)

PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"the table has {sorted(PEAKS)}"
        ) from None


def least_time_s(ops: float, bytes_: float, p: Peaks) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / p.flops
    t_bytes = bytes_ / p.hbm_bytes_per_s
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
