"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip. A kind
not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses

SOURCE = 'Google Cloud documentation, "TPU v5e"'


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s, bf16 operands, f32 accumulate
    int8_ops: float          # OP/s, int8 operands
    hbm_bytes_per_s: float


PEAKS = {
    "TPU v5 lite": Peak(bf16_flops=197e12, int8_ops=393e12,
                        hbm_bytes_per_s=819e9),
}

# Matrix-unit peak by operand dtype (HLO spelling). f32 has no published
# v5e peak, so an f32 kernel has no roofline here.
_COMPUTE = {"bf16": "bf16_flops", "s8": "int8_ops"}


def peak(kind: str) -> Peak:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"device kind {kind!r} has no published peaks in "
                       f"bench/peaks.py; known: {sorted(PEAKS)}") from None


def compute_peak(p: Peak, dtype: str) -> float:
    """Peak operations per second for matrix products on ``dtype`` operands."""
    field = _COMPUTE.get(dtype)
    if field is None:
        raise KeyError(f"no published matrix peak for operand dtype {dtype!r}")
    return getattr(p, field)


def least_time_s(flops: float, nbytes: float, p: Peak, dtype: str) -> tuple[float, str]:
    """Roofline least time of one call and the bound that sets it."""
    t_compute = flops / compute_peak(p, dtype)
    t_memory = nbytes / p.hbm_bytes_per_s
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
