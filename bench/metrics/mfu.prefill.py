"""Model FLOPs of the traced prefill batches over device busy time, over chips x bf16 peak; shared blocks count per application (benchmark's count, device trace)."""

from bench import readings


def read(run):
    return readings.mfu_pct(run)
