"""Model FLOPs of the traced decode steps over device busy time, over chips x bf16 peak (benchmark's count, device trace)."""

from bench import readings


def read(run):
    return readings.mfu_pct(run)
