"""Bytes the traced decode steps must move over device busy time x peak HBM bandwidth (benchmark's count, device trace)."""

from bench import readings


def read(run):
    return readings.hbm_roofline_pct(run)
