"""Device time in TSM2X kernel launches over device busy time in the traced prefill window (device trace)."""

from bench import readings


def read(run):
    return readings.tsm2x_share_pct(run)
