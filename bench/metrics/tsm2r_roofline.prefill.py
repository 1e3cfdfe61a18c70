"""Roofline least time of the tsm2r launches over their device time in the traced prefill window (device trace)."""

from bench import readings


def read(run):
    return readings.kernel_roofline_pct(run, "tsm2r")
