"""Idle share of the device in the traced prefill window (device trace)."""

from bench import readings


def read(run):
    return readings.idle_pct(run)
