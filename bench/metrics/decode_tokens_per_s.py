"""Tokens generated and read back to the host over the whole window, per second of it (host clock)."""

from bench import readings


def read(run):
    return readings.rate(run, "decode")
