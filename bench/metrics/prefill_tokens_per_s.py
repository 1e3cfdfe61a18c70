"""Prompt tokens prefilled over the whole window, per second of it (host clock)."""

from bench import readings


def read(run):
    return readings.rate(run, "prefill")
