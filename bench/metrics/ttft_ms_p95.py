"""95th percentile over every batch of the window of the time from handing its prompts to the engine until their first tokens are on the host (host clock)."""

from bench import readings


def read(run):
    return readings.ttft_ms(run, 95)
