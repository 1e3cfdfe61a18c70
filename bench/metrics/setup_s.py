"""Seconds from process start to the first timed request: imports, weights, compilation or cache loads, warm-up and the set-up the traffic needs (host clock)."""


def read(run):
    return run.setup_s
