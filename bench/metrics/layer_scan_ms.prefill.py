"""Device ms per round of the traced window in ops under the program's
``layers`` scope and outside every ``block`` and ``shared_block``: the layer
scan's own slicing of stacked weights and caches and stacking of per-layer
outputs (device trace, attributed by ``scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.ms_per_round(run, scopes.layer_scan)
