"""Device ms per round of the traced window in ops under the program's
``dense`` or ``unembed`` scopes: every projection, LoRA halves and vocab
projection included (device trace, attributed by ``scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.ms_per_round(run, scopes.projection)
