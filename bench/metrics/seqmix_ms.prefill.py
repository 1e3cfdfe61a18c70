"""Device ms per round of the traced window in ops under the program's
``wkv``, ``ssd`` or ``attn_core`` scopes: the sequence mixers (device
trace, attributed by ``scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.ms_per_round(run, scopes.sequence_mixer)
