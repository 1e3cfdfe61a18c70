"""Device ms per round of the traced window in ops under the program's
``moe`` scope and outside its ``experts`` and ``dense`` scopes: expert
selection, the sort by expert, the gather of token rows and the combine
(device trace, attributed by ``scopes.py``). Nothing to read where the
program names no ``moe`` scope."""

from bench import scopes


def dispatch(path) -> bool:
    return "moe" in path and "experts" not in path and "dense" not in path


def read(run):
    att = scopes.of_run(run)
    if att is None or not any("moe" in p for p in att.ns):
        return None
    return scopes.ms_per_round(run, dispatch)
