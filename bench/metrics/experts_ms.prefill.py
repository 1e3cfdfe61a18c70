"""Device ms per round of the traced window in ops under the program's
``moe`` scope and, beneath it, ``experts``: the held experts' grouped
matmuls and their SwiGLU (device trace, attributed by ``scopes.py``).
Nothing to read where the program names no such scope."""

from bench import scopes


def held_experts(path) -> bool:
    return "moe" in path and "experts" in path


def read(run):
    att = scopes.of_run(run)
    if att is None or not any(held_experts(p) for p in att.ns):
        return None
    return scopes.ms_per_round(run, held_experts)
