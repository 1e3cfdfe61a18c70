"""The held experts' roofline least time over their device time under
``moe``/``experts`` in the traced prefill window. Least time per round is
max(FLOPs / bf16 peak, bytes / HBM bandwidth) from the configuration's
``experts_flops`` and ``experts_bytes`` (each layer's held weights read
once, the gathered rows in and out, at the expected routed pairs).
Nothing to read for a configuration without those counts or a program
without that scope."""

from bench import scopes


def held_experts(path) -> bool:
    return "moe" in path and "experts" in path


def read(run):
    flops = getattr(run.cfg_mod, "experts_flops", None)
    nbytes = getattr(run.cfg_mod, "experts_bytes", None)
    att = scopes.of_run(run)
    if (flops is None or nbytes is None or att is None
            or not any(held_experts(p) for p in att.ns)):
        return None
    spent_s = scopes.ms_per_round(run, held_experts) * 1e-3
    gen = run.generator
    least_s = max(flops(run.sizes, gen.batch, gen.length) / run.peak.bf16_flops,
                  nbytes(run.sizes, gen.batch, gen.length) / run.peak.hbm_bytes_per_s)
    return 100.0 * least_s / spent_s
