#!/usr/bin/env python3
"""Readings that set a cell's limits (``limits/<cell>.json``).

    python bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--control-seeds 1,2,3]

One process on the cell's chip; the benchmark's own runs never call it.
For each seed it builds the weights, serves the cell's traffic for
``--seconds`` through the kind's generator exactly as ``run.py`` does, and
prints one JSON line with the numbers the kind compares against the
float32 reference (``check.py``) as ``program.<number>``: the lower
readings. With ``--control-seeds`` it also reads the control, the
reference computed in fp8 (``refops.mm``) in the program's place, at the
same positions (``control.<number>``: the upper readings), and sends the
control's numbers through ``check.verdict`` at the cell's limits
(``control.correct``, which has to be false).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import check, load, serving  # noqa: E402


def _seeds(text: str) -> list[int]:
    return [int(x) for x in text.split(",") if x]


def reading(kind, cfg_mod, sizes, cfg, traffic, seed, seconds, control=False,
            limits=None):
    params = serving.build_params(cfg_mod, sizes, seed)
    gen = kind.Generator(traffic, cfg_mod, sizes, seed)
    gen.build(cfg)
    rec = gen.window(params, gen.setup(params), seconds)
    gen.release()
    prog, ctl = gen.numbers(params, "fp8" if control else None)
    out = {f"program.{k}": v for k, v in prog.items()}
    out["rounds"] = rec.rounds
    if ctl is not None:
        out.update({f"control.{k}": v for k, v in ctl.items()})
        if limits is not None:
            out["control.correct"] = check.verdict(ctl, 0, limits)[0]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    import jax
    from repro.launch import cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py needs a TPU", file=sys.stderr)
        return 2
    cache.configure_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = load.cell(load.benchmark(), args.workload)
    sizes, cfg_mod = load.config(cell["config"])
    traffic = load.traffic(cell["traffic"])
    kind = load.kind(traffic["kind"])
    limits = load.limits(cell["name"])
    cfg = serving.program_config(cfg_mod, sizes)
    for seed in dict.fromkeys(args.seeds + args.control_seeds):
        r = reading(kind, cfg_mod, sizes, cfg, traffic, seed, args.seconds,
                    control=seed in args.control_seeds, limits=limits)
        print(json.dumps({"workload": args.workload, "seed": seed, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
