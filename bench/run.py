#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's weights on the device from the seed, warms up every
shape the window uses (``setup_s``: from process start to the first timed
request, compilation or cache loads included), offers the cell's traffic
through the generator of its kind (``kinds/<kind>.py``) for ``--seconds``
(``--trace 1``: a traced window of at most ``TRACE_SECONDS``), then
frees the program's state and compares a sample of what it served with
the configuration's float32 reference (``check.py``). The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its
limit; those are also the last lines on stderr.

Every metric named in ``BENCHMARK.json`` is read by ``metrics/<name>.py``
from the ``Run`` below; a reader that finds nothing returns ``None`` and
the metric is left out. Off a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import check, load, peaks, readings, serving, tracing  # noqa: E402

TRACE_SECONDS = 3.0


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""
    sizes: dict
    cfg_mod: object
    traffic: dict
    generator: object
    record: serving.Record
    setup_s: float
    chips: int
    device_kind: str
    param_bytes: int
    trace: tracing.Trace | None = None

    @property
    def peak(self) -> peaks.Peak:
        return peaks.peak(self.device_kind)

    def device_ops(self) -> dict[str, list[tracing.Event]]:
        """Device ops inside the traced window, per chip used."""
        return {d: self.trace.ops_in_window(d) for d in self.trace.devices[:self.chips]}

    def window_s(self) -> float:
        lo, hi = self.trace.window()
        return (hi - lo) * 1e-9


def _say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def read_metrics(entries: list[dict], run: Run, root: pathlib.Path) -> dict:
    out = {}
    for m in entries:
        value = load.metric(m["name"], root).read(run)
        if value is None:
            _say(f"{m['name']}: nothing to read in this run")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(bm: dict, cell: dict, seed: int, seconds: float, trace: bool,
             devices: list, t_start: float, root: pathlib.Path = load.BENCH) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    from repro.core import tsmm

    sizes, cfg_mod = load.config(cell["config"], root)
    traffic = load.traffic(cell["traffic"], root)
    limits = load.limits(cell["name"], root)
    cfg = serving.program_config(cfg_mod, sizes)
    params = serving.build_params(cfg_mod, sizes, seed)
    serving.check_layout(params, cfg)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    gen = load.kind(traffic["kind"], root).Generator(traffic, cfg_mod, sizes, seed)
    gen.build(cfg)
    with tsmm.record_dispatches() as dispatches:
        rec = gen.setup(params)
    serving.freeze_heap()
    setup_s = time.perf_counter() - t_start
    _say(f"set-up {setup_s:.3f} s; dispatches traced: "
         f"{[(e.kind, e.executor, e.shape) for e in dispatches if e.kind != 'dense']}")

    xspace = None
    with (serving.CompileCounter() as compiles, serving.GcPauses() as pauses,
          tempfile.TemporaryDirectory() as tmp):
        compiles.active = pauses.active = True
        if trace:
            jax.profiler.start_trace(tmp)
        rec = gen.window(params, rec, min(seconds, TRACE_SECONDS) if trace else seconds)
        if trace:
            jax.profiler.stop_trace()
            xspace = tracing.read(glob.glob(os.path.join(
                tmp, "plugins", "profile", "*", "*.xplane.pb"))[0])
        compiles.active = pauses.active = False
    serving.thaw_heap()
    if compiles.count:
        _say(f"WARNING: {compiles.count} trace/compile events inside the window")
    _say(f"window {rec.seconds:.3f} s: {rec.stalls()}; {pauses.summary()}")
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)

    # The program's state goes before the reference runs; the reference
    # rebuilds the weights from the seed with the configuration's builder.
    gen.release()
    del params
    ref_params = serving.build_params(cfg_mod, sizes, seed)
    t_ref = time.perf_counter()
    numbers, _ = gen.numbers(ref_params)
    _say(f"reference {time.perf_counter() - t_ref:.3f} s; numbers {numbers}")
    del ref_params
    correct, checks = check.verdict(numbers, rec.failed, limits)

    run = Run(sizes=sizes, cfg_mod=cfg_mod, traffic=traffic, generator=gen,
              record=rec, setup_s=setup_s, chips=len(devices),
              device_kind=devices[0].device_kind, param_bytes=param_bytes,
              trace=xspace)
    group = "per_layer" if trace else "end_to_end"
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": rec.attempted, "failed": rec.failed,
              "metrics": read_metrics(load.cell_metrics(bm, cell["name"], group), run, root),
              "device": device}
    if trace:
        ops = run.device_ops()
        device["busy_s"] = readings.busy_s(run)
        device["window_s"] = run.window_s()
        first = xspace.devices[0]
        result["breakdown"] = {"device_ops": tracing.top_ops(ops[first]),
                               "idle_gaps": tracing.idle_by_host(xspace, first)}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bm = load.benchmark()
    cell = load.cell(bm, args.workload)

    import jax
    from repro.launch import cache

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        _say(f"needs {cell['chips']} TPU chip(s); JAX found "
             f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    cache.configure_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    result = run_cell(bm, cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell["chips"]], T_START)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
