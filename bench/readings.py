"""Reductions the metric readers in ``metrics/`` share. Each returns a
number, or ``None`` where the run holds nothing to read; none returns 0
for a share of a peak or a roofline it could not measure."""

from __future__ import annotations

import sys

import numpy as np

from bench import peaks, tracing


def _note(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def rate(run, kind: str):
    """Work per second of the whole window of a ``kind`` traffic mix:
    prompt tokens prefilled, or tokens generated."""
    rec = run.record
    if rec.kind != kind or not rec.rounds:
        return None
    return rec.tokens / rec.seconds


def ttft_ms(run, q: float):
    rec = run.record
    if not rec.ttft_s:
        return None
    return float(np.percentile(np.asarray(rec.ttft_s) * 1e3, q))


def busy_s(run):
    """Seconds in which an op ran on the device in the traced window,
    averaged over the chips used."""
    ops = run.device_ops()
    return sum(tracing.busy_ns(v) for v in ops.values()) / len(ops) * 1e-9


def idle_pct(run):
    """100 x (1 - union of device op intervals / traced window), averaged
    over the chips used."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - busy_s(run) / run.window_s())


def mfu_pct(run):
    """The configuration's model FLOPs of the traced window's work over
    the device's busy time in it, over chips x bf16 peak: the step's
    share of the peak, the host's idle gaps left to ``device_idle``."""
    if run.trace is None or not run.record.rounds:
        return None
    return 100.0 * run.generator.flops(run.record) / busy_s(run) / (
        run.chips * run.peak.bf16_flops)


def hbm_roofline_pct(run):
    """Bytes the traced window's steps must move (the kind's
    ``moved_bytes``) over the device's busy time x chips x peak HBM
    bandwidth."""
    moved = getattr(run.generator, "moved_bytes", None)
    if run.trace is None or moved is None or not run.record.rounds:
        return None
    return 100.0 * moved(run.record, run.param_bytes) / (
        busy_s(run) * run.chips * run.peak.hbm_bytes_per_s)


def kernel_roofline_pct(run, kernel: str):
    """Least time of every launch of ``kernel`` (``tsm2r``: also its int8
    and split variants) in the traced window over their summed device
    time. Least time is max(flops / matrix peak, logical bytes / HBM
    bandwidth) from the launch's HLO shapes (``tracing.gemm_cost``)."""
    if run.trace is None:
        return None
    evs = [e for d in run.trace.devices[:run.chips]
           for e in run.trace.ops_in_window(d, whole=True)
           if tracing.TSM2X_FAMILIES.match(tracing.op_family(e.name))
           and tracing.op_family(e.name).startswith(kernel + "_")]
    if not evs:
        return None
    if any("split" in tracing.op_family(e.name) for e in evs):
        _note(f"{kernel}: split launches finish in sum_partials_pallas, "
              "which this reading does not attribute; left out")
        return None
    least, spent, bounds = 0.0, 0.0, set()
    for e in evs:
        flops, nbytes, dtype = tracing.gemm_cost(e.name)
        try:
            t, bound = peaks.least_time_s(flops, nbytes, run.peak, dtype)
        except KeyError as err:
            _note(f"{kernel}: {err}; left out")
            return None
        least += t
        spent += e.dur_ns * 1e-9
        bounds.add(bound)
    _note(f"{kernel}: {len(evs)} launches, {spent * 1e3:.4f} ms on the device, "
          f"least {least * 1e3:.4f} ms, bound by {'+'.join(sorted(bounds))}; "
          f"shapes (dtype, dims, memory space) {tracing.typed_shapes(evs[0].name)}")
    return 100.0 * least / spent


def tsm2x_share_pct(run):
    """Device time in TSM2X kernel launches over device busy time."""
    if run.trace is None:
        return None
    ops = run.device_ops()
    busy = sum(tracing.busy_ns(v) for v in ops.values())
    kern = sum(e.dur_ns for v in ops.values() for e in v
               if tracing.TSM2X_FAMILIES.match(tracing.op_family(e.name)))
    return 100.0 * kern / busy
