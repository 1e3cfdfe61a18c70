"""The trace reduction (``bench/tracing.py``) on synthetic intervals and on
a small trace recorded on a TPU v5e (``data/small.xplane.pb``, made by
``record_small_trace.py``: three rounds of one tsm2r launch at
4096 x 2048 x 64, one XLA dot and a 5 ms host pause)."""

from __future__ import annotations

import pathlib

import numpy as np
import pytest

import bench_testlib  # noqa: F401  (puts the repo on sys.path)
from bench import peaks, tracing

SMALL = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"
TSM2R_HLO = ("%tsm2r_pallas.1 = bf16[4096,64]{1,0:T(8,128)(2,1)S(1)} custom-call("
             "bf16[4096,2048]{1,0:T(8,128)(2,1)} %x.1, bf16[2048,64]{1,0:T(8,128)(2,1)S(1)} "
             "%copy), custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
             "{bf16[4096,2048]{1,0}, bf16[2048,64]{1,0}}")


def _brute_union(intervals, lo, hi) -> int:
    mask = np.zeros(int(hi - lo), bool)
    for s, e in intervals:
        mask[int(max(s, lo) - lo):int(min(e, hi) - lo)] = True
    return int(mask.sum())


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_union_and_gaps_match_brute_force(seed):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, 5000, 60)
    ivs = [(float(s), float(s + d)) for s, d in zip(starts, rng.integers(1, 200, 60))]
    events = [tracing.Event("op", s, e - s) for s, e in ivs]
    busy = tracing.busy_ns(events)
    assert busy == _brute_union(ivs, 0, 6000)
    idle = sum(e - s for s, e in tracing.gaps(events, 0.0, 6000.0))
    assert busy + idle == 6000


def test_hlo_names_and_shapes():
    assert tracing.op_name(TSM2R_HLO) == "tsm2r_pallas.1"
    assert tracing.op_family(TSM2R_HLO) == "tsm2r_pallas"
    assert tracing.opcode(TSM2R_HLO) == "custom-call"
    assert tracing.TSM2X_FAMILIES.match("tsm2r_pallas")
    assert tracing.TSM2X_FAMILIES.match("tsmt_q8_pallas_split")
    assert not tracing.TSM2X_FAMILIES.match("fusion")
    outs, args = tracing.typed_shapes(TSM2R_HLO)
    assert outs == [("bf16", (4096, 64), 1)]
    # The layout constraints after the operand list are not operands.
    assert args == [("bf16", (4096, 2048), 0), ("bf16", (2048, 64), 1)]
    flops, nbytes, dtype = tracing.gemm_cost(TSM2R_HLO)
    assert flops == 2 * 4096 * 2048 * 64
    assert nbytes == 4096 * 2048 * 2        # only A lives in HBM
    assert dtype == "bf16"
    fusion = "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f"
    assert tracing.op_family(fusion) == "fusion:kLoop"
    loop = "%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b"
    assert tracing.opcode(loop) == "while"
    assert tracing.top_ops([tracing.Event(loop, 0, 100), tracing.Event(fusion, 10, 5)]) \
        == [["fusion:kLoop", 5e-9]]


def test_launch_shift_pairs_last_programs_with_last_launches():
    mods = [tracing.Event("m", t, 10) for t in (100, 200, 300)]
    lchs = [tracing.Event("DoEnqueueProgram", t, 1) for t in (5, 1150, 1240, 1345)]
    assert tracing.launch_shift(mods, lchs) == 1050     # max(1050, 1040, 1045)
    assert tracing.launch_shift([], lchs) == 0.0


def test_idle_attributed_to_innermost_host_span():
    ops = [tracing.Event("a", 0, 100), tracing.Event("b", 300, 100)]
    host = [tracing.Event("bench.window", 0, 1000),
            tracing.Event("bench.call", 90, 300),
            tracing.Event("bench.to_host", 150, 100),
            tracing.Event("bench.prompts", 450, 600)]
    tr = tracing.Trace(ops={"/device:TPU:0": ops}, modules={}, host=host, shift_ns=0.0)
    got = dict(tracing.idle_by_host(tr, "/device:TPU:0"))
    # gap 100-300 (mid 200: to_host inside call), gap 400-1000 (mid 700)
    assert got == pytest.approx({"bench.to_host": 200e-9, "bench.prompts": 600e-9})


@pytest.fixture(scope="module")
def small():
    return tracing.read(SMALL)


def test_recorded_trace_busy_time(small):
    dev = small.devices
    assert dev == ["/device:TPU:0"]
    ops = small.ops_in_window(dev[0])
    lo, hi = small.window()
    brute = _brute_union([(e.start_ns, e.end_ns) for e in ops], lo, hi)
    assert tracing.busy_ns(ops) == pytest.approx(brute, abs=len(ops))
    assert 0 < tracing.busy_ns(ops) < hi - lo


def test_recorded_trace_kernel_and_breakdown(small):
    ops = small.ops_in_window(small.devices[0], whole=True)
    kern = [e for e in ops if tracing.op_family(e.name) == "tsm2r_pallas"]
    assert len(kern) == 3
    top = tracing.top_ops(ops)
    assert top[0][0] == "tsm2r_pallas"
    assert top[0][1] == pytest.approx(sum(e.dur_ns for e in kern) * 1e-9)
    p = peaks.peak("TPU v5 lite")
    for e in kern:
        flops, nbytes, dtype = tracing.gemm_cost(e.name)
        least, bound = peaks.least_time_s(flops, nbytes, p, dtype)
        assert bound == "memory"
        # A roofline share cannot pass 100%: the least time is a lower bound.
        assert least <= e.dur_ns * 1e-9
    # Every device program starts after the host launched it.
    launches = sorted(e.start_ns for e in small.host if e.name == tracing.LAUNCH_EVENT)
    mods = sorted(e.start_ns + small.shift_ns for e in small.modules[small.devices[0]])
    assert all(m >= l for m, l in zip(mods, launches[-len(mods):]))
