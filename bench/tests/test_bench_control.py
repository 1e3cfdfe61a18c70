"""The control of ``correct`` at a size a test run can hold: the reference
computed in fp8 in the program's place reads several times the program's
numbers, and fails the real cells' limits where the program passes them.

On the chip the same readings, at the cells' own sizes, set each cell's
limit (``bench/calibrate.py``; readings in PERF.md). Here the decode mix is
capped by ``max_new`` so every seed compares the same number of tokens.
"""

from __future__ import annotations

import pytest

from bench_testlib import TRAFFIC, small_sizes
from bench import calibrate, load, serving

DECODE = {**TRAFFIC["decode-small"], "max_new": 96}
PREFILL = {**TRAFFIC["prefill-small"], "batch": 4, "check_requests": 16}


def _reading(name, traffic, seed, seconds, limits=None):
    _, mod = load.config(name)
    sizes = small_sizes(name)
    cfg = serving.program_config(mod, sizes)
    return calibrate.reading(load.kind(traffic["kind"]), mod, sizes, cfg, traffic,
                             seed, seconds, control=True, limits=limits)


@pytest.mark.parametrize("name", ["rwkv6-1.6b", "zamba2-1.2b"])
def test_fp8_control_reads_far_above_the_program(name):
    for seed in (3, 4):
        r = _reading(name, DECODE, seed, 600.0)      # ends at max_new
        assert r["rounds"] == 96 - 2                # steps after the warm-up
        assert r["control.max_gap"] > 3 * r["program.max_gap"], r


@pytest.mark.parametrize("name", ["rwkv6-1.6b", "zamba2-1.2b"])
def test_fp8_control_fails_the_prefill_limits(name):
    """Fed through ``check.verdict`` at the real prefill cell's limits, the
    control's numbers are not correct and the program's are."""
    limits = load.limits(f"{name}.prefill-4x1024")
    for seed in (3, 4):
        r = _reading(name, PREFILL, seed, 1.0, limits)
        assert r["control.logit_err"] > 3 * r["program.logit_err"], r
        assert r["control.correct"] is False, r
        assert r["program.logit_err"] < limits["logit_err"], r
        assert r["program.token_gap_excess"] <= 0 and r["control.token_gap_excess"] <= 0, r
