"""Shared benchmark utilities.

Measurement policy on this CPU container (documented in EXPERIMENTS.md):
* jnp/XLA paths (dot baseline, V0/V1 ladder) are WALL-CLOCK timed -- they
  compile natively, so relative CPU timings are meaningful proxies.
* Pallas kernels run in interpret mode here (Python), so their wall time
  is meaningless; the kernel numbers reported are the *modeled v5e* terms
  from core/perf_model.py (the paper's own Fig.7/11 metric -- bandwidth
  fraction), plus numerics validation against the oracle.

A/B arms and policy scopes: the dispatch policy is captured at *trace*
time, so two arms that share one jitted callable silently reuse the first
arm's baked-in policy -- the timing-leakage bug. ``timeit_arm`` gives each
arm a fresh ``jax.jit`` wrapper traced inside its own policy scope (via
``core.autotune.jit_isolated``, the same harness the autotuner uses) and
asserts through ``record_dispatches`` that the arm actually hit its
intended executor.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import tsmm
from repro.core.autotune import jit_isolated, time_call  # noqa: F401


def timeit(fn, *args, reps: int = 5, warmup: int = 2) -> float:
    """Median wall time (us) of jitted fn (one timing loop repo-wide:
    ``core.autotune.time_call``)."""
    return time_call(fn, *args, reps=reps, warmup=warmup) * 1e6


def timeit_arm(fn, *args, policy=None, expect_executors=None, reps: int = 5,
               warmup: int = 1):
    """Time one A/B arm with jit-cache isolation + dispatch sanity.

    ``fn`` is wrapped in a *fresh* ``jax.jit`` and traced under ``policy``
    (a GemmPolicy, or None for the current scope), so the arm owns its
    cache entry. ``expect_executors``: exact set of executor names the
    trace must have dispatched to (raises AssertionError otherwise); None
    skips the check. Returns ``(us_per_call, dispatch_log)``.
    """
    f, log = jit_isolated(fn, *args, policy=policy)
    if expect_executors is not None:
        seen = {e.executor for e in log}
        if seen != set(expect_executors):
            raise AssertionError(
                f"arm hit executors {sorted(seen)}, expected "
                f"{sorted(expect_executors)}; dispatch log: {log}")
    return timeit(f, *args, reps=reps, warmup=warmup), log


def dispatch_sanity(m: int = 4096, k: int = 512, n: int = 8):
    """One row per canonical policy arm: did a fresh jit under that policy
    hit the executor the policy intends? Emitted into the --json report so
    CI can fail on silent dispatch regressions (benchmarks/
    check_regression.py gates on these rows vs the committed baseline).

    Split-reduction arms: ``tsmm_t`` under ``split=4`` vs ``split="never"``
    must both stay on the kernel executor AND the dispatch events must
    carry the scope's split knob (``DispatchEvent.split``) -- a policy that
    silently stops threading the knob fails the arm even though the
    executor looks right.

    The ``quant_int8`` arm asserts the int8 operand path the same way:
    kernel executor, with ``DispatchEvent.quant == "int8"`` on every
    event.

    On a >1-device backend mesh arms join: ``tsmm_t`` under a DP mesh
    must land on ``shard_map`` (reduce="psum", replicated output) and on
    ``shard_map-scatter`` (reduce="psum_scatter", sharded output); the
    ``mesh_psum_split`` arm asserts that a split scope does not disturb
    the collective contract (same executors, split knob on every event
    down to the per-shard re-dispatch)."""
    a, b = rand(0, (m, k)), rand(1, (k, n))
    arms = [
        ("dense", tsmm.GemmPolicy(mode="dense"), "dense-xla"),
        ("auto", tsmm.GemmPolicy(), "pallas-tpu"),
        ("interpret", tsmm.GemmPolicy(interpret=True), "interpret"),
    ]
    out = []
    for name, pol, expect in arms:
        _, log = jit_isolated(lambda a_, b_: tsmm.tsmm(a_, b_), a, b,
                              policy=pol)
        observed = sorted({e.executor for e in log})
        out.append({"arm": name, "shape": [m, k, n], "expected": expect,
                    "observed": observed, "ok": observed == [expect]})
    # Split-vs-sequential arms on the headline TSMT (PowerSGD/ABFT) shape.
    x_t, y_t = rand(4, (m, 64)), rand(5, (m, n))
    split_arms = [
        ("tsmt_split4", tsmm.GemmPolicy(split=4), 4),
        ("tsmt_sequential", tsmm.GemmPolicy(split="never"), "never"),
    ]
    for name, pol, knob in split_arms:
        _, log = jit_isolated(lambda x_, y_: tsmm.tsmm_t(x_, y_), x_t, y_t,
                              policy=pol)
        observed = sorted({e.executor for e in log})
        splits_seen = sorted({str(e.split) for e in log})
        out.append({"arm": name, "shape": [m, 64, n],
                    "expected": "pallas-tpu", "observed": observed,
                    "split": splits_seen,
                    "ok": (observed == ["pallas-tpu"]
                           and splits_seen == [str(knob)])})
    # Quantized arm: the int8 operand path must stay on the kernel
    # executor AND every dispatch event must carry the quant knob
    # (``DispatchEvent.quant``) -- a policy that silently stops threading
    # quant="int8" through dispatch fails the arm even though the
    # executor looks right.
    _, log = jit_isolated(lambda a_, b_: tsmm.tsmm(a_, b_), a, b,
                          policy=tsmm.GemmPolicy(quant="int8"))
    observed = sorted({e.executor for e in log})
    quants_seen = sorted({str(e.quant) for e in log})
    out.append({"arm": "quant_int8", "shape": [m, k, n],
                "expected": "pallas-tpu", "observed": observed,
                "quant": quants_seen,
                "ok": (observed == ["pallas-tpu"]
                       and quants_seen == ["int8"])})
    # Online-ABFT arms. abft="none" is the zero-overhead contract: exactly
    # ONE dispatch, no checksum GEMMs in the trace. The guarded modes must
    # dispatch exactly four GEMMs (protected + the three checksum stages of
    # ``contracts.abft_stage_shapes``) with the mode stamped on exactly one
    # event (``DispatchEvent.abft``) -- a wrap that guards the checksum
    # GEMMs recursively, or stops stamping, fails the arm even though the
    # executors look right.
    _, log = jit_isolated(lambda a_, b_: tsmm.tsmm(a_, b_), a, b,
                          policy=tsmm.GemmPolicy(abft="none"))
    observed = sorted({e.executor for e in log})
    out.append({"arm": "abft_none", "shape": [m, k, n],
                "expected": "pallas-tpu", "observed": observed,
                "events": len(log),
                "ok": observed == ["pallas-tpu"] and len(log) == 1})
    for mode in ("verify", "correct"):
        _, log = jit_isolated(lambda a_, b_: tsmm.tsmm(a_, b_), a, b,
                              policy=tsmm.GemmPolicy(abft=mode))
        observed = sorted({e.executor for e in log})
        flagged = [e for e in log if e.abft == mode]
        out.append({"arm": f"abft_{mode}", "shape": [m, k, n],
                    "expected": sorted({"dense-xla", "pallas-tpu"}),
                    "observed": observed, "events": len(log),
                    "abft": sorted({e.abft for e in log}),
                    "ok": (observed == ["dense-xla", "pallas-tpu"]
                           and len(log) == 4 and len(flagged) == 1)})
    # QR stages: both GEMMs of the CholeskyQR2 factorization (Gram and
    # R^-1 apply, every pass) must land on the tall-skinny kernels -- the
    # Gram as tsmt, the apply as tsm2l, and nothing on dense-xla. The
    # kind set is asserted alongside the executor set: a classifier drift
    # that silently sent the Gram to tsm2r would keep the executor green.
    from repro import linalg
    a_qr = rand(6, (m, 16))
    _, log = jit_isolated(lambda a_: linalg.tsqr(a_)[0], a_qr,
                          policy=tsmm.GemmPolicy())
    observed = sorted({e.executor for e in log})
    kinds = sorted({e.kind for e in log})
    out.append({"arm": "qr_stages", "shape": [m, 16, 16],
                "expected": "pallas-tpu", "observed": observed,
                "kinds": kinds,
                "ok": (observed == ["pallas-tpu"]
                       and kinds == ["tsm2l", "tsmt"])})
    devs = jax.devices()
    # The mesh arms need a per-shard shape that still classifies tsmt and
    # a scatter dim that divides the shard count: scale the tall dim with
    # the device count and skip when 64 rows can't tile the shards (odd
    # or >64-device backends) rather than emit guaranteed-false rows.
    if len(devs) > 1 and 64 % len(devs) == 0:
        from jax.sharding import Mesh
        import numpy as np
        mesh = Mesh(np.array(devs), ("data",))
        m_mesh = 2048 * len(devs)
        x, y = rand(2, (m_mesh, 64)), rand(3, (m_mesh, n))
        mesh_arms = [
            ("mesh_psum", tsmm.GemmPolicy(reduce="psum"), "shard_map",
             "auto"),
            ("mesh_psum_scatter", tsmm.GemmPolicy(reduce="psum_scatter"),
             "shard_map-scatter", "auto"),
            # Split partials must not change the psum contract: same
            # executor pair, the split knob visible on every event.
            ("mesh_psum_split", tsmm.GemmPolicy(reduce="psum", split=2),
             "shard_map", 2),
        ]
        for name, pol, expect, knob in mesh_arms:
            with jax.set_mesh(mesh):
                _, log = jit_isolated(lambda x_, y_: tsmm.tsmm_t(x_, y_),
                                      x, y, policy=pol)
            observed = sorted({e.executor for e in log})
            splits_seen = sorted({str(e.split) for e in log})
            # Exact set, like the base arms: the outer executor plus the
            # per-shard kernel re-dispatch and NOTHING else -- an extra
            # dense-xla sneaking into the trace is a dispatch regression.
            expected = sorted({expect, "pallas-tpu"})
            out.append({"arm": name, "shape": [m_mesh, 64, n],
                        "expected": expected, "observed": observed,
                        "split": splits_seen,
                        "ok": (observed == expected
                               and splits_seen == [str(knob)])})
    return out


def rand(key, shape, dtype=jnp.float32):
    return jax.random.uniform(jax.random.PRNGKey(key), shape, jnp.float32,
                              -1, 1).astype(dtype)


def emit(rows, header=("name", "us_per_call", "derived")):
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))
    return rows
