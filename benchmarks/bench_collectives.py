"""psum vs psum_scatter arms for the sharded-consumer ``tsmm_t`` path.

Three jit-cache-isolated arms per shape under a data-parallel mesh over
every local device (``timeit_arm`` asserts each arm's executor via the
dispatch spy, so a silent dispatch regression fails the run rather than
timing the wrong thing):

* ``psum``          -- the replicated-output default (``shard_map``),
* ``psum_scatter``  -- the sharded-output executor (``shard_map-scatter``),
* ``dense``         -- stock XLA under GSPMD, the no-kernel control,
* ``psum_split``    -- psum with per-shard split reduction (``split=2``):
  split partials are summed inside each shard's kernel epilogue, so the
  executor pair and the collective contract must match the plain psum arm
  exactly -- this arm exists to catch a split path leaking partials across
  the shard boundary.

On this CPU container the per-shard kernels run in interpret mode, so the
absolute times exercise the mechanism only (see benchmarks/common.py's
measurement policy); the interesting CI signal is the executor assertions
plus the relative psum/psum_scatter trend, which is collective-structure,
not kernel, time. On a single-device backend the section emits one
"skipped" row instead of rows that would time nothing (CI runs it with
``XLA_FLAGS=--xla_force_host_platform_device_count=2``).

This file is in the ruff-format ratchet set (see ci.yml) -- keep edits
formatter-clean.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from benchmarks.common import emit, rand, timeit_arm
from repro.core import tsmm

# (per_shard_m, a_dim, b_dim): the tall dim is PER SHARD and scales with
# the device count at run time, so the local shape classifies tsmt (and
# the scatter dim divides) on any power-of-two mesh size up to a_dim --
# fixed global shapes would drop out of the per-shard regime at >4
# devices and fail the executor assertions.
SHAPES = [
    (4096, 256, 8),
    (8192, 512, 16),
]

SKIP_NOTE = "single-device backend: psum vs psum_scatter needs a >=2-device mesh"

# Per-shard re-dispatch logs the inner kernel executor alongside the outer
# shard_map one; the dense control must stay pure dense-xla.
EXPECT_PSUM = {"shard_map", "pallas-tpu"}
EXPECT_SCATTER = {"shard_map-scatter", "pallas-tpu"}
EXPECT_DENSE = {"dense-xla"}


def _mmt(x, y):
    return tsmm.tsmm_t(x, y)


def run():
    rows = []
    devs = jax.devices()
    if len(devs) < 2:
        rows.append(("collectives_skipped", 0, SKIP_NOTE))
        return emit(rows)
    mesh = Mesh(np.array(devs), ("data",))
    psum_pol = tsmm.GemmPolicy(reduce="psum")
    scatter_pol = tsmm.GemmPolicy(reduce="psum_scatter")
    dense_pol = tsmm.GemmPolicy(mode="dense")
    split_pol = tsmm.GemmPolicy(reduce="psum", split=2)
    for shard_m, a_dim, b_dim in SHAPES:
        m = shard_m * len(devs)
        x, y = rand(0, (m, a_dim)), rand(1, (m, b_dim))
        with jax.set_mesh(mesh):
            us_p, _ = timeit_arm(
                _mmt, x, y, policy=psum_pol, expect_executors=EXPECT_PSUM
            )
            us_s, _ = timeit_arm(
                _mmt, x, y, policy=scatter_pol, expect_executors=EXPECT_SCATTER
            )
            us_d, _ = timeit_arm(
                _mmt, x, y, policy=dense_pol, expect_executors=EXPECT_DENSE
            )
            us_k, split_log = timeit_arm(
                _mmt, x, y, policy=split_pol, expect_executors=EXPECT_PSUM
            )
        assert {e.split for e in split_log} == {2}, split_log
        tag = f"m{m}_a{a_dim}_b{b_dim}"
        note_p = f"replicated out, {len(devs)} shards"
        note_s = f"sharded out; psum/scatter={us_p / us_s:.2f}"
        note_k = f"per-shard split=2; psum/psum_split={us_p / us_k:.2f}"
        rows.append((f"tsmmt_psum_{tag}", f"{us_p:.1f}", note_p))
        rows.append((f"tsmmt_psum_scatter_{tag}", f"{us_s:.1f}", note_s))
        rows.append((f"tsmmt_dense_{tag}", f"{us_d:.1f}", "dense-xla control"))
        rows.append((f"tsmmt_psum_split_{tag}", f"{us_k:.1f}", note_k))
    return emit(rows)


if __name__ == "__main__":
    run()
