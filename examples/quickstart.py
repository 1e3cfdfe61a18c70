"""Quickstart: the TSM2X public API in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py

Shows: shape-dispatched tall-and-skinny matmul (the paper's TSM2R/TSM2L),
the transposed TSMT extension, batched N-d operands, the scoped GemmPolicy
(dense A/B arm, hardware spec selection), the performance model's bound
classifier, and kernel-vs-oracle validation (interpret mode on CPU).
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import perf_model, tsmm
from repro.kernels import ref
from repro.launch.cache import configure_compilation_cache

configure_compilation_cache()

key = jax.random.PRNGKey(0)

# --- Paper case (i): large regular x tall-and-skinny (TSM2R) ---------------
m = k, n = (4096, 4096), 8
a = jax.random.normal(key, (4096, 4096), jnp.float32)
b = jax.random.normal(jax.random.fold_in(key, 1), (4096, 8), jnp.float32)
c = tsmm.tsmm(a, b)                       # dispatches to the TSM2R kernel
np.testing.assert_allclose(np.asarray(c), np.asarray(ref.tsm2r_ref(a, b)),
                           rtol=1e-3, atol=1e-4)
print(f"TSM2R 4096x4096 @ 4096x8 -> {c.shape}, "
      f"kind={tsmm.classify_gemm(4096, 4096, 8)}, "
      f"bound={perf_model.classify(4096, 4096, 8)}")

# --- Paper case (ii): tall-and-skinny x small square (TSM2L) ---------------
a2 = jax.random.normal(key, (102400, 4), jnp.float32)
b2 = jax.random.normal(jax.random.fold_in(key, 2), (4, 4), jnp.float32)
c2 = tsmm.tsmm(a2, b2)
np.testing.assert_allclose(np.asarray(c2), np.asarray(ref.tsm2l_ref(a2, b2)),
                           rtol=1e-3, atol=1e-4)
print(f"TSM2L 102400x4 @ 4x4 -> {c2.shape}, "
      f"bound={perf_model.classify(102400, 4, 4)}  (the paper's latency case)")

# --- Beyond paper: transposed reduction over huge m (TSMT) ------------------
x = jax.random.normal(key, (65536, 128), jnp.float32)
y = jax.random.normal(jax.random.fold_in(key, 3), (65536, 4), jnp.float32)
q = tsmm.tsmm_t(x, y)                     # X^T Y without materializing X^T
np.testing.assert_allclose(np.asarray(q), np.asarray(x.T @ y), rtol=1e-3,
                           atol=1e-3)
print(f"TSMT  (65536x128)^T @ 65536x4 -> {q.shape}  (PowerSGD/ABFT shape)")

# --- Batched N-d operands: tsmm owns the leading-dim collapse ---------------
a4 = jax.random.normal(key, (8, 512, 4))          # (batch, m, k)
c4 = tsmm.tsmm(a4, b2)                            # -> (8, 512, 4)
np.testing.assert_allclose(np.asarray(c4),
                           np.asarray(jnp.einsum("bmk,kn->bmn", a4, b2)),
                           rtol=1e-3, atol=1e-3)
print(f"batched {a4.shape} @ {b2.shape} -> {c4.shape} "
      "(classified on the collapsed tall dim)")

# --- GemmPolicy: every dispatch knob, lexically scoped ----------------------
with tsmm.policy(mode="dense"):                   # the A/B escape hatch
    c_dense = tsmm.tsmm(a2, b2)
np.testing.assert_allclose(np.asarray(c_dense), np.asarray(c2), rtol=1e-3,
                           atol=1e-3)
with tsmm.policy(spec=perf_model.V5P):            # newer hardware generation
    print(f"policy(spec=V5P): bound for 20480^2 x n=200 = "
          f"{tsmm.bound_class(20480, 20480, 200)} "
          f"(V5E: {perf_model.classify(20480, 20480, 200)})")
with tsmm.record_dispatches() as log:             # the dispatch spy
    tsmm.tsmm(a, b)
print(f"dispatch spy: {log[0].kind} via {log[0].executor} "
      f"for shape {log[0].shape}")

# --- The performance model that drives block choice -------------------------
bm, bk, splits = perf_model.choose_params_tsm2r(20480, 20480, 16)
print(f"v5e params for 20480^2 x n=16: block_m={bm} block_k={bk} "
      f"splits={splits}, "
      f"modeled bw util="
      f"{perf_model.modeled_bandwidth_utilization(20480, 20480, 16, bm, bk, splits=splits):.1%}")
print(f"t2_threshold(v5e, bf16) = {perf_model.t2_threshold():.0f} "
      "(paper: all n<=32 cases sit below it => memory-bound)")
print("OK")
