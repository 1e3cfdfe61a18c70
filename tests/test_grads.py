"""Gradient correctness for the differentiable TSM2X subsystem.

``jax.grad`` through ``tsmm``/``tsmm_t`` (interpret mode on CPU) must match
the pure-jnp oracles in ``kernels/ref.py`` for all three shape classes, and
the backward must stay inside the paper's tall-skinny regime: the VJP of
one class lands in another (TSM2L's Abar is TSM2L-shaped, every Bbar is the
TSMTTSM shape), asserted both via ``classify_gemm`` on the cotangent shapes
and by recording what the backward actually dispatches.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tsmm
from repro.kernels import ops, ref

TOL = dict(rtol=1e-3, atol=1e-3)


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def _grads(fn, a, b, ct):
    def loss(a_, b_):
        return jnp.sum(fn(a_, b_) * ct)

    return jax.grad(loss, (0, 1))(a, b)


# ---------------------------------------------------------------------------
# grad(tsmm) == grad(oracle) for the three shape classes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,m,k,n", [
    ("tsm2r", 4096, 2048, 8),    # m ~ k >> n
    ("tsm2l", 4096, 16, 8),      # m >> k ~ n
])
def test_tsmm_grad_matches_oracle(kind, m, k, n):
    assert tsmm.classify_gemm(m, k, n) == kind  # forward hits the kernel
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(m + n), 3)
    a, b, ct = _rand(k1, (m, k)), _rand(k2, (k, n)), _rand(k3, (m, n))
    da, db = _grads(lambda x, y: tsmm.tsmm(x, y, interpret=True), a, b, ct)
    ra, rb = _grads(ref.tsm2r_ref, a, b, ct)
    np.testing.assert_allclose(np.asarray(da), np.asarray(ra), **TOL)
    np.testing.assert_allclose(np.asarray(db), np.asarray(rb), **TOL)


def test_tsmm_t_grad_matches_oracle():
    m, a_dim, b_dim = 4096, 32, 8   # TSMT: reduction over the huge m
    assert tsmm.classify_gemm_t(m, a_dim, b_dim) == "tsmt"
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    x, y = _rand(k1, (m, a_dim)), _rand(k2, (m, b_dim))
    ct = _rand(k3, (a_dim, b_dim))
    dx, dy = _grads(lambda u, v: tsmm.tsmm_t(u, v, interpret=True), x, y, ct)
    rx, ry = _grads(ref.tsmt_ref, x, y, ct)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(rx), **TOL)
    np.testing.assert_allclose(np.asarray(dy), np.asarray(ry), **TOL)


# ---------------------------------------------------------------------------
# Backward routing stays in the tall-skinny regime
# ---------------------------------------------------------------------------

def test_cotangent_shapes_classify_tall_skinny():
    """The paper's cross-class VJP structure, checked on the classifier."""
    # TSM2L forward C[m,n] = A[m,k] B[k,n] with m >> k ~ n:
    m, k, n = 4096, 16, 8
    assert tsmm.classify_gemm(m, k, n) == "tsm2l"
    # Abar = Chat[m,n] B^T[n,k]  -> TSM2L again (tiny contraction n).
    assert tsmm.classify_gemm(m, n, k) == "tsm2l"
    # Bbar = A^T[k,m] Chat[m,n]  -> the TSMTTSM shape (Ernst et al.).
    assert tsmm.classify_gemm_t(m, k, n) == "tsmt"
    # TSMT forward C[a,b] = X[m,a]^T Y[m,b]:
    a_dim, b_dim = 32, 8
    assert tsmm.classify_gemm_t(m, a_dim, b_dim) == "tsmt"
    # Xbar = Y[m,b] Chat^T[b,a] and Ybar = X[m,a] Chat[a,b] -> TSM2L-shaped.
    assert tsmm.classify_gemm(m, b_dim, a_dim) == "tsm2l"
    assert tsmm.classify_gemm(m, a_dim, b_dim) == "tsm2l"


def test_backward_dispatches_through_classifier(monkeypatch):
    """Record what the VJP actually calls: the TSM2L backward must re-enter
    the dispatcher and route Abar to tsm2l and Bbar to tsmt."""
    calls = []
    real_tsmm, real_tsmm_t = tsmm.tsmm, tsmm.tsmm_t

    def spy_tsmm(a, b, **kw):
        calls.append(("tsmm", tsmm.classify_gemm(a.shape[0], a.shape[1],
                                                 b.shape[1])))
        return real_tsmm(a, b, **kw)

    def spy_tsmm_t(x, y, **kw):
        calls.append(("tsmm_t", tsmm.classify_gemm_t(x.shape[0], x.shape[1],
                                                     y.shape[1])))
        return real_tsmm_t(x, y, **kw)

    monkeypatch.setattr(tsmm, "tsmm", spy_tsmm)
    monkeypatch.setattr(tsmm, "tsmm_t", spy_tsmm_t)

    m, k, n = 4096, 16, 8
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    a, b = _rand(k1, (m, k)), _rand(k2, (k, n))
    jax.grad(lambda a_, b_: jnp.sum(
        ops.tsm2l(a_, b_, interpret=True)))(a, b)
    assert ("tsmm", "tsm2l") in calls       # Abar path
    assert ("tsmm_t", "tsmt") in calls      # Bbar path


# ---------------------------------------------------------------------------
# Finite differences (directional) and the escape hatch
# ---------------------------------------------------------------------------

def test_finite_difference_directional():
    m, k, n = 2048, 8, 8
    assert tsmm.classify_gemm(m, k, n) == "tsm2l"
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    a, b = _rand(k1, (m, k)), _rand(k2, (k, n))
    # A unit-scale direction with a step sized for f32: the central
    # difference's truncation error is O(eps^2), and the loss change
    # (~eps * |grad|) stays far above the f32 rounding of the summed loss.
    # (A direction scaled down by m moved each entry by ~40 ulps, so
    # rounding alone put the quotient percents off.)
    da_dir = _rand(k3, (m, k))

    def loss(a_):
        return jnp.sum(jnp.tanh(tsmm.tsmm(a_, b, interpret=True)))

    eps = 1e-3
    fd = (loss(a + eps * da_dir) - loss(a - eps * da_dir)) / (2 * eps)
    analytic = jnp.vdot(jax.grad(loss)(a), da_dir)
    np.testing.assert_allclose(float(fd), float(analytic), rtol=1e-2)


def test_repro_tsmm_off_forces_dense(monkeypatch):
    """The deprecated env var still works as a process-default alias: it is
    read into the default GemmPolicy (on refresh), not per-trace."""
    monkeypatch.setenv("REPRO_TSMM", "off")
    try:
        with pytest.deprecated_call():
            tsmm.refresh_default_policy()
        assert tsmm.default_policy().mode == "dense"
        assert not tsmm.enabled()
        k1, k2 = jax.random.split(jax.random.PRNGKey(3))
        a, b = _rand(k1, (4096, 16)), _rand(k2, (16, 8))
        # Dense path: still correct, still differentiable.
        with tsmm.record_dispatches() as log:
            np.testing.assert_allclose(np.asarray(tsmm.tsmm(a, b)),
                                       np.asarray(ref.tsm2r_ref(a, b)), **TOL)
        assert [e.executor for e in log] == ["dense-xla"]
        da, db = _grads(tsmm.tsmm, a, b, jnp.ones((4096, 8)))
        ra, rb = _grads(ref.tsm2r_ref, a, b, jnp.ones((4096, 8)))
        np.testing.assert_allclose(np.asarray(da), np.asarray(ra), **TOL)
        np.testing.assert_allclose(np.asarray(db), np.asarray(rb), **TOL)
    finally:
        monkeypatch.delenv("REPRO_TSMM")
        tsmm.refresh_default_policy()
    assert tsmm.enabled()
