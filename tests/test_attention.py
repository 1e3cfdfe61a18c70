"""Attention substrate tests: chunked online-softmax vs naive oracle,
GQA grouping, SWA windows, MLA, decode equivalence, ring caches, and the
fused causal kernel against the chunk scan and its path choice."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import compat
from repro.models import attention

TOL = dict(rtol=2e-4, atol=2e-5)


def naive_attention(q, k, v, causal=True, window=None, scale=None):
    b, sq, h, d = q.shape
    _, sk, hk, _ = k.shape
    g = h // hk
    scale = scale or d ** -0.5
    kr = jnp.repeat(k, g, axis=2)
    vr = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(jnp.float32) * scale
    qp, kp = jnp.arange(sq)[:, None], jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vr).astype(q.dtype)


def _qkv(key, b, sq, sk, h, hk, d, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, sq, h, d), dtype)
    k = jax.random.normal(k2, (b, sk, hk, d), dtype)
    v = jax.random.normal(k3, (b, sk, hk, d), dtype)
    return q, k, v


@pytest.mark.parametrize("h,hk", [(4, 4), (8, 2), (6, 1)])
def test_chunked_matches_naive_gqa(h, hk):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 64, 64, h, hk, 16)
    got = attention.chunked_attention(q, k, v, q_chunk=16, kv_chunk=16)
    np.testing.assert_allclose(got, naive_attention(q, k, v), **TOL)


@pytest.mark.parametrize("qc,kc", [(8, 8), (16, 32), (64, 64), (13, 7)])
def test_chunk_size_invariance(qc, kc):
    """Chunk sizes (incl. non-divisors, which fall back) never change output."""
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 56, 56, 4, 2, 8)
    got = attention.chunked_attention(q, k, v, q_chunk=qc, kv_chunk=kc)
    np.testing.assert_allclose(got, naive_attention(q, k, v), **TOL)


def test_sliding_window():
    q, k, v = _qkv(jax.random.PRNGKey(2), 2, 64, 64, 4, 4, 8)
    got = attention.chunked_attention(q, k, v, window=16, q_chunk=16, kv_chunk=16)
    np.testing.assert_allclose(got, naive_attention(q, k, v, window=16), **TOL)


def test_bidirectional():
    q, k, v = _qkv(jax.random.PRNGKey(3), 2, 32, 48, 4, 4, 8)
    got = attention.chunked_attention(q, k, v, causal=False, q_chunk=16, kv_chunk=16)
    np.testing.assert_allclose(got, naive_attention(q, k, v, causal=False), **TOL)


def test_decode_matches_full():
    """decode_attention at position t == row t of full causal attention."""
    b, s, h, hk, d = 2, 24, 4, 2, 8
    q, k, v = _qkv(jax.random.PRNGKey(4), b, s, s, h, hk, d)
    full = naive_attention(q, k, v)
    for t in [0, 7, 23]:
        got = attention.decode_attention(q[:, t:t + 1], k, v, t + 1)
        np.testing.assert_allclose(got[:, 0], full[:, t], **TOL)


def test_decode_window_matches():
    b, s, h, d = 1, 32, 4, 8
    q, k, v = _qkv(jax.random.PRNGKey(5), b, s, s, h, h, d)
    full = naive_attention(q, k, v, window=8)
    for t in [10, 31]:
        got = attention.decode_attention(q[:, t:t + 1], k, v, t + 1, window=8)
        np.testing.assert_allclose(got[:, 0], full[:, t], **TOL)


def test_gqa_fwd_then_decode_equivalence():
    """Prefill(S) + decode(S..S+2) == full forward(S+3) last rows."""
    d_model, h, hk, hd = 32, 4, 2, 8
    cfg = dict(n_heads=h, n_kv=hk, head_dim=hd)
    key = jax.random.PRNGKey(6)
    params = attention.gqa_init(key, d_model, h, hk, hd, dtype=jnp.float32)
    s_total = 20
    x = jax.random.normal(jax.random.PRNGKey(7), (2, s_total, d_model))
    full, _ = attention.gqa_fwd(params, x, q_chunk=8, kv_chunk=8, **cfg)

    s0 = s_total - 3
    _, (k, v) = attention.gqa_fwd(params, x[:, :s0], q_chunk=8, kv_chunk=8, **cfg)
    ck = jnp.zeros((2, s_total, hk, hd)).at[:, :s0].set(k)
    cv = jnp.zeros((2, s_total, hk, hd)).at[:, :s0].set(v)
    for t in range(s0, s_total):
        out, ck, cv = attention.gqa_decode(params, x[:, t:t + 1], ck, cv, t, **cfg)
        np.testing.assert_allclose(out[:, 0], full[:, t], rtol=2e-3, atol=1e-4)


def test_ring_cache_decode_matches_window():
    """SWA ring cache (size=window) == windowed attention over full cache."""
    d_model, h, hd, w = 32, 4, 8, 8
    cfg = dict(n_heads=h, n_kv=h, head_dim=hd)
    params = attention.gqa_init(jax.random.PRNGKey(8), d_model, h, h, hd,
                                dtype=jnp.float32)
    s = 24
    x = jax.random.normal(jax.random.PRNGKey(9), (1, s, d_model))
    full, _ = attention.gqa_fwd(params, x, window=w, q_chunk=8, kv_chunk=8, **cfg)

    ring_k = jnp.zeros((1, w, h, hd))
    ring_v = jnp.zeros((1, w, h, hd))
    big_k = jnp.zeros((1, s, h, hd))
    big_v = jnp.zeros((1, s, h, hd))
    for t in range(s):
        out_r, ring_k, ring_v = attention.gqa_decode(
            params, x[:, t:t + 1], ring_k, ring_v, t, ring_window=w, **cfg)
        out_f, big_k, big_v = attention.gqa_decode(
            params, x[:, t:t + 1], big_k, big_v, t, window=w, **cfg)
        np.testing.assert_allclose(out_r, out_f, rtol=2e-3, atol=1e-4)
        np.testing.assert_allclose(out_r[:, 0], full[:, t], rtol=2e-3, atol=1e-4)


def test_mla_fwd_and_decode_equivalence():
    d_model, h = 32, 4
    mla_kw = dict(n_heads=h, nope_dim=8, rope_dim=4, v_dim=8)
    params = attention.mla_init(jax.random.PRNGKey(10), d_model, h,
                                q_lora=16, kv_lora=8, dtype=jnp.float32, **{
                                    k: v for k, v in mla_kw.items() if k != "n_heads"})
    s = 12
    x = jax.random.normal(jax.random.PRNGKey(11), (2, s, d_model))
    full, (c, kpe) = attention.mla_fwd(params, x, q_chunk=4, kv_chunk=4, **mla_kw)

    s0 = s - 3
    _, (c0, kpe0) = attention.mla_fwd(params, x[:, :s0], q_chunk=4, kv_chunk=4, **mla_kw)
    cc = jnp.zeros((2, s, 8)).at[:, :s0].set(c0)
    ckpe = jnp.zeros((2, s, 4)).at[:, :s0].set(kpe0)
    for t in range(s0, s):
        for absorb in (True, False):
            out, cc2, ckpe2 = attention.mla_decode(params, x[:, t:t + 1], cc, ckpe,
                                                   t, absorb=absorb, **mla_kw)
            np.testing.assert_allclose(out[:, 0], full[:, t], rtol=2e-3, atol=1e-4)
        cc, ckpe = cc2, ckpe2


@settings(max_examples=10, deadline=None)
@given(sq=st.integers(4, 40), h=st.sampled_from([2, 4]), seed=st.integers(0, 999))
def test_property_causality(sq, h, seed):
    """Perturbing future tokens never changes past outputs."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    q, k, v = _qkv(k1, 1, sq, sq, h, h, 8)
    out1 = attention.chunked_attention(q, k, v, q_chunk=8, kv_chunk=8)
    t = sq // 2
    k2v = k.at[:, t:].add(jax.random.normal(k2, k[:, t:].shape))
    v2v = v.at[:, t:].add(1.0)
    out2 = attention.chunked_attention(q, k2v, v2v, q_chunk=8, kv_chunk=8)
    np.testing.assert_allclose(out1[:, :t], out2[:, :t], rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The fused causal kernel (interpret mode on the CPU) against the chunk scan
# ---------------------------------------------------------------------------

BF16_TOL = dict(rtol=2e-2, atol=2e-2)      # the kernels' bf16 tolerance
S_KERNEL = 2 * attention.KERNEL_BLOCK      # two blocks: (q0, kv1) fully masked


def _spy_kernel(monkeypatch):
    """Record each call of the fused kernel (at trace time)."""
    calls = []
    real = attention._causal_kernel

    def spy(q, *args, **kwargs):
        calls.append(q.shape)
        return real(q, *args, **kwargs)
    monkeypatch.setattr(attention, "_causal_kernel", spy)
    return calls


def _qkv_dims(key, b, s, h, hk, dk, dv, dtype=jnp.float32):
    q, k, _ = _qkv(key, b, s, s, h, hk, dk, dtype)
    v = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hk, dv), dtype)
    return q, k, v


def _scan(q, k, v, scale=None):
    return attention._chunk_scan(
        q, k, v, causal=True, window=None, q_offset=0, kv_valid_len=None,
        q_chunk=attention.KERNEL_BLOCK, kv_chunk=attention.KERNEL_BLOCK,
        scale=scale if scale is not None else q.shape[-1] ** -0.5)


@pytest.mark.parametrize("b,h,hk,dk,dv", [
    (1, 4, 4, 192, 128),    # MLA's widths (Dk != Dv), heads scaled down
    (2, 4, 2, 128, 128),    # GQA, g = 2
    (1, 2, 2, 128, 128),    # one kv head per q head (zamba2's attention)
    (1, 4, 1, 128, 128),    # one kv head for all
], ids=["mla", "gqa_g2", "mha_g1", "mqa"])
def test_causal_kernel_matches_scan(monkeypatch, b, h, hk, dk, dv):
    calls = _spy_kernel(monkeypatch)
    q, k, v = _qkv_dims(jax.random.PRNGKey(20), b, S_KERNEL, h, hk, dk, dv)
    got = attention.chunked_attention(q, k, v, q_chunk=512, kv_chunk=512,
                                      softmax_scale=0.13)
    assert calls == [q.shape]
    np.testing.assert_allclose(got, _scan(q, k, v, 0.13), **TOL)


def test_causal_kernel_bf16_matches_scan(monkeypatch):
    calls = _spy_kernel(monkeypatch)
    q, k, v = _qkv_dims(jax.random.PRNGKey(21), 1, S_KERNEL, 4, 4, 192, 128,
                        jnp.bfloat16)
    got = attention.chunked_attention(q, k, v)
    assert calls and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32),
                               _scan(q, k, v).astype(jnp.float32), **BF16_TOL)


def test_causal_kernel_gradients_match_scan(monkeypatch):
    """The kernel's own VJP (one-chip training) against the scan's."""
    calls = _spy_kernel(monkeypatch)
    q, k, v = _qkv_dims(jax.random.PRNGKey(22), 1, S_KERNEL, 4, 2, 64, 128)

    def loss(attend):
        return lambda q, k, v: (attend(q, k, v) ** 2).sum()
    got = jax.grad(loss(attention.chunked_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(_scan), argnums=(0, 1, 2))(q, k, v)
    assert calls
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("h,hk,dk,dtype", [
    (4, 4, 192, jnp.float32),     # MLA's widths (Dk != Dv)
    (4, 1, 128, jnp.float32),     # one kv head for all: dk, dv summed over 4
    (2, 2, 192, jnp.bfloat16),    # bf16 operands, f32 statistics
], ids=["mla", "mqa", "mla_bf16"])
def test_causal_kernel_vjp_runs_the_backward_kernels(monkeypatch, h, hk, dk,
                                                     dtype):
    """Gradients through the kernel come from its own backward kernels
    (``causal_attention_vjp``), not a recomputation by the scan, and agree
    with the scan's."""
    from repro.kernels import attention as attention_kernel
    vjps = []
    real = attention_kernel.causal_attention_vjp

    def spy(*args, **kwargs):
        vjps.append(args[0].shape)
        return real(*args, **kwargs)
    monkeypatch.setattr(attention_kernel, "causal_attention_vjp", spy)
    scans = []
    real_scan = attention._chunk_scan

    def scan_spy(*args, **kwargs):
        scans.append(args[0].shape)
        return real_scan(*args, **kwargs)
    q, k, v = _qkv_dims(jax.random.PRNGKey(24), 1, S_KERNEL, h, hk, dk, 128,
                        dtype)
    w = jax.random.normal(jax.random.PRNGKey(25), (*q.shape[:3], 128))

    def loss(attend):
        return lambda q, k, v: (attend(q, k, v).astype(jnp.float32) * w).sum()
    got = jax.grad(loss(attention.chunked_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(_scan), argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(attention, "_chunk_scan", scan_spy)
    jax.grad(loss(attention.chunked_attention), argnums=(0, 1, 2))(q, k, v)
    assert vjps == [(1, h, S_KERNEL, dk)] * 2 and not scans
    tol = TOL if dtype == jnp.float32 else BF16_TOL
    for g, wnt in zip(got, want):
        assert g.dtype == dtype
        g, wnt = g.astype(jnp.float32), wnt.astype(jnp.float32)
        np.testing.assert_allclose(g, wnt, rtol=tol["rtol"],
                                   atol=tol["rtol"] * float(jnp.abs(wnt).max()))


@pytest.mark.parametrize("case,kernel", [
    ("causal_from_0", True),
    ("one_block", True),
    ("one_device_mesh", True),
    ("window", False),
    ("kv_valid_len", False),
    ("non_causal", False),
    ("q_offset", False),
    ("traced_q_offset", False),
    ("cross_lengths", False),
    ("untiled_length", False),
    ("narrow_values", False),
    ("multi_device_mesh", False),
])
def test_attention_path_choice(monkeypatch, case, kernel):
    """Only causal self-attention from a static 0 over every key, at a
    tiling length, with lane-wide values and off a multi-chip mesh, takes
    the kernel."""
    from jax.sharding import Mesh
    calls = _spy_kernel(monkeypatch)
    s = {"untiled_length": 1000, "one_block": 256}.get(case, S_KERNEL)
    dv = 64 if case == "narrow_values" else 128
    q, k, v = _qkv_dims(jax.random.PRNGKey(23), 1, s, 2, 2, 128, dv)
    kw = {"window": dict(window=256), "kv_valid_len": dict(kv_valid_len=900),
          "non_causal": dict(causal=False), "q_offset": dict(q_offset=512),
          "traced_q_offset": dict(q_offset=jnp.int32(0))}.get(case, {})
    if case == "cross_lengths":
        k, v = k[:, :attention.KERNEL_BLOCK], v[:, :attention.KERNEL_BLOCK]
    mesh = {"one_device_mesh": lambda: jax.set_mesh(
                Mesh(np.array(jax.devices()[:1]), ("data",))),
            "multi_device_mesh": lambda: jax.sharding.use_abstract_mesh(
                compat.abstract_mesh((2,), ("data",)))}.get(case)
    with (mesh() if mesh else contextlib.nullcontext()):
        out = jax.eval_shape(
            lambda q, k, v: attention.chunked_attention(q, k, v, **kw), q, k, v)
    assert out.shape == (*q.shape[:3], dv)
    assert bool(calls) == kernel
