"""Mamba2 chunked-vs-recurrent, RWKV6 chunked-vs-step, MoE invariants."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models import mamba2, moe, rwkv6
from repro.models.mamba2 import Mamba2Config
from repro.models.moe import MoEConfig
from repro.models.rwkv6 import RWKV6Config


# ---------------------------------------------------------------------------
# Mamba2
# ---------------------------------------------------------------------------

CFG_M = Mamba2Config(d_inner=32, n_heads=4, state_dim=8, n_groups=2, chunk=8)


def _mamba_params(key, d_model=16):
    return mamba2.mamba2_init(key, d_model, CFG_M, jnp.float32)


def test_mamba2_chunked_matches_recurrent():
    p = _mamba_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 16))
    got = mamba2.mamba2_fwd(p, x, CFG_M)
    want = mamba2.mamba2_ref_recurrent(p, x, CFG_M)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk", [4, 6, 12, 24])
def test_mamba2_chunk_invariance(chunk):
    cfg = Mamba2Config(d_inner=32, n_heads=4, state_dim=8, n_groups=2, chunk=chunk)
    p = _mamba_params(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 16))
    base = mamba2.mamba2_fwd(p, x, CFG_M)
    got = mamba2.mamba2_fwd(p, x, cfg)
    np.testing.assert_allclose(got, base, rtol=2e-4, atol=2e-4)


def test_mamba2_prefill_state_seeds_decode():
    """fwd(S, return_state) then decode(t) == fwd(S+3) at tail positions."""
    p = _mamba_params(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 19, 16))
    full = mamba2.mamba2_fwd(p, x, CFG_M)
    s0 = 16
    _, (ssm, conv) = mamba2.mamba2_fwd(p, x[:, :s0], CFG_M, return_state=True)
    for t in range(s0, 19):
        out, ssm, conv = mamba2.mamba2_decode(p, x[:, t:t + 1], ssm, conv, CFG_M)
        np.testing.assert_allclose(out[:, 0], full[:, t], rtol=2e-3, atol=2e-4)


def test_mamba2_no_nans_long_decay():
    """Extreme dt must not overflow the chunked log-decay path."""
    p = _mamba_params(jax.random.PRNGKey(6))
    p = dict(p, dt_bias=jnp.full_like(p["dt_bias"], 6.0))  # huge decay
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(7), (1, 32, 16))
    out = mamba2.mamba2_fwd(p, x, CFG_M)
    assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# RWKV6
# ---------------------------------------------------------------------------

CFG_R = RWKV6Config(n_heads=4, head_dim=8, decay_lora_rank=4, chunk=8)


def _rwkv_params(key, d=32):
    return rwkv6.rwkv6_time_mix_init(key, d, CFG_R, jnp.float32)


def _strong_decay(p, key, w0=2.0):
    """Decays of exp(-exp(w0 + lora)): around -7.4 per step, and past -8
    where the LoRA (random up-projection) pushes them."""
    a = p["w_lora"]["a"]
    up = 0.5 * jax.random.normal(key, p["w_lora"]["b"].shape) / a.shape[1] ** 0.5
    return dict(p, w0=jnp.full_like(p["w0"], w0), w_lora=dict(p["w_lora"], b=up))


def _step_state(p, x, cfg):
    """The state the per-step recurrence ends in: decode over every token."""
    b, _, d = x.shape

    def step(carry, xt):
        st, prev = carry
        _, st, prev = rwkv6.rwkv6_time_mix_decode(p, xt[:, None], st, prev, cfg)
        return (st, prev), None

    init = (jnp.zeros((b, cfg.n_heads, cfg.head_dim, cfg.head_dim)), jnp.zeros((b, 1, d)))
    (st, _), _ = jax.lax.scan(step, init, jnp.moveaxis(x, 1, 0))
    return st


@pytest.mark.parametrize("chunk,seq,strong", [(8, 24, False), (32, 128, True),
                                              (64, 128, True)],
                         ids=["chunk8", "strong-chunk32", "strong-chunk64"])
def test_rwkv6_chunked_matches_step(chunk, seq, strong):
    """Chunked form == per-step oracle, in output and final state; with
    strong decays (log-decay past -8 a step) nothing overflows."""
    cfg = dataclasses.replace(CFG_R, chunk=chunk)
    p = _rwkv_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, 32))
    if strong:
        p = _strong_decay(p, jax.random.PRNGKey(8))
        logw = rwkv6._projections(p, x, jnp.zeros((2, 1, 32)))[-1]
        assert float(logw.min()) < -8.0
    got, (st, _) = rwkv6.rwkv6_time_mix(p, x, cfg, return_state=True)
    assert np.isfinite(np.asarray(got)).all()
    want = rwkv6.rwkv6_time_mix_ref(p, x, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(st, _step_state(p, x, cfg), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk,seq", [(8, 24), (32, 64)])
def test_rwkv6_chunked_grad_matches_step(chunk, seq):
    """jax.grad through the chunked form (the training path) == through the
    per-step oracle, with strong decays: no masked exponent leaks a NaN."""
    cfg = dataclasses.replace(CFG_R, chunk=chunk)
    p = _strong_decay(_rwkv_params(jax.random.PRNGKey(0)), jax.random.PRNGKey(9))
    x = jax.random.normal(jax.random.PRNGKey(10), (2, seq, 32))
    w = jax.random.normal(jax.random.PRNGKey(11), (2, seq, 32))

    def loss(fn):
        return lambda p, x: jnp.sum(w * fn(p, x, cfg))

    got = jax.grad(loss(rwkv6.rwkv6_time_mix), argnums=(0, 1))(p, x)
    want = jax.grad(loss(rwkv6.rwkv6_time_mix_ref), argnums=(0, 1))(p, x)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(g)).all(), path
        np.testing.assert_allclose(g, r, rtol=1e-3, atol=1e-3 * float(jnp.abs(r).max()),
                                   err_msg=jax.tree_util.keystr(path))


def test_rwkv6_bonus_exact_under_bf16_matmuls(monkeypatch):
    """The bonus u stays an f32 product where a default-precision matmul
    rounds its f32 operands to bf16, as the TPU's does (the CPU computes
    such a matmul in f32, so the rounding is applied here by hand): the
    chunked form's y(u) - y(0) is r_t (u * k_t) v_t to f32 accuracy."""
    einsum = jnp.einsum

    def bf16_einsum(spec, *ops, precision=None, **kw):
        if precision is None:
            ops = [o.astype(jnp.bfloat16).astype(o.dtype) if o.dtype == jnp.float32 else o
                   for o in ops]
        return einsum(spec, *ops, precision=precision, **kw)

    monkeypatch.setattr(jnp, "einsum", bf16_einsum)
    monkeypatch.setattr(rwkv6, "_out_stage", lambda params, y, g, h, dh: y)
    h, dh = CFG_R.n_heads, CFG_R.head_dim
    p = _rwkv_params(jax.random.PRNGKey(0))
    p = dict(p, u=jax.random.normal(jax.random.PRNGKey(12), (h, dh)))
    x = jax.random.normal(jax.random.PRNGKey(13), (2, 24, 32))
    y = rwkv6.rwkv6_time_mix(p, x, CFG_R)
    y0 = rwkv6.rwkv6_time_mix(dict(p, u=jnp.zeros_like(p["u"])), x, CFG_R)
    r, k, v = (a.reshape(2, 24, h, dh)
               for a in rwkv6._projections(p, x, jnp.zeros((2, 1, 32)))[:3])
    bonus = (r * k * p["u"]).sum(-1, keepdims=True) * v
    np.testing.assert_allclose(y - y0, bonus, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(bonus).max()))


@pytest.mark.parametrize("chunk", [4, 6, 24])
def test_rwkv6_chunk_invariance(chunk):
    cfg = RWKV6Config(n_heads=4, head_dim=8, decay_lora_rank=4, chunk=chunk)
    p = _rwkv_params(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 32))
    np.testing.assert_allclose(rwkv6.rwkv6_time_mix(p, x, cfg),
                               rwkv6.rwkv6_time_mix(p, x, CFG_R),
                               rtol=2e-4, atol=2e-4)


def test_rwkv6_prefill_then_decode():
    p = _rwkv_params(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32))
    full = rwkv6.rwkv6_time_mix(p, x, CFG_R)
    s0 = 13
    _, (st, xprev) = rwkv6.rwkv6_time_mix(p, x[:, :s0], CFG_R, return_state=True)
    for t in range(s0, 16):
        out, st, xprev = rwkv6.rwkv6_time_mix_decode(p, x[:, t:t + 1], st, xprev, CFG_R)
        np.testing.assert_allclose(out[:, 0], full[:, t], rtol=2e-3, atol=2e-4)


def test_rwkv6_channel_mix_shift():
    p = rwkv6.rwkv6_channel_mix_init(jax.random.PRNGKey(6), 32, 64, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 8, 32))
    full = rwkv6.rwkv6_channel_mix(p, x)
    # per-token with carried x_prev must match
    prev = jnp.zeros((1, 1, 32))
    for t in range(8):
        out = rwkv6.rwkv6_channel_mix(p, x[:, t:t + 1], x_prev=prev)
        np.testing.assert_allclose(out[:, 0], full[:, t], rtol=1e-4, atol=1e-5)
        prev = x[:, t:t + 1]


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

CFG_E = MoEConfig(n_experts=4, top_k=2, d_ff_expert=32)


def _moe_params(key, d=16, cfg=CFG_E):
    return moe.moe_init(key, d, cfg, jnp.float32)


def dense_moe_oracle(params, x, cfg):
    """Held experts evaluated densely for every token, weighted by the same
    router's combine weights (zero where an expert is not chosen)."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    w, idx, _ = moe.route(params, xt, cfg)
    e0, eh = cfg.held
    ew = params["experts"]
    g = jnp.einsum("td,edf->tef", xt, ew["w_gate"])
    u = jnp.einsum("td,edf->tef", xt, ew["w_up"])
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, ew["w_down"])
    onehot = jax.nn.one_hot(idx - e0, eh)                # (t,k,eh); not held: 0
    combine = jnp.einsum("tk,tke->te", w, onehot)
    out = jnp.einsum("te,ted->td", combine, y)
    if cfg.n_shared:
        from repro.models import layers
        out = out + layers.swiglu(params["shared"], xt)
    return out.reshape(b, s, d)


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_moe_matches_dense_oracle(router):
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                    router=router, n_shared=1 if router == "sigmoid" else 0,
                    d_ff_shared=32, routed_scale=2.5 if router == "sigmoid" else 1.0)
    p = _moe_params(jax.random.PRNGKey(0), cfg=cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    got, _ = moe.moe_fwd(p, x, cfg)
    want = dense_moe_oracle(p, x, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_moe_dropless_under_full_skew(monkeypatch):
    """Every token routed to one held expert: the buffer of held pairs takes
    them all (the large buffer, since the small one holds 4x an even share)
    and the layer matches the dense oracle. Row tiles of 8 make the small
    buffer smaller than the large one at this size."""
    monkeypatch.setattr(moe, "TM", 8)
    cfg = MoEConfig(n_experts=16, top_k=2, d_ff_expert=16, router="sigmoid",
                    n_shared=1, d_ff_shared=16, routed_scale=2.5, n_group=4,
                    topk_group=2, first_held=4, n_held=4)
    p = _moe_params(jax.random.PRNGKey(2), cfg=cfg)
    p["router_bias"] = p["router_bias"].at[5].set(10.0)     # held expert 1 of 4
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, 16))
    _, idx, _ = moe.route(p, x.reshape(-1, 16), cfg)
    assert (np.asarray(idx) == 5).any(-1).all()
    got, _ = moe.moe_fwd(p, x, cfg)
    np.testing.assert_allclose(got, dense_moe_oracle(p, x, cfg), rtol=1e-5, atol=1e-5)


def test_moe_held_shares_sum_to_whole_layer(monkeypatch):
    """Expert parallelism over 4 ranks: each rank holds 4 of 16 experts and
    routes over all 16; the ranks' outputs, with the shared expert counted
    once, add up to the layer that holds every expert."""
    monkeypatch.setattr(moe, "TM", 8)
    whole = MoEConfig(n_experts=16, top_k=4, d_ff_expert=16, router="sigmoid",
                      n_shared=1, d_ff_shared=16, routed_scale=2.5, n_group=4,
                      topk_group=2)
    p = _moe_params(jax.random.PRNGKey(4), cfg=whole)
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(5), (16,))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 16))
    want, _ = moe.moe_fwd(p, x, whole)
    from repro.models import layers
    shared = layers.swiglu(p["shared"], x)
    total = shared
    for rank in range(4):
        cfg = dataclasses.replace(whole, first_held=4 * rank, n_held=4)
        pr = {**p, "experts": jax.tree.map(lambda a: a[4 * rank:4 * rank + 4],
                                           p["experts"])}
        got, _ = moe.moe_fwd(pr, x, cfg)
        total = total + (got - shared)
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


def test_moe_capacity_drops_excess():
    """The GSPMD paths' capacity dispatch drops pairs past capacity."""
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff_expert=16, capacity_factor=0.25)
    p = _moe_params(jax.random.PRNGKey(2), cfg=cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, 16))
    out, metrics = moe.moe_fwd(p, x, cfg)
    assert float(metrics["moe_dropped_frac"]) > 0.0
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("groups", [1, 4])
def test_moe_capacity_with_room_matches_dense_oracle(groups):
    """With room for every pair, capacity dispatch in any number of groups
    is the dropless layer: the same noaux_tc routing, nothing dropped."""
    cfg = moe.for_gspmd(MoEConfig(n_experts=8, top_k=2, d_ff_expert=16,
                                  router="sigmoid", n_shared=1, d_ff_shared=16,
                                  routed_scale=2.5, n_group=4, topk_group=2),
                        groups, 32)
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    assert cfg.dispatch_groups == groups
    p = _moe_params(jax.random.PRNGKey(10), cfg=cfg)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 16, 16))
    got, metrics = moe.moe_fwd(p, x, cfg)
    assert float(metrics["moe_dropped_frac"]) < 1e-6
    np.testing.assert_allclose(got, dense_moe_oracle(p, x, cfg), rtol=2e-4, atol=2e-4)


def test_moe_held_count_defaults_to_every_expert():
    cfg = MoEConfig(n_experts=8, top_k=2, d_ff_expert=16)
    assert cfg.n_held == 8 and cfg.held == (0, 8)
    assert MoEConfig(8, 2, 16, first_held=4, n_held=4).held == (4, 4)


def _noaux_tc_loop(scores, bias, cfg):
    """DeepSeek-V3's noaux_tc selection one token at a time, in numpy."""
    e = scores.shape[-1]
    per = e // cfg.n_group
    ids, weights = [], []
    for s_t in scores:
        biased = s_t + bias
        groups = sorted(range(cfg.n_group), key=lambda g: -np.sort(
            biased[g * per:(g + 1) * per])[-2:].sum())[:cfg.topk_group]
        cand = [i for g in groups for i in range(g * per, (g + 1) * per)]
        chosen = sorted(cand, key=lambda i: -biased[i])[:cfg.top_k]
        w = s_t[chosen] / s_t[chosen].sum() * cfg.routed_scale
        ids.append(chosen)
        weights.append(w)
    return np.array(ids), np.array(weights)


def test_noaux_tc_selection_matches_loop_oracle():
    """Group-limited selection picks the oracle's experts; the bias moves the
    choice but not the combine weights, which are the unbiased scores
    renormalised and scaled."""
    cfg = MoEConfig(n_experts=32, top_k=4, d_ff_expert=8, router="sigmoid",
                    routed_scale=2.5, n_group=8, topk_group=3)
    p = _moe_params(jax.random.PRNGKey(7), cfg=cfg)
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (32,))
    xt = jax.random.normal(jax.random.PRNGKey(9), (64, 16))
    w, idx, _ = moe.route(p, xt, cfg)
    scores = np.asarray(jax.nn.sigmoid(xt @ p["router_w"]), np.float64)
    want_idx, want_w = _noaux_tc_loop(scores, np.asarray(p["router_bias"], np.float64), cfg)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    _, idx0, _ = moe.route({**p, "router_bias": jnp.zeros(32)}, xt, cfg)
    moved = [set(a) != set(b) for a, b in zip(np.asarray(idx), np.asarray(idx0))]
    assert 0 < np.mean(moved) < 1


def test_moe_weights_sum_to_one():
    p = _moe_params(jax.random.PRNGKey(4))
    xt = jax.random.normal(jax.random.PRNGKey(5), (32, 16))
    w, idx, _ = moe.route(p, xt, CFG_E)
    np.testing.assert_allclose(w.sum(-1), np.ones(32), rtol=1e-5)
    assert (idx >= 0).all() and (idx < CFG_E.n_experts).all()


def test_router_bias_pushes_balance():
    p = _moe_params(jax.random.PRNGKey(6),
                    cfg=MoEConfig(4, 2, 32, router="sigmoid"))
    counts = jnp.array([100.0, 10.0, 10.0, 10.0])
    p2 = moe.update_router_bias(p, counts, rate=0.1)
    # overloaded expert bias goes down, underloaded up
    assert p2["router_bias"][0] < p["router_bias"][0]
    assert (p2["router_bias"][1:] > p["router_bias"][1:]).all()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 999), t=st.integers(8, 48))
def test_moe_token_conservation(seed, t):
    """Every token receives its top-k mixture: the output is linear in the
    combine weights, which sum to 1 -- check the combine path by verifying
    no token's output is zeroed."""
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff_expert=8)
    p = _moe_params(jax.random.PRNGKey(seed), cfg=cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, t, 16))
    out, _ = moe.moe_fwd(p, x, cfg)
    assert (np.abs(np.asarray(out)).sum(-1) > 0).all()
