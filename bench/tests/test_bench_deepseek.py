"""deepseek-v3-671b at small widths on the CPU: the serving path against the
configuration's plain reference (prefill logits, then decode logits through
the MLA latent cache against the reference's full forward), the mapping of
the benchmark's sizes onto the program's chip-share configuration, YaRN
against the reference's own derivation, and the counts by hand."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib  # noqa: F401  (puts the repository on the path)
from bench import load, serving

B, PROMPT, STEPS = 2, 24, 6
NAME = "deepseek-v3-671b"
# Published widths cut to a test's size; this chip holds experts 4-7 of 32
# (rank 1 of 8), 4 per token, 4 groups of which 2 are kept.
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160,
             moe_intermediate_size=32, n_routed_experts=4, num_experts_per_tok=4,
             n_group=4, topk_group=2, num_hidden_layers=3, vocab_size=256)

# float32: the program sums in another order (chunked attention, absorbed
# decode, grouped matmuls) than the reference; measured 2e-7 of the largest
# logit.
F32_TOL = 1e-4
# bfloat16: every activation rounds to bf16 between f32 products; measured
# 0.4% of the largest logit over this stack, 40x the float32 limit.
BF16_TOL = 2e-2


def small_sizes(**extra) -> dict:
    sizes, _ = load.config(NAME)
    dep = {**sizes["deployment"], "routed_experts_total": 32, "expert_rank": 1}
    return {**sizes, **SMALL, "deployment": dep, **extra}


def served_logits(dtype, seed=5):
    from repro.models import model
    from repro.serve import engine

    _, mod = load.config(NAME)
    sizes = small_sizes(dtype=dtype)
    cfg = serving.program_config(mod, sizes)
    params = serving.build_params(mod, sizes, seed)
    serving.check_layout(params, cfg)
    toks = serving.prompts(seed, 1, 0, B, PROMPT + STEPS, sizes["vocab_size"])
    prefill, decode = (jax.jit(f) for f in engine.make_serve_fns(cfg))
    cache = model.init_cache(cfg, B, PROMPT + STEPS)
    lg, cache = prefill(params, {"tokens": jnp.asarray(toks[:, :PROMPT])}, cache)
    got = [lg]
    for i in range(STEPS - 1):
        lg, cache = decode(params, jnp.asarray(toks[:, PROMPT + i:PROMPT + i + 1]),
                           PROMPT + i, cache)
        got.append(lg)
    got = np.stack([np.asarray(g) for g in got], axis=1)
    ref = np.asarray(mod.logits(params, mod.hidden(params, toks[:, :-1], sizes), sizes))
    return got, ref[:, PROMPT - 1:]


def rel(a, b) -> float:
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.fixture
def small_tiles(monkeypatch):
    """Row tiles of 8, so that the prefill's buffer of held pairs is smaller
    than one of every possible pair, as at the real size."""
    from repro.models import moe
    monkeypatch.setattr(moe, "TM", 8)


@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL), ("bfloat16", BF16_TOL)])
def test_serving_matches_reference(small_tiles, dtype, tol):
    got, ref = served_logits(dtype)
    assert rel(got[:, 0], ref[:, 0]) < tol, "prefill"
    assert rel(got[:, 1:], ref[:, 1:]) < tol, "decode through the latent cache"


def test_bias_in_the_combine_weights_fails_the_comparison(small_tiles, monkeypatch):
    """The correction bias moves the choice only: a router that lets it into
    the combine weights reads outside the float32 limit (measured 6.7e-4
    of the largest logit)."""
    from repro.models import moe
    route = moe.route

    def leaky(params, xt, cfg):
        _, idx, probs = route(params, xt, cfg)
        logits = moe.layers.dense(params["router_w"].astype(xt.dtype), xt)
        biased = jax.nn.sigmoid(logits.astype(jnp.float32)) + params["router_bias"]
        w = jnp.take_along_axis(biased, idx, axis=1)
        return w / w.sum(-1, keepdims=True) * cfg.routed_scale, idx, probs
    monkeypatch.setattr(moe, "route", leaky)
    got, ref = served_logits("float32")
    assert rel(got, ref) > 5 * F32_TOL


def test_sizes_map_to_the_chip_share():
    """The benchmark's sizes give the program's ``chip_share()``: published
    widths, 1 dense and 4 MoE layers, experts 0-7 of 256 held."""
    from repro.configs import deepseek_v3_671b
    from repro.models import model
    sizes, mod = load.config(NAME)
    cfg = serving.program_config(mod, sizes)
    assert cfg == deepseek_v3_671b.chip_share()
    assert cfg.moe.held == (0, 8) and cfg.moe.n_experts == 256
    assert (cfg.n_layers, cfg.first_k_dense) == (5, 1)
    shapes = jax.eval_shape(lambda k: mod.make_params(k, sizes), jax.random.PRNGKey(0))
    serving.check_layout(shapes, cfg)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        jax.eval_shape(lambda k: model.init(k, cfg), jax.random.PRNGKey(0)))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 9.5e9 < nbytes < 9.6e9


def test_yarn_matches_the_reference():
    """The program's YaRN frequencies and softmax factor are the reference's
    own derivation: the first 10 of 32 frequencies kept, from the 23rd on
    divided by 40, and (0.1 ln 40 + 1)^2 on the scale."""
    from repro.models import layers
    sizes, mod = load.config(NAME)
    inv_ref, scale_ref = mod._yarn(sizes)
    y = layers.YaRN(factor=40.0, original_max_pos=4096)
    inv = y.inv_freq(64, 10000.0)
    np.testing.assert_allclose(inv, inv_ref, rtol=1e-6)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert y.softmax_factor() == pytest.approx((0.1 * np.log(40) + 1) ** 2)
    assert scale_ref == pytest.approx(192 ** -0.5 * y.softmax_factor())


def test_counts_by_hand():
    s = small_sizes()
    _, mod = load.config(NAME)
    # MLA per token: 64x32 + 32x4x24 + 64x16 + 16x4x32 + 64x8 + 4x16x64 = 12800
    # MACs -> 25600 FLOPs, 3 layers; the dense MLP 3x64x160 MACs -> 61440; per
    # MoE layer the router 64x32 and the shared expert 3x64x32 -> 16384, 2 layers.
    per_tok = 3 * 25600 + 61440 + 2 * 16384
    # attention: 2 x 4 x (16 + 8 + 16) = 320 FLOPs per key per layer; causal 8: 36 keys.
    attn = 3 * 320 * 36
    # held pairs: 16 tokens x 4 per token x 4 held / 32 = 8 per MoE layer, each
    # a SwiGLU of 3 x 64 x 32 MACs.
    experts = 2 * 8 * 2 * 3 * 64 * 32
    head = 2 * 64 * 256
    assert mod.experts_flops(s, 2, 8) == experts
    assert mod.prefill_flops(s, 2, 8) == 16 * per_tok + 2 * attn + experts + 2 * head
    # bytes: each MoE layer's 4 held experts (3 x 64 x 32 bf16) once, and the
    # 8 gathered rows in and out, 64 wide.
    assert mod.experts_bytes(s, 2, 8) == 2 * (4 * 3 * 64 * 32 * 2 + 2 * 8 * 64 * 2)

