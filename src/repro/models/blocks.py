"""Per-layer blocks: init / train-forward / prefill / decode for every layer
kind used by the ten assigned architectures.

Kinds:
  attn_mlp   dense transformer layer (GQA + MLP)        [llama/qwen/chatglm/
                                                          mistral/hubert]
  attn_moe   GQA + routed MoE                            [mixtral]
  mla_mlp    DeepSeek MLA + dense MLP                    [deepseek first-3]
  mla_moe    DeepSeek MLA + MoE (shared+routed)          [deepseek]
  mamba      Mamba2 layer                                [zamba2 backbone]
  rwkv       RWKV6 time-mix + channel-mix                [rwkv6]
  cross_mlp  gated cross-attention to image tokens + MLP [llama3.2-vision]

Residual/pre-norm convention: x = x + f(norm(x)) everywhere (hubert uses
LayerNorm via cfg.norm, others RMSNorm).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import attention, layers, mamba2, moe, rwkv6


def _norm_init(cfg, d=None):
    d = d or cfg.d_model
    return (layers.layernorm_init(d) if cfg.norm == "ln"
            else layers.rmsnorm_init(d))


def norm_apply(cfg, p, x):
    return (layers.layernorm(p, x, cfg.norm_eps) if cfg.norm == "ln"
            else layers.rmsnorm(p, x, cfg.norm_eps))


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _mlp_init(key, cfg):
    if cfg.mlp_type == "gelu":
        return layers.gelu_mlp_init(key, cfg.d_model, cfg.d_ff, _dtype(cfg))
    return layers.swiglu_init(key, cfg.d_model, cfg.d_ff, _dtype(cfg))


@layers.scoped("mlp")
def _mlp_fwd(cfg, p, x):
    return (layers.gelu_mlp(p, x) if cfg.mlp_type == "gelu"
            else layers.swiglu(p, x))


def _attn_kwargs(cfg):
    return dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                rope_fraction=cfg.rope_fraction)


def _mla_kwargs(cfg):
    m = cfg.mla
    return dict(n_heads=cfg.n_heads, nope_dim=m.nope_dim, rope_dim=m.rope_dim,
                v_dim=m.v_dim, rope_theta=cfg.rope_theta, yarn=m.yarn,
                norm_eps=cfg.norm_eps)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def block_init(key, cfg, kind: str):
    ks = jax.random.split(key, 4)
    dt = _dtype(cfg)
    if kind in ("attn_mlp", "attn_moe"):
        p = {
            "norm1": _norm_init(cfg),
            "attn": attention.gqa_init(ks[0], cfg.d_model, cfg.n_heads,
                                       cfg.n_kv_heads, cfg.resolved_head_dim,
                                       qkv_bias=cfg.qkv_bias, dtype=dt),
            "norm2": _norm_init(cfg),
        }
        p["ffn"] = (moe.moe_init(ks[1], cfg.d_model, cfg.moe, dt)
                    if kind == "attn_moe" else _mlp_init(ks[1], cfg))
        return p
    if kind in ("mla_mlp", "mla_moe"):
        m = cfg.mla
        p = {
            "norm1": _norm_init(cfg),
            "attn": attention.mla_init(ks[0], cfg.d_model, cfg.n_heads,
                                       q_lora=m.q_lora, kv_lora=m.kv_lora,
                                       nope_dim=m.nope_dim, rope_dim=m.rope_dim,
                                       v_dim=m.v_dim, dtype=dt),
            "norm2": _norm_init(cfg),
        }
        p["ffn"] = (moe.moe_init(ks[1], cfg.d_model, cfg.moe, dt)
                    if kind == "mla_moe" else _mlp_init(ks[1], cfg))
        return p
    if kind == "mamba":
        return {
            "norm1": _norm_init(cfg),
            "mixer": mamba2.mamba2_init(ks[0], cfg.d_model, cfg.ssm, dt),
        }
    if kind == "rwkv":
        return {
            "norm1": _norm_init(cfg),
            "time_mix": rwkv6.rwkv6_time_mix_init(ks[0], cfg.d_model, cfg.rwkv, dt),
            "norm2": _norm_init(cfg),
            "channel_mix": rwkv6.rwkv6_channel_mix_init(ks[1], cfg.d_model,
                                                        cfg.d_ff, dt),
        }
    if kind == "cross_mlp":
        return {
            "norm1": _norm_init(cfg),
            "attn": attention.gqa_init(ks[0], cfg.d_model, cfg.n_heads,
                                       cfg.n_kv_heads, cfg.resolved_head_dim,
                                       dtype=dt),
            "kv_proj_k": layers.dense_init(ks[1], cfg.vision_dim,
                                           cfg.n_kv_heads * cfg.resolved_head_dim, dt),
            "kv_proj_v": layers.dense_init(ks[2], cfg.vision_dim,
                                           cfg.n_kv_heads * cfg.resolved_head_dim, dt),
            "gate_attn": jnp.zeros((), jnp.float32),
            "norm2": _norm_init(cfg),
            "ffn": _mlp_init(ks[3], cfg),
            "gate_ffn": jnp.zeros((), jnp.float32),
        }
    raise ValueError(kind)


def cross_kv(p, cfg, image_embeds):
    """Project image-patch embeddings to cross-attention K/V."""
    b, s_img, _ = image_embeds.shape
    hk, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    k = layers.dense(p["kv_proj_k"], image_embeds).reshape(b, s_img, hk, hd)
    v = layers.dense(p["kv_proj_v"], image_embeds).reshape(b, s_img, hk, hd)
    return k, v


# ---------------------------------------------------------------------------
# Train forward (full sequence, no cache)
# ---------------------------------------------------------------------------

@layers.scoped("block")
def block_fwd(p, x, cfg, kind: str, extras=None):
    """Returns (x, metrics). Profile scope ``block``: one layer, with its
    norms and residual adds."""
    metrics = {}
    if kind in ("attn_mlp", "attn_moe"):
        h, _ = attention.gqa_fwd(p["attn"], norm_apply(cfg, p["norm1"], x),
                                 causal=cfg.causal, window=cfg.attn_window,
                                 q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                 **_attn_kwargs(cfg))
        x = x + h
        h2in = norm_apply(cfg, p["norm2"], x)
        if kind == "attn_moe":
            h2, metrics = moe.moe_fwd(p["ffn"], h2in, cfg.moe)
        else:
            h2 = _mlp_fwd(cfg, p["ffn"], h2in)
        return x + h2, metrics
    if kind in ("mla_mlp", "mla_moe"):
        h, _ = attention.mla_fwd(p["attn"], norm_apply(cfg, p["norm1"], x),
                                 causal=cfg.causal, q_chunk=cfg.q_chunk,
                                 kv_chunk=cfg.kv_chunk, **_mla_kwargs(cfg))
        x = x + h
        h2in = norm_apply(cfg, p["norm2"], x)
        if kind == "mla_moe":
            h2, metrics = moe.moe_fwd(p["ffn"], h2in, cfg.moe)
        else:
            h2 = _mlp_fwd(cfg, p["ffn"], h2in)
        return x + h2, metrics
    if kind == "mamba":
        h = mamba2.mamba2_fwd(p["mixer"], norm_apply(cfg, p["norm1"], x), cfg.ssm)
        return x + h, metrics
    if kind == "rwkv":
        h = rwkv6.rwkv6_time_mix(p["time_mix"], norm_apply(cfg, p["norm1"], x),
                                 cfg.rwkv)
        x = x + h
        h2 = rwkv6.rwkv6_channel_mix(p["channel_mix"],
                                     norm_apply(cfg, p["norm2"], x))
        return x + h2, metrics
    if kind == "cross_mlp":
        kv = cross_kv(p, cfg, extras["image_embeds"])
        h, _ = attention.gqa_fwd(p["attn"], norm_apply(cfg, p["norm1"], x),
                                 causal=False, kv_override=kv,
                                 q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                 **{**_attn_kwargs(cfg), "rope_fraction": 0.0})
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
        h2 = _mlp_fwd(cfg, p["ffn"], norm_apply(cfg, p["norm2"], x))
        return x + jnp.tanh(p["gate_ffn"]).astype(x.dtype) * h2, metrics
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Cache init / prefill / decode
# ---------------------------------------------------------------------------

def cache_init(cfg, kind: str, batch: int, max_len: int):
    """Zero cache entry for one layer of this kind."""
    dt = _dtype(cfg)
    hd, hk = cfg.resolved_head_dim, cfg.n_kv_heads
    if kind in ("attn_mlp", "attn_moe"):
        s = min(max_len, cfg.attn_window) if cfg.attn_window else max_len
        return {"k": jnp.zeros((batch, s, hk, hd), dt),
                "v": jnp.zeros((batch, s, hk, hd), dt)}
    if kind in ("mla_mlp", "mla_moe"):
        m = cfg.mla
        return {"c": jnp.zeros((batch, max_len, m.kv_lora), dt),
                "kpe": jnp.zeros((batch, max_len, m.rope_dim), dt)}
    if kind == "mamba":
        s = cfg.ssm
        return {"ssm": jnp.zeros((batch, s.n_heads, s.state_dim,
                                  s.d_inner // s.n_heads), jnp.float32),
                "conv": jnp.zeros((batch, s.conv_width - 1,
                                   s.d_inner + 2 * s.n_groups * s.state_dim), dt)}
    if kind == "rwkv":
        r = cfg.rwkv
        return {"wkv": jnp.zeros((batch, r.n_heads, r.head_dim, r.head_dim),
                                 jnp.float32),
                "tm_prev": jnp.zeros((batch, 1, cfg.d_model), dt),
                "cm_prev": jnp.zeros((batch, 1, cfg.d_model), dt)}
    if kind == "cross_mlp":
        return {"k": jnp.zeros((batch, cfg.vision_seq, hk, hd), dt),
                "v": jnp.zeros((batch, cfg.vision_seq, hk, hd), dt)}
    raise ValueError(kind)


@layers.scoped("block")
def block_prefill(p, x, cfg, kind: str, cache, extras=None):
    """Full-sequence forward that also fills the cache. Returns (x, cache).
    Profile scope ``block``, as ``block_fwd``."""
    s = x.shape[1]
    if kind in ("attn_mlp", "attn_moe"):
        h, (k, v) = attention.gqa_fwd(
            p["attn"], norm_apply(cfg, p["norm1"], x), causal=cfg.causal,
            window=cfg.attn_window, q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            **_attn_kwargs(cfg))
        if cfg.attn_window and cache["k"].shape[1] == cfg.attn_window:
            w = cfg.attn_window
            if s >= w:  # ring layout: slot = pos % w
                k_last, v_last = k[:, -w:], v[:, -w:]
                shift = s % w
                cache = {"k": jnp.roll(k_last, shift, axis=1),
                         "v": jnp.roll(v_last, shift, axis=1)}
            else:
                cache = {"k": lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1),
                         "v": lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1)}
        else:
            cache = {"k": lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1),
                     "v": lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1)}
        x = x + h
        h2in = norm_apply(cfg, p["norm2"], x)
        h2 = (moe.moe_fwd(p["ffn"], h2in, cfg.moe)[0] if kind == "attn_moe"
              else _mlp_fwd(cfg, p["ffn"], h2in))
        return x + h2, cache
    if kind in ("mla_mlp", "mla_moe"):
        h, (c, kpe) = attention.mla_fwd(
            p["attn"], norm_apply(cfg, p["norm1"], x), causal=cfg.causal,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk, **_mla_kwargs(cfg))
        cache = {"c": lax.dynamic_update_slice_in_dim(cache["c"], c, 0, 1),
                 "kpe": lax.dynamic_update_slice_in_dim(cache["kpe"], kpe, 0, 1)}
        x = x + h
        h2in = norm_apply(cfg, p["norm2"], x)
        h2 = (moe.moe_fwd(p["ffn"], h2in, cfg.moe)[0] if kind == "mla_moe"
              else _mlp_fwd(cfg, p["ffn"], h2in))
        return x + h2, cache
    if kind == "mamba":
        h, (ssm, conv) = mamba2.mamba2_fwd(
            p["mixer"], norm_apply(cfg, p["norm1"], x), cfg.ssm,
            return_state=True)
        return x + h, {"ssm": ssm, "conv": conv}
    if kind == "rwkv":
        n1 = norm_apply(cfg, p["norm1"], x)
        h, (wkv, tm_prev_n) = rwkv6.rwkv6_time_mix(p["time_mix"], n1, cfg.rwkv,
                                                   return_state=True)
        x = x + h
        n2 = norm_apply(cfg, p["norm2"], x)
        h2, cm_prev_n = rwkv6.rwkv6_channel_mix(p["channel_mix"], n2,
                                                return_state=True)
        # Cache the *normed* last inputs: decode re-normalizes the new token,
        # so store what the mixers actually consumed.
        return x + h2, {"wkv": wkv, "tm_prev": tm_prev_n, "cm_prev": cm_prev_n}
    if kind == "cross_mlp":
        k, v = cross_kv(p, cfg, extras["image_embeds"])
        cache = {"k": k, "v": v}
        h, _ = attention.gqa_fwd(p["attn"], norm_apply(cfg, p["norm1"], x),
                                 causal=False, kv_override=(k, v),
                                 q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                 **{**_attn_kwargs(cfg), "rope_fraction": 0.0})
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
        h2 = _mlp_fwd(cfg, p["ffn"], norm_apply(cfg, p["norm2"], x))
        return x + jnp.tanh(p["gate_ffn"]).astype(x.dtype) * h2, cache
    raise ValueError(kind)


@layers.scoped("block")
def block_decode(p, x, cfg, kind: str, cache, pos, extras=None):
    """One-token step. x: (B,1,d). Returns (x, cache). Profile scope
    ``block``, as ``block_fwd``."""
    if kind in ("attn_mlp", "attn_moe"):
        ring = (cfg.attn_window
                if cfg.attn_window and cache["k"].shape[1] == cfg.attn_window
                else None)
        h, ck, cv = attention.gqa_decode(
            p["attn"], norm_apply(cfg, p["norm1"], x), cache["k"], cache["v"],
            pos, window=None if ring else cfg.attn_window, ring_window=ring,
            **_attn_kwargs(cfg))
        cache = {"k": ck, "v": cv}
        x = x + h
        h2in = norm_apply(cfg, p["norm2"], x)
        h2 = (moe.moe_fwd(p["ffn"], h2in, cfg.moe)[0] if kind == "attn_moe"
              else _mlp_fwd(cfg, p["ffn"], h2in))
        return x + h2, cache
    if kind in ("mla_mlp", "mla_moe"):
        h, cc, ckpe = attention.mla_decode(
            p["attn"], norm_apply(cfg, p["norm1"], x), cache["c"], cache["kpe"],
            pos, absorb=cfg.mla_absorb, **_mla_kwargs(cfg))
        cache = {"c": cc, "kpe": ckpe}
        x = x + h
        h2in = norm_apply(cfg, p["norm2"], x)
        h2 = (moe.moe_fwd(p["ffn"], h2in, cfg.moe)[0] if kind == "mla_moe"
              else _mlp_fwd(cfg, p["ffn"], h2in))
        return x + h2, cache
    if kind == "mamba":
        h, ssm, conv = mamba2.mamba2_decode(
            p["mixer"], norm_apply(cfg, p["norm1"], x), cache["ssm"],
            cache["conv"], cfg.ssm)
        return x + h, {"ssm": ssm, "conv": conv}
    if kind == "rwkv":
        n1 = norm_apply(cfg, p["norm1"], x)
        h, wkv, tm_prev = rwkv6.rwkv6_time_mix_decode(
            p["time_mix"], n1, cache["wkv"], cache["tm_prev"], cfg.rwkv)
        x = x + h
        n2 = norm_apply(cfg, p["norm2"], x)
        h2 = rwkv6.rwkv6_channel_mix(p["channel_mix"], n2,
                                     x_prev=cache["cm_prev"])
        return x + h2, {"wkv": wkv, "tm_prev": tm_prev, "cm_prev": n2}
    if kind == "cross_mlp":
        ctx = attention.decode_attention(
            _cross_q(p, cfg, norm_apply(cfg, p["norm1"], x)),
            cache["k"], cache["v"], cache["k"].shape[1])
        b = x.shape[0]
        h = layers.dense(p["attn"]["wo"],
                         ctx.reshape(b, 1, cfg.n_heads * cfg.resolved_head_dim))
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
        h2 = _mlp_fwd(cfg, p["ffn"], norm_apply(cfg, p["norm2"], x))
        return x + jnp.tanh(p["gate_ffn"]).astype(x.dtype) * h2, cache
    raise ValueError(kind)


def _cross_q(p, cfg, x):
    b = x.shape[0]
    q = layers.dense(p["attn"]["wq"], x)
    return q.reshape(b, 1, cfg.n_heads, cfg.resolved_head_dim)
