"""zamba2-1.2b (Zamba2, arXiv:2411.15242) as the repo runs it.

Sizes are in ``zamba2-1.2b.json``; this file imports nothing of the
program (see ``rwkv6-1.6b.py`` for what each function is for).

Layer equations, Mamba2 layer (H heads of size P, state size N, one group;
``x'`` the RMS-normed input):
    [u, z, B, C, dt] = x' W_in
    [u, B, C] = silu(causal depthwise conv4([u, B, C]) + b)
    dt = softplus(dt + dt_bias);  a_t = exp(-dt_t exp(A_log))
    S_t = a_t S_{t-1} + dt_t B_t^T u_t;  y_t = C_t S_t + D u_t
    x += (RMSNorm(y) * silu(z)) W_out
Shared block, applied after every 6 Mamba2 layers with LoRA pair g
(``n`` the RMS-normed input, RoPE on q and k, causal softmax attention):
    x += Attn(n) + n A_g^att B_g^att
    x += SwiGLU(n2) + n2 A_g^ffn B_g^ffn          (n2 = RMSNorm(x))
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench import refops as R

Q_BLOCK = 512          # reference attention: queries per block


def _dims(s):
    di = s["mamba_expand"] * s["hidden_size"]
    return di, di // s["mamba_headdim"], s["mamba_d_state"], s["mamba_ngroups"]


def _groups(s):
    g = s["num_hidden_layers"] // s["hybrid_period"]
    return g, s["num_hidden_layers"] - g * s["hybrid_period"]


def program(s):
    di, heads, n, groups = _dims(s)
    return "zamba2-1.2b", {
        "n_layers": s["num_hidden_layers"], "d_model": s["hidden_size"],
        "n_heads": s["num_attention_heads"],
        "n_kv_heads": s["num_key_value_heads"],
        "head_dim": s["attention_head_dim"], "d_ff": s["intermediate_size"],
        "vocab_size": s["vocab_size"], "rope_theta": s["rope_theta"],
        "rope_fraction": 1.0, "norm": "rms", "norm_eps": s["rms_norm_eps"],
        "tie_embeddings": s["tie_word_embeddings"], "dtype": s["dtype"],
        "hybrid_period": s["hybrid_period"],
        "shared_lora_rank": s["adapter_rank"],
        "ssm": {"d_inner": di, "n_heads": heads, "state_dim": n,
                "n_groups": groups, "conv_width": s["mamba_d_conv"]},
    }


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def make_params(key, s):
    """Seeded weights in the program's layout. Projections N(0, 1/fan_in);
    every product that writes the residual stream (Mamba2 out, attention
    out, MLP down, both LoRA up-projections, drawn from the seed and not
    zero) is scaled by 1/sqrt(2 x writers); A_log and dt_bias follow
    Mamba2's initialisation (A in [1, 16], dt in [1e-3, 1e-1])."""
    d, f, V = s["hidden_size"], s["intermediate_size"], s["vocab_size"]
    di, H, N, G = _dims(s)
    h, hk, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["attention_head_dim"])
    r, W = s["adapter_rank"], s["mamba_d_conv"]
    groups, rem = _groups(s)
    dt = jnp.dtype(s["dtype"])
    f32 = jnp.float32
    keys = iter(jax.random.split(key, 64))
    res = (2.0 * (s["num_hidden_layers"] + 2 * groups)) ** -0.5

    def normal(shape, std, dtype):
        return (jax.random.normal(next(keys), shape, f32) * std).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, f32, lo, hi)

    def scale(shape, dtype):
        return {"scale": (1.0 + normal(shape, 0.1, f32)).astype(dtype)}

    def mamba(lead):
        conv = di + 2 * G * N
        dt0 = jnp.exp(uniform(lead + (H,), jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "norm1": scale(lead + (d,), f32),
            "mixer": {
                "in_proj": normal(lead + (d, 2 * di + 2 * G * N + H), d ** -0.5, dt),
                "conv_w": normal(lead + (W, conv), W ** -0.5, dt),
                "conv_b": normal(lead + (conv,), 0.1, dt),
                "A_log": jnp.log(uniform(lead + (H,), 1.0, 16.0)),
                "D": 1.0 + normal(lead + (H,), 0.1, f32),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "norm": scale(lead + (di,), dt),
                "out_proj": normal(lead + (di, d), res * di ** -0.5, dt),
            },
        }

    def lora():
        return {"a": normal((groups, d, r), d ** -0.5, dt),
                "b": normal((groups, r, d), res * r ** -0.5, dt)}

    segments = [{"mamba": mamba((groups, s["hybrid_period"])),
                 "lora_attn": lora(), "lora_ffn": lora()}]
    if rem:
        segments.append(mamba((rem,)))
    return {
        "embed": {"table": normal((V, d), 1.0, dt)},
        "segments": segments,
        "shared_block": {
            "norm1": scale((d,), f32),
            "attn": {"wq": normal((d, h * hd), d ** -0.5, dt),
                     "wk": normal((d, hk * hd), d ** -0.5, dt),
                     "wv": normal((d, hk * hd), d ** -0.5, dt),
                     "wo": normal((h * hd, d), res * (h * hd) ** -0.5, dt)},
            "norm2": scale((d,), f32),
            "ffn": {"w_gate": normal((d, f), d ** -0.5, dt),
                    "w_up": normal((d, f), d ** -0.5, dt),
                    "w_down": normal((f, d), res * f ** -0.5, dt)},
        },
        "final_norm": scale((d,), f32),
        "lm_head": {"table": normal((V, d), d ** -0.5, dt)},
    }


# ---------------------------------------------------------------------------
# Work of one step (the benchmark's own count)
# ---------------------------------------------------------------------------

def _mamba_flops(s):
    """One token through one Mamba2 layer: 2 per multiply-add of the two
    projections and of the width-4 depthwise conv, and 5 N P per head for
    the recurrence as written (decay, B^T u, update, readout)."""
    d = s["hidden_size"]
    di, H, N, G = _dims(s)
    conv = di + 2 * G * N
    return (2 * (d * (2 * di + 2 * G * N + H) + di * d)
            + 2 * s["mamba_d_conv"] * conv + 5 * H * N * s["mamba_headdim"])


def _shared_flops(s):
    """One token through one application of the shared block, attention
    scores and values excluded (they depend on the context)."""
    d, f, r = s["hidden_size"], s["intermediate_size"], s["adapter_rank"]
    h, hk, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["attention_head_dim"])
    return 2 * (d * (h + 2 * hk) * hd + h * hd * d + 3 * d * f + 2 * 2 * d * r)


def _attn_flops_per_key(s):
    return 4 * s["num_attention_heads"] * s["attention_head_dim"]


def _head(s):
    return 2 * s["hidden_size"] * s["vocab_size"]


def prefill_flops(s, batch: int, seq: int) -> float:
    """One prefill; each shared-block application counts once, causal
    attention counts the keys each query needs, logits at the last position
    only."""
    groups, _ = _groups(s)
    tok = s["num_hidden_layers"] * _mamba_flops(s) + groups * _shared_flops(s)
    attn = groups * _attn_flops_per_key(s) * seq * (seq + 1) // 2
    return float(batch * (seq * tok + attn + _head(s)))


def decode_flops(s, batch: int, pos: int) -> float:
    groups, _ = _groups(s)
    tok = (s["num_hidden_layers"] * _mamba_flops(s) + groups * _shared_flops(s)
           + groups * _attn_flops_per_key(s) * (pos + 1) + _head(s))
    return float(batch * tok)


def decode_bytes(s, param_bytes: int, batch: int, pos: int) -> float:
    """Every weight once (of the input embedding only the gathered rows),
    Mamba2 state and conv rows read and written, and per application the
    keys and values of positions 0..pos read and one slot written."""
    d, V = s["hidden_size"], s["vocab_size"]
    di, H, N, G = _dims(s)
    item = jnp.dtype(s["dtype"]).itemsize
    groups, _ = _groups(s)
    kv = 2 * s["num_key_value_heads"] * s["attention_head_dim"] * item
    mamba_state = (H * N * s["mamba_headdim"] * 4
                   + (s["mamba_d_conv"] - 1) * (di + 2 * G * N) * item)
    state = 2 * s["num_hidden_layers"] * batch * mamba_state
    cache = groups * batch * (pos + 1 + 1) * kv
    return float(param_bytes - V * d * item + batch * d * item + state + cache)


# ---------------------------------------------------------------------------
# Plain float32 reference
# ---------------------------------------------------------------------------

def _ssm(u, dt, a, bb, cc, d_skip):
    """The recurrence, one step per token. u: (B, T, H, P); dt, a: (B, T, H);
    bb, cc: (B, T, H, N)."""
    b, _, h, p = u.shape
    n = bb.shape[-1]

    def step(state, inp):
        ut, dtt, at, bt, ct = inp
        state = at[..., None, None] * state + (dtt[..., None] * bt)[..., :, None] * ut[..., None, :]
        y = jnp.einsum("bhn,bhnp->bhp", ct, state, precision=R.HIGHEST)
        return state, y + d_skip[None, :, None] * ut

    tm = lambda x: jnp.moveaxis(x, 1, 0)
    _, ys = lax.scan(step, jnp.zeros((b, h, n, p), R.F32),
                     (tm(u), tm(dt), tm(a), tm(bb), tm(cc)))
    return jnp.moveaxis(ys, 0, 1)


@functools.partial(jax.jit, static_argnames=("eps", "dims", "lowp"))
def _mamba(p, x, eps, dims, lowp):
    di, H, N, G = dims
    m = p["mixer"]
    b, t, _ = x.shape
    n1 = R.rmsnorm(x, p["norm1"]["scale"], eps)
    proj = R.mm(n1, m["in_proj"], lowp)
    u, z, bb, cc, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + G * N, 2 * di + 2 * G * N], axis=-1)
    conv_in = jnp.concatenate([u, bb, cc], axis=-1)
    w = m["conv_w"].astype(R.F32)
    width = w.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((b, width - 1, conv_in.shape[-1]), R.F32), conv_in], axis=1)
    conv = sum(padded[:, i:i + t] * w[i] for i in range(width))
    conv = R.silu(conv + m["conv_b"].astype(R.F32))
    u, bb, cc = jnp.split(conv, [di, di + G * N], axis=-1)
    dt = R.softplus(dt + m["dt_bias"])
    a = jnp.exp(-dt * jnp.exp(m["A_log"]))
    per = H // G
    grouped = lambda v: jnp.repeat(v.reshape(b, t, G, N), per, axis=2)
    y = _ssm(u.reshape(b, t, H, di // H), dt, a, grouped(bb), grouped(cc),
             m["D"]).reshape(b, t, di)
    y = R.rmsnorm(y, m["norm"]["scale"], eps) * R.silu(z)
    return x + R.mm(y, m["out_proj"], lowp)


def _rope(x, theta):
    """Rotate-half RoPE over the whole head. x: (B, T, heads, D)."""
    t, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=R.F32) / dh)
    ang = jnp.arange(t, dtype=R.F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """Causal softmax attention, queries in blocks. q: (B, T, h, D);
    k, v: (B, T, hk, D)."""
    b, t, h, dh = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    outs = []
    for q0 in range(0, t, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=R.HIGHEST) * dh ** -0.5
        qpos = q0 + jnp.arange(qb.shape[1])
        sc = jnp.where(jnp.arange(t)[None, :] <= qpos[:, None], sc, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                               precision=R.HIGHEST))
    return jnp.concatenate(outs, axis=1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "hd", "lowp"))
def _shared(sp, la, lf, x, eps, theta, hd, lowp):
    b, t, _ = x.shape
    at, ffn = sp["attn"], sp["ffn"]
    n1 = R.rmsnorm(x, sp["norm1"]["scale"], eps)
    q, k, v = (R.mm(n1, at[w], lowp) for w in ("wq", "wk", "wv"))
    heads = lambda a: a.reshape(b, t, -1, hd)
    ctx = _attention(_rope(heads(q), theta), _rope(heads(k), theta), heads(v))
    lora = lambda p, a: R.mm(R.mm(a, p["a"], lowp), p["b"], lowp)
    x = x + R.mm(ctx.reshape(b, t, -1), at["wo"], lowp) + lora(la, n1)
    n2 = R.rmsnorm(x, sp["norm2"]["scale"], eps)
    mlp = R.mm(R.silu(R.mm(n2, ffn["w_gate"], lowp)) * R.mm(n2, ffn["w_up"], lowp),
               ffn["w_down"], lowp)
    return x + mlp + lora(lf, n2)


def hidden(params, tokens, s, lowp=None):
    """Final-normed hidden states (B, S, d) in float32 of the token ids
    (B, S), computed one layer at a time."""
    eps, theta = s["rms_norm_eps"], s["rope_theta"]
    dims = _dims(s)
    groups, rem = _groups(s)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][tokens].astype(R.F32)
        seg = params["segments"][0]
        for g in range(groups):
            for i in range(s["hybrid_period"]):
                x = _mamba(R.layer(seg["mamba"], g, i), x, eps, dims, lowp)
            x = _shared(params["shared_block"], R.layer(seg["lora_attn"], g),
                        R.layer(seg["lora_ffn"], g), x, eps, theta,
                        s["attention_head_dim"], lowp)
        for i in range(rem):
            x = _mamba(R.layer(params["segments"][1], i), x, eps, dims, lowp)
        return R.rmsnorm(x, params["final_norm"]["scale"], eps)


def logits(params, h, s, lowp=None):
    with jax.default_matmul_precision("highest"):
        return R.mm(h, params["lm_head"]["table"].T, lowp)
