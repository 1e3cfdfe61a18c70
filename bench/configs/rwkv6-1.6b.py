"""rwkv6-1.6b (RWKV-6 "Finch", arXiv:2404.05892) as the repo runs it.

Sizes are in ``rwkv6-1.6b.json``. This file holds what the benchmark needs
beside them and imports nothing of the program:

* ``program(s)`` -- the repo's architecture id and the ``ModelConfig``
  fields the sizes set;
* ``make_params(key, s)`` -- the weights, drawn on the device from the seed
  in the program's parameter layout and dtypes;
* ``prefill_flops`` / ``decode_flops`` / ``decode_bytes`` -- the
  benchmark's own count of the work of one step;
* ``hidden`` / ``logits`` -- the plain float32 reference forward, layer by
  layer.

Layer equations (per head, head size D, state S of D x D, ``x'`` the
normed input, ``xx`` its token shift):
    m_i = x' + (xx - x') * mu_i            i in r, k, v, w, g
    r, k, v, g = m_r Wr, m_k Wk, m_v Wv, m_g Wg
    w_t = exp(-exp(w0 + (m_w A) B))        (decay LoRA, rank 64)
    y_t = r_t (S_{t-1} + diag(u) k_t^T v_t);  S_t = diag(w_t) S_{t-1} + k_t^T v_t
    x  += (LN_x(y) * silu(g)) Wo
    channel mix: x += sigmoid(m_r Wr') * (relu(m_k Wk')^2 Wv')
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench import refops as R


def program(s):
    heads = s["attention_hidden_size"] // s["head_size"]
    return "rwkv6-1.6b", {
        "n_layers": s["num_hidden_layers"], "d_model": s["hidden_size"],
        "n_heads": heads, "n_kv_heads": heads, "head_dim": s["head_size"],
        "d_ff": s["intermediate_size"], "vocab_size": s["vocab_size"],
        "norm": "ln", "norm_eps": s["layer_norm_epsilon"],
        "tie_embeddings": s["tie_word_embeddings"], "dtype": s["dtype"],
        "rwkv": {"n_heads": heads, "head_dim": s["head_size"],
                 "decay_lora_rank": s["time_decay_extra_dim"]},
    }


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def make_params(key, s):
    """Seeded weights in the program's layout. Scales keep a random
    24-layer stack well conditioned: projections N(0, 1/fan_in), the two
    products that write the residual stream scaled by 1/sqrt(2L); the decay
    bias w0 follows Finch's per-channel schedule from -6 to -1, so the decay
    LoRA (up-projection drawn from the seed, not zero) moves the output."""
    L, d, f = s["num_hidden_layers"], s["hidden_size"], s["intermediate_size"]
    V, dh, r = s["vocab_size"], s["head_size"], s["time_decay_extra_dim"]
    h = s["attention_hidden_size"] // dh
    dt = jnp.dtype(s["dtype"])
    f32 = jnp.float32
    keys = iter(jax.random.split(key, 32))
    res = (2.0 * L) ** -0.5

    def normal(shape, std, dtype):
        return (jax.random.normal(next(keys), shape, f32) * std).astype(dtype)

    def uniform(shape, dtype):
        return jax.random.uniform(next(keys), shape, f32).astype(dtype)

    def ln(lead, dtype):
        return {"scale": (1.0 + normal(lead + (d,), 0.1, f32)).astype(dtype),
                "bias": normal(lead + (d,), 0.1, dtype)}

    ratio = jnp.arange(L, dtype=f32)[:, None] / max(L - 1, 1)
    chan = jnp.arange(d, dtype=f32)[None, :] / (d - 1)
    w0 = -6.0 + 5.0 * chan ** (0.7 + 1.3 * ratio)
    seg = {
        "norm1": ln((L,), f32),
        "time_mix": {
            "mu": uniform((L, 5, d), dt),
            "w0": w0,
            "w_lora": {"a": normal((L, d, r), d ** -0.5, dt),
                       "b": normal((L, r, d), r ** -0.5, dt)},
            "u": normal((L, h, dh), 0.5, f32),
            "wr": normal((L, d, d), d ** -0.5, dt),
            "wk": normal((L, d, d), d ** -0.5, dt),
            "wv": normal((L, d, d), d ** -0.5, dt),
            "wg": normal((L, d, d), d ** -0.5, dt),
            "wo": normal((L, d, d), res * d ** -0.5, dt),
            "ln_x": ln((L,), dt),
        },
        "norm2": ln((L,), f32),
        "channel_mix": {
            "mu": uniform((L, 2, d), dt),
            "wk": normal((L, d, f), d ** -0.5, dt),
            "wv": normal((L, f, d), res * f ** -0.5, dt),
            "wr": normal((L, d, d), d ** -0.5, dt),
        },
    }
    return {
        "embed": {"table": normal((V, d), 1.0, dt)},
        "segments": [seg],
        "final_norm": ln((), f32),
        "lm_head": {"table": normal((V, d), d ** -0.5, dt)},
    }


# ---------------------------------------------------------------------------
# Work of one step (the benchmark's own count)
# ---------------------------------------------------------------------------

def _per_token(s):
    """FLOPs of one token through all layers: 2 per multiply-add of every
    projection, and 7 D^2 per head for the recurrence as written (k^T v,
    u-bonus, readout, decay, update)."""
    d, f, r = s["hidden_size"], s["intermediate_size"], s["time_decay_extra_dim"]
    dh = s["head_size"]
    h = s["attention_hidden_size"] // dh
    proj = 5 * d * d + 2 * d * r + 2 * d * f + d * d
    return s["num_hidden_layers"] * (2 * proj + 7 * h * dh * dh)


def _head(s):
    return 2 * s["hidden_size"] * s["vocab_size"]


def prefill_flops(s, batch: int, seq: int) -> float:
    """One prefill of ``batch`` prompts of ``seq`` tokens; logits at the last
    position only, as the serving path computes them."""
    return float(batch * seq * _per_token(s) + batch * _head(s))


def decode_flops(s, batch: int, pos: int) -> float:
    return float(batch * (_per_token(s) + _head(s)))


def decode_bytes(s, param_bytes: int, batch: int, pos: int) -> float:
    """Bytes one decode step must move: every weight once, but of the input
    embedding only the ``batch`` rows it gathers; the recurrent state
    (f32 WKV matrices and the two token-shift rows per layer) read and
    written."""
    d, V, dh = s["hidden_size"], s["vocab_size"], s["head_size"]
    h = s["attention_hidden_size"] // dh
    item = jnp.dtype(s["dtype"]).itemsize
    state = s["num_hidden_layers"] * batch * (h * dh * dh * 4 + 2 * d * item)
    return float(param_bytes - V * d * item + batch * d * item + 2 * state)


# ---------------------------------------------------------------------------
# Plain float32 reference
# ---------------------------------------------------------------------------

def _wkv(r, k, v, w, u):
    """The recurrence, one step per token. r, k, v, w: (B, S, H, D)."""
    b, _, h, dh = r.shape

    def step(state, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhd,bhde->bhe", rt, state + u[None, :, :, None] * kv,
                       precision=R.HIGHEST)
        return wt[..., :, None] * state + kv, y

    tm = lambda a: jnp.moveaxis(a, 1, 0)
    _, ys = lax.scan(step, jnp.zeros((b, h, dh, dh), R.F32),
                     (tm(r), tm(k), tm(v), tm(w)))
    return jnp.moveaxis(ys, 0, 1)


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def _layer(p, x, eps, lowp):
    tm, cm = p["time_mix"], p["channel_mix"]
    b, t, d = x.shape
    h, dh = tm["u"].shape
    n1 = R.layernorm(x, p["norm1"]["scale"], p["norm1"]["bias"], eps)
    xx = R.shift(n1)
    mu = tm["mu"].astype(R.F32)
    xr, xk, xv, xw, xg = (n1 + (xx - n1) * mu[i] for i in range(5))
    r = R.mm(xr, tm["wr"], lowp)
    k = R.mm(xk, tm["wk"], lowp)
    v = R.mm(xv, tm["wv"], lowp)
    g = R.mm(xg, tm["wg"], lowp)
    lora = R.mm(R.mm(xw, tm["w_lora"]["a"], lowp), tm["w_lora"]["b"], lowp)
    w = jnp.exp(-jnp.exp(tm["w0"] + lora))
    heads = lambda a: a.reshape(b, t, h, dh)
    y = _wkv(heads(r), heads(k), heads(v), heads(w), tm["u"]).reshape(b, t, d)
    y = R.layernorm(y, tm["ln_x"]["scale"], tm["ln_x"]["bias"], eps)
    x = x + R.mm(y * R.silu(g), tm["wo"], lowp)
    n2 = R.layernorm(x, p["norm2"]["scale"], p["norm2"]["bias"], eps)
    xx = R.shift(n2)
    mu = cm["mu"].astype(R.F32)
    kk = jnp.square(jax.nn.relu(R.mm(n2 + (xx - n2) * mu[0], cm["wk"], lowp)))
    rr = jax.nn.sigmoid(R.mm(n2 + (xx - n2) * mu[1], cm["wr"], lowp))
    return x + rr * R.mm(kk, cm["wv"], lowp)


def hidden(params, tokens, s, lowp=None):
    """Final-normed hidden states (B, S, d) in float32 of the token ids
    (B, S), computed one layer at a time."""
    eps = s["layer_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][tokens].astype(R.F32)
        seg = params["segments"][0]
        for i in range(s["num_hidden_layers"]):
            x = _layer(R.layer(seg, i), x, eps, lowp)
        fn = params["final_norm"]
        return R.layernorm(x, fn["scale"], fn["bias"], eps)


def logits(params, h, s, lowp=None):
    with jax.default_matmul_precision("highest"):
        return R.mm(h, params["lm_head"]["table"].T, lowp)
