"""deepseek-v3-671b (DeepSeek-V3, arXiv:2412.19437) as the repo runs it: one
expert-parallel rank's share, cut in depth.

Sizes are in ``deepseek-v3-671b.json``; this file imports nothing of the
program (see ``rwkv6-1.6b.py`` for what each function is for) and adds the
counts of the held experts' work that ``experts_roofline.prefill`` reads.

Layer equations (``x'`` the RMS-normed input, H heads, YaRN RoPE on the
64-wide rope part, causal softmax at scale (nope + rope)^-1/2 x mscale^2):
    c_q = RMSNorm(x' W_dq);  [q_nope, q_pe] = c_q W_uq
    c_kv = RMSNorm(x' W_dkv);  k_pe = x' W_kr (one head, shared)
    [k_nope, v] = c_kv W_ukv;  q = [q_nope, rope(q_pe)];  k = [k_nope, rope(k_pe)]
    x += Attn(q, k, v) W_o
Dense layer: x += SwiGLU(x'). MoE layer (E routed experts, k per token,
this chip holding experts [e0, e0 + E_here)):
    s = sigmoid(x' W_r);  b the correction bias
    group score = sum of the 2 best (s + b) in each of n_group groups;
    keep the topk_group best groups; chosen = top-k of (s + b) within them
    g_e = 2.5 s_e / sum_{chosen} s   for e chosen, else 0
    x += SwiGLU_shared(x') + sum_{e held} g_e SwiGLU_e(x')
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import refops as R

Q_BLOCK = 256          # reference attention: queries per block
REF_ROWS = 4           # reference: sequences through the stack at a time
VOCAB_BLOCK = 16384    # reference logits: vocabulary rows per product


def _held(s):
    d = s["deployment"]
    n = s["n_routed_experts"]
    return d["expert_rank"] * n, n, d["routed_experts_total"]


def _n_moe(s):
    return s["num_hidden_layers"] - s["first_k_dense_replace"]


def program(s):
    rs = s["rope_scaling"]
    if rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError(f"rope_scaling {rs}: the program builds YaRN with "
                         "mscale equal to mscale_all_dim")
    e0, eh, e = _held(s)
    f = s["moe_intermediate_size"]
    return "deepseek-v3-671b", {
        "n_layers": s["num_hidden_layers"], "d_model": s["hidden_size"],
        "n_heads": s["num_attention_heads"],
        "n_kv_heads": s["num_key_value_heads"], "head_dim": s["v_head_dim"],
        "d_ff": s["intermediate_size"], "vocab_size": s["vocab_size"],
        "rope_theta": float(s["rope_theta"]), "norm": "rms",
        "norm_eps": s["rms_norm_eps"], "tie_embeddings": s["tie_word_embeddings"],
        "dtype": s["dtype"], "first_k_dense": s["first_k_dense_replace"],
        "mla": {"q_lora": s["q_lora_rank"], "kv_lora": s["kv_lora_rank"],
                "nope_dim": s["qk_nope_head_dim"], "rope_dim": s["qk_rope_head_dim"],
                "v_dim": s["v_head_dim"],
                "yarn": {"factor": float(rs["factor"]),
                         "original_max_pos": rs["original_max_position_embeddings"],
                         "beta_fast": float(rs["beta_fast"]),
                         "beta_slow": float(rs["beta_slow"]),
                         "mscale_all_dim": float(rs["mscale_all_dim"])}},
        "moe": {"n_experts": e, "top_k": s["num_experts_per_tok"],
                "d_ff_expert": f, "n_shared": s["n_shared_experts"],
                "d_ff_shared": f * s["n_shared_experts"],
                "router": s["scoring_func"],
                "routed_scale": s["routed_scaling_factor"],
                "n_group": s["n_group"], "topk_group": s["topk_group"],
                "first_held": e0, "n_held": eh},
    }


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def make_params(key, s):
    """Seeded weights in the program's layout, drawn in their own dtype.
    Projections N(0, 1/fan_in); the products that write the residual stream
    (attention out, MLP and expert down-projections) scaled by 1/sqrt(2 x
    writers) of the published depth (61 layers), the depth scaling the other
    configurations take at their full depth: a cut in depth leaves each
    layer's weights, and so its share of the residual stream, as they are
    in the whole model; norm scales 1 + N(0, 0.1); the
    router's correction bias N(0, 0.01): it moves the choice of about half
    the tokens, and the busiest expert's load stays within 1.7x of an even
    share (PERF.md)."""
    d, V = s["hidden_size"], s["vocab_size"]
    h, ql, kl = s["num_attention_heads"], s["q_lora_rank"], s["kv_lora_rank"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    F, f = s["intermediate_size"], s["moe_intermediate_size"]
    _, eh, e = _held(s)
    fs = f * s["n_shared_experts"]
    nd, nm = s["first_k_dense_replace"], _n_moe(s)
    dt = jnp.dtype(s["dtype"])
    f32 = jnp.float32
    keys = iter(jax.random.split(key, 64))
    res = (2.0 * 2 * s["deployment"]["published"]["num_hidden_layers"]) ** -0.5

    def normal(shape, std, dtype):
        return jax.random.normal(next(keys), shape, dtype) * jnp.asarray(std, dtype)

    def scale(shape, dtype):
        return {"scale": 1.0 + normal(shape, 0.1, dtype)}

    def swiglu(lead, width):
        return {"w_gate": normal(lead + (d, width), d ** -0.5, dt),
                "w_up": normal(lead + (d, width), d ** -0.5, dt),
                "w_down": normal(lead + (width, d), res * width ** -0.5, dt)}

    def layer(n, ffn):
        lead = (n,)
        return {
            "norm1": scale(lead + (d,), f32),
            "attn": {"wdq": normal(lead + (d, ql), d ** -0.5, dt),
                     "q_norm": scale(lead + (ql,), dt),
                     "wuq": normal(lead + (ql, h * (dn + dr)), ql ** -0.5, dt),
                     "wdkv": normal(lead + (d, kl), d ** -0.5, dt),
                     "kv_norm": scale(lead + (kl,), dt),
                     "wukv": normal(lead + (kl, h * (dn + dv)), kl ** -0.5, dt),
                     "wkr": normal(lead + (d, dr), d ** -0.5, dt),
                     "wo": normal(lead + (h * dv, d), res * (h * dv) ** -0.5, dt)},
            "norm2": scale(lead + (d,), f32),
            "ffn": ffn,
        }

    experts = swiglu((nm, eh), f)
    moe = {"router_w": normal((nm, d, e), d ** -0.5, f32),
           "router_bias": normal((nm, e), 0.01, f32),
           "experts": experts, "shared": swiglu((nm,), fs)}
    return {
        "embed": {"table": normal((V, d), 1.0, dt)},
        "segments": [layer(nd, swiglu((nd,), F)), layer(nm, moe)],
        "final_norm": scale((d,), f32),
        "lm_head": {"table": normal((V, d), d ** -0.5, dt)},
    }


# ---------------------------------------------------------------------------
# Work of one step (the benchmark's own count)
# ---------------------------------------------------------------------------

def _routed_pairs(s, tokens: int) -> float:
    """Expected (token, held expert) pairs: tokens x k x E_here / E."""
    _, eh, e = _held(s)
    return tokens * s["num_experts_per_tok"] * eh / e


def _mla_flops(s):
    d, h = s["hidden_size"], s["num_attention_heads"]
    ql, kl = s["q_lora_rank"], s["kv_lora_rank"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    return 2 * (d * ql + ql * h * (dn + dr) + d * kl + kl * h * (dn + dv)
                + d * dr + h * dv * d)


def _swiglu_flops(s, width):
    return 2 * 3 * s["hidden_size"] * width


def prefill_flops(s, batch: int, seq: int) -> float:
    """One prefill. Routed work counts the expected T k E_here / E pairs
    on held experts (the experts of other chips are not this chip's
    work); causal attention counts the keys each query needs (scores over
    nope + rope, values over v); logits at the last position only."""
    d, h = s["hidden_size"], s["num_attention_heads"]
    _, _, e = _held(s)
    tokens = batch * seq
    per_tok = (s["num_hidden_layers"] * _mla_flops(s)
               + s["first_k_dense_replace"] * _swiglu_flops(s, s["intermediate_size"])
               + _n_moe(s) * (2 * d * e + _swiglu_flops(
                   s, s["moe_intermediate_size"] * s["n_shared_experts"])))
    attn = (s["num_hidden_layers"] * 2 * h
            * (s["qk_nope_head_dim"] + s["qk_rope_head_dim"] + s["v_head_dim"])
            * seq * (seq + 1) // 2)
    return float(tokens * per_tok + batch * attn + experts_flops(s, batch, seq)
                 + batch * 2 * d * s["vocab_size"])


def experts_flops(s, batch: int, seq: int) -> float:
    """The held experts' SwiGLU products of one prefill, every MoE layer."""
    return float(_n_moe(s) * _routed_pairs(s, batch * seq)
                 * _swiglu_flops(s, s["moe_intermediate_size"]))


def experts_bytes(s, batch: int, seq: int) -> float:
    """Bytes the held experts' products must move in one prefill: each
    layer's held weights read once, and the gathered rows read in and the
    expert outputs written, d wide each."""
    d, f = s["hidden_size"], s["moe_intermediate_size"]
    _, eh, _ = _held(s)
    item = jnp.dtype(s["dtype"]).itemsize
    return float(_n_moe(s) * (eh * 3 * d * f * item
                              + 2 * _routed_pairs(s, batch * seq) * d * item))


# ---------------------------------------------------------------------------
# Plain float32 reference
# ---------------------------------------------------------------------------

def _yarn(s):
    """DeepSeek-V3's YaRN: (inverse frequencies of the rope part, softmax
    scale), as its published modeling code computes them."""
    rs, dim, base = s["rope_scaling"], s["qk_rope_head_dim"], float(s["rope_theta"])
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * np.log(orig / (rot * 2 * np.pi)) / (2 * np.log(base))

    low = max(int(np.floor(corr(rs["beta_fast"]))), 0)
    high = min(int(np.ceil(corr(rs["beta_slow"]))), dim - 1)
    high = high + 0.001 if low == high else high
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inv = extra / factor * (1 - keep) + extra * keep
    mscale = 0.1 * rs["mscale_all_dim"] * np.log(factor) + 1.0
    scale = (s["qk_nope_head_dim"] + dim) ** -0.5 * mscale ** 2
    return inv.astype(np.float32), float(scale)


def _rope(x, inv):
    """Rotate-half RoPE. x: (B, T, heads, D)."""
    t, dh = x.shape[1], x.shape[-1]
    ang = jnp.arange(t, dtype=R.F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps", "dims", "scale", "lowp"))
def _mla(p, x, inv, eps, dims, scale, lowp):
    h, dn, dr, dv = dims
    b, t, _ = x.shape
    a = p["attn"]
    n1 = R.rmsnorm(x, p["norm1"]["scale"], eps)
    q = R.mm(R.rmsnorm(R.mm(n1, a["wdq"], lowp), a["q_norm"]["scale"], eps),
             a["wuq"], lowp).reshape(b, t, h, dn + dr)
    ckv = R.rmsnorm(R.mm(n1, a["wdkv"], lowp), a["kv_norm"]["scale"], eps)
    kv = R.mm(ckv, a["wukv"], lowp).reshape(b, t, h, dn + dv)
    k_pe = _rope(R.mm(n1, a["wkr"], lowp)[:, :, None, :], inv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (b, t, h, dr))], axis=-1)
    v = kv[..., dn:]
    outs = []
    for q0 in range(0, t, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=R.HIGHEST) * scale
        qpos = q0 + jnp.arange(qb.shape[1])
        sc = jnp.where(jnp.arange(t)[None, :] <= qpos[:, None], sc, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                               precision=R.HIGHEST))
    ctx = jnp.concatenate(outs, axis=1).reshape(b, t, h * dv)
    return x + R.mm(ctx, a["wo"], lowp)


def _swiglu(p, x, lowp):
    return R.mm(R.silu(R.mm(x, p["w_gate"], lowp)) * R.mm(x, p["w_up"], lowp),
                p["w_down"], lowp)


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def _dense_ffn(p, x, eps, lowp):
    return x + _swiglu(p["ffn"], R.rmsnorm(x, p["norm2"]["scale"], eps), lowp)


def gates(logits, bias, s):
    """The combine weight of every expert (..., E): DeepSeek-V3's noaux_tc
    selection written with sorts, zero where an expert is not chosen."""
    e, k = logits.shape[-1], s["num_experts_per_tok"]
    ng, kg = s["n_group"], s["topk_group"]
    scores = 1.0 / (1.0 + jnp.exp(-logits))
    biased = scores + bias
    grouped = biased.reshape(*biased.shape[:-1], ng, e // ng)
    group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    group_rank = jnp.argsort(jnp.argsort(-group_score, axis=-1), axis=-1)
    kept = jnp.repeat(group_rank < kg, e // ng, axis=-1)
    masked = jnp.where(kept, biased, -jnp.inf)
    rank = jnp.argsort(jnp.argsort(-masked, axis=-1), axis=-1)
    chosen = rank < k
    w = jnp.where(chosen, scores, 0.0)
    return w / w.sum(-1, keepdims=True) * s["routed_scaling_factor"]


@functools.partial(jax.jit, static_argnames=("eps", "held", "s_key", "lowp"))
def _moe_ffn(p, x, eps, held, s_key, lowp):
    s = dict(s_key)
    e0, eh = held
    n2 = R.rmsnorm(x, p["norm2"]["scale"], eps)
    ffn = p["ffn"]
    g = gates(R.mm(n2, ffn["router_w"], lowp), ffn["router_bias"], s)
    out = _swiglu(ffn["shared"], n2, lowp)
    for i in range(eh):
        ex = jax.tree.map(lambda a: a[i], ffn["experts"])
        out = out + g[..., e0 + i:e0 + i + 1] * _swiglu(ex, n2, lowp)
    return x + out


def _routing_key(s):
    keys = ("num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor")
    return tuple((k, s[k]) for k in keys)


def hidden(params, tokens, s, lowp=None):
    """Final-normed hidden states (B, S, d) in float32 of the token ids
    (B, S), computed one layer at a time, ``REF_ROWS`` sequences at a
    time. The MoE evaluates the held experts densely for every token,
    weighted by their gates."""
    eps = s["rms_norm_eps"]
    inv, scale = _yarn(s)
    dims = (s["num_attention_heads"], s["qk_nope_head_dim"],
            s["qk_rope_head_dim"], s["v_head_dim"])
    e0, eh, _ = _held(s)
    dense, moe = params["segments"]
    outs = []
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, tokens.shape[0], REF_ROWS):
            x = params["embed"]["table"][tokens[r0:r0 + REF_ROWS]].astype(R.F32)
            for i in range(s["first_k_dense_replace"]):
                p = R.layer(dense, i)
                x = _dense_ffn(p, _mla(p, x, inv, eps, dims, scale, lowp), eps, lowp)
            for i in range(_n_moe(s)):
                p = R.layer(moe, i)
                x = _moe_ffn(p, _mla(p, x, inv, eps, dims, scale, lowp), eps,
                             (e0, eh), _routing_key(s), lowp)
            outs.append(R.rmsnorm(x, params["final_norm"]["scale"], eps))
    return jnp.concatenate(outs, axis=0)


def logits(params, h, s, lowp=None):
    table = params["lm_head"]["table"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [R.mm(h, table[v0:v0 + VOCAB_BLOCK].T, lowp)
             for v0 in range(0, table.shape[0], VOCAB_BLOCK)], axis=-1)
