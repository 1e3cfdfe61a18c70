"""RWKV6 ("Finch") block: time-mix with data-dependent decay + channel-mix.

Recurrence per head (head dim D, matrix state S: (D, D)):
    wkv_t = S_{t-1} + diag(u) k_t v_t^T
    y_t   = r_t @ wkv_t
    S_t   = diag(w_t) S_{t-1} + k_t v_t^T
with w_t = exp(-exp(w0 + lora_w(x'_t))) -- the *data-dependent* decay that
distinguishes RWKV6; the decay LoRA (rank 64) is a tall-and-skinny GEMM
pair served by the TSM2X dispatcher at large batch*seq.

Two evaluation paths:
* ``rwkv6_time_mix`` -- chunked form (training/prefill), mirroring the
  chunked-GLA decomposition. The intra-chunk (L x L) scores are one
  elementwise expression over (L, L, D) reduced over D, which XLA fuses
  into one reduction on the vector units (the per-channel decay keeps
  them off the MXU); nothing of size L x L x D is written to memory. The
  readouts, the chunk-end state contributions and the inter-chunk readout
  are batched matmuls on the MXU, the current token's bonus ``u`` an
  elementwise f32 product, and the inter-chunk state is carried by a
  sequential scan over chunks.
* ``rwkv6_time_mix_ref`` -- per-step lax.scan oracle (tests + a perf
  baseline for §Perf: the step form has O(1) arithmetic intensity, the
  chunked form lifts it by ~L).

Token shift: RWKV's x'_t = lerp(x_t, x_{t-1}, mu) with learned per-channel
mu for each of r/k/v/w/g (the full RWKV6 uses a LoRA for the lerp too; we
keep the per-channel form and put the LoRA on the decay, the part the
paper's data-dependence claim rests on).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers


@dataclasses.dataclass(frozen=True)
class RWKV6Config:
    n_heads: int
    head_dim: int = 64
    decay_lora_rank: int = 64
    chunk: int = 64


def rwkv6_time_mix_init(key, d_model: int, cfg: RWKV6Config, dtype):
    ks = jax.random.split(key, 8)
    d = d_model
    h, dh = cfg.n_heads, cfg.head_dim
    assert h * dh == d
    return {
        "mu": (jax.random.uniform(ks[0], (5, d), jnp.float32)).astype(dtype),
        "w0": jnp.full((d,), -6.0, jnp.float32),          # slow default decay
        "w_lora": layers.lora_init(ks[1], d, d, cfg.decay_lora_rank, dtype),
        "u": jnp.zeros((h, dh), jnp.float32),             # per-head bonus
        "wr": layers.dense_init(ks[2], d, d, dtype),
        "wk": layers.dense_init(ks[3], d, d, dtype),
        "wv": layers.dense_init(ks[4], d, d, dtype),
        "wg": layers.dense_init(ks[5], d, d, dtype),
        "wo": layers.dense_init(ks[6], d, d, dtype),
        "ln_x": layers.layernorm_init(d, dtype),          # per-head group norm
    }


def _token_shift(x, prev):
    """x: (B,S,d); prev: (B,1,d) last token of previous segment (or zeros)."""
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _projections(params, x, x_prev):
    xx = _token_shift(x, x_prev)
    mu = params["mu"].astype(x.dtype)
    mix = [x + (xx - x) * mu[i] for i in range(5)]
    xr, xk, xv, xw, xg = mix
    r = layers.dense(params["wr"], xr)
    k = layers.dense(params["wk"], xk)
    v = layers.dense(params["wv"], xv)
    g = layers.dense(params["wg"], xg)
    logw = -jnp.exp(params["w0"] +
                    layers.lora_apply(params["w_lora"], xw).astype(jnp.float32))
    return r, k, v, g, logw                               # logw <= 0


def _headed(x, h, dh):
    return x.reshape(*x.shape[:-1], h, dh)


def _out_stage(params, y, g, h, dh):
    b, s = y.shape[0], y.shape[1]
    y = y.reshape(b, s, h * dh).astype(g.dtype)
    y = layers.layernorm(params["ln_x"], y)
    return layers.dense(params["wo"], y * jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype))


def _intra_scores(rc, kc, cum, cum_tm1):
    """Intra-chunk scores A[t, s] = sum_d r_t[d] k_s[d] exp(cum_{t-1} - cum_s)[d]
    for s < t, 0 for s >= t: (B, nc, L, L, H) from (B, nc, L, H, D) operands.

    The decay stays pairwise: factored as exp(cum_{t-1}) exp(-cum_s) it
    overflows once a chunk decays past e^-88. One elementwise expression
    reduced over d, so XLA emits one reduce fusion whose only output is
    the scores. The mask is applied to the exponent: masked pairs read
    exp(-inf) = 0, so no positive exponent is evaluated, whatever the
    decay (and no inf reaches a gradient)."""
    pos = jnp.arange(rc.shape[2])
    strict = (pos[:, None] > pos[None, :])[:, :, None, None]
    dec = jnp.exp(jnp.where(strict, cum_tm1[:, :, :, None] - cum[:, :, None], -jnp.inf))
    return (rc[:, :, :, None] * kc[:, :, None] * dec).sum(-1)


@layers.scoped("time_mix")
def rwkv6_time_mix(params, x, cfg: RWKV6Config, *, state=None, x_prev=None,
                   return_state: bool = False):
    """Chunked evaluation. x: (B,S,d). state: (B,H,D,D) f32.

    Profile scopes: ``time_mix``, with the WKV sequence mixer under
    ``time_mix/wkv``: the intra-chunk scores and their readout under
    ``wkv/intra``, the inter-chunk state scan and its readout under
    ``wkv/state``."""
    b, s, d = x.shape
    h, dh = cfg.n_heads, cfg.head_dim
    if x_prev is None:
        x_prev = jnp.zeros((b, 1, d), x.dtype)
    r, k, v, g, logw = _projections(params, x, x_prev)
    with jax.named_scope("wkv"):
        rh = _headed(r, h, dh).astype(jnp.float32)
        kh = _headed(k, h, dh).astype(jnp.float32)
        vh = _headed(v, h, dh).astype(jnp.float32)
        lw = _headed(logw, h, dh)                              # (B,S,H,D)

        lc = min(cfg.chunk, s)
        while s % lc:
            lc -= 1
        nc = s // lc
        rc = rh.reshape(b, nc, lc, h, dh)
        kc = kh.reshape(b, nc, lc, h, dh)
        vc = vh.reshape(b, nc, lc, h, dh)
        lwc = lw.reshape(b, nc, lc, h, dh)
        cum = jnp.cumsum(lwc, axis=2)                          # inclusive
        # Decay applies on steps s+1 .. t-1: y_t reads S_{t-1}.
        cum_tm1 = cum - lwc                                    # cum_{t-1}

        with jax.named_scope("intra"):
            scores = _intra_scores(rc, kc, cum, cum_tm1)       # (B,nc,L,L,H)
            y_intra = jnp.einsum("bctsh,bcshd->bcthd", scores, vc)
            # Diagonal (current token) via bonus u: an elementwise f32
            # product, kept out of the readout (a default-precision matmul
            # may round its operands to bf16), in the projections' (B,S,d)
            # layout rather than the chunked one.
            ruk = r.astype(jnp.float32) * k.astype(jnp.float32) * params["u"].reshape(-1)
            y_diag = _headed(ruk, h, dh).sum(-1, keepdims=True) * vh

        with jax.named_scope("state"):
            # Chunk-end state contributions: sum_t exp(cum_L - cum_t) k_t v_t^T
            dec_end = jnp.exp(cum[:, :, -1:, :, :] - cum)      # (B,nc,L,H,D)
            s_chunk = jnp.einsum("bcthd,bcthe->bchde", kc * dec_end, vc)
            chunk_decay = jnp.exp(cum[:, :, -1])               # (B,nc,H,D)

            def scan_fn(st, inp):
                sc, dec_c = inp
                out_st = st
                return st * dec_c[..., None] + sc, out_st

            init = jnp.zeros((b, h, dh, dh), jnp.float32) if state is None else state
            final_state, s_starts = lax.scan(
                scan_fn, init, (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
            s_starts = jnp.moveaxis(s_starts, 0, 1)            # (B,nc,H,D,D)

            # Inter-chunk: y_t += r_t (exp(cum_{t-1}) .) S_in
            r_dec = rc * jnp.exp(cum_tm1)
            y_inter = jnp.einsum("bcthd,bchde->bcthe", r_dec, s_starts)

        y = (y_intra + y_inter).reshape(b, s, h, dh) + y_diag
    out = _out_stage(params, y, g, h, dh)
    if return_state:
        return out, (final_state, x[:, -1:])
    return out


def rwkv6_time_mix_ref(params, x, cfg: RWKV6Config):
    """Per-step oracle (also the latency-bound perf baseline)."""
    b, s, d = x.shape
    h, dh = cfg.n_heads, cfg.head_dim
    r, k, v, g, logw = _projections(params, x, jnp.zeros((b, 1, d), x.dtype))
    rh = _headed(r, h, dh).astype(jnp.float32)
    kh = _headed(k, h, dh).astype(jnp.float32)
    vh = _headed(v, h, dh).astype(jnp.float32)
    wh = jnp.exp(_headed(logw, h, dh))

    def step(st, inp):
        rt, kt, vt, wt = inp                                # (B,H,D)
        kv = jnp.einsum("bhd,bhe->bhde", kt, vt)
        wkv = st + params["u"][None, :, :, None] * kv
        # repro: allow-raw-param-matmul (wkv is the recurrent attention
        # STATE, not a parameter -- the name trips the weight heuristic)
        yt = jnp.einsum("bhd,bhde->bhe", rt, wkv)
        return st * wt[..., None] + kv, yt

    _, ys = lax.scan(step, jnp.zeros((b, h, dh, dh), jnp.float32),
                     (jnp.moveaxis(rh, 1, 0), jnp.moveaxis(kh, 1, 0),
                      jnp.moveaxis(vh, 1, 0), jnp.moveaxis(wh, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1)                              # (B,S,H,D)
    return _out_stage(params, y, g, h, dh)


@layers.scoped("time_mix")
def rwkv6_time_mix_decode(params, x, state, x_prev, cfg: RWKV6Config):
    """One token. x: (B,1,d); state: (B,H,D,D); x_prev: (B,1,d).

    Profile scopes: ``time_mix``, the state update under ``time_mix/wkv``."""
    h, dh = cfg.n_heads, cfg.head_dim
    r, k, v, g, logw = _projections(params, x, x_prev)
    with jax.named_scope("wkv"):
        rt = _headed(r, h, dh)[:, 0].astype(jnp.float32)
        kt = _headed(k, h, dh)[:, 0].astype(jnp.float32)
        vt = _headed(v, h, dh)[:, 0].astype(jnp.float32)
        wt = jnp.exp(_headed(logw, h, dh)[:, 0])
        kv = jnp.einsum("bhd,bhe->bhde", kt, vt)
        wkv = state + params["u"][None, :, :, None] * kv
        # repro: allow-raw-param-matmul (wkv is recurrent state; see time_mix)
        yt = jnp.einsum("bhd,bhde->bhe", rt, wkv)[:, None]      # (B,1,H,D)
        new_state = state * wt[..., None] + kv
    out = _out_stage(params, yt, g, h, dh)
    return out, new_state, x


# ---------------------------------------------------------------------------
# Channel mix (RWKV's FFN)
# ---------------------------------------------------------------------------

def rwkv6_channel_mix_init(key, d_model: int, d_ff: int, dtype):
    ks = jax.random.split(key, 3)
    return {
        "mu": jax.random.uniform(ks[0], (2, d_model), jnp.float32).astype(dtype),
        "wk": layers.dense_init(ks[1], d_model, d_ff, dtype),
        "wv": layers.dense_init(ks[2], d_ff, d_model, dtype),
        "wr": layers.dense_init(jax.random.fold_in(key, 7), d_model, d_model, dtype),
    }


@layers.scoped("channel_mix")
def rwkv6_channel_mix(params, x, *, x_prev=None, return_state: bool = False):
    """RWKV's FFN; profile scope ``channel_mix``."""
    b, s, d = x.shape
    if x_prev is None:
        x_prev = jnp.zeros((b, 1, d), x.dtype)
    xx = _token_shift(x, x_prev)
    mu = params["mu"].astype(x.dtype)
    xk = x + (xx - x) * mu[0]
    xr = x + (xx - x) * mu[1]
    k = jnp.square(jax.nn.relu(layers.dense(params["wk"], xk).astype(jnp.float32)))
    r = jax.nn.sigmoid(layers.dense(params["wr"], xr).astype(jnp.float32))
    out = (r * layers.dense(params["wv"], k.astype(x.dtype)).astype(jnp.float32)).astype(x.dtype)
    if return_state:
        return out, x[:, -1:]
    return out
