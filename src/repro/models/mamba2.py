"""Mamba2 (SSD) layer: chunked matmul-form scan for training/prefill, O(1)
recurrent step for decode. Zamba2's backbone.

State-space recurrence per head h (state size N, head dim P):
    S_t = a_t * S_{t-1} + dt_t * B_t x_t^T          (S: (N, P))
    y_t = C_t @ S_t + D * x_t
with a_t = exp(dt_t * A) (scalar per head per step, A < 0).

Chunked (SSD) evaluation over chunks of length L turns the recurrence into
MXU-friendly matmuls: an intra-chunk (L x L) masked "attention" against
decay weights plus an inter-chunk state carried by a lax.scan -- the same
decomposition as Mamba-2's SSD algorithm (arXiv:2405.21060), adapted to
dense jnp (the (L x L) tile is the VMEM-sized working set).

Includes the depthwise causal conv (width 4) over [x, B, C] and the gated
RMSNorm output stage.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    d_inner: int           # expansion * d_model
    n_heads: int           # d_inner / head_dim
    state_dim: int = 64
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 128


def mamba2_init(key, d_model: int, cfg: Mamba2Config, dtype):
    ks = jax.random.split(key, 4)
    di, h, n, g = cfg.d_inner, cfg.n_heads, cfg.state_dim, cfg.n_groups
    conv_dim = di + 2 * g * n
    proj_out = 2 * di + 2 * g * n + h   # x, z, B, C, dt
    return {
        "in_proj": layers.dense_init(ks[0], d_model, proj_out, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_width, conv_dim), jnp.float32)
                   * cfg.conv_width ** -0.5).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.zeros((h,), jnp.float32),          # A = -exp(A_log) = -1
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": layers.rmsnorm_init(di, dtype),
        "out_proj": layers.dense_init(ks[2], di, d_model, dtype),
    }


def _split_proj(proj, cfg: Mamba2Config):
    di, g, n, h = cfg.d_inner, cfg.n_groups, cfg.state_dim, cfg.n_heads
    x, z, bb, cc, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + g * n, 2 * di + 2 * g * n], axis=-1)
    return x, z, bb, cc, dt


def _causal_conv(seq, w, b, prev=None):
    """Depthwise causal conv. seq: (B, S, C); w: (W, C); prev: (B, W-1, C)."""
    width = w.shape[0]
    if prev is None:
        prev = jnp.zeros((seq.shape[0], width - 1, seq.shape[-1]), seq.dtype)
    padded = jnp.concatenate([prev, seq], axis=1)
    out = sum(padded[:, i:i + seq.shape[1]] * w[i] for i in range(width))
    new_prev = padded[:, -(width - 1):] if width > 1 else prev
    return jax.nn.silu((out + b).astype(jnp.float32)).astype(seq.dtype), new_prev


@layers.scoped("mamba")
def mamba2_fwd(params, x_in, cfg: Mamba2Config, *, initial_state=None,
               conv_state=None, return_state: bool = False):
    """x_in: (B, S, d_model). Chunked SSD scan.

    Returns out, or (out, (ssm_state, conv_state)) when return_state
    (prefill needs the states to seed decode). Profile scopes: ``mamba``,
    with the chunked SSD scan under ``mamba/ssd``.
    """
    b, s, _ = x_in.shape
    di, h, n, g = cfg.d_inner, cfg.n_heads, cfg.state_dim, cfg.n_groups
    p = di // h
    hg = h // g

    proj = layers.dense(params["in_proj"], x_in)
    x, z, bb, cc, dt = _split_proj(proj, cfg)
    conv_in = jnp.concatenate([x, bb, cc], axis=-1)
    conv_out, conv_state_new = _causal_conv(conv_in, params["conv_w"],
                                            params["conv_b"], conv_state)
    x, bb, cc = jnp.split(conv_out, [di, di + g * n], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])   # (B,S,H)
    a_neg = -jnp.exp(params["A_log"])                                  # (H,)
    loga = dt * a_neg                                                  # log decay

    with jax.named_scope("ssd"):
        lc = min(cfg.chunk, s)
        while s % lc:
            lc -= 1
        nc = s // lc
        xh = x.reshape(b, nc, lc, h, p).astype(jnp.float32)
        bh = bb.reshape(b, nc, lc, g, n).astype(jnp.float32)
        ch = cc.reshape(b, nc, lc, g, n).astype(jnp.float32)
        dtc = dt.reshape(b, nc, lc, h)
        logac = loga.reshape(b, nc, lc, h)

        cum = jnp.cumsum(logac, axis=2)                                    # (B,nc,L,H)

        # Intra-chunk: scores[t, s'] = (C_t . B_s') * exp(cum_t - cum_s') * dt_s'
        seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]                # (B,nc,L,L,H)
        tri = jnp.tril(jnp.ones((lc, lc), bool))
        decay = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)
        cb = jnp.einsum("bclgn,bcsgn->bclsg", ch, bh)                      # (B,nc,L,L,G)
        cb = jnp.repeat(cb, hg, axis=-1)                                   # -> (...,H)
        scores = cb * decay * dtc[:, :, None, :, :]
        y_intra = jnp.einsum("bclsh,bcshp->bclhp", scores, xh)

        # Chunk-end states: S_c = sum_t exp(cum_L - cum_t) dt_t B_t x_t^T
        dec_to_end = jnp.exp(cum[:, :, -1:, :] - cum)                      # (B,nc,L,H)
        b_rep = jnp.repeat(bh, hg, axis=3)                                 # (B,nc,L,H,N)
        s_chunk = jnp.einsum("bclhn,bclhp->bchnp",
                             b_rep, xh * (dtc * dec_to_end)[..., None])

        # Inter-chunk scan: carry state, emit state at chunk *start*.
        chunk_decay = jnp.exp(cum[:, :, -1, :])                            # (B,nc,H)

        def scan_fn(state, inp):
            s_c, dec = inp                                                 # (B,H,N,P), (B,H)
            out_state = state
            new_state = state * dec[..., None, None] + s_c
            return new_state, out_state

        init = (jnp.zeros((b, h, n, p), jnp.float32) if initial_state is None
                else initial_state.astype(jnp.float32))
        final_state, s_starts = lax.scan(
            scan_fn, init,
            (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
        s_starts = jnp.moveaxis(s_starts, 0, 1)                            # (B,nc,H,N,P)

        c_rep = jnp.repeat(ch, hg, axis=3)                                 # (B,nc,L,H,N)
        y_inter = jnp.einsum("bclhn,bchnp->bclhp",
                             c_rep * jnp.exp(cum)[..., None], s_starts)

        y = (y_intra + y_inter).reshape(b, s, di)
    y = y + (x.astype(jnp.float32).reshape(b, s, h, p)
             * params["D"][None, None, :, None]).reshape(b, s, di)
    y = y.astype(x_in.dtype)
    y = layers.rmsnorm(params["norm"], y) * jax.nn.silu(z.astype(jnp.float32)).astype(x_in.dtype)
    out = layers.dense(params["out_proj"], y)
    if return_state:
        return out, (final_state, conv_state_new)
    return out


@layers.scoped("mamba")
def mamba2_decode(params, x_in, state, conv_state, cfg: Mamba2Config):
    """One token. x_in: (B, 1, d_model); state: (B, H, N, P) f32.

    Profile scopes: ``mamba``, the state update under ``mamba/ssd``."""
    b = x_in.shape[0]
    di, h, n, g = cfg.d_inner, cfg.n_heads, cfg.state_dim, cfg.n_groups
    p = di // h
    hg = h // g

    proj = layers.dense(params["in_proj"], x_in)
    x, z, bb, cc, dt = _split_proj(proj, cfg)
    conv_in = jnp.concatenate([x, bb, cc], axis=-1)
    conv_out, conv_state = _causal_conv(conv_in, params["conv_w"],
                                        params["conv_b"], conv_state)
    x, bb, cc = jnp.split(conv_out, [di, di + g * n], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])[:, 0]  # (B,H)
    a = jnp.exp(dt * -jnp.exp(params["A_log"]))                             # (B,H)
    with jax.named_scope("ssd"):
        xh = x.reshape(b, h, p).astype(jnp.float32)
        b_rep = jnp.repeat(bb.reshape(b, g, n), hg, axis=1)                     # (B,H,N)
        c_rep = jnp.repeat(cc.reshape(b, g, n), hg, axis=1)

        state = state * a[..., None, None] + jnp.einsum(
            "bhn,bhp->bhnp", b_rep, xh * dt[..., None])
        y = jnp.einsum("bhn,bhnp->bhp", c_rep, state)
    y = y + xh * params["D"][None, :, None]
    y = y.reshape(b, 1, di).astype(x_in.dtype)
    y = layers.rmsnorm(params["norm"], y) * jax.nn.silu(z.astype(jnp.float32)).astype(x_in.dtype)
    return layers.dense(params["out_proj"], y), state, conv_state


def mamba2_ref_recurrent(params, x_in, cfg: Mamba2Config):
    """Step-by-step oracle for testing the chunked path."""
    b, s, _ = x_in.shape
    h, n, p = cfg.n_heads, cfg.state_dim, cfg.d_inner // cfg.n_heads
    state = jnp.zeros((b, h, n, p), jnp.float32)
    conv_state = jnp.zeros((b, cfg.conv_width - 1, cfg.d_inner + 2 * cfg.n_groups * cfg.state_dim),
                           x_in.dtype)
    outs = []
    for t in range(s):
        o, state, conv_state = mamba2_decode(params, x_in[:, t:t + 1], state,
                                             conv_state, cfg)
        outs.append(o)
    return jnp.concatenate(outs, axis=1)
