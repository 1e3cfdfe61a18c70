"""Attention: causal prefill in one fused kernel, chunked online-softmax
(flash-style in pure JAX) for the rest, GQA, SWA, MLA (DeepSeek latent
attention), cross-attention, and decode paths.

Which path ``chunked_attention`` runs, read from its call:
- Causal self-attention from position 0 over every key (no window, no
  padded cache, Sq == Skv, a length that tiles into lane-aligned blocks of
  at most ``KERNEL_BLOCK``, values a multiple of 128 wide), with no
  multi-chip GSPMD mesh in context: one Pallas kernel
  (``kernels/attention.py``). Each step keeps a (block x block) score tile,
  its f32 softmax statistics and the output accumulator in VMEM, and steps
  wholly above the diagonal are never visited; the f32 score tiles the
  scan writes to HBM go away (PERF.md). Its VJP is two kernels of the same
  kind (dq; dk and dv), so one-chip training runs it too.
- Everything else -- cross-attention, non-causal encoders, sliding
  windows, padded caches, continuation offsets, lengths that do not tile,
  GSPMD meshes (a Pallas call has no partitioning rule) -- runs the
  two-level chunk scan. It keeps the live f32 score tile at
  (q_chunk x kv_chunk) with exact online-softmax accumulation, so
  (B, H, S, S) scores are never materialized (~17 GB per device at S=32k).
  Causality there is at chunk granularity: fully-masked chunk pairs are
  still computed and zeroed (static grid).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import attention as attention_kernel
from repro.kernels import compat
from repro.models import layers

_NEG = -1e30
# Most rows of q and of k/v per step of the fused causal kernel: a sequence
# of up to this many tokens is one block, a longer one is cut into blocks of
# this many (the chip's timing at S=1024: PERF.md).
KERNEL_BLOCK = 1024


# ---------------------------------------------------------------------------
# Core chunked attention
# ---------------------------------------------------------------------------

@layers.scoped("attn_core")
def chunked_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                      q_offset=0, kv_valid_len=None,
                      q_chunk: int = 1024, kv_chunk: int = 1024,
                      softmax_scale: float | None = None):
    """q: (B, Sq, H, D); k, v: (B, Skv, Hk, Dk/Dv); H % Hk == 0.

    ``q_offset``: global position of q[0] (prefill continuation / decode).
    ``kv_valid_len``: mask out cache slots >= this (scalar or (B,)).
    Supports Dk != Dv (MLA attends with 192-dim keys, 128-dim values).
    Causal self-attention from a static position 0 over every key, at a
    length that tiles into the kernel's blocks, with values a multiple of
    128 wide and outside a multi-chip mesh, runs the fused kernel
    (``_causal_kernel``); everything else the chunk scan, in
    (q_chunk x kv_chunk) tiles. Both take bf16 operands into f32 scores and
    statistics and feed the probabilities to the MXU in one bf16 pass, as
    the chip runs an f32 product at default precision.
    Profile scope ``attn_core``: scores, softmax and values.
    """
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    if _takes_kernel(q, k, v, causal=causal, window=window, q_offset=q_offset,
                     kv_valid_len=kv_valid_len):
        return _causal_kernel(q, k, v, scale)
    return _chunk_scan(q, k, v, causal=causal, window=window, q_offset=q_offset,
                       kv_valid_len=kv_valid_len, q_chunk=q_chunk,
                       kv_chunk=kv_chunk, scale=scale)


def _chunk_scan(q, k, v, *, causal, window, q_offset, kv_valid_len, q_chunk,
                kv_chunk, scale):
    """``chunked_attention`` as a two-level scan over (q chunk, kv chunk)
    pairs, every pair computed."""
    b, sq, h, dk = q.shape
    _, skv, hk, _ = k.shape
    dv = v.shape[-1]
    g = h // hk

    # Pad sequences to chunk multiples rather than shrinking the chunk: a
    # divisor-shrink fallback degenerates to chunk=1 on prime lengths
    # (vision_seq=1601 gave a 1601-step kv scan per cross-attn layer).
    if kv_valid_len is None:
        kv_valid = jnp.full((b,), skv, jnp.int32)
    else:
        kv_valid = jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32), (b,))

    qc = min(q_chunk, sq)
    kc = min(kv_chunk, skv)
    sq_pad = -(-sq // qc) * qc
    skv_pad = -(-skv // kc) * kc
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0), (0, 0)))
    if skv_pad != skv:
        k = jnp.pad(k, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))
        kv_valid = jnp.minimum(kv_valid, skv)   # padded slots masked out
    nq, nk = sq_pad // qc, skv_pad // kc

    qs = q.reshape(b, nq, qc, hk, g, dk)
    ks = k.reshape(b, nk, kc, hk, dk)
    vs = v.reshape(b, nk, kc, hk, dv)

    def q_step(_, qi_and_chunk):
        qi, q_blk = qi_and_chunk                      # q_blk: (b, qc, hk, g, dk)
        q_pos = q_offset + qi * qc + jnp.arange(qc)   # (qc,)

        # NB: kv_step is remat'd (see lax.scan below). Without it, the
        # backward saves every f32 score/probability tile stacked over both
        # scan levels -- the full S^2 attention backward (~28 GiB/device at
        # train_4k, measured) that chunking exists to avoid. With remat,
        # only the (m, l, acc) carries are saved and tiles are recomputed.
        def kv_step(carry, ki_and_blk):
            m_run, l_run, acc = carry
            ki, k_blk, v_blk = ki_and_blk
            kv_pos = ki * kc + jnp.arange(kc)         # (kc,)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk.astype(jnp.float32),
                           k_blk.astype(jnp.float32)) * scale
            mask = kv_pos[None, :] <= q_pos[:, None] if causal else \
                jnp.ones((qc, kc), bool)
            if window is not None:
                mask &= kv_pos[None, :] > q_pos[:, None] - window
            mask = mask[None] & (kv_pos[None, None, :] < kv_valid[:, None, None])
            mask = mask[:, None, None]                # (b,1,1,qc,kc)
            s = jnp.where(mask, s, _NEG)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None]) * mask  # zero fully-masked rows
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_blk.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hk, g, qc), _NEG, jnp.float32)
        l0 = jnp.zeros((b, hk, g, qc), jnp.float32)
        a0 = jnp.zeros((b, hk, g, qc, dv), jnp.float32)
        (m_f, l_f, acc_f), _ = lax.scan(
            jax.checkpoint(kv_step), (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0)))
        out = acc_f / jnp.maximum(l_f, 1e-30)[..., None]   # (b,hk,g,qc,dv)
        return None, jnp.einsum("bhgqd->bqhgd", out)

    _, outs = lax.scan(q_step, None, (jnp.arange(nq), jnp.moveaxis(qs, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq_pad, h, dv)[:, :sq]
    return out.astype(q.dtype)


def _kernel_block(s: int) -> int:
    """Rows of q and of k/v per kernel step for a sequence of s tokens."""
    return min(s, KERNEL_BLOCK)


def _takes_kernel(q, k, v, *, causal, window, q_offset, kv_valid_len) -> bool:
    """Whether ``chunked_attention`` runs the fused kernel: causal
    self-attention from position 0 over every key, at a length that tiles
    into lane-aligned blocks and with values a multiple of the 128 lanes
    wide, outside a multi-chip GSPMD mesh (a Pallas call has no partitioning
    rule there; ``core/tsmm.py`` applies the same test)."""
    s = q.shape[1]
    block = _kernel_block(s)
    mesh = compat.get_context_mesh()
    return (causal and window is None and kv_valid_len is None
            and isinstance(q_offset, int) and q_offset == 0
            and k.shape[1] == s and s % block == 0 and block % 128 == 0
            and v.shape[-1] % 128 == 0
            and (mesh is None or mesh.size == 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _causal_kernel(q, k, v, scale):
    """Causal attention of q (B, S, H, Dk) over k (B, S, Hk, Dk) and
    v (B, S, Hk, Dv) in one Pallas kernel (``kernels/attention.py``), which
    reads them head-major and writes the output token-major. Its VJP is the
    kernel's own backward, from the forward's row log-sum-exp."""
    b, s, h, _ = q.shape
    out = attention_kernel.causal_attention(
        *_head_major(q, k, v), scale=scale, block=_kernel_block(s),
        interpret=compat.auto_interpret(None))
    return out.reshape(b, s, h, v.shape[-1])


def _head_major(*xs):
    return tuple(jnp.swapaxes(x, 1, 2) for x in xs)


def _causal_kernel_fwd(q, k, v, scale):
    b, s, h, _ = q.shape
    qkv = _head_major(q, k, v)
    out, lse = attention_kernel.causal_attention(
        *qkv, scale=scale, block=_kernel_block(s),
        interpret=compat.auto_interpret(None), with_lse=True)
    return out.reshape(b, s, h, v.shape[-1]), (qkv, out, lse)


def _causal_kernel_bwd(scale, res, g):
    qkv, out, lse = res
    grads = attention_kernel.causal_attention_vjp(
        *qkv, out, lse, g.reshape(out.shape), scale=scale,
        block=_kernel_block(out.shape[1]),
        interpret=compat.auto_interpret(None))
    return _head_major(*grads)


_causal_kernel.defvjp(_causal_kernel_fwd, _causal_kernel_bwd)


@layers.scoped("attn_core")
def decode_attention(q, k_cache, v_cache, cur_len, *, window: int | None = None,
                     softmax_scale: float | None = None):
    """Single-step decode: q (B, 1, H, D) against a (B, S, Hk, D) cache.

    ``cur_len``: number of valid cache slots per batch element (the new
    token's own k/v must already be written at cur_len - 1).
    """
    b, _, h, dk = q.shape
    _, s, hk, _ = k_cache.shape
    g = h // hk
    scale = softmax_scale if softmax_scale is not None else dk ** -0.5
    qh = q.reshape(b, hk, g, dk)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qh.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(s)
    lens = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    mask = pos[None, :] < lens[:, None]
    if window is not None:
        mask &= pos[None, :] >= lens[:, None] - window
    scores = jnp.where(mask[:, None, None, :], scores, _NEG)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA self-attention module
# ---------------------------------------------------------------------------

def gqa_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
             *, qkv_bias: bool = False, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": layers.dense_init(ks[1], d_model, n_kv * head_dim, dtype),
        "wv": layers.dense_init(ks[2], d_model, n_kv * head_dim, dtype),
        "wo": layers.dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
    return p


def gqa_project_qkv(params, x, positions, *, n_heads, n_kv, head_dim,
                    rope_theta=10000.0, rope_fraction=1.0):
    b, s, _ = x.shape
    q = layers.dense(params["wq"], x)
    k = layers.dense(params["wk"], x)
    v = layers.dense(params["wv"], x)
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, s, n_heads, head_dim)
    k = k.reshape(b, s, n_kv, head_dim)
    v = v.reshape(b, s, n_kv, head_dim)
    if rope_fraction > 0:
        q = layers.apply_rope(q, positions, theta=rope_theta, fraction=rope_fraction)
        k = layers.apply_rope(k, positions, theta=rope_theta, fraction=rope_fraction)
    return q, k, v


@layers.scoped("attn")
def gqa_fwd(params, x, *, n_heads, n_kv, head_dim, causal=True,
            window=None, rope_theta=10000.0, rope_fraction=1.0,
            q_chunk=1024, kv_chunk=1024, positions=None,
            kv_override=None):
    """Full-sequence attention (train / prefill). Returns (out, (k, v)).

    ``kv_override``: (k, v) to attend over instead of self-projections
    (cross-attention passes pre-projected image keys/values). Profile
    scope ``attn``: the projections and ``attn_core``.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = gqa_project_qkv(params, x, positions, n_heads=n_heads, n_kv=n_kv,
                              head_dim=head_dim, rope_theta=rope_theta,
                              rope_fraction=rope_fraction)
    if kv_override is not None:
        k, v = kv_override
    ctx = chunked_attention(q, k, v, causal=causal, window=window,
                            q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = layers.dense(params["wo"], ctx.reshape(b, s, n_heads * head_dim))
    return out, (k, v)


@layers.scoped("attn")
def gqa_decode(params, x, cache_k, cache_v, pos, *, n_heads, n_kv, head_dim,
               window=None, rope_theta=10000.0, rope_fraction=1.0,
               ring_window: int | None = None):
    """One-token decode. x: (B, 1, d). pos: scalar current position.

    Writes the new k/v at slot ``pos`` (or ``pos % ring_window`` for SWA
    ring caches) and attends over valid slots. Returns (out, cache_k, cache_v).
    """
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = gqa_project_qkv(params, x, positions, n_heads=n_heads, n_kv=n_kv,
                              head_dim=head_dim, rope_theta=rope_theta,
                              rope_fraction=rope_fraction)
    slot = pos if ring_window is None else pos % ring_window
    cache_k = lax.dynamic_update_slice_in_dim(cache_k, k, slot, axis=1)
    cache_v = lax.dynamic_update_slice_in_dim(cache_v, v, slot, axis=1)
    if ring_window is None:
        ctx = decode_attention(q, cache_k, cache_v, pos + 1, window=window)
    else:
        # Ring cache: all slots <= min(pos+1, ring) are valid; positions wrap,
        # and softmax is permutation-invariant so slot order is irrelevant.
        valid = jnp.minimum(pos + 1, ring_window)
        ctx = decode_attention(q, cache_k, cache_v, valid)
    out = layers.dense(params["wo"], ctx.reshape(b, 1, n_heads * head_dim))
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, d_model: int, n_heads: int, *, q_lora: int, kv_lora: int,
             nope_dim: int, rope_dim: int, v_dim: int, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    return {
        "wdq": layers.dense_init(ks[0], d_model, q_lora, dtype),
        "q_norm": layers.rmsnorm_init(q_lora, dtype),
        "wuq": layers.dense_init(ks[1], q_lora, n_heads * (nope_dim + rope_dim), dtype),
        "wdkv": layers.dense_init(ks[2], d_model, kv_lora, dtype),
        "kv_norm": layers.rmsnorm_init(kv_lora, dtype),
        "wukv": layers.dense_init(ks[3], kv_lora, n_heads * (nope_dim + v_dim), dtype),
        "wkr": layers.dense_init(ks[4], d_model, rope_dim, dtype),
        "wo": layers.dense_init(ks[5], n_heads * v_dim, d_model, dtype),
    }


def _mla_q(params, x, positions, *, n_heads, nope_dim, rope_dim, inv_freq, eps):
    b, s, _ = x.shape
    cq = layers.rmsnorm(params["q_norm"], layers.dense(params["wdq"], x), eps)
    q = layers.dense(params["wuq"], cq).reshape(b, s, n_heads, nope_dim + rope_dim)
    q_nope, q_pe = q[..., :nope_dim], q[..., nope_dim:]
    q_pe = layers.apply_rope(q_pe, positions, inv_freq=inv_freq)
    return q_nope, q_pe


def _mla_latent(params, x, positions, *, inv_freq, eps):
    c = layers.rmsnorm(params["kv_norm"], layers.dense(params["wdkv"], x), eps)
    k_pe = layers.dense(params["wkr"], x)[:, :, None, :]      # (b,s,1,rope)
    k_pe = layers.apply_rope(k_pe, positions, inv_freq=inv_freq)
    return c, k_pe


def _mla_rope(nope_dim, rope_dim, rope_theta, yarn):
    """(inverse frequencies of the rotated rope_dim, softmax scale): plain
    RoPE at ``rope_theta`` and ``(nope + rope)^-0.5``, or YaRN's ramped
    frequencies and that scale times its ``mscale^2``."""
    scale = (nope_dim + rope_dim) ** -0.5
    if yarn is None:
        return layers.rope_freqs(rope_dim, 1.0, rope_theta)[0], scale
    return yarn.inv_freq(rope_dim, rope_theta), scale * yarn.softmax_factor()


@layers.scoped("attn")
def mla_fwd(params, x, *, n_heads, nope_dim, rope_dim, v_dim,
            rope_theta=10000.0, yarn=None, norm_eps=1e-5, causal=True,
            q_chunk=1024, kv_chunk=1024, positions=None):
    """Full-sequence MLA. Returns (out, (c_latent, k_pe)) -- the latent cache.

    ``yarn`` (``layers.YaRN``): DeepSeek-V3's rope scaling, in the rotated
    frequencies and the softmax scale. ``norm_eps``: the q and kv latent
    RMSNorms'. Profile scope ``attn``: the projections and ``attn_core``.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    inv_freq, scale = _mla_rope(nope_dim, rope_dim, rope_theta, yarn)
    q_nope, q_pe = _mla_q(params, x, positions, n_heads=n_heads,
                          nope_dim=nope_dim, rope_dim=rope_dim,
                          inv_freq=inv_freq, eps=norm_eps)
    c, k_pe = _mla_latent(params, x, positions, inv_freq=inv_freq, eps=norm_eps)
    kv = layers.dense(params["wukv"], c).reshape(b, s, n_heads, nope_dim + v_dim)
    k_nope, v = kv[..., :nope_dim], kv[..., nope_dim:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (*k_pe.shape[:2], n_heads, rope_dim))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    ctx = chunked_attention(q, k, v, causal=causal, q_chunk=q_chunk,
                            kv_chunk=kv_chunk, softmax_scale=scale)
    out = layers.dense(params["wo"], ctx.reshape(b, s, n_heads * v_dim))
    return out, (c, k_pe[:, :, 0, :])


@layers.scoped("attn")
def mla_decode(params, x, cache_c, cache_kpe, pos, *, n_heads, nope_dim,
               rope_dim, v_dim, rope_theta=10000.0, yarn=None, norm_eps=1e-5,
               absorb: bool = True):
    """One-token MLA decode over the latent cache; ``yarn`` and
    ``norm_eps`` as ``mla_fwd``'s.

    ``absorb=True`` (beyond-paper optimization, recorded in §Perf): fold
    W_uk into the query and W_uv into the output so attention runs directly
    in the 512-dim latent space -- O(S * kv_lora) per step instead of
    re-expanding the whole cache to per-head k/v (O(S * H * (nope+v))).
    Profile scopes: ``attn``; the folds, projections of the query and the
    output, under ``dense``; the latent-space scores, softmax and values
    under ``attn_core``.
    """
    b = x.shape[0]
    kv_lora = cache_c.shape[-1]
    positions = jnp.full((b, 1), pos, jnp.int32)
    inv_freq, scale = _mla_rope(nope_dim, rope_dim, rope_theta, yarn)
    q_nope, q_pe = _mla_q(params, x, positions, n_heads=n_heads,
                          nope_dim=nope_dim, rope_dim=rope_dim,
                          inv_freq=inv_freq, eps=norm_eps)
    c_new, kpe_new = _mla_latent(params, x, positions, inv_freq=inv_freq,
                                 eps=norm_eps)
    cache_c = lax.dynamic_update_slice_in_dim(cache_c, c_new, pos, axis=1)
    cache_kpe = lax.dynamic_update_slice_in_dim(cache_kpe, kpe_new[:, :, 0, :], pos, axis=1)
    s_len = cache_c.shape[1]
    wukv = params["wukv"].reshape(kv_lora, n_heads, nope_dim + v_dim)
    wuk, wuv = wukv[..., :nope_dim], wukv[..., nope_dim:]

    if absorb:
        # q_c[b,h,l] = sum_d q_nope[b,h,d] * wuk[l,h,d]
        with jax.named_scope("dense"):
            # repro: allow-raw-param-matmul (absorbed decode: the 3-D per-head
            # W_uk slice folds into a batch-1 f32 einsum -- no 2-D tsmm form,
            # and per-step shapes never classify tall-skinny)
            q_c = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0].astype(jnp.float32),
                             wuk.astype(jnp.float32))
        with jax.named_scope("attn_core"):
            s_nope = jnp.einsum("bhl,bsl->bhs", q_c, cache_c.astype(jnp.float32))
            s_pe = jnp.einsum("bhd,bsd->bhs", q_pe[:, 0].astype(jnp.float32),
                              cache_kpe.astype(jnp.float32))
            scores = (s_nope + s_pe) * scale
            mask = jnp.arange(s_len)[None, None, :] <= pos
            scores = jnp.where(mask, scores, _NEG)
            p = jax.nn.softmax(scores, axis=-1)
            ctx_c = jnp.einsum("bhs,bsl->bhl", p, cache_c.astype(jnp.float32))
        with jax.named_scope("dense"):
            # repro: allow-raw-param-matmul (absorbed decode W_uv fold; see wuk)
            ctx = jnp.einsum("bhl,lhd->bhd", ctx_c, wuv.astype(jnp.float32))
    else:
        # repro: allow-raw-param-matmul (non-absorbed decode re-expands the
        # latent cache through the 3-D per-head W_ukv -- same exemption as
        # the absorbed path's folds above)
        kv = jnp.einsum("bsl,lhd->bshd", cache_c.astype(jnp.float32),
                        wukv.astype(jnp.float32))
        k_nope, v = kv[..., :nope_dim], kv[..., nope_dim:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(cache_kpe[:, :, None, :].astype(jnp.float32),
                                      (*cache_kpe.shape[:2], n_heads, rope_dim))], -1)
        q = jnp.concatenate([q_nope, q_pe], -1)
        ctx = decode_attention(q, k.astype(x.dtype), v.astype(x.dtype), pos + 1,
                               softmax_scale=scale)[:, 0]
    out = layers.dense(params["wo"],
                       ctx.reshape(b, 1, n_heads * v_dim).astype(x.dtype))
    return out, cache_c, cache_kpe
