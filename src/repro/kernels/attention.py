"""Causal self-attention in one Pallas kernel: prefill's attention core.

``causal_attention(q, k, v)`` computes softmax(scale * q k^T, causal) v for
q (B, H, S, Dk), k (B, Hk, S, Dk) and v (B, Hk, S, Dv), H % Hk == 0, in
(block x block) tiles, and writes it token-major, (B, S, H * Dv), the
layout of the output projection's input:

* Grid ``(B, H, S/block, S/block)`` with ``dimension_semantics=("parallel",
  "parallel", "parallel", "arbitrary")``: the kv axis is the sequential
  online-softmax reduction. The running max and sum (f32, replicated over
  the 128 lanes) and the (block, Dv) f32 accumulator live in VMEM scratch
  across it, and the score tile never leaves VMEM.
* Blocks wholly above the diagonal are never computed, and their k/v index
  map repeats the diagonal block, so the pipeline fetches nothing for them.
  Only the diagonal block is masked; the output is written there, its last
  contributing block, as the (block, Dv) column block of head h: Dv is a
  multiple of the 128 lanes.
* Arithmetic as the chunk scan's in ``models/attention.py`` on the chip:
  the operands go to the MXU in their own dtype with f32 accumulation, the
  scale multiplies the f32 scores, and the probabilities are rounded to the
  dtype of v for their product, which is the one bf16 pass the chip gives
  an f32 product at default precision (PERF.md).

``causal_attention_vjp(...)`` is its backward, in two kernels with the
same blocks and skip: ``dq`` walks each q block's kv blocks up to the diagonal, and
``dk``/``dv`` walk each kv block's q blocks from the diagonal down, over the
``H / Hk`` q heads that share the kv head. Both recompute the probabilities
from the forward's row log-sum-exp (``with_lse=True``) and feed the MXU as
the forward does: bf16 operands, f32 accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels import compat

LANES = 128
SUBLANES = 8
_NEG = -1e30          # the chunk scan's mask value and starting max


def _lanes(x, n: int):
    """A (rows, LANES) lane-replicated statistic as (rows, n), n a multiple
    of LANES."""
    return jnp.tile(x, (1, n // LANES))


def _kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale):
    lse_ref = rest[0] if len(rest) == 4 else None
    m_ref, l_ref, acc_ref = rest[-3:]
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(diagonal: bool):
        s = lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = _masked(s, diagonal, 0)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, s.shape[1]))
        corr = jnp.exp(m_prev - m_next)
        l_ref[...] = corr * l_ref[...] + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_next
        v = v_ref[...]
        pv = lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_ref[...] = _lanes(corr, v.shape[1]) * acc_ref[...] + pv

    @pl.when(ki < qi)
    def _below():
        step(diagonal=False)

    @pl.when(ki == qi)
    def _diagonal():
        step(diagonal=True)
        o_ref[...] = (acc_ref[...] / _lanes(l_ref[...], acc_ref.shape[1])
                      ).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])


def causal_attention(q, k, v, *, scale: float, block: int, interpret: bool,
                     with_lse: bool = False):
    """q (B, H, S, Dk), k (B, Hk, S, Dk), v (B, Hk, S, Dv) -> (B, S, H * Dv)
    in q's dtype; S a multiple of ``block``, and ``block`` and Dv multiples
    of LANES. ``with_lse`` also returns each row's f32 log-sum-exp of the
    scaled scores, (B, H, S), which ``causal_attention_vjp`` takes."""
    b, h, s, dk = q.shape
    hk, dv = k.shape[1], v.shape[-1]
    g, n = h // hk, s // block

    def q_map(bi, hi, qi, ki):
        return bi, hi, qi, 0

    def kv_map(bi, hi, qi, ki):
        return bi, hi // g, jnp.minimum(ki, qi), 0

    def out_map(bi, hi, qi, ki):
        return bi, qi, hi

    out_specs = pl.BlockSpec((None, block, dv), out_map)
    out_shape = jax.ShapeDtypeStruct((b, s, h * dv), q.dtype)
    if with_lse:
        out_specs = [out_specs, pl.BlockSpec((None, None, block, LANES), q_map)]
        out_shape = [out_shape, jax.ShapeDtypeStruct((b, h, s, LANES), jnp.float32)]
    res = compat.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=(b, h, n, n),
        in_specs=[pl.BlockSpec((None, None, block, dk), q_map),
                  pl.BlockSpec((None, None, block, dk), kv_map),
                  pl.BlockSpec((None, None, block, dv), kv_map)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[compat.VMEM((block, LANES), jnp.float32),
                        compat.VMEM((block, LANES), jnp.float32),
                        compat.VMEM((block, dv), jnp.float32)],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="causal_attention",
    )(q, k, v)
    if with_lse:
        out, lse = res
        return out, lse[..., 0]
    return res


def _masked(s, diagonal: bool, q_axis: int):
    """Scores with the future masked on a diagonal block; ``q_axis`` is the
    axis of s that runs over q positions."""
    if not diagonal:
        return s
    q_pos = lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(k_pos <= q_pos, s, _NEG)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_ref,
               *, scale):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(diagonal: bool):
        k = k_ref[...]
        s = lax.dot_general(q_ref[...], k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(_masked(s, diagonal, 0) - lse_ref[0][:, None])
        dp = lax.dot_general(do_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0][:, None])
        acc_ref[...] += lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki < qi)
    def _below():
        step(diagonal=False)

    @pl.when(ki == qi)
    def _diagonal():
        step(diagonal=True)
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale):
    ki, gi, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(diagonal: bool):
        q, do = q_ref[...], do_ref[...]
        # kv rows by q columns, so the row statistics broadcast down rows
        st = lax.dot_general(k_ref[...], q, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
        pt = jnp.exp(_masked(st, diagonal, 1) - lse_ref[:1, :])
        dv_acc[...] += lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[...], do, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[:1, :])
        dk_acc[...] += lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi > ki)
    def _below():
        step(diagonal=False)

    @pl.when(qi == ki)
    def _diagonal():
        step(diagonal=True)

    @pl.when((gi == pl.num_programs(3) - 1) & (qi == pl.num_programs(4) - 1))
    def _write():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def causal_attention_vjp(q, k, v, out, lse, dout, *, scale: float, block: int,
                         interpret: bool):
    """The cotangents of ``causal_attention``'s q, k and v, head-major as
    they were given, from its output and row log-sum-exp (``with_lse``) and
    the output's cotangent ``dout``, both token-major (B, S, H * Dv)."""
    b, h, s, dk = q.shape
    hk, dv = k.shape[1], v.shape[-1]
    g, n = h // hk, s // block
    di = jnp.einsum("bshd,bshd->bhs",
                    *(x.reshape(b, s, h, dv).astype(jnp.float32)
                      for x in (dout, out)))
    # row statistics laid along lanes, repeated over a sublane tile
    lse, di = (jnp.broadcast_to(x[:, :, None, :], (b, h, SUBLANES, s))
               for x in (lse, di))
    row_block = (None, None, SUBLANES, block)
    semantics = ("parallel", "parallel", "parallel", "arbitrary")

    def q_map(bi, hi, qi, ki):
        return bi, hi, qi, 0

    def kv_map(bi, hi, qi, ki):
        return bi, hi // g, jnp.minimum(ki, qi), 0

    dq = compat.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(b, h, n, n),
        in_specs=[pl.BlockSpec((None, None, block, dk), q_map),
                  pl.BlockSpec((None, None, block, dk), kv_map),
                  pl.BlockSpec((None, None, block, dv), kv_map),
                  pl.BlockSpec((None, block, dv),
                               lambda bi, hi, qi, ki: (bi, qi, hi)),
                  pl.BlockSpec(row_block, lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
                  pl.BlockSpec(row_block, lambda bi, hi, qi, ki: (bi, hi, 0, qi))],
        out_specs=pl.BlockSpec((None, None, block, dk), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[compat.VMEM((block, dk), jnp.float32)],
        compiler_params=compat.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="causal_attention_dq",
    )(q, k, v, dout, lse, di)

    # grid (B, Hk, kv block, q head of the group, q block); q blocks above
    # the diagonal repeat the diagonal one, so nothing is fetched for them
    def qh(bi, ji, gi):
        return ji * g + gi

    def q_map_kv(bi, ji, ki, gi, qi):
        return bi, qh(bi, ji, gi), jnp.maximum(qi, ki), 0

    def kv_map_kv(bi, ji, ki, gi, qi):
        return bi, ji, ki, 0

    def row_map_kv(bi, ji, ki, gi, qi):
        return bi, qh(bi, ji, gi), 0, jnp.maximum(qi, ki)

    dk_, dv_ = compat.pallas_call(
        functools.partial(_dkv_kernel, scale=scale),
        grid=(b, hk, n, g, n),
        in_specs=[pl.BlockSpec((None, None, block, dk), q_map_kv),
                  pl.BlockSpec((None, None, block, dk), kv_map_kv),
                  pl.BlockSpec((None, None, block, dv), kv_map_kv),
                  pl.BlockSpec((None, block, dv),
                               lambda bi, ji, ki, gi, qi: (
                                   bi, jnp.maximum(qi, ki), qh(bi, ji, gi))),
                  pl.BlockSpec(row_block, row_map_kv),
                  pl.BlockSpec(row_block, row_map_kv)],
        out_specs=[pl.BlockSpec((None, None, block, dk), kv_map_kv),
                   pl.BlockSpec((None, None, block, dv), kv_map_kv)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[compat.VMEM((block, dk), jnp.float32),
                        compat.VMEM((block, dv), jnp.float32)],
        compiler_params=compat.CompilerParams(
            dimension_semantics=semantics + ("arbitrary",)),
        interpret=interpret,
        name="causal_attention_dkv",
    )(q, k, v, dout, lse, di)
    return dq, dk_, dv_
