"""Int8 quantized TSM2X kernels: int8 tiles, f32 accumulate, dequant epilogue.

The paper's whole argument is that tall-and-skinny GEMM is HBM-bandwidth
bound; int8 operands cut the dominant streamed-bytes term 2-4x on exactly
that regime. This module holds both halves of the low-precision path:

* **Quantization helpers** -- per-row-block symmetric scales
  (``scale = absmax / 127``) for the tall operand, carried as a tiny
  ``(blocks, 1)`` f32 sidecar where ``blocks = m / block_m`` matches the
  kernel's resolved row blocking, plus a single per-tensor scale for the
  small operand. Zero blocks quantize with ``scale = 1`` so dequant is
  exact. ``quantize_param``/``dequantize_weights`` wrap the same scheme as
  an offline weight-compression record for ``serve/engine`` (arrays-only
  dict, so records pass through ``jax.jit`` pytrees).
* **Quantized kernel variants** of tsm2r/tsm2l/tsmt (plus split-reduction
  forms). Tiles are loaded as int8 (1 byte/elem of HBM traffic), the MXU
  contraction accumulates in int32 (exact: ``127*127*block <= 2^31`` for
  every feasible block), and the scales multiply into the f32 accumulator
  epilogue. Scale placement per kind:

  - **tsm2r**: A's scale is per m-block (grid dim ``i``), constant across
    the sequential k sweep, so both scales fold in once at the flush.
  - **tsm2l**: single-shot kernel; scales fold into the one store.
  - **tsmt**: both operands' scales vary along the *reduced* m axis, so
    each accumulate step is dequantized before ``+=`` (still f32
    accumulate, just per-step scaling).

  Split variants emit f32 partials exactly like their unquantized
  siblings, so ``kernels/reduce.py`` and the shard_map collectives are
  unchanged -- dequant happened before the partials left the kernel.

Numerics: symmetric per-block int8 bounds the element error by
``scale / 2 = absmax / 254`` per operand; the dot accumulates ~``sqrt(k)``
of it. ``tests/test_quant.py`` pins the round-trip bound exactly and the
GEMM-vs-f32-oracle error at 5% of the output absmax.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat

QMAX = 127.0


# ---------------------------------------------------------------------------
# Quantization helpers (trace-safe: usable on activations under jit)
# ---------------------------------------------------------------------------


def quantize_blocks(x: jnp.ndarray, block_rows: int):
    """Symmetric int8 quantization per ``block_rows``-row band.

    Returns ``(q, scale)`` with ``q`` int8 of ``x.shape`` and ``scale`` a
    ``(m // block_rows, 1)`` f32 sidecar; ``dequant = q * scale[band]``.
    All-zero bands get ``scale = 1`` so they round-trip exactly.
    """
    m = x.shape[0]
    assert m % block_rows == 0, (m, block_rows)
    blocks = m // block_rows
    g = x.reshape((blocks, block_rows) + x.shape[1:]).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(g), axis=tuple(range(1, g.ndim)))
    scale = jnp.where(absmax > 0.0, absmax / QMAX, 1.0)
    expand = scale.reshape((blocks,) + (1,) * (g.ndim - 1))
    q = jnp.clip(jnp.round(g / expand), -QMAX, QMAX).astype(jnp.int8)
    return q.reshape(x.shape), scale[:, None]


def dequantize_blocks(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.float32):
    """Inverse of ``quantize_blocks``; band size is implied by the shapes."""
    blocks = scale.shape[0]
    block_rows = q.shape[0] // blocks
    g = q.reshape((blocks, block_rows) + q.shape[1:]).astype(jnp.float32)
    out = g * scale.reshape((blocks,) + (1,) * (g.ndim - 1))
    return out.reshape(q.shape).astype(dtype)


def quantize_tensor(x: jnp.ndarray):
    """Per-tensor symmetric int8; scale returned as a ``(1, 1)`` f32 array
    (the shape the kernels' constant-index scale BlockSpec expects)."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf))
    scale = jnp.where(absmax > 0.0, absmax / QMAX, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -QMAX, QMAX).astype(jnp.int8)
    return q, scale.reshape(1, 1)


def fake_quant(x: jnp.ndarray) -> jnp.ndarray:
    """Quantize -> dequantize in ``x.dtype``. This is the honest int8 wire
    format for collectives: raw int8 psum is not sum-safe across ranks with
    different scales, so each rank dequantizes before the reduction and the
    byte saving is accounted where the transfer is priced."""
    q, scale = quantize_tensor(x)
    return (q.astype(jnp.float32) * scale[0, 0]).astype(x.dtype)


# --- offline weight records (serve path) -----------------------------------


def _is_qrec(t) -> bool:
    return isinstance(t, dict) and "q8" in t and "q8_scale" in t


def quantize_param(w: jnp.ndarray, *, block_rows: int = 256):
    """Offline per-tile record for one 2D weight: ``{"q8", "q8_scale"}``.

    Arrays-only so the record is a plain jit-safe pytree; the band size and
    original row count are recoverable from the shapes. Falls back to one
    per-tensor band when ``block_rows`` does not divide the rows.
    """
    m = w.shape[0]
    br = block_rows if block_rows and m % block_rows == 0 else m
    q, scale = quantize_blocks(w, br)
    return {"q8": q, "q8_scale": scale}


def dequantize_param(rec, dtype=jnp.float32) -> jnp.ndarray:
    return dequantize_blocks(rec["q8"], rec["q8_scale"], dtype)


def quantize_weights(params, *, block_rows: int = 256, min_size: int = 4096):
    """Quantize every large 2D floating leaf of a params pytree offline.

    Small/odd leaves (biases, norms, embeddings reshaped elsewhere) pass
    through untouched, so the result drops into the same model code.
    """

    def one(w):
        if (
            not hasattr(w, "ndim")
            or w.ndim != 2
            or w.size < min_size
            or not jnp.issubdtype(jnp.asarray(w).dtype, jnp.floating)
        ):
            return w
        return quantize_param(w, block_rows=block_rows)

    return jax.tree_util.tree_map(one, params)


def dequantize_weights(params, dtype=jnp.float32):
    """Inverse of ``quantize_weights``; non-record leaves pass through."""
    return jax.tree_util.tree_map(
        lambda t: dequantize_param(t, dtype) if _is_qrec(t) else t,
        params,
        is_leaf=_is_qrec,
    )


def has_quantized_weights(params) -> bool:
    found = []
    jax.tree_util.tree_map(
        lambda t: found.append(True) if _is_qrec(t) else None,
        params,
        is_leaf=_is_qrec,
    )
    return bool(found)


def _band_scales(scale: jnp.ndarray) -> jnp.ndarray:
    """``(blocks, 1)`` band sidecar as ``(blocks, 1, 1)`` for the launch:
    each grid step reads a ``(1, 1, 1)`` block, whose two minor dims equal
    the array's -- the only way Mosaic accepts a block this small (a
    ``(1, 1)`` block of a ``(blocks, 1)`` array breaks its 8x128 tiling
    rule)."""
    return scale.reshape(scale.shape[0], 1, 1)


# ---------------------------------------------------------------------------
# Quantized TSM2R: C[m,n] = A @ B, A per-m-block scales, B per-tensor
# ---------------------------------------------------------------------------


def _tsm2r_q8_kernel(a_ref, b_ref, as_ref, bs_ref, o_ref, acc_ref):
    """acc[bm, n] += int32(A8[bm, bk] @ B8[bk, n]); scales fold at flush
    (A's scale is per m-block, constant across the sequential k sweep)."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.int32
    ).astype(jnp.float32)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * (as_ref[0, 0, 0] * bs_ref[0, 0])).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "out_dtype",
        "block_m",
        "block_k",
        "interpret",
        "vmem_limit_bytes",
    ),
)
def tsm2r_q8_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    a_scale: jnp.ndarray,
    b_scale: jnp.ndarray,
    *,
    out_dtype,
    block_m: int,
    block_k: int,
    interpret: bool,
    vmem_limit_bytes: int,
) -> jnp.ndarray:
    """Quantized TSM2R. ``a``/``b`` int8, ``a_scale`` ``(m/bm, 1)`` f32
    (one band per grid row block), ``b_scale`` ``(1, 1)`` f32."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and k % block_k == 0, (m, k, block_m, block_k)
    assert a_scale.shape == (m // block_m, 1), (a_scale.shape, m, block_m)
    assert b_scale.shape == (1, 1), b_scale.shape
    grid = (m // block_m, k // block_k)

    return compat.pallas_call(
        _tsm2r_q8_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j: (i, j)),
            pl.BlockSpec((block_k, n), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, n), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[compat.VMEM((block_m, n), jnp.float32)],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(a, b, _band_scales(a_scale), b_scale)


def _tsm2r_q8_split_kernel(a_ref, b_ref, as_ref, bs_ref, o_ref):
    """Split slice s: f32 partial O[s][bm, n] += dequantized A8 B8. Scales
    fold per step (cheap; the partial leaves the kernel already in real
    units so the reduce tree stays quantization-blind)."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += (
        jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.int32).astype(
            jnp.float32
        )
        * (as_ref[0, 0, 0] * bs_ref[0, 0])
    )[None]


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m",
        "block_k",
        "splits",
        "interpret",
        "vmem_limit_bytes",
    ),
)
def tsm2r_q8_pallas_split(
    a: jnp.ndarray,
    b: jnp.ndarray,
    a_scale: jnp.ndarray,
    b_scale: jnp.ndarray,
    *,
    block_m: int,
    block_k: int,
    splits: int,
    interpret: bool,
    vmem_limit_bytes: int,
) -> jnp.ndarray:
    """Split-reduction quantized TSM2R: ``(splits, m, n)`` f32 partials,
    already dequantized -- sum with ``reduce.reduce_partials`` as usual."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and k % (splits * block_k) == 0, (
        m,
        k,
        block_m,
        block_k,
        splits,
    )
    assert a_scale.shape == (m // block_m, 1), (a_scale.shape, m, block_m)
    assert b_scale.shape == (1, 1), b_scale.shape
    steps = k // (splits * block_k)
    grid = (splits, m // block_m, steps)

    return compat.pallas_call(
        _tsm2r_q8_split_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda s, i, j: (i, s * steps + j)),
            pl.BlockSpec((block_k, n), lambda s, i, j: (s * steps + j, 0)),
            pl.BlockSpec((1, 1, 1), lambda s, i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda s, i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, n), lambda s, i, j: (s, i, 0)),
        out_shape=jax.ShapeDtypeStruct((splits, m, n), jnp.float32),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(a, b, _band_scales(a_scale), b_scale)


# ---------------------------------------------------------------------------
# Quantized TSM2L: C[m,n] = A @ B with k, n tiny; single-shot per m block
# ---------------------------------------------------------------------------


def _tsm2l_q8_kernel(a_ref, b_ref, as_ref, bs_ref, o_ref):
    """O[bm, n] = (int32(A8 @ B8) * sA * sB); B window is constant."""
    o_ref[...] = (
        jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.int32).astype(
            jnp.float32
        )
        * (as_ref[0, 0, 0] * bs_ref[0, 0])
    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "out_dtype",
        "block_m",
        "interpret",
        "vmem_limit_bytes",
    ),
)
def tsm2l_q8_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    a_scale: jnp.ndarray,
    b_scale: jnp.ndarray,
    *,
    out_dtype,
    block_m: int,
    interpret: bool,
    vmem_limit_bytes: int,
) -> jnp.ndarray:
    """Quantized TSM2L. ``a_scale`` ``(m/bm, 1)`` f32, ``b_scale``
    ``(1, 1)`` f32; B stays VMEM-resident exactly as in the f32 kernel."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0, (m, block_m)
    assert a_scale.shape == (m // block_m, 1), (a_scale.shape, m, block_m)
    assert b_scale.shape == (1, 1), b_scale.shape
    grid = (m // block_m,)

    return compat.pallas_call(
        _tsm2l_q8_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(a, b, _band_scales(a_scale), b_scale)


# ---------------------------------------------------------------------------
# Quantized TSMT: C[a,b] = X^T @ Y; both scales vary along the reduced axis
# ---------------------------------------------------------------------------


def _tsmt_q8_kernel(x_ref, y_ref, xs_ref, ys_ref, o_ref, acc_ref):
    """acc[ba, b] += int32(X8^T Y8) * sX[j] * sY[j]: the m-band scales
    change every sequential step, so dequant happens before each ``+=``."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dot = jax.lax.dot_general(
        x_ref[...],
        y_ref[...],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    acc_ref[...] += dot.astype(jnp.float32) * (xs_ref[0, 0, 0] * ys_ref[0, 0, 0])

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "out_dtype",
        "block_m",
        "block_a",
        "interpret",
        "vmem_limit_bytes",
    ),
)
def tsmt_q8_pallas(
    x: jnp.ndarray,
    y: jnp.ndarray,
    x_scale: jnp.ndarray,
    y_scale: jnp.ndarray,
    *,
    out_dtype,
    block_m: int,
    block_a: int,
    interpret: bool,
    vmem_limit_bytes: int,
) -> jnp.ndarray:
    """Quantized TSMT. Both operands are tall, so both carry per-m-band
    ``(m/bm, 1)`` f32 sidecars indexed by the sequential grid dim."""
    m, a = x.shape
    m2, b = y.shape
    assert m == m2, (x.shape, y.shape)
    assert m % block_m == 0 and a % block_a == 0, (m, a, block_m, block_a)
    assert x_scale.shape == (m // block_m, 1), (x_scale.shape, m, block_m)
    assert y_scale.shape == (m // block_m, 1), (y_scale.shape, m, block_m)
    grid = (a // block_a, m // block_m)

    return compat.pallas_call(
        _tsmt_q8_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_a), lambda i, j: (j, i)),
            pl.BlockSpec((block_m, b), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_a, b), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((a, b), out_dtype),
        scratch_shapes=[compat.VMEM((block_a, b), jnp.float32)],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(x, y, _band_scales(x_scale), _band_scales(y_scale))


def _tsmt_q8_split_kernel(x_ref, y_ref, xs_ref, ys_ref, o_ref):
    """Split slice s: f32 partial O[s][ba, b] += dequantized X8^T Y8."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    dot = jax.lax.dot_general(
        x_ref[...],
        y_ref[...],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    o_ref[...] += (dot.astype(jnp.float32) * (xs_ref[0, 0, 0] * ys_ref[0, 0, 0]))[None]


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m",
        "block_a",
        "splits",
        "interpret",
        "vmem_limit_bytes",
    ),
)
def tsmt_q8_pallas_split(
    x: jnp.ndarray,
    y: jnp.ndarray,
    x_scale: jnp.ndarray,
    y_scale: jnp.ndarray,
    *,
    block_m: int,
    block_a: int,
    splits: int,
    interpret: bool,
    vmem_limit_bytes: int,
) -> jnp.ndarray:
    """Split-reduction quantized TSMT: ``(splits, a, b)`` f32 partials,
    dequantized in-kernel so the reduce/psum machinery is unchanged."""
    m, a = x.shape
    m2, b = y.shape
    assert m == m2, (x.shape, y.shape)
    assert m % (splits * block_m) == 0 and a % block_a == 0, (
        m,
        a,
        block_m,
        block_a,
        splits,
    )
    assert x_scale.shape == (m // block_m, 1), (x_scale.shape, m, block_m)
    assert y_scale.shape == (m // block_m, 1), (y_scale.shape, m, block_m)
    steps = m // (splits * block_m)
    grid = (splits, a // block_a, steps)

    return compat.pallas_call(
        _tsmt_q8_split_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_a), lambda s, i, j: (s * steps + j, i)),
            pl.BlockSpec((block_m, b), lambda s, i, j: (s * steps + j, 0)),
            pl.BlockSpec((1, 1, 1), lambda s, i, j: (s * steps + j, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda s, i, j: (s * steps + j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_a, b), lambda s, i, j: (s, i, 0)),
        out_shape=jax.ShapeDtypeStruct((splits, a, b), jnp.float32),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(x, y, _band_scales(x_scale), _band_scales(y_scale))
