"""Tree-reduce epilogue for the split-reduction (split-K) kernels.

The split variants of TSMT/TSM2R emit an ``(S, rows, cols)`` stack of f32
partial products (one slab per reduction slice). This module owns the sum
over the leading axis:

* small stacks (a few MB -- every PowerSGD/ABFT shape) go through a plain
  ``jnp.sum``: XLA fuses the (S, a, b) reduction into the consumer and a
  custom kernel would only add a dispatch;
* large stacks (split TSM2R outputs: (S, m, n) with m huge) go through a
  tiny Pallas kernel gridded over the row axis, so the partials stream
  through VMEM once instead of materializing an XLA reduce tree.

Both paths accumulate in f32 and cast once at the end -- the split kernels
already accumulate their own slice in f32, so split-K results are
bitwise-stable against the split factor up to the final reassociation
(pinned vs the sequential kernels in tests/test_split.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat

# Below this many f32 partial elements the jnp.sum path wins (no second
# kernel dispatch; XLA fuses). 1 MiB of partials ~ every skinny-output
# (tsmt) case; split tsm2r stacks at paper shapes are tens of MB.
JNP_REDUCE_MAX_ELEMS = 1 << 18


def _sum_lead_kernel(x_ref, o_ref):
    """One grid cell: O[br, cols] = sum_S X[S, br, cols] (f32 accumulate)."""
    o_ref[...] = jnp.sum(
        x_ref[...].astype(jnp.float32), axis=0
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_r", "out_dtype",
                                             "interpret",
                                             "vmem_limit_bytes"))
def sum_partials_pallas(p: jnp.ndarray, *, block_r: int, out_dtype,
                        interpret: bool,
                        vmem_limit_bytes: int) -> jnp.ndarray:
    """Pallas sum over the leading axis of ``(S, rows, cols)``.

    Requires ``rows % block_r == 0`` (the split kernels' row axis is
    already a block multiple). The whole S stack of one row block is
    resident per cell -- callers size ``block_r`` against VMEM
    (:func:`reduce_partials` does).
    """
    s, rows, cols = p.shape
    assert rows % block_r == 0, (rows, block_r)
    return compat.pallas_call(
        _sum_lead_kernel,
        grid=(rows // block_r,),
        in_specs=[pl.BlockSpec((s, block_r, cols), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((block_r, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), out_dtype),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(p)


def epilogue_block_r(s: int, rows: int, cols: int, *, block_r: int,
                     vmem_budget: int) -> int | None:
    """Row block the Pallas epilogue would launch with, or None.

    The pure half of :func:`reduce_partials`: None means the fused
    ``jnp.sum`` path runs (single slice, small stack, or no VMEM-feasible
    row block that divides ``rows``). A returned value is the resolved
    ``block_r`` -- the (S, rows, cols) sweep in ``repro.analysis.audit``
    and the launch metadata on ``DispatchEvent`` both derive the epilogue
    grid ``(rows // block_r,)`` from it.
    """
    if s == 1 or s * rows * cols <= JNP_REDUCE_MAX_ELEMS:
        return None
    block_r = min(block_r, rows)
    # Double-buffered in stack + out block, f32; lane-padded cols
    # approximates the tile.
    cols_pad = ((cols + 127) // 128) * 128

    def cell_bytes(br):
        return 2 * (s + 1) * br * cols_pad * 4

    while cell_bytes(block_r) > vmem_budget and block_r % 2 == 0 and block_r > 8:
        block_r //= 2
    if rows % block_r != 0:  # defensive: fall back to the fused XLA sum
        return None
    return block_r


def reduce_partials(p: jnp.ndarray, out_dtype, *, block_r: int,
                    vmem_budget: int, interpret: bool) -> jnp.ndarray:
    """Sum the ``(S, rows, cols)`` partials stack to ``(rows, cols)``.

    ``block_r`` is the emitting kernel's row block (it divides rows by
    construction); :func:`epilogue_block_r` halves it while the per-cell
    stack would overrun ``vmem_budget`` bytes, or returns None to take the
    fused ``jnp.sum`` path (small stacks, or no feasible block). The same
    ``vmem_budget`` is the kernel's scoped-VMEM limit.
    """
    s, rows, cols = p.shape
    if s == 1:
        return p[0].astype(out_dtype)
    br = epilogue_block_r(s, rows, cols, block_r=block_r,
                          vmem_budget=vmem_budget)
    if br is None:
        return jnp.sum(p.astype(jnp.float32), axis=0).astype(out_dtype)
    return sum_partials_pallas(p, block_r=br, out_dtype=out_dtype,
                               interpret=interpret,
                               vmem_limit_bytes=vmem_budget)
