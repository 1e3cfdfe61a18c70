"""TSMT Pallas kernel: C[a,b] = X[m,a]^T @ Y[m,b] with m >> a, b.

Beyond-paper extension: the transposed tall-and-skinny case ("TSMTTSM",
Ernst et al. [38]) which the paper explicitly leaves uncovered. The
framework needs it for:

* PowerSGD's second projection  Q = G^T P  (G: huge gradient matrix,
  P: m x r with r in {2..16});
* ABFT checksum *verification*  s = G^T e  against the encoded checksum.

Shape character: the reduction axis is the huge one (m), both output dims
are small. The TPU formulation:

* Grid ``(a/ba, m/bm)`` with the m axis innermost-sequential
  (``dimension_semantics=("parallel", "arbitrary")``): a (ba x b) f32
  accumulator in VMEM survives the m sweep; X and Y windows stream through
  double-buffered VMEM exactly once per a-block.
* The second output dim (b) must be small (<= ~512): it stays unblocked so
  the accumulator is a single VMEM tile. Callers orient their operands so
  the large output dim is first (ops.tsmt handles this; it raises a clear
  ValueError past the limit instead of compiling a huge accumulator).

Split reduction (``tsmt_pallas_split``): with PowerSGD/ABFT shapes
(a, b <= 16) the parallel grid dim collapses to ``a/ba == 1`` cell, so one
core sweeps the entire m reduction while the rest of the chip idles. The
split variant cuts the m sweep into S independent slices -- grid
``(S, a/ba, m/(S*bm))`` with ``dimension_semantics=("parallel",
"parallel", "arbitrary")`` -- each accumulating its own f32 partial into an
``(S, a, b)`` stack; ``repro.kernels.reduce.reduce_partials`` sums the
stack. This is the TSM paper's leap-based global-reduce, discretized:
occupancy x S for one extra (tiny) partials round trip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat


def _tsmt_kernel(x_ref, y_ref, o_ref, acc_ref):
    """One grid cell: acc[ba, b] += X[bm, ba]^T @ Y[bm, b]."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_a", "interpret",
                                             "vmem_limit_bytes"))
def tsmt_pallas(x: jnp.ndarray, y: jnp.ndarray, *, block_m: int, block_a: int,
                interpret: bool,
                vmem_limit_bytes: int) -> jnp.ndarray:
    """Raw pallas_call; requires m % block_m == 0 and a % block_a == 0.

    ``interpret`` runs the body in Python (``compat.auto_interpret``
    resolves it once, in ``kernels/ops``); ``vmem_limit_bytes`` is the
    scoped-VMEM limit handed to Mosaic, the same budget the block chooser
    sized the windows against (``analysis.contracts.vmem_limit_bytes``). Use
    ``repro.kernels.ops.tsmt`` for the padded/dispatched public entry;
    under a multi-chip mesh the ``shard_map`` executor in
    ``repro.core.tsmm`` runs that entry per shard and psums the partials.
    """
    m, a = x.shape
    m2, b = y.shape
    assert m == m2, (x.shape, y.shape)
    assert m % block_m == 0 and a % block_a == 0, (m, a, block_m, block_a)
    grid = (a // block_a, m // block_m)

    return compat.pallas_call(
        _tsmt_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_a), lambda i, j: (j, i)),
            pl.BlockSpec((block_m, b), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_a, b), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((a, b), x.dtype),
        scratch_shapes=[compat.VMEM((block_a, b), jnp.float32)],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(x, y)


def _tsmt_split_kernel(x_ref, y_ref, o_ref):
    """One grid cell of reduction slice s: O[s][ba, b] += X^T Y over the
    slice's m blocks. The output block is f32 and invariant in the inner
    sequential axis, so it stays VMEM-resident across the slice's sweep --
    no scratch accumulator needed."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[None]


@functools.partial(jax.jit, static_argnames=("block_m", "block_a", "splits",
                                             "interpret",
                                             "vmem_limit_bytes"))
def tsmt_pallas_split(x: jnp.ndarray, y: jnp.ndarray, *, block_m: int,
                      block_a: int, splits: int,
                      interpret: bool,
                      vmem_limit_bytes: int) -> jnp.ndarray:
    """Split-reduction TSMT: returns the ``(splits, a, b)`` f32 partials.

    Requires ``m % (splits * block_m) == 0`` and ``a % block_a == 0``
    (``ops.tsmt`` pads). Grid ``(splits, a/ba, m/(S*bm))``: the first two
    dims are parallel (slices are independent), the third sweeps one
    slice's m blocks sequentially. Callers sum the leading axis
    (``repro.kernels.reduce.reduce_partials``).
    """
    m, a = x.shape
    m2, b = y.shape
    assert m == m2, (x.shape, y.shape)
    assert m % (splits * block_m) == 0 and a % block_a == 0, \
        (m, a, block_m, block_a, splits)
    steps = m // (splits * block_m)   # m blocks per reduction slice
    grid = (splits, a // block_a, steps)

    return compat.pallas_call(
        _tsmt_split_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_a),
                         lambda s, i, j: (s * steps + j, i)),
            pl.BlockSpec((block_m, b), lambda s, i, j: (s * steps + j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_a, b), lambda s, i, j: (s, i, 0)),
        out_shape=jax.ShapeDtypeStruct((splits, a, b), jnp.float32),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(x, y)
