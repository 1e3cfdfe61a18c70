"""TSM2R Pallas kernel: C[m,n] = A[m,k] @ B[k,n] with m ~ k >> n.

TPU-native restatement of paper Algorithm 4 (outer product + shared-memory
staging + data prefetch):

* Grid ``(m/bm, k/bk)`` with ``dimension_semantics=("parallel", "arbitrary")``:
  the k axis is the innermost sequential reduction, so Mosaic double-buffers
  the next (bm, bk) A window and (bk, n) B window while the MXU consumes the
  current ones -- exactly the nextA/nextB register prefetch of Algorithm 4,
  done by the pipeliner instead of by hand.
* A f32 accumulator lives in VMEM scratch across the k steps of one m-row of
  the grid (the paper's register-resident C_{1:t2}); it is zeroed on the
  first k step and flushed to the output window on the last. Consequence:
  **A is streamed from HBM exactly once** (Algorithm 2's outer-product
  guarantee).
* B's (bk, n) window is re-fetched once per m-block -- the analogue of the
  paper's ``n/t1`` B-reload factor; with k*n tiny this is noise (it is the
  term the paper also drops, Section 3.1.8 "minor inaccuracy").
* The shared-memory bank-conflict analysis (paper Section 3.1.4) has no TPU
  analogue; the corresponding layout fact is that XLA stores a skinny
  (., n) array at 128 lanes in HBM, so n < 128 windows stream padded.

Block sizes (bm, bk) come from ``repro.core.perf_model.choose_params_tsm2r``,
the discrete Algorithm-5 analogue -- which also picks the split factor S for
``tsm2r_pallas_split``, the split-reduction variant: the k sweep is cut into
S independent parallel slices (grid ``(S, m/bm, k/(S*bk))``,
``dimension_semantics=("parallel", "parallel", "arbitrary")``) emitting an
``(S, m, n)`` stack of f32 partials that
``repro.kernels.reduce.reduce_partials`` sums. Splitting widens the parallel
grid when ``m/bm`` alone cannot occupy a multi-core chip, at the cost of the
partials round trip -- the occupancy term in ``tsm2r_model_time`` prices
exactly that trade.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat


def _tsm2r_kernel(a_ref, b_ref, o_ref, acc_ref):
    """One grid cell: acc[bm, n] += A[bm, bk] @ B[bk, n]."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "interpret",
                                             "vmem_limit_bytes"))
def tsm2r_pallas(a: jnp.ndarray, b: jnp.ndarray, *, block_m: int, block_k: int,
                 interpret: bool,
                 vmem_limit_bytes: int) -> jnp.ndarray:
    """Raw pallas_call; requires m % block_m == 0 and k % block_k == 0.

    ``interpret`` runs the body in Python (``compat.auto_interpret``
    resolves it once, in ``kernels/ops``); ``vmem_limit_bytes`` is the
    scoped-VMEM limit handed to Mosaic, the same budget the block chooser
    sized the windows against (``analysis.contracts.vmem_limit_bytes``). Use
    ``repro.kernels.ops.tsm2r`` for the padded/dispatched public entry;
    under a multi-chip mesh the ``shard_map`` executor in
    ``repro.core.tsmm`` invokes that entry per shard (this call has no
    GSPMD partitioning rule of its own).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and k % block_k == 0, (m, k, block_m, block_k)
    grid = (m // block_m, k // block_k)

    return compat.pallas_call(
        _tsm2r_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j: (i, j)),
            pl.BlockSpec((block_k, n), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, n), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[compat.VMEM((block_m, n), jnp.float32)],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(a, b)


def _tsm2r_split_kernel(a_ref, b_ref, o_ref):
    """One grid cell of reduction slice s: O[s][bm, n] += A B over the
    slice's k blocks. The f32 output block is invariant in the inner
    sequential axis (VMEM-resident across the slice) -- no scratch."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )[None]


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "splits",
                                             "interpret",
                                             "vmem_limit_bytes"))
def tsm2r_pallas_split(a: jnp.ndarray, b: jnp.ndarray, *, block_m: int,
                       block_k: int, splits: int,
                       interpret: bool,
                       vmem_limit_bytes: int) -> jnp.ndarray:
    """Split-reduction TSM2R: returns the ``(splits, m, n)`` f32 partials.

    Requires ``m % block_m == 0`` and ``k % (splits * block_k) == 0``
    (``ops.tsm2r`` pads). Grid ``(splits, m/bm, k/(S*bk))``: slices are
    parallel, each sweeps its own k range sequentially. Callers sum the
    leading axis (``repro.kernels.reduce.reduce_partials``).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and k % (splits * block_k) == 0, \
        (m, k, block_m, block_k, splits)
    steps = k // (splits * block_k)   # k blocks per reduction slice
    grid = (splits, m // block_m, steps)

    return compat.pallas_call(
        _tsm2r_split_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k),
                         lambda s, i, j: (i, s * steps + j)),
            pl.BlockSpec((block_k, n), lambda s, i, j: (s * steps + j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, n), lambda s, i, j: (s, i, 0)),
        out_shape=jax.ShapeDtypeStruct((splits, m, n), jnp.float32),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(a, b)
