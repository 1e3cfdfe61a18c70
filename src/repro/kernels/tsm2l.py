"""TSM2L Pallas kernel: C[m,n] = A[m,k] @ B[k,n] with m >> k ~ n (both tiny).

TPU-native restatement of paper Section 3.2 (Algorithms 6/7):

The GPU problem: with k tiny, each thread's reduction is too shallow to hide
latency -> latency-bound; the fix is launching fewer, fatter threads (tcf).
The TPU analogue: with k tiny there is no reduction grid axis at all -- the
whole B (k x n, at most a few KB) is pinned in VMEM for the kernel's
lifetime, and the grid runs over m only. The tcf trade becomes the choice of
``block_m`` (rows per grid cell):

* block_m too small  -> many shallow grid steps; per-step fixed cost
  dominates (the latency-bound failure mode of the naive port, Fig. 4).
* block_m too large  -> too few steps for the pipeliner to overlap the next
  A-window DMA with current compute (and VMEM pressure).

``choose_params_tsm2l`` picks block_m from the same modeled-time argmin the
paper derives tcf from (Fig. 5's sweep is reproduced in
``benchmarks/bench_tsm2l.py``).

Opt1 vs Opt2 (sequential vs interleaved tiles): Mosaic's grid pipelining
*is* the interleaved schedule (Opt2) -- compute on tile i overlaps the DMA
of tile i+1, and there is no C re-load because the accumulator never leaves
the grid cell. Opt1 (sequential, C re-staged per tile) only exists on GPUs
because registers are per-thread; it would be strictly worse here and is
represented in benchmarks by disabling pipelining (grid=1 chunks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat


def _tsm2l_kernel(a_ref, b_ref, o_ref):
    """One grid cell: O[bm, n] = A[bm, k] @ B[k, n]; B window is constant."""
    o_ref[...] = jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret",
                                             "vmem_limit_bytes"))
def tsm2l_pallas(a: jnp.ndarray, b: jnp.ndarray, *, block_m: int,
                 interpret: bool,
                 vmem_limit_bytes: int) -> jnp.ndarray:
    """Raw pallas_call; requires m % block_m == 0.

    ``interpret`` runs the body in Python (``compat.auto_interpret``
    resolves it once, in ``kernels/ops``); ``vmem_limit_bytes`` is the
    scoped-VMEM limit handed to Mosaic, the same budget the block chooser
    sized the windows against (``analysis.contracts.vmem_limit_bytes``). Use
    ``repro.kernels.ops.tsm2l`` for the padded/dispatched public entry;
    the ``shard_map`` executor in ``repro.core.tsmm`` handles multi-chip
    meshes by invoking that entry per shard.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0, (m, block_m)
    grid = (m // block_m,)

    return compat.pallas_call(
        _tsm2l_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            # index_map is constant: B is fetched once and stays resident.
            pl.BlockSpec((k, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(a, b)
