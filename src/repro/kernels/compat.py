"""The repo's one seam onto JAX/Pallas spellings (written for JAX 0.9.0).

Kernel/model/test code imports these names from here instead of spelling
the JAX API at each call site, so a future JAX move is one edit:

* ``CompilerParams`` / ``VMEM`` -- the Mosaic compiler-params class and the
  VMEM scratch memory space (``pltpu.CompilerParams`` / ``pltpu.VMEM``).
* ``abstract_mesh(axis_sizes, axis_names)`` and ``make_mesh(shape,
  axis_names)`` -- meshes with explicit ``AxisType.Auto`` axes.
* ``get_context_mesh()`` -- the mesh set by ``jax.set_mesh(mesh)``, read as
  ``jax.sharding.get_abstract_mesh()`` (valid both eagerly and inside
  ``jit``); None outside one, and None inside a ``shard_map`` body, whose
  axes are manual (per-shard code dispatches on its local shapes).
* ``mesh_axis_sizes(mesh)`` -- ``{axis_name: size}`` for a Mesh or
  AbstractMesh.
* ``shard_map(...)`` -- ``jax.shard_map`` with ``check_vma=False`` (the
  psum-producing dispatch bodies are not replication-typed).
* ``psum_scatter(x, axis)`` / ``all_gather(x, axis)`` -- the collective pair
  the sharded-output ``tsmm_t`` path is built on, pinned to the tiled
  convention.
* ``auto_interpret(requested)`` -- the ONE place the Pallas interpret mode
  is chosen: an explicit True/False wins (tests set True to run kernel
  bodies in Python on the CPU); None means interpret exactly when the
  default backend is not a TPU. On a TPU only an explicit setting selects
  interpret.
* ``pallas_call(...)`` / ``capture_launches()`` -- the launch-recording
  shim. Every in-repo kernel routes its ``pl.pallas_call`` through
  :func:`pallas_call`, which is a zero-overhead pass-through outside a
  :func:`capture_launches` scope and otherwise records a
  :class:`LaunchCapture` (grid, BlockSpec block shapes + index-map
  callables, dimension_semantics, operand/out/scratch avals, the kernel
  fn, the interpret flag) per invocation. ``repro.analysis.kernel_verify``
  drives the kernel entry points under ``jax.eval_shape`` inside such a
  scope to verify the grid dataflow statically -- no device, no compile.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "CompilerParams",
    "VMEM",
    "abstract_mesh",
    "make_mesh",
    "get_context_mesh",
    "mesh_axis_sizes",
    "shard_map",
    "psum_scatter",
    "all_gather",
    "auto_interpret",
    "BlockSpecCapture",
    "LaunchCapture",
    "capture_launches",
    "pallas_call",
]

CompilerParams = pltpu.CompilerParams
VMEM = pltpu.VMEM


def auto_interpret(requested: bool | None = None) -> bool:
    """Pallas interpret mode: ``requested`` when given, else interpret
    exactly when the default backend is not a TPU."""
    if requested is not None:
        return bool(requested)
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Meshes
# ---------------------------------------------------------------------------

def abstract_mesh(axis_sizes: tuple[int, ...], axis_names: tuple[str, ...]):
    """``AbstractMesh((16, 16), ("data", "model"))`` with Auto axes."""
    from jax.sharding import AbstractMesh, AxisType
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names),
                        axis_types=(AxisType.Auto,) * len(axis_names))


def make_mesh(shape: tuple[int, ...], axis_names: tuple[str, ...],
              devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (GSPMD-style),
    over ``devices`` (default: all of them)."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names), devices=devices)


def get_context_mesh():
    """The ``jax.set_mesh`` context mesh (an AbstractMesh), or None outside
    one and inside a ``shard_map`` body (manual axes)."""
    m = jax.sharding.get_abstract_mesh()
    if m.empty or m.manual_axes:
        return None
    return m


def mesh_axis_sizes(mesh) -> dict:
    """``{axis_name: size}`` for a Mesh or AbstractMesh."""
    return dict(mesh.shape)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication (vma) checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Collectives for sharded-output tsmm_t (psum_scatter / all_gather)
# ---------------------------------------------------------------------------

def psum_scatter(x, axis_name, *, scatter_dimension: int = 0):
    """Tiled reduce-scatter over ``axis_name`` (a name or tuple of names):
    the *global* result equals ``lax.psum(x, axis)`` with each shard keeping
    only its ``scatter_dimension`` slab. Requires
    ``x.shape[scatter_dimension]`` divisible by the axis size (callers
    check; the tsmm dispatcher falls back to dense when it doesn't)."""
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=True)


def all_gather(x, axis_name, *, axis: int = 0):
    """Tiled all-gather over ``axis_name``: shards concatenate along
    ``axis`` (the inverse of :func:`psum_scatter` on the same axis)."""
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


# ---------------------------------------------------------------------------
# Launch-recording pallas_call shim (repro.analysis.kernel_verify)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockSpecCapture:
    """One ``pl.BlockSpec`` as captured at launch-construction time.

    ``block_shape`` entries may be None (pallas' "whole dim" spelling);
    ``index_map`` is the raw Python callable, evaluable with plain ints.
    """
    block_shape: tuple
    index_map: object


@dataclasses.dataclass(frozen=True)
class LaunchCapture:
    """Everything the dataflow verifier needs about one ``pallas_call``.

    Captured when the launch is *constructed* inside a
    :func:`capture_launches` scope -- i.e. at trace time, before any
    compile -- so ``jax.eval_shape`` over a kernel entry point is enough
    to populate it. ``operands``/``out_shapes``/``scratch_shapes`` are
    ``jax.ShapeDtypeStruct``-like (``.shape``/``.dtype``); ``kernel`` is
    the Python kernel function (for AST guard inspection).
    """
    name: str
    kernel: object
    grid: tuple
    in_specs: tuple          # of BlockSpecCapture
    out_specs: tuple         # of BlockSpecCapture
    operands: tuple          # abstract values of the call's array args
    out_shapes: tuple        # ShapeDtypeStructs
    scratch_shapes: tuple    # ShapeDtypeStructs (dtype normalized)
    dimension_semantics: tuple | None
    interpret: bool


_CAPTURE_STACK: list[list] = []


@contextlib.contextmanager
def capture_launches():
    """Collect a ``LaunchCapture`` per :func:`pallas_call` in scope.

    Scopes nest; each capture lands only in the innermost collector.
    Trace-time only -- typical use wraps a ``jax.eval_shape`` of an
    (unjitted) kernel entry point.
    """
    log: list[LaunchCapture] = []
    _CAPTURE_STACK.append(log)
    try:
        yield log
    finally:
        _CAPTURE_STACK.pop()


def _capture_spec(spec) -> BlockSpecCapture:
    return BlockSpecCapture(
        block_shape=tuple(getattr(spec, "block_shape", ()) or ()),
        index_map=getattr(spec, "index_map", None),
    )


def _capture_sds(x):
    """Normalize anything shaped (MemoryRef, ShapeDtypeStruct, aval) to a
    plain ShapeDtypeStruct. Scratch MemoryRefs carry ``dtype`` as a scalar
    *class* (e.g. ``jnp.float32``) on some versions -- ``jnp.dtype``
    canonicalizes both spellings."""
    return jax.ShapeDtypeStruct(tuple(x.shape), jnp.dtype(x.dtype))


def pallas_call(kernel, *, grid, in_specs, out_specs, out_shape,
                scratch_shapes=(), compiler_params=None, interpret=False,
                **kwargs):
    """``pl.pallas_call`` pass-through that records the launch spec.

    Outside a :func:`capture_launches` scope this adds one truthiness
    check per trace. Inside one, the returned callable logs a
    :class:`LaunchCapture` each time it is invoked (so the recorded
    operand avals are the ones actually passed). The keyword-only
    signature pins the subset of the ``pallas_call`` surface the repo's
    kernels use; new kwargs flow through ``**kwargs`` untouched.
    """
    inner = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch_shapes,
        compiler_params=compiler_params, interpret=interpret, **kwargs)
    if not _CAPTURE_STACK:
        return inner

    out_specs_t = out_specs if isinstance(out_specs, (tuple, list)) \
        else (out_specs,)
    out_shape_t = out_shape if isinstance(out_shape, (tuple, list)) \
        else (out_shape,)
    semantics = getattr(compiler_params, "dimension_semantics", None)

    def recorded(*operands):
        _CAPTURE_STACK[-1].append(LaunchCapture(
            name=getattr(kernel, "__name__", repr(kernel)),
            kernel=kernel,
            grid=tuple(grid),
            in_specs=tuple(_capture_spec(s) for s in in_specs),
            out_specs=tuple(_capture_spec(s) for s in out_specs_t),
            operands=tuple(_capture_sds(x) for x in operands),
            out_shapes=tuple(_capture_sds(s) for s in out_shape_t),
            scratch_shapes=tuple(_capture_sds(s) for s in scratch_shapes),
            dimension_semantics=(tuple(semantics)
                                 if semantics is not None else None),
            interpret=bool(interpret),
        ))
        return inner(*operands)

    return recorded
