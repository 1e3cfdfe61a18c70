"""Grid-dataflow verifier for the Pallas kernel layer.

``analysis.contracts`` proves the *numbers* of a launch configuration
(VMEM budgets, quantization, divisibility). This module proves the
*dataflow*: that the index maps, ``dimension_semantics`` and ``pl.when``
guard structure of every committed kernel actually implement the
race-free, initialized, f32-accumulated schedule the paper's algorithms
assume. A swapped index-map lambda, a dropped init guard, or a
``parallel`` tag on a reduction dim all pass the config auditor clean and
all corrupt results on real TPU while interpret-mode tests (which
serialize the grid) stay green -- this is the layer that catches them
statically.

How it works
------------

1. **Capture.** Kernel entry points route their ``pl.pallas_call``
   through ``kernels.compat.pallas_call``; :func:`capture_kernel` invokes
   an entry under ``jax.eval_shape`` inside ``compat.capture_launches``,
   so each launch's grid, BlockSpec block shapes + index-map callables,
   ``dimension_semantics``, operand/out avals, and scratch
   ShapeDtypeStructs are recorded without touching a device. The jit
   wrapper is bypassed (``__wrapped__``) so the capture cannot be
   swallowed by a warm jit cache.
2. **Cell enumeration.** Index maps are plain Python callables on int
   grid coordinates, so they are evaluated directly: exhaustively up to
   :data:`EXHAUSTIVE_CELL_LIMIT` grid cells, corner-sampled above it
   (first/second/middle/last-two coordinates per dim -- the values where
   ``s * steps + j``-style arithmetic drifts first). Sampled runs are
   flagged in the audit report (``sampled``): a clean sampled result is
   evidence, not proof.
3. **Invariant families** (one stable rule id each):

   ====================  ==================================================
   ``write-race``        two cells with different ``parallel`` coordinates
                         map an output to the same block
   ``revisit-init`` /    an output/scratch block revisited along
   ``revisit-flush``     ``arbitrary`` dims must be zero-initialized under
                         ``pl.when(program_id(d) == 0)`` (accumulators)
                         and flushed under ``pl.when(program_id(d) ==
                         num_programs(d) - 1)`` (scratch-staged outputs) --
                         detected by AST inspection of the kernel fn
   ``index-bounds``      block_index x block_shape must lie inside the
                         padded operand dims for every cell
   ``accumulator-dtype`` scratch/partial accumulators are f32 regardless
                         of operand dtype
   ====================  ==================================================

   Supporting rules: ``semantics-invalid``, ``index-map-error``,
   ``index-map-arity``, ``kernel-arity``, ``guard-unverifiable``,
   ``capture-empty``, ``capture-count``, and ``launch-meta-drift`` (the
   captured grid/semantics must equal the pure
   ``contracts.launch_grid`` derivation that ``kernels/ops.py`` stamps
   onto ``DispatchEvent.launches``).

``analysis/audit.py`` sweeps :func:`verify_kernel_config` over the same
resolved-config space as the existing sections (all five kernels plus the
``kernels/reduce.py`` epilogue) as the ``kernel-dataflow`` report section,
enforced under ``--strict`` in CI.
"""

from __future__ import annotations

import ast
import functools
import inspect
import itertools
import math
import operator
import textwrap

import jax
import jax.numpy as jnp

from repro.analysis import contracts
from repro.analysis.contracts import Violation
from repro.kernels import compat

__all__ = [
    "EXHAUSTIVE_CELL_LIMIT",
    "sample_cells",
    "capture_kernel",
    "verify_capture",
    "verify_kernel_config",
]

# Above this many grid cells the index-map evaluation corner-samples
# instead of enumerating. Committed kernels' grids are products of
# dim/block quotients -- a few thousand cells at the paper shapes -- so
# the exhaustive path is the common one.
EXHAUSTIVE_CELL_LIMIT = 4096


def sample_cells(grid) -> tuple[list[tuple[int, ...]], bool]:
    """Grid cells to evaluate: ``(cells, exhaustive)``.

    Exhaustive product under :data:`EXHAUSTIVE_CELL_LIMIT`; otherwise the
    per-dim corner set {0, 1, mid, last-1, last} (<= 5^ndim cells) --
    enough to catch offset/stride drift in affine index maps, documented
    as a sample (not a proof) in the audit report.
    """
    total = math.prod(grid)
    if total <= EXHAUSTIVE_CELL_LIMIT:
        return list(itertools.product(*[range(g) for g in grid])), True
    axes = []
    for g in grid:
        axes.append(sorted({v for v in (0, 1, g // 2, g - 2, g - 1)
                            if 0 <= v < g}))
    return list(itertools.product(*axes)), False


# ---------------------------------------------------------------------------
# Capture: abstract invocation of the committed kernel entry points
# ---------------------------------------------------------------------------

def _unjit(fn):
    """The traced function under a ``jax.jit`` wrapper. Bypassing jit is
    what makes capture reliable: a warm jit cache would skip re-tracing
    (and therefore skip the pallas_call construction being recorded)."""
    return getattr(fn, "__wrapped__", fn)


def capture_kernel(kind, padded_shape, params, dtype, *, quant="none"
                   ) -> list[compat.LaunchCapture]:
    """Launch captures of the committed ``kind`` entry at ``padded_shape``.

    ``padded_shape`` follows the ``check_grid`` convention -- the operand
    shape after ``ops``' zero-padding (``audit._padded_shape``), or the
    ``(splits, rows, cols)`` partials stack for ``kind="reduce"`` -- so
    the abstract invocation is exactly the launch dispatch performs.

    ``quant="int8"`` captures the quantized entry instead: int8 operand
    avals plus the f32 scale sidecars (``(m//block_m, 1)`` per-row-block
    for streamed operands, ``(1, 1)`` per-tensor for the resident B of
    tsm2r/tsm2l), with ``dtype`` becoming the kernel's ``out_dtype``.
    ``kind="reduce"`` has no quantized variant (split partials are f32
    either way).
    """
    from repro.core import perf_model
    from repro.kernels import quant as kquant
    from repro.kernels import reduce as kreduce
    from repro.kernels import tsm2l, tsm2r, tsmt

    # Launch-only arguments: nothing is compiled here, so the default
    # spec's limit stands in for whichever spec resolved ``params``.
    launch = {"interpret": True,
              "vmem_limit_bytes": contracts.vmem_limit_bytes(perf_model.V5E)}
    p = dict(params)
    s = p.get("splits", 1)
    dtype = jnp.dtype(dtype)
    q8 = quant == "int8"
    if q8 and kind == "reduce":
        raise ValueError("kind='reduce' has no quantized variant")
    f32 = jnp.float32
    if kind == "tsm2r":
        m, k, n = padded_shape
        if q8:
            args = (jax.ShapeDtypeStruct((m, k), jnp.int8),
                    jax.ShapeDtypeStruct((k, n), jnp.int8),
                    jax.ShapeDtypeStruct((m // p["block_m"], 1), f32),
                    jax.ShapeDtypeStruct((1, 1), f32))
            if s == 1:
                fn = functools.partial(_unjit(kquant.tsm2r_q8_pallas),
                                       out_dtype=dtype,
                                       block_m=p["block_m"],
                                       block_k=p["block_k"], **launch)
            else:
                # Split partials are f32 regardless of caller dtype.
                fn = functools.partial(_unjit(kquant.tsm2r_q8_pallas_split),
                                       block_m=p["block_m"],
                                       block_k=p["block_k"], splits=s,
                                       **launch)
        else:
            args = (jax.ShapeDtypeStruct((m, k), dtype),
                    jax.ShapeDtypeStruct((k, n), dtype))
            if s == 1:
                fn = functools.partial(_unjit(tsm2r.tsm2r_pallas),
                                       block_m=p["block_m"],
                                       block_k=p["block_k"], **launch)
            else:
                fn = functools.partial(_unjit(tsm2r.tsm2r_pallas_split),
                                       block_m=p["block_m"],
                                       block_k=p["block_k"], splits=s,
                                       **launch)
    elif kind == "tsm2l":
        m, k, n = padded_shape
        if q8:
            args = (jax.ShapeDtypeStruct((m, k), jnp.int8),
                    jax.ShapeDtypeStruct((k, n), jnp.int8),
                    jax.ShapeDtypeStruct((m // p["block_m"], 1), f32),
                    jax.ShapeDtypeStruct((1, 1), f32))
            fn = functools.partial(_unjit(kquant.tsm2l_q8_pallas),
                                   out_dtype=dtype, block_m=p["block_m"],
                                   **launch)
        else:
            args = (jax.ShapeDtypeStruct((m, k), dtype),
                    jax.ShapeDtypeStruct((k, n), dtype))
            fn = functools.partial(_unjit(tsm2l.tsm2l_pallas),
                                   block_m=p["block_m"], **launch)
    elif kind == "tsmt":
        m, a, b = padded_shape
        if q8:
            args = (jax.ShapeDtypeStruct((m, a), jnp.int8),
                    jax.ShapeDtypeStruct((m, b), jnp.int8),
                    jax.ShapeDtypeStruct((m // p["block_m"], 1), f32),
                    jax.ShapeDtypeStruct((m // p["block_m"], 1), f32))
            if s == 1:
                fn = functools.partial(_unjit(kquant.tsmt_q8_pallas),
                                       out_dtype=dtype,
                                       block_m=p["block_m"],
                                       block_a=p["block_a"], **launch)
            else:
                # Split partials are f32 regardless of caller dtype.
                fn = functools.partial(_unjit(kquant.tsmt_q8_pallas_split),
                                       block_m=p["block_m"],
                                       block_a=p["block_a"], splits=s,
                                       **launch)
        else:
            args = (jax.ShapeDtypeStruct((m, a), dtype),
                    jax.ShapeDtypeStruct((m, b), dtype))
            if s == 1:
                fn = functools.partial(_unjit(tsmt.tsmt_pallas),
                                       block_m=p["block_m"],
                                       block_a=p["block_a"], **launch)
            else:
                fn = functools.partial(_unjit(tsmt.tsmt_pallas_split),
                                       block_m=p["block_m"],
                                       block_a=p["block_a"], splits=s,
                                       **launch)
    elif kind == "reduce":
        stack, rows, cols = padded_shape
        args = (jax.ShapeDtypeStruct((stack, rows, cols), jnp.float32),)
        fn = functools.partial(_unjit(kreduce.sum_partials_pallas),
                               block_r=p["block_r"], out_dtype=dtype,
                               **launch)
    else:
        raise ValueError(f"unknown kernel kind {kind!r}")

    with compat.capture_launches() as log:
        jax.eval_shape(fn, *args)
    return list(log)


# ---------------------------------------------------------------------------
# AST guard inspection (pl.when init/flush patterns)
# ---------------------------------------------------------------------------

def _dotted(node) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _grid_fn_dim(node, suffix) -> int | None:
    """Dim argument of a ``pl.program_id(d)`` / ``pl.num_programs(d)``
    call node, else None."""
    if (isinstance(node, ast.Call)
            and _dotted(node.func).split(".")[-1] == suffix
            and len(node.args) == 1
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, int)):
        return node.args[0].value
    return None


def _classify_cond(cond):
    """Guard class of a ``pl.when`` condition: ``("first", d)`` for
    ``program_id(d) == 0``, ``("last", d)`` for
    ``program_id(d) == num_programs(d) - 1``, else ``("other", None)``."""
    if (isinstance(cond, ast.Compare) and len(cond.ops) == 1
            and isinstance(cond.ops[0], ast.Eq)):
        for a, b in ((cond.left, cond.comparators[0]),
                     (cond.comparators[0], cond.left)):
            d = _grid_fn_dim(a, "program_id")
            if d is None:
                continue
            if isinstance(b, ast.Constant) and b.value == 0:
                return ("first", d)
            if (isinstance(b, ast.BinOp) and isinstance(b.op, ast.Sub)
                    and isinstance(b.right, ast.Constant)
                    and b.right.value == 1
                    and _grid_fn_dim(b.left, "num_programs") == d):
                return ("last", d)
    return ("other", None)


def _classify_when(deco):
    """Guard class of a ``@pl.when(cond)`` decorator node, else None."""
    if (isinstance(deco, ast.Call)
            and _dotted(deco.func).split(".")[-1] == "when"
            and len(deco.args) == 1):
        return _classify_cond(deco.args[0])
    return None


def _collect_writes(stmts, guard, writes):
    """Record (kind, guard) per ref-subscript write, descending into
    ``pl.when``-decorated inner defs (which set the guard) and ordinary
    compound statements (which inherit it)."""
    for st in stmts:
        if isinstance(st, (ast.Assign, ast.AugAssign)):
            targets = st.targets if isinstance(st, ast.Assign) else [st.target]
            for t in targets:
                if isinstance(t, ast.Subscript) and isinstance(t.value,
                                                               ast.Name):
                    kind = ("accum" if isinstance(st, ast.AugAssign)
                            else "assign")
                    writes.setdefault(t.value.id, []).append((kind, guard))
        elif isinstance(st, ast.FunctionDef):
            g = guard
            for deco in st.decorator_list:
                cls = _classify_when(deco)
                if cls is not None:
                    g = cls
                    break
            _collect_writes(st.body, g, writes)
        elif isinstance(st, (ast.If, ast.With, ast.For, ast.While)):
            _collect_writes(st.body, guard, writes)
            _collect_writes(st.orelse, guard, writes)


def _guard_summary(kernel_fn) -> dict | None:
    """``{ref_name: [(write_kind, guard), ...]}`` from the kernel source,
    or None when the source is unavailable (lambdas, C extensions)."""
    try:
        src = textwrap.dedent(inspect.getsource(kernel_fn))
        tree = ast.parse(src)
    except (OSError, TypeError, SyntaxError, IndentationError):
        return None
    fdef = next((n for n in tree.body if isinstance(n, ast.FunctionDef)),
                None)
    if fdef is None:
        return None
    writes: dict = {}
    _collect_writes(fdef.body, None, writes)
    return writes


def _param_roles(cap) -> tuple[list, list] | None:
    """``(output_names, scratch_names)`` of the kernel fn's ref params by
    pallas position convention (inputs, outputs, scratch), or None when
    the signature is unreadable."""
    try:
        names = list(inspect.signature(cap.kernel).parameters)
    except (TypeError, ValueError):
        return None
    n_in, n_out = len(cap.in_specs), len(cap.out_specs)
    if len(names) != n_in + n_out + len(cap.scratch_shapes):
        return None
    return names[n_in:n_in + n_out], names[n_in + n_out:]


# ---------------------------------------------------------------------------
# The verifier
# ---------------------------------------------------------------------------

def _eval_maps(cap, cells, sub):
    """Evaluate every BlockSpec's index map over ``cells``.

    Returns ``(violations, out_maps)``; ``out_maps`` is a list of
    ``(out_index, {cell: block_index})`` for the output specs that
    evaluated clean (bounds violations are reported once per spec, at the
    first offending cell).
    """
    out: list[Violation] = []
    out_maps = []
    specs = ([(f"in[{i}]", s, op.shape) for i, (s, op)
              in enumerate(zip(cap.in_specs, cap.operands))]
             + [(f"out[{i}]", s, o.shape) for i, (s, o)
                in enumerate(zip(cap.out_specs, cap.out_shapes))])
    for label, spec, oshape in specs:
        mapping: dict = {}
        clean = True
        for cell in cells:
            try:
                idx = spec.index_map(*cell)
            except Exception as e:  # noqa: BLE001 - report, don't crash
                out.append(Violation(
                    "index-map-error", sub,
                    f"{label} index map raised at cell {cell}: {e!r}"))
                clean = False
                break
            if not isinstance(idx, tuple):
                idx = (idx,)
            try:
                idx = tuple(operator.index(v) for v in idx)
            except TypeError:
                out.append(Violation(
                    "index-map-error", sub,
                    f"{label} index map returned non-integer block index "
                    f"{idx!r} at cell {cell}"))
                clean = False
                break
            block = tuple(spec.block_shape)
            if len(idx) != len(block) or len(block) != len(oshape):
                out.append(Violation(
                    "index-map-arity", sub,
                    f"{label}: block index {idx} / block shape {block} / "
                    f"operand rank {len(oshape)} disagree"))
                clean = False
                break
            oob = False
            for a, bi in enumerate(idx):
                bs = block[a] if block[a] is not None else oshape[a]
                if bi < 0 or (bi + 1) * bs > oshape[a]:
                    out.append(Violation(
                        "index-bounds", sub,
                        f"{label} cell {cell}: block {idx} x shape {block} "
                        f"reaches outside operand dims {tuple(oshape)} "
                        f"(axis {a})"))
                    oob = True
                    break
            if oob:
                clean = False
                break
            mapping[cell] = idx
        if label.startswith("out") and clean:
            out_maps.append((int(label[4:-1]), mapping))
    return out, out_maps


def verify_capture(cap, *, subject: str | None = None) -> list[Violation]:
    """All dataflow violations of one captured launch (empty == clean)."""
    sub = subject or cap.name
    out: list[Violation] = []
    grid = tuple(int(g) for g in cap.grid)
    ndim = len(grid)
    sem = cap.dimension_semantics
    if sem is None:
        # Undeclared semantics serialize the whole grid (safe); the RA006
        # lint rule separately requires committed kernels to declare.
        sem = ("arbitrary",) * ndim
    if len(sem) != ndim or any(x not in ("parallel", "arbitrary")
                               for x in sem):
        return [Violation(
            "semantics-invalid", sub,
            f"dimension_semantics {sem} does not label grid {grid} "
            "(one 'parallel'/'arbitrary' per dim)")]

    # accumulator dtype: scratch is f32, always
    for i, sds in enumerate(cap.scratch_shapes):
        if jnp.dtype(sds.dtype) != jnp.float32:
            out.append(Violation(
                "accumulator-dtype", sub,
                f"scratch[{i}] accumulates in "
                f"{jnp.dtype(sds.dtype).name}; partial accumulators must "
                "be float32 regardless of operand dtype"))

    cells, _ = sample_cells(grid)
    map_vios, out_maps = _eval_maps(cap, cells, sub)
    out.extend(map_vios)

    par_dims = [d for d in range(ndim) if sem[d] == "parallel"]
    roles = _param_roles(cap)
    summary = _guard_summary(cap.kernel)

    for i_out, mapping in out_maps:
        groups: dict = {}
        for cell, idx in mapping.items():
            groups.setdefault(idx, []).append(cell)
        raced = False
        revisit: set[int] = set()
        for idx, cs in groups.items():
            if len(cs) < 2:
                continue
            projs: dict = {}
            for c in cs:
                projs.setdefault(tuple(c[d] for d in par_dims),
                                 c)
            if len(projs) > 1 and not raced:
                raced = True
                c1, c2 = list(projs.values())[:2]
                out.append(Violation(
                    "write-race", sub,
                    f"out[{i_out}]: cells {c1} and {c2} differ in parallel "
                    f"dims {par_dims} but both write block {idx} -- "
                    "concurrent grid cells race on the output"))
            for d in range(ndim):
                if len({c[d] for c in cs}) > 1:
                    revisit.add(d)
        if raced or not revisit:
            continue

        # Revisits happen only along arbitrary dims here (no race), so the
        # kernel body must carry the init/flush guard discipline.
        if roles is None:
            out.append(Violation(
                "kernel-arity", sub,
                f"kernel fn params do not match "
                f"{len(cap.in_specs)} in + {len(cap.out_specs)} out + "
                f"{len(cap.scratch_shapes)} scratch refs"))
            continue
        out_names, scratch_names = roles
        if summary is None:
            out.append(Violation(
                "guard-unverifiable", sub,
                f"out[{i_out}] is revisited along dims {sorted(revisit)} "
                "but the kernel source is unavailable for pl.when guard "
                "inspection"))
            continue
        ref = out_names[i_out]
        writes = summary.get(ref, [])
        accum_guards = [g for k, g in writes if k == "accum"]
        assign_guards = [g for k, g in writes if k == "assign"]

        if accum_guards:
            # Direct accumulation (split kernels): the output block must be
            # zero-initialized on the first step of each revisit dim, and
            # accumulate in f32.
            for d in sorted(revisit):
                if ("first", d) not in assign_guards:
                    out.append(Violation(
                        "revisit-init", sub,
                        f"out[{i_out}] ({ref}) accumulates across revisits "
                        f"along dim {d} without a "
                        f"pl.when(pl.program_id({d}) == 0) zero-init"))
            odt = jnp.dtype(cap.out_shapes[i_out].dtype)
            if odt != jnp.float32:
                out.append(Violation(
                    "accumulator-dtype", sub,
                    f"out[{i_out}] ({ref}) is a revisited accumulator of "
                    f"dtype {odt.name}; partial accumulators must be "
                    "float32"))
        else:
            # Scratch-staged pattern: every write to the revisited output
            # must sit under the last-step flush guard...
            for d in sorted(revisit):
                if not assign_guards or any(g != ("last", d)
                                            for g in assign_guards):
                    out.append(Violation(
                        "revisit-flush", sub,
                        f"out[{i_out}] ({ref}) is revisited along dim {d} "
                        "but written outside a pl.when(pl.program_id"
                        f"({d}) == pl.num_programs({d}) - 1) flush guard"))
            # ...and the scratch accumulators feeding it need first-step
            # init on the same dims.
            for sname in scratch_names:
                swrites = summary.get(sname, [])
                if not any(k == "accum" for k, _ in swrites):
                    continue
                sassigns = [g for k, g in swrites if k == "assign"]
                for d in sorted(revisit):
                    if ("first", d) not in sassigns:
                        out.append(Violation(
                            "revisit-init", sub,
                            f"scratch {sname} accumulates across dim {d} "
                            "revisits without a pl.when(pl.program_id"
                            f"({d}) == 0) zero-init"))
    return out


def verify_kernel_config(kind, padded_shape, params, dtype, *, quant="none"
                         ) -> tuple[list[Violation], dict]:
    """Capture + verify one committed kernel configuration.

    Returns ``(violations, info)``; ``info`` reports the grid, whether the
    cell enumeration was exhaustive, and the capture count -- the audit
    section logs non-exhaustive entries. Beyond :func:`verify_capture`'s
    families this proves ``launch-meta-drift``: the captured grid and
    semantics equal the pure ``contracts.launch_grid`` derivation the
    dispatcher stamps onto ``DispatchEvent.launches`` (quantized launches
    share the unquantized grid derivation -- the scale sidecars add
    BlockSpecs, not grid dims).
    """
    p = dict(params)
    tag = " int8" if quant == "int8" else ""
    sub = (f"{kind} padded {tuple(padded_shape)} "
           f"{jnp.dtype(dtype).name}{tag} {p}")
    caps = capture_kernel(kind, padded_shape, p, dtype, quant=quant)
    if not caps:
        return ([Violation(
            "capture-empty", sub,
            "entry point constructed no pallas_call under capture -- is "
            "the kernel routed through compat.pallas_call?")],
            {"subject": sub, "grid": (), "cells": 0, "exhaustive": True,
             "launches": 0})
    out: list[Violation] = []
    if len(caps) != 1:
        out.append(Violation(
            "capture-count", sub,
            f"entry point launched {len(caps)} pallas_calls; kernel "
            "entries launch exactly one (epilogues are separate entries)"))
    for cap in caps:
        out.extend(verify_capture(cap, subject=sub))
    want_grid, want_sem = contracts.launch_grid(kind, padded_shape, p)
    got = caps[0]
    got_sem = got.dimension_semantics
    if (tuple(got.grid) != tuple(want_grid)
            or tuple(got_sem or ()) != tuple(want_sem)):
        out.append(Violation(
            "launch-meta-drift", sub,
            f"captured grid {tuple(got.grid)} / semantics {got_sem} != "
            f"contracts.launch_grid {tuple(want_grid)} / {want_sem}: the "
            "DispatchEvent launch metadata no longer describes the real "
            "launch"))
    cells, exhaustive = sample_cells(tuple(int(g) for g in got.grid))
    info = {"subject": sub, "grid": tuple(int(g) for g in got.grid),
            "cells": len(cells), "exhaustive": exhaustive,
            "launches": len(caps)}
    return out, info
