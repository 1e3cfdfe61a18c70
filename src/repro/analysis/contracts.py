"""Kernel-launch contracts: every feasibility predicate, in one pure module.

The paper's method is choosing launch parameters analytically; the price is
that the *model*, the *dispatcher* and the *kernels* must agree on what a
legal configuration is. PR 3 found the cost of disagreement the hard way (a
sublane-quantized clamp in ``ops.py`` against a lane-quantized filter in
``perf_model`` silently launched blocks the model never scored). This module
is the fix-by-construction: the predicates live HERE, side-effect-free, and
both halves import them --

* ``core.perf_model`` builds its candidate grids from :func:`feasible`,
* ``kernels/ops.py`` clamps resolved params with :func:`ceil_mult` and
  (under ``GemmPolicy.verify_contracts``) asserts the chosen config with
  :func:`check_kernel_config`,
* ``analysis/audit.py`` sweeps everything the choosers can emit through the
  same checks offline.

Import discipline: stdlib + ``jax.numpy`` ONLY (jnp is used for dtype
introspection, never for arrays). Nothing from ``repro.*`` -- the contract
layer must be importable by every other layer without cycles. ``spec`` and
``policy`` arguments are duck-typed (``TPUSpec`` / ``GemmPolicy`` satisfy
them) for the same reason.

Shapes are ``(m, d1, d2)`` triples in the tuning-table convention:
``(m, k, n)`` for tsm2r/tsm2l, ``(m, a, b)`` for tsmt (m is the tall dim;
the *reduction* is k for tsm2r, m for tsmt, and VMEM-resident for tsm2l).
Params are the kwargs dicts the ops take: ``block_m``/``block_k``/
``block_a``/``splits``.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

__all__ = [
    "KINDS",
    "Violation",
    "ceil_mult",
    "bytes_per_elem",
    "min_sublane",
    "vmem_budget",
    "vmem_limit_bytes",
    "tsm2r_footprint",
    "tsm2l_footprint",
    "tsmt_footprint",
    "kernel_footprint",
    "reduction_axis",
    "feasible",
    "check_kernel_config",
    "check_grid",
    "launch_grid",
    "scatter_divisible",
    "check_scatter",
    "check_backward_policy",
    "check_tuning_record",
    "executor_reduce_ok",
    "qr_stage_shapes",
    "abft_stage_shapes",
    "TSMT_MAX_B",
    "ABFT_TOL_FACTOR",
]

KINDS = ("tsm2r", "tsm2l", "tsmt")

# The TSMT kernels keep their (block_a, b) f32 accumulator as ONE unblocked
# VMEM tile; this is the hard cap on the small output dim (kernels/ops.py
# re-exports it -- the value is a contract, so it lives here).
TSMT_MAX_B = 512

# Safety margin on the online-ABFT detection tolerance (``ft/abft.py``'s
# ``tolerance``): the threshold is ABFT_TOL_FACTOR * eps * (sqrt(rows) +
# sqrt(reduction) + 32) * column_magnitude. The sqrt terms are random-walk
# rounding growth over the checksum reduction and the protected GEMM's own
# contraction; the factor absorbs the distribution's tail (tuned against
# the clean-run false-positive tests -- a genuine high-order bit flip sits
# many orders of magnitude above this line, so the margin is cheap).
ABFT_TOL_FACTOR = 16.0

# Required param keys per kind (schema half of the tuning-record contract).
PARAM_KEYS = {
    "tsm2r": ("block_m", "block_k"),
    "tsm2l": ("block_m",),
    "tsmt": ("block_m", "block_a"),
}


@dataclasses.dataclass(frozen=True)
class Violation:
    """One broken contract: which rule, on what subject, and why."""

    rule: str        # stable rule id, e.g. "vmem-budget", "lane-quant"
    subject: str     # what was checked, e.g. "tsm2r (4096, 4096, 16) f32"
    detail: str      # human-readable explanation with the numbers

    def to_json(self) -> dict:
        return {"rule": self.rule, "subject": self.subject,
                "detail": self.detail}


def ceil_mult(x: int, q: int) -> int:
    """Smallest multiple of ``q`` >= ``x`` (the quantization primitive)."""
    return ((x + q - 1) // q) * q


def bytes_per_elem(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def min_sublane(spec, dtype) -> int:
    """Dtype-aware sublane quantum for ``block_m``.

    4- and 2-byte dtypes keep the spec's f32 sublane granularity -- the
    historical contract: every kernel accumulator is f32, so 8-row
    quantization is what the pipeline actually stages. 1-byte operands
    (the int8 quantized path) have a ``(4 * sublane, lane)`` native tile:
    a block_m off that quantum still compiles but Mosaic pads every int8
    window 4x -- exactly the silent drift class these contracts kill, so
    int8 configs quantize to the full 32-row tile.
    """
    if bytes_per_elem(dtype) == 1:
        return spec.sublane * 4
    return spec.sublane


def vmem_budget(spec) -> float:
    """Bytes of VMEM the pipeliner may use under ``spec``."""
    return spec.vmem_bytes * spec.vmem_usable


def vmem_limit_bytes(spec) -> int:
    """The scoped-VMEM limit every kernel launch passes to Mosaic
    (``CompilerParams(vmem_limit_bytes=...)``): exactly the budget the
    block choosers sized the windows against, so a config the model calls
    feasible is one the compiler accepts. Mosaic's own default scoped
    limit (16 MiB) is far below the chooser's budget, and large windows
    were refused at compile time before the two were tied together."""
    return int(vmem_budget(spec))


# ---------------------------------------------------------------------------
# Per-grid-cell VMEM footprints (moved verbatim from core/perf_model --
# perf_model now delegates here, so there is exactly one copy of this math)
# ---------------------------------------------------------------------------

def tsm2r_footprint(bm: int, bk: int, n: int, dtype, out_dtype=None) -> int:
    """VMEM bytes for one TSM2R grid cell: double-buffered in-streams,
    f32 accumulator scratch, output window.

    ``out_dtype`` prices the output window separately from the streamed
    operands -- the quantized kernels load int8 tiles but store the
    caller's dtype (None = same as ``dtype``, the unquantized case). The
    quantized kernels' (1, 1) scale windows are a few bytes and ignored.
    """
    b = bytes_per_elem(dtype)
    ob = bytes_per_elem(out_dtype if out_dtype is not None else dtype)
    n_pad = ceil_mult(n, 128)
    a_win = 2 * bm * bk * b          # double-buffered A window
    b_win = 2 * bk * n_pad * b       # double-buffered B window
    acc = bm * n_pad * 4             # f32 accumulator scratch
    out = bm * n_pad * ob            # output window
    return a_win + b_win + acc + out


def tsm2l_footprint(bm: int, k: int, n: int, dtype, out_dtype=None) -> int:
    """VMEM bytes for one TSM2L grid cell: double-buffered A window, the
    whole (k, n) B operand resident, f32 accumulator + output window
    (priced at ``out_dtype`` when it differs -- see tsm2r_footprint)."""
    b = bytes_per_elem(dtype)
    ob = bytes_per_elem(out_dtype if out_dtype is not None else dtype)
    return (2 * bm * ceil_mult(k, 128) * b
            + ceil_mult(k, 8) * ceil_mult(n, 128) * b
            + bm * ceil_mult(n, 128) * (4 + ob))


def tsmt_footprint(bm: int, ba: int, bdim: int, dtype, out_dtype=None) -> int:
    """VMEM bytes for one TSMT grid cell: double-buffered X and Y windows
    plus the unblocked (ba, bdim) f32 accumulator (``out_dtype`` accepted
    for signature uniformity; the output rides the accumulator tile and
    was never priced separately here)."""
    del out_dtype
    b = bytes_per_elem(dtype)
    return (2 * bm * ba * b + 2 * bm * ceil_mult(bdim, 128) * b
            + ba * ceil_mult(bdim, 128) * 4)


def kernel_footprint(kind: str, shape, params, dtype, out_dtype=None) -> int:
    """Per-grid-cell VMEM bytes of ``params`` for ``kind`` at ``shape``.

    Split-invariant by construction: the split kernels stage the same
    windows and accumulator per cell, S only re-partitions the grid.
    """
    m, d1, d2 = shape
    p = dict(params)
    if kind == "tsm2r":
        return tsm2r_footprint(p["block_m"], p["block_k"], d2, dtype,
                               out_dtype)
    if kind == "tsm2l":
        return tsm2l_footprint(p["block_m"], d1, d2, dtype, out_dtype)
    if kind == "tsmt":
        return tsmt_footprint(p["block_m"], p["block_a"], d2, dtype,
                              out_dtype)
    raise ValueError(f"unknown kernel kind {kind!r}: valid kinds are "
                     f"{', '.join(KINDS)}")


def reduction_axis(kind: str, shape) -> tuple[str, int]:
    """(param name of the reduction block, reduction dim size) for the
    kinds whose reduction axis is gridded; tsm2l keeps its contraction
    VMEM-resident and has no split dimension."""
    m, d1, _ = shape
    if kind == "tsm2r":
        return "block_k", d1
    if kind == "tsmt":
        return "block_m", m
    raise ValueError(f"kind {kind!r} has no gridded reduction axis")


# ---------------------------------------------------------------------------
# Feasibility (the candidate-filter predicate, shared with perf_model)
# ---------------------------------------------------------------------------

def feasible(kind: str, shape, params, dtype, spec,
             out_dtype=None) -> bool:
    """True iff ``params`` is a launchable configuration for ``kind`` at
    ``shape`` under ``spec`` -- the exact predicate the perf model's
    candidate enumerators filter with (so the model's search space and the
    kernels' legal space are one set by construction):

    * parallel blocks never exceed the quantized dim (pure-padding blocks
      are not candidates): ``block_m <= ceil_mult(m, sublane)``, and the
      lane-axis block <= ``ceil_mult(dim, lane)``;
    * the per-cell VMEM footprint fits ``spec``'s budget;
    * S > 1 only when every reduction slice owns >= one whole block
      (``s * block <= ceil_mult(reduction, q)``); tsm2l admits no split.

    The TSMT accumulator limit is deliberately NOT part of this predicate:
    it is a dispatch-level contract on the *shape* (``ops.tsmt`` refuses
    before parameter resolution), not a per-candidate constraint, so it
    must not prune the candidate grid the perf model scores.
    """
    return not [v for v in check_kernel_config(kind, shape, params, dtype,
                                               spec, out_dtype=out_dtype)
                if v.rule != "accumulator-limit"]


def check_kernel_config(kind: str, shape, params, dtype, spec, *,
                        max_b: int | None = None,
                        out_dtype=None) -> list[Violation]:
    """Every contract violation of ``params`` (empty list == feasible).

    ``max_b`` overrides the TSMT accumulator limit (``GemmPolicy.
    max_skinny_t`` scopes can raise it past :data:`TSMT_MAX_B`).
    ``out_dtype`` is the quantized-path split: ``dtype`` is what the
    operand tiles stream as (int8 under ``GemmPolicy.quant="int8"``, which
    also widens the sublane quantum -- :func:`min_sublane`), ``out_dtype``
    what the kernel stores. None = same dtype, the unquantized case.
    """
    m, d1, d2 = shape
    p = dict(params)
    subject = f"{kind} {tuple(shape)} {jnp.dtype(dtype).name}"
    if out_dtype is not None:
        subject += f"->{jnp.dtype(out_dtype).name}"
    subject += f" {p}"
    out: list[Violation] = []

    missing = [k for k in PARAM_KEYS.get(kind, ()) if k not in p]
    if kind not in KINDS:
        return [Violation("unknown-kind", subject,
                          f"unknown kernel kind {kind!r}")]
    if missing:
        return [Violation("missing-params", subject,
                          f"missing required params {missing}")]

    bm = p["block_m"]
    splits = p.get("splits", 1)
    lane, sub = spec.lane, min_sublane(spec, dtype)

    # -- positivity / integrality -------------------------------------------
    blocks = {k: v for k, v in p.items() if k.startswith("block")}
    for name, v in {**blocks, "splits": splits}.items():
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            out.append(Violation(
                "bad-param", subject,
                f"{name}={v!r} must be a positive int"))
    if any(v.rule == "bad-param" for v in out):
        return out

    # -- hardware quantization ----------------------------------------------
    # block_m tiles the sublane (second-minor) axis of every kernel's tall
    # operand; the lane-axis block (block_k for tsm2r, block_a for tsmt)
    # must be a whole number of 128-wide lane tiles. A misquantized block
    # still compiles but pads every window inside Mosaic -- the silent
    # model-vs-kernel drift class this module exists to kill.
    if bm % sub != 0:
        out.append(Violation(
            "sublane-quant", subject,
            f"block_m={bm} is not a multiple of sublane={sub}"))
    lane_block = {"tsm2r": "block_k", "tsmt": "block_a"}.get(kind)
    if lane_block is not None and p[lane_block] % lane != 0:
        out.append(Violation(
            "lane-quant", subject,
            f"{lane_block}={p[lane_block]} is not a multiple of "
            f"lane={lane}"))

    # -- parallel blocks must not exceed the quantized dim ------------------
    if bm > ceil_mult(m, sub):
        out.append(Violation(
            "block-exceeds-dim", subject,
            f"block_m={bm} > ceil_mult(m={m}, {sub})={ceil_mult(m, sub)}: "
            "the block is pure padding"))
    if kind == "tsm2r" and p["block_k"] > ceil_mult(d1, lane):
        out.append(Violation(
            "block-exceeds-dim", subject,
            f"block_k={p['block_k']} > ceil_mult(k={d1}, {lane})="
            f"{ceil_mult(d1, lane)}"))
    if kind == "tsmt" and p["block_a"] > ceil_mult(d1, lane):
        out.append(Violation(
            "block-exceeds-dim", subject,
            f"block_a={p['block_a']} > ceil_mult(a={d1}, {lane})="
            f"{ceil_mult(d1, lane)}"))

    # -- VMEM budget --------------------------------------------------------
    fp = kernel_footprint(kind, shape, p, dtype, out_dtype)
    budget = vmem_budget(spec)
    if fp > budget:
        out.append(Violation(
            "vmem-budget", subject,
            f"footprint {fp} B > budget {int(budget)} B "
            f"({spec.vmem_bytes} B x vmem_usable={spec.vmem_usable})"))

    # -- split-K whole-slice feasibility ------------------------------------
    if kind == "tsm2l":
        if splits != 1:
            out.append(Violation(
                "split-unsupported", subject,
                f"splits={splits}: tsm2l keeps its whole contraction "
                "VMEM-resident and has no split dimension"))
    elif splits > 1:
        rname, rdim = reduction_axis(kind, shape)
        q = lane if rname == "block_k" else sub
        if splits * p[rname] > ceil_mult(rdim, q):
            out.append(Violation(
                "split-whole-slice", subject,
                f"splits={splits} x {rname}={p[rname]} > "
                f"ceil_mult({rdim}, {q})={ceil_mult(rdim, q)}: slices past "
                "the reduction are pure zero-padding work"))

    # -- TSMT unblocked accumulator limit -----------------------------------
    if kind == "tsmt":
        limit = max(TSMT_MAX_B, max_b or 0)
        if d2 > limit:
            out.append(Violation(
                "accumulator-limit", subject,
                f"tsmt small output dim b={d2} exceeds the unblocked f32 "
                f"accumulator limit ({limit})"))

    return out


def check_grid(kind: str, padded_shape, params) -> list[Violation]:
    """Grid-divisibility contract of the raw kernels' padded operands.

    ``kernels/ops.py`` zero-pads so these hold by construction (zero
    padding is exact for GEMM); calling the ``*_pallas`` kernels directly
    asserts the same conditions at trace time. The auditor re-derives the
    padded shape from the resolver's output and proves exactness here.

    ``kind="reduce"`` is the split-partials epilogue
    (``kernels/reduce.py``): ``padded_shape`` is the ``(splits, rows,
    cols)`` partials stack and ``params`` carries ``block_r`` (as resolved
    by ``reduce.epilogue_block_r``); the contract is ``rows % block_r``.
    """
    p = dict(params)
    s = p.get("splits", 1)
    subject = f"{kind} padded {tuple(padded_shape)} {p}"
    out = []
    if kind == "reduce":
        _, rows, _ = padded_shape
        if rows % p["block_r"] != 0:
            out.append(Violation(
                "grid-divisibility", subject,
                f"partials rows={rows} is not a multiple of "
                f"block_r={p['block_r']}"))
        return out
    m, d1, _ = padded_shape
    if m % p["block_m"] != 0:
        out.append(Violation(
            "grid-divisibility", subject,
            f"padded m={m} is not a multiple of block_m={p['block_m']}"))
    if kind == "tsm2r" and d1 % (s * p["block_k"]) != 0:
        out.append(Violation(
            "grid-divisibility", subject,
            f"padded k={d1} is not a multiple of splits*block_k="
            f"{s * p['block_k']}"))
    if kind == "tsmt" and m % (s * p["block_m"]) != 0:
        out.append(Violation(
            "grid-divisibility", subject,
            f"padded m={m} is not a multiple of splits*block_m="
            f"{s * p['block_m']}"))
    return out


def launch_grid(kind: str, padded_shape, params
                ) -> tuple[tuple[int, ...], tuple[str, ...]]:
    """``(grid, dimension_semantics)`` of the launch ``kind`` runs.

    The dataflow half of the grid contract (:func:`check_grid` is the
    divisibility half): this is the single statement of which grid each
    kernel launches at a padded operand shape, consumed by

    * ``kernels/ops.py`` -- stamps it onto ``DispatchEvent.launches`` so
      trace-time spies can assert grid shape;
    * ``analysis/kernel_verify`` -- proves the *captured* ``pallas_call``
      grid/semantics equal this derivation (``launch-meta-drift``).

    ``kind="reduce"`` follows the :func:`check_grid` convention:
    ``padded_shape=(splits, rows, cols)``, ``params={"block_r": ...}``.
    """
    p = dict(params)
    s = p.get("splits", 1)
    if kind == "tsm2r":
        m, k, _ = padded_shape
        if s == 1:
            return ((m // p["block_m"], k // p["block_k"]),
                    ("parallel", "arbitrary"))
        return ((s, m // p["block_m"], k // (s * p["block_k"])),
                ("parallel", "parallel", "arbitrary"))
    if kind == "tsm2l":
        return ((padded_shape[0] // p["block_m"],), ("arbitrary",))
    if kind == "tsmt":
        m, a, _ = padded_shape
        if s == 1:
            return ((a // p["block_a"], m // p["block_m"]),
                    ("parallel", "arbitrary"))
        return ((s, a // p["block_a"], m // (s * p["block_m"])),
                ("parallel", "parallel", "arbitrary"))
    if kind == "reduce":
        return ((padded_shape[1] // p["block_r"],), ("parallel",))
    raise ValueError(f"unknown kernel kind {kind!r}: valid kinds are "
                     f"{', '.join(KINDS + ('reduce',))}")


# ---------------------------------------------------------------------------
# Tall-skinny QR stage contracts
# ---------------------------------------------------------------------------

def qr_stage_shapes(m: int, r: int, *, shards: int = 1
                    ) -> tuple[tuple[str, tuple[int, int, int]], ...]:
    """The GEMM-stage (kind, shape) pairs one tall-skinny QR resolves.

    ``repro.linalg``'s CholeskyQR2 factors an ``(m, r)`` operand through
    exactly two kernel dispatches per pass -- the Gram matrix ``A^T A``
    (a ``tsmt`` at ``(m, r, r)``) and the ``R^{-1}`` apply (a ``tsm2l``
    at ``(m, r, r)``); the small Cholesky/triangular solves between them
    are (r, r) host-shaped and never touch the kernels. ``shards > 1``
    describes the tree-TSQR variant, whose local factor runs the same two
    stages on the per-shard row count (``m`` must tile over the shards --
    the same divisibility the shard_map executors require).

    This is the contract the auditor sweeps (``audit_qr_configs``): every
    shape the QR subsystem can hand ``ops.resolve_params`` must resolve to
    a launchable configuration.
    """
    if shards < 1 or (shards > 1 and m % shards != 0):
        raise ValueError(
            f"qr_stage_shapes: m={m} does not tile over shards={shards} "
            "(tree-TSQR requires the tall dim to divide the shard count)")
    m_loc = m // shards
    return (("tsmt", (m_loc, r, r)), ("tsm2l", (m_loc, r, r)))


# ---------------------------------------------------------------------------
# Online-ABFT stage contracts
# ---------------------------------------------------------------------------

def abft_stage_shapes(kind: str, shape, s: int = 2
                      ) -> tuple[tuple[str, tuple[int, int, int]], ...]:
    """The checksum-GEMM (entry, shape) triples the online ABFT wrap
    dispatches around one protected ``(kind, (m, d1, d2))`` GEMM, with
    ``s`` checksum columns (>= 2: plain + ramp -- fewer cannot localize).

    For ``tsm2r``/``tsm2l`` (``A(m,k) @ B(k,n)``, shape ``(m, k, n)``):
    ``u = A^T e`` (mmt over m), ``c_ref = B^T u`` (mmt over k),
    ``c_out = C^T e`` (mmt over m). For ``tsmt``
    (``X(m,a)^T Y(m,b)``, shape ``(m, a, b)``): ``v = X e`` (mm over m),
    ``c_ref^T = v^T Y`` (mmt over m), ``c_out = C^T e`` (mmt over a).

    This is the contract ``audit_abft_configs`` sweeps: every checksum
    shape the wrap can hand the dispatcher must classify, and when it
    classifies to a kernel kind must resolve to a launchable config.
    """
    if s < 2:
        raise ValueError(
            f"abft_stage_shapes: s={s} checksum columns cannot localize "
            "(need the plain column AND the ramp: s >= 2)")
    m, d1, d2 = shape
    if kind in ("tsm2r", "tsm2l"):
        return (("mmt", (m, d1, s)),       # u = A^T e
                ("mmt", (d1, d2, s)),      # c_ref = B^T u
                ("mmt", (m, d2, s)))       # c_out = C^T e
    if kind == "tsmt":
        return (("mm", (m, d1, s)),        # v = X e
                ("mmt", (m, s, d2)),       # c_ref^T = v^T Y
                ("mmt", (d1, d2, s)))      # c_out = C^T e
    raise ValueError(
        f"abft_stage_shapes: unknown kind {kind!r}: the online wrap only "
        f"protects {', '.join(KINDS)}")


# ---------------------------------------------------------------------------
# Collective-layout contracts
# ---------------------------------------------------------------------------

def scatter_divisible(rows: int, shards: int) -> bool:
    """psum_scatter's existence condition: the scattered output rows must
    tile exactly over the DP shards (the dispatcher falls back to dense
    otherwise; a pinned scatter executor raises)."""
    return shards >= 1 and rows % shards == 0


def check_scatter(rows: int, shards: int) -> list[Violation]:
    if scatter_divisible(rows, shards):
        return []
    return [Violation(
        "psum-scatter-divisibility", f"rows={rows} shards={shards}",
        f"psum_scatter output rows ({rows}) do not divide the {shards} "
        "shards: the row-sharded layout cannot exist")]


def executor_reduce_ok(declared, reduce: str) -> bool:
    """Does an executor whose declared reduce contract is ``declared``
    (an iterable of mode names) implement ``reduce``?"""
    return reduce in tuple(declared)


# ---------------------------------------------------------------------------
# Policy contracts
# ---------------------------------------------------------------------------

def check_backward_policy(fwd, bwd) -> list[Violation]:
    """The VJP re-dispatch invariants ``tsmm.backward_policy`` must honor
    (duck-typed on the GemmPolicy fields so this layer stays pure):

    * ``reduce`` is preserved, except "none" -> "psum" (stacked partials
      would change the cotangent shape, which custom_vjp forbids);
    * an int ``split`` pin is stripped to "auto" (shape-specific), while
      "auto"/"never" are preserved (scope-wide intent);
    * the executor pin is dropped (a pinned shard_map executor must not
      recurse per-shard);
    * a forward-kind force degrades to "auto"; "dense"/"auto" survive;
    * ``quant`` is preserved verbatim (scope-wide numeric intent: an int8
      scope keeps its cotangent GEMMs quantizable);
    * ``abft`` is preserved verbatim (scope-wide integrity intent: the
      cotangent GEMMs of a verify/correct scope get their own checksums).
    """
    subject = f"backward_policy({fwd!r})"
    out = []
    want_reduce = "psum" if fwd.reduce == "none" else fwd.reduce
    if bwd.reduce != want_reduce:
        out.append(Violation(
            "backward-reduce", subject,
            f"backward reduce={bwd.reduce!r}, expected {want_reduce!r} "
            f"(forward reduce={fwd.reduce!r})"))
    want_split = "auto" if isinstance(fwd.split, int) else fwd.split
    if bwd.split != want_split:
        out.append(Violation(
            "backward-split", subject,
            f"backward split={bwd.split!r}, expected {want_split!r} "
            f"(forward split={fwd.split!r})"))
    if bwd.executor is not None:
        out.append(Violation(
            "backward-executor", subject,
            f"backward keeps executor pin {bwd.executor!r}; the VJP must "
            "re-select (a pinned shard_map executor would recurse)"))
    want_mode = fwd.mode if fwd.mode in ("auto", "dense") else "auto"
    if bwd.mode != want_mode:
        out.append(Violation(
            "backward-mode", subject,
            f"backward mode={bwd.mode!r}, expected {want_mode!r} "
            f"(forward mode={fwd.mode!r})"))
    want_quant = getattr(fwd, "quant", "none")
    if getattr(bwd, "quant", "none") != want_quant:
        out.append(Violation(
            "backward-quant", subject,
            f"backward quant={getattr(bwd, 'quant', 'none')!r}, expected "
            f"{want_quant!r}: quant is scope-wide numeric intent and must "
            "survive the VJP re-dispatch"))
    want_abft = getattr(fwd, "abft", "none")
    if getattr(bwd, "abft", "none") != want_abft:
        out.append(Violation(
            "abft-policy", subject,
            f"backward abft={getattr(bwd, 'abft', 'none')!r}, expected "
            f"{want_abft!r}: abft is scope-wide integrity intent and must "
            "survive the VJP re-dispatch"))
    return out


# ---------------------------------------------------------------------------
# Tuning-table contracts
# ---------------------------------------------------------------------------

def check_tuning_record(kind: str, shape, params, dtype, spec, *,
                        executor: str = "", known_executors=()) -> list[Violation]:
    """Contract check of one committed TuningTable entry.

    ``spec`` should be the table's *effective* spec for the record's bucket
    (``TuningTable.fitted_spec``): winners measured under the relaxed
    ``explore_vmem`` budget are legal exactly when calibration widened
    ``vmem_usable`` to cover them -- an entry over even the widened budget
    is a stale or corrupted commit.
    """
    out = check_kernel_config(kind, shape, params, dtype, spec)
    if known_executors and executor not in known_executors:
        out.append(Violation(
            "unknown-executor",
            f"{kind} {tuple(shape)} executor={executor!r}",
            f"record's executor {executor!r} is not registered "
            f"(known: {sorted(known_executors)})"))
    return out
