"""Public jit'd entry points for the TSM2X kernels.

Handles: block-size AND split-factor selection (measured winners from
``GemmPolicy.tuning_table`` when present, else the analytic perf model --
run under the table's bucket-local fitted spec when it has one; explicit
per-call block/``splits=`` kwargs beat both, and ``GemmPolicy.split`` pins
S scope-wide), padding to block multiples (zero-padding is exact for GEMM;
split paths pad the reduction to whole S-slices), interpret-mode
resolution (``compat.auto_interpret`` of the policy field: kernel bodies run
in Python off-TPU and compile via Mosaic on a TPU), and the scoped-VMEM
limit each launch hands Mosaic -- the budget of the spec the block chooser
ran under (``contracts.vmem_limit_bytes``). Split (S > 1) dispatch runs the
``*_pallas_split`` kernel and sums the (S, ...) f32 partials through
``repro.kernels.reduce.reduce_partials`` before slicing off the padding,
so callers see the exact sequential-kernel contract.

All three entries carry ``jax.custom_vjp`` rules that take the resolved
``GemmPolicy`` through their nondiff args, so the backward re-enters
``repro.core.tsmm`` under the *caller's* scope -- the paper's central
observation applied to autodiff: the VJP of one tall-and-skinny GEMM class
lands in another.

    tsm2r/tsm2l:  C = A B        Abar = Chat B^T   (TSM2L-shaped for TSM2L)
                                 Bbar = A^T Chat   (TSMTTSM shape -> tsmt)
    tsmt:         C = X^T Y      Xbar = Y Chat^T   (TSM2L-shaped)
                                 Ybar = X Chat     (TSM2L-shaped)

Routing goes through ``tsmm.classify_gemm`` / ``tsmm.classify_gemm_t`` with
the scoped thresholds, so gradients stay inside the tall-skinny regime
instead of falling back to XLA dense dots; shapes that leave the regime
degrade to ``dot_general`` exactly like the forward dispatcher does.

Under a multi-chip mesh the backward re-dispatch also keeps the caller's
*collective*: ``tsmm.backward_policy`` preserves ``GemmPolicy.reduce``, so
in a ``reduce="psum_scatter"`` scope the weight-gradient ``tsmm_t``s here
(``Bbar = A^T Chat``) land on the ``shard_map-scatter`` executor and come
back row-sharded over the DP axes -- no all-gather between the kernel and
a ZeRO-sharded optimizer. Only ``reduce="none"`` is rewritten (to "psum"):
stacked partials would change the cotangent shape, which custom_vjp
forbids.

``spec=`` / ``interpret=`` kwargs are kept as per-call overrides of the
corresponding policy fields (prefer ``with tsmm.policy(...)`` scopes).

Under ``GemmPolicy.quant="int8"`` each impl quantizes its padded operands
(per-resolved-row-block scales for the tall operand, per-tensor for the
small one -- ``kernels/quant.py``) and launches the quantized kernel
variant; parameter resolution, tuning-table lookups and contract checks
all run against the int8 *effective dtype*, so the grid that is scored,
tuned and audited is the grid that launches. Outputs (and split partials,
which are dequantized in-kernel) keep the unquantized path's dtypes
exactly, so the reduce epilogue and the VJP rules below are unchanged.

Online ABFT sits ABOVE this layer: the checksum wrap
(``tsmm._abft_guard``) and the fault-injection tap
(``ft.inject.tap_executor``) both live at the dispatcher's
executor-registry boundary, so every arm routed through ``repro.core.tsmm``
-- including the split and quantized paths here -- is guarded and
injectable, while the impls in this module stay checksum-free. Calling
``ops.tsm2r``/``tsm2l``/``tsmt`` directly bypasses both the guard and
the tap; the
backward re-dispatch goes through ``tsmm`` and so re-enters them
(``tsmm.backward_policy`` preserves ``GemmPolicy.abft``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.analysis import contracts
from repro.core import perf_model
from repro.kernels import compat, ref
from repro.kernels import quant as kquant
from repro.kernels.reduce import epilogue_block_r, reduce_partials
from repro.kernels.tsm2l import tsm2l_pallas
from repro.kernels.tsm2r import tsm2r_pallas, tsm2r_pallas_split
from repro.kernels.tsmt import tsmt_pallas, tsmt_pallas_split

# The TSMT kernels keep their (block_a, b) f32 accumulator as ONE unblocked
# VMEM tile, so the small output dim is hard-limited (the classifier's
# max_skinny_t default is derived from the same t2_threshold ~ 481, rounded
# up to the lane multiple). Past it, ops.tsmt refuses loudly instead of
# silently compiling a huge accumulator tile. The value is a contract, so
# it is owned by ``analysis.contracts`` and re-exported here.
TSMT_MAX_B = contracts.TSMT_MAX_B


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _dispatcher():
    # Deferred: repro.core.tsmm imports this module (forward dispatch);
    # the backward-pass dependency in the other direction stays lazy.
    from repro.core import tsmm
    return tsmm


def _effective_policy(policy, spec, interpret):
    """The caller's policy with legacy per-call kwargs folded in."""
    p = policy if policy is not None else _dispatcher().current_policy()
    repl = {}
    if spec is not None and spec is not p.spec:
        repl["spec"] = spec
    if interpret is not None and interpret != p.interpret:
        repl["interpret"] = interpret
    return dataclasses.replace(p, **repl) if repl else p


def _tuned_params(policy, kind, dims, dtype, interpret) -> dict | None:
    """Measured-best block params from ``policy.tuning_table``, if any.

    The table is keyed by (kind, shape bucket, dtype, spec name, executor);
    the executor key matches how this call will actually run, so a table
    tuned in interpret mode never silences the analytic model on hardware.
    Records tuned before the split-reduction dimension existed carry no
    "splits" key; consumers default it to 1 (the sequential kernel they
    actually measured).
    """
    table = policy.tuning_table
    if table is None:
        return None
    executor = "interpret" if interpret else "pallas-tpu"
    rec = table.lookup(kind, *dims, dtype=dtype, spec=policy.spec.name,
                       executor=executor)
    return None if rec is None else rec.params_dict


def _analytic_spec(policy, kind, dims, dtype):
    """Spec driving the analytic parameter choice for this shape: the
    tuning table's bucket-local fitted constants when it carries any
    (``TuningTable.fitted_spec`` -- bucket fit first, global fit second),
    else the policy's spec unchanged. Duck-typed so pre-fit tables (and
    any hashable stand-in) keep working."""
    table = policy.tuning_table
    fitted = getattr(table, "fitted_spec", None)
    if fitted is None:
        return policy.spec
    return fitted(kind, *dims, dtype=dtype, spec=policy.spec)


def _policy_split(policy) -> int | None:
    """The policy's split pin as an int, or None for "auto" (resolve from
    the tuning table / analytic chooser)."""
    s = policy.split
    if s == "never":
        return 1
    if s == "auto":
        return None
    return int(s)


def _vmem_limit(policy, kind, dims, dtype) -> int:
    """Scoped-VMEM limit of one launch: the budget of the very spec the
    block chooser ran under (``_analytic_spec``), so the compiler enforces
    what the model sized against."""
    return contracts.vmem_limit_bytes(_analytic_spec(policy, kind, dims,
                                                     dtype))


def _note_launch(kind, padded_shape, params):
    """Stamp the resolved launch onto the current DispatchEvent (no-op
    outside a `tsmm.record_dispatches` scope). Grid and semantics come
    from the pure contract -- `analysis.kernel_verify` proves that
    derivation equals the captured `pallas_call` (launch-meta-drift)."""
    grid, sem = contracts.launch_grid(kind, padded_shape, params)
    _dispatcher().note_launch(kind, grid, sem,
                              dict(params).get("splits", 1))


# ---------------------------------------------------------------------------
# Parameter resolution (pure; shared by the impls and analysis/audit)
# ---------------------------------------------------------------------------

def _resolve_tsm2r(m, k, n, dtype, policy, block_m, block_k, splits,
                   interpret):
    explicit_bk = block_k is not None
    if splits is None:
        splits = _policy_split(policy)
    if block_m is None or block_k is None or splits is None:
        tuned = _tuned_params(policy, "tsm2r", (m, k, n), dtype, interpret)
        if tuned is None:
            bm, bk, s = perf_model.choose_params_tsm2r(
                m, k, n, _analytic_spec(policy, "tsm2r", (m, k, n), dtype),
                dtype)
        else:
            bm, bk = tuned["block_m"], tuned["block_k"]
            s = tuned.get("splits", 1)
        block_m = block_m or bm
        block_k = block_k or bk
        if splits is None:
            splits = s
    # Sublane quantum is dtype-aware (int8 tiles are 32 rows deep); for
    # f32/bf16 this is exactly spec.sublane, as before.
    block_m = min(block_m, _ceil_mult(m, contracts.min_sublane(policy.spec,
                                                               dtype)))
    # block_k is a lane dim of the A window: clamp with the same lane
    # quantization the perf model's candidate filter uses, so the block the
    # kernel runs is the block the VMEM budget was checked against.
    block_k = min(block_k, _ceil_mult(k, policy.spec.lane))
    if splits > 1 and not explicit_bk:
        # A pinned S must be honored even when the chooser (which assumed
        # its own S) picked a block too deep for S whole slices: shrink
        # the reduction block -- unless the caller pinned it explicitly,
        # in which case the block wins and S clamps below.
        block_k = min(block_k,
                      _ceil_mult(-(-k // splits), policy.spec.lane))
    # Each reduction slice must own >= one block, or the extra slices are
    # pure zero-padding work: clamp S like the candidate filter does.
    splits = max(1, min(splits, -(-k // block_k)))
    return {"block_m": block_m, "block_k": block_k, "splits": splits}


def _resolve_tsm2l(m, k, n, dtype, policy, block_m, interpret):
    if block_m is None:
        tuned = _tuned_params(policy, "tsm2l", (m, k, n), dtype, interpret)
        block_m = (tuned["block_m"] if tuned is not None else
                   perf_model.choose_params_tsm2l(
                       m, k, n, _analytic_spec(policy, "tsm2l", (m, k, n),
                                               dtype), dtype))
    block_m = min(block_m, _ceil_mult(m, contracts.min_sublane(policy.spec,
                                                               dtype)))
    return {"block_m": block_m}


def _resolve_tsmt(m, a_dim, b_dim, dtype, policy, block_m, block_a, splits,
                  interpret):
    explicit_bm = block_m is not None
    if splits is None:
        splits = _policy_split(policy)
    if block_m is None or block_a is None or splits is None:
        tuned = _tuned_params(policy, "tsmt", (m, a_dim, b_dim), dtype,
                              interpret)
        if tuned is None:
            bm, ba, s = perf_model.choose_params_tsmt(
                m, a_dim, b_dim,
                _analytic_spec(policy, "tsmt", (m, a_dim, b_dim), dtype),
                dtype)
        else:
            bm, ba = tuned["block_m"], tuned["block_a"]
            s = tuned.get("splits", 1)
        block_m = block_m or bm
        block_a = block_a or ba
        if splits is None:
            splits = s
    sub = contracts.min_sublane(policy.spec, dtype)
    block_m = min(block_m, _ceil_mult(m, sub))
    # block_a is a lane dim of the X window: lane-quantized clamp, matching
    # the perf model's candidate filter (see _resolve_tsm2r).
    block_a = min(block_a, _ceil_mult(a_dim, policy.spec.lane))
    if splits > 1 and not explicit_bm:
        # honor a pinned S by shrinking the reduction block (m here);
        # an explicit block_m kwarg wins and S clamps instead.
        block_m = min(block_m, _ceil_mult(-(-m // splits), sub))
    # m is the reduction here: each slice must own >= one m block.
    splits = max(1, min(splits, -(-m // block_m)))
    return {"block_m": block_m, "block_a": block_a, "splits": splits}


def resolve_params(kind: str, m: int, d1: int, d2: int, dtype, policy, *,
                   block_m: int | None = None, block_k: int | None = None,
                   block_a: int | None = None, splits: int | None = None,
                   interpret: bool | None = None) -> dict:
    """Resolve the launch parameters dispatch would use -- without running.

    The exact trace-time logic of the op entry points, factored out so the
    offline auditor (``analysis/audit.py``) can sweep it: tuned winner from
    ``policy.tuning_table`` -> analytic chooser (under the table's fitted
    spec) -> quantized clamps -> split-slice clamp. Explicit kwargs beat
    both sources, exactly like the per-call kwargs on ``tsm2r``/``tsm2l``/
    ``tsmt``. ``(d1, d2)`` are ``(k, n)`` for tsm2r/tsm2l, ``(a, b)`` for
    tsmt.

    When ``policy.verify_contracts`` is set the resolved configuration is
    asserted against ``analysis.contracts.check_kernel_config`` under the
    same effective spec the chooser ran with; a violation raises
    ``ValueError`` (trace time, never on-device).

    Under ``policy.quant="int8"`` the whole resolution runs against the
    int8 *effective dtype* -- tuning-table lookups (dtype is already a key
    dimension, so quantized grids get their own measured winners with no
    schema fork), the analytic chooser's byte pricing, the clamps' wider
    32-row sublane quantum, and the contract check (which then prices the
    output window at the caller's ``dtype``). ``verify_contracts`` scopes
    additionally *reject* explicitly pinned blocks the int8 quantization
    would silently re-quantize, mirroring the lane-clamp contract: a pin
    that survives unchanged on the f32 path can be off the 32-row quantum
    or clamped to a different value under int8, and a quantized launch the
    caller didn't ask for must fail loudly.
    """
    if interpret is None:
        interpret = compat.auto_interpret(policy.interpret)
    quant = getattr(policy, "quant", "none") == "int8"
    eff_dtype = jnp.int8 if quant else dtype
    if kind == "tsm2r":
        params = _resolve_tsm2r(m, d1, d2, eff_dtype, policy, block_m,
                                block_k, splits, interpret)
    elif kind == "tsm2l":
        params = _resolve_tsm2l(m, d1, d2, eff_dtype, policy, block_m,
                                interpret)
    elif kind == "tsmt":
        params = _resolve_tsmt(m, d1, d2, eff_dtype, policy, block_m,
                               block_a, splits, interpret)
    else:
        raise ValueError(f"unknown kernel kind {kind!r}: valid kinds are "
                         f"{', '.join(contracts.KINDS)}")
    if getattr(policy, "verify_contracts", False):
        if quant:
            sub = contracts.min_sublane(policy.spec, eff_dtype)
            bad = []
            for name, pin in (("block_m", block_m), ("block_k", block_k),
                              ("block_a", block_a)):
                if pin is None or name not in params:
                    continue
                q = sub if name == "block_m" else policy.spec.lane
                if pin % q != 0 or params[name] != pin:
                    bad.append(
                        f"[pinned-block-quant] {name}={pin} is infeasible "
                        f"under the int8 tile quantization (quantum {q}; "
                        f"resolution would re-quantize it to "
                        f"{params[name]})")
            if bad:
                raise ValueError(
                    "GemmPolicy.verify_contracts: explicit block pin(s) "
                    "rejected rather than silently re-quantized under "
                    "quant='int8': " + "; ".join(bad))
        eff_spec = _analytic_spec(policy, kind, (m, d1, d2), eff_dtype)
        violations = contracts.check_kernel_config(
            kind, (m, d1, d2), params, eff_dtype, eff_spec,
            max_b=getattr(policy, "max_skinny_t", None),
            out_dtype=dtype if quant else None)
        if violations:
            raise ValueError(
                "GemmPolicy.verify_contracts: resolved kernel config "
                f"breaks {len(violations)} contract(s): "
                + "; ".join(f"[{v.rule}] {v.detail}" for v in violations))
    return params


# ---------------------------------------------------------------------------
# TSM2R
# ---------------------------------------------------------------------------

def _tsm2r_impl(a, b, block_m, block_k, splits, policy):
    m, k = a.shape
    n = b.shape[1]
    interpret = compat.auto_interpret(policy.interpret)
    p = resolve_params("tsm2r", m, k, n, a.dtype, policy, block_m=block_m,
                       block_k=block_k, splits=splits, interpret=interpret)
    block_m, block_k, splits = p["block_m"], p["block_k"], p["splits"]
    quant = getattr(policy, "quant", "none") == "int8"
    vmem = _vmem_limit(policy, "tsm2r", (m, k, n),
                       jnp.int8 if quant else a.dtype)
    if splits == 1:
        a_p = _pad_to(_pad_to(a, 0, block_m), 1, block_k)
        b_p = _pad_to(b, 0, block_k)
        _note_launch("tsm2r", (a_p.shape[0], a_p.shape[1], n), p)
        if quant:
            a_q, a_s = kquant.quantize_blocks(a_p, block_m)
            b_q, b_s = kquant.quantize_tensor(b_p)
            out = kquant.tsm2r_q8_pallas(
                a_q, b_q, a_s, b_s, out_dtype=a.dtype, block_m=block_m,
                block_k=block_k, interpret=interpret, vmem_limit_bytes=vmem)
        else:
            out = tsm2r_pallas(a_p, b_p, block_m=block_m, block_k=block_k,
                               interpret=interpret, vmem_limit_bytes=vmem)
        return out[:m]
    # Split reduction: pad k so every slice is whole (zero-padding is exact
    # for GEMM, so m % (S*bk) non-multiples cost only the padded stream).
    a_p = _pad_to(_pad_to(a, 0, block_m), 1, splits * block_k)
    b_p = _pad_to(b, 0, splits * block_k)
    _note_launch("tsm2r", (a_p.shape[0], a_p.shape[1], n), p)
    if quant:
        a_q, a_s = kquant.quantize_blocks(a_p, block_m)
        b_q, b_s = kquant.quantize_tensor(b_p)
        parts = kquant.tsm2r_q8_pallas_split(
            a_q, b_q, a_s, b_s, block_m=block_m, block_k=block_k,
            splits=splits, interpret=interpret, vmem_limit_bytes=vmem)
    else:
        parts = tsm2r_pallas_split(a_p, b_p, block_m=block_m,
                                   block_k=block_k, splits=splits,
                                   interpret=interpret, vmem_limit_bytes=vmem)
    br = epilogue_block_r(splits, a_p.shape[0], n, block_r=block_m,
                          vmem_budget=vmem)
    if br is not None:
        _note_launch("reduce", (splits, a_p.shape[0], n), {"block_r": br})
    out = reduce_partials(parts, a.dtype, block_r=block_m,
                          vmem_budget=vmem, interpret=interpret)
    return out[:m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _tsm2r_diff(a, b, block_m, block_k, splits, policy):
    return _tsm2r_impl(a, b, block_m, block_k, splits, policy)


def _tsm2r_fwd(a, b, block_m, block_k, splits, policy):
    return _tsm2r_impl(a, b, block_m, block_k, splits, policy), (a, b)


def _tsm2r_bwd(block_m, block_k, splits, policy, res, ct):
    a, b = res
    tsmm = _dispatcher()
    bp = tsmm.backward_policy(policy)
    # Abar[m,k] = Chat[m,n] B^T[n,k]: tiny contraction; TSM2L-shaped when
    # k is small, dense when k ~ m (the TSM2R case) -- classifier decides.
    da = tsmm.tsmm(ct, b.T, policy=bp)
    # Bbar[k,n] = A^T[k,m] Chat[m,n]: reduction over tall m -- the TSMTTSM
    # shape (Ernst et al.), dispatched via classify_gemm_t.
    db = tsmm.tsmm_t(a, ct, policy=bp)
    return da.astype(a.dtype), db.astype(b.dtype)


_tsm2r_diff.defvjp(_tsm2r_fwd, _tsm2r_bwd)


def tsm2r(a: jnp.ndarray, b: jnp.ndarray, *, block_m: int | None = None,
          block_k: int | None = None, splits: int | None = None,
          spec: perf_model.TPUSpec | None = None,
          interpret: bool | None = None,
          policy=None) -> jnp.ndarray:
    """C[m,n] = A[m,k] @ B[k,n], m ~ k >> n. Paper's TSM2R. Differentiable.

    ``splits=`` pins the split-reduction factor per call (like the block
    kwargs it beats the policy, the tuning table, and the model; S=1 is
    the sequential kernel).
    """
    p = _effective_policy(policy, spec, interpret)
    return _tsm2r_diff(a, b, block_m, block_k, splits, p)


# ---------------------------------------------------------------------------
# TSM2L
# ---------------------------------------------------------------------------

def _tsm2l_impl(a, b, block_m, policy):
    m, k = a.shape
    n = b.shape[1]
    interpret = compat.auto_interpret(policy.interpret)
    block_m = resolve_params("tsm2l", m, k, n, a.dtype, policy,
                             block_m=block_m, interpret=interpret)["block_m"]
    a_p = _pad_to(a, 0, block_m)
    _note_launch("tsm2l", (a_p.shape[0], k, n), {"block_m": block_m})
    quant = getattr(policy, "quant", "none") == "int8"
    vmem = _vmem_limit(policy, "tsm2l", (m, k, n),
                       jnp.int8 if quant else a.dtype)
    if quant:
        a_q, a_s = kquant.quantize_blocks(a_p, block_m)
        b_q, b_s = kquant.quantize_tensor(b)
        out = kquant.tsm2l_q8_pallas(a_q, b_q, a_s, b_s, out_dtype=a.dtype,
                                     block_m=block_m, interpret=interpret,
                                     vmem_limit_bytes=vmem)
    else:
        out = tsm2l_pallas(a_p, b, block_m=block_m, interpret=interpret,
                           vmem_limit_bytes=vmem)
    return out[:m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _tsm2l_diff(a, b, block_m, policy):
    return _tsm2l_impl(a, b, block_m, policy)


def _tsm2l_fwd(a, b, block_m, policy):
    return _tsm2l_impl(a, b, block_m, policy), (a, b)


def _tsm2l_bwd(block_m, policy, res, ct):
    a, b = res
    tsmm = _dispatcher()
    bp = tsmm.backward_policy(policy)
    # Abar[m,k] = Chat[m,n] B^T[n,k]: m >> n ~ k -- exactly TSM2L again.
    da = tsmm.tsmm(ct, b.T, policy=bp)
    # Bbar[k,n] = A^T Chat: tall-m reduction -> TSMT.
    db = tsmm.tsmm_t(a, ct, policy=bp)
    return da.astype(a.dtype), db.astype(b.dtype)


_tsm2l_diff.defvjp(_tsm2l_fwd, _tsm2l_bwd)


def tsm2l(a: jnp.ndarray, b: jnp.ndarray, *, block_m: int | None = None,
          spec: perf_model.TPUSpec | None = None,
          interpret: bool | None = None,
          policy=None) -> jnp.ndarray:
    """C[m,n] = A[m,k] @ B[k,n], m >> k ~ n. Paper's TSM2L. Differentiable."""
    p = _effective_policy(policy, spec, interpret)
    return _tsm2l_diff(a, b, block_m, p)


# ---------------------------------------------------------------------------
# TSMT
# ---------------------------------------------------------------------------

def _tsmt_impl(x, y, block_m, block_a, splits, policy):
    m, a_dim = x.shape
    b_dim = y.shape[1]
    interpret = compat.auto_interpret(policy.interpret)
    p = resolve_params("tsmt", m, a_dim, b_dim, x.dtype, policy,
                       block_m=block_m, block_a=block_a, splits=splits,
                       interpret=interpret)
    block_m, block_a, splits = p["block_m"], p["block_a"], p["splits"]
    quant = getattr(policy, "quant", "none") == "int8"
    vmem = _vmem_limit(policy, "tsmt", (m, a_dim, b_dim),
                       jnp.int8 if quant else x.dtype)
    if splits == 1:
        x_p = _pad_to(_pad_to(x, 0, block_m), 1, block_a)
        y_p = _pad_to(y, 0, block_m)
        _note_launch("tsmt", (x_p.shape[0], x_p.shape[1], b_dim), p)
        if quant:
            x_q, x_s = kquant.quantize_blocks(x_p, block_m)
            y_q, y_s = kquant.quantize_blocks(y_p, block_m)
            out = kquant.tsmt_q8_pallas(
                x_q, y_q, x_s, y_s, out_dtype=x.dtype, block_m=block_m,
                block_a=block_a, interpret=interpret, vmem_limit_bytes=vmem)
        else:
            out = tsmt_pallas(x_p, y_p, block_m=block_m, block_a=block_a,
                              interpret=interpret, vmem_limit_bytes=vmem)
        return out[:a_dim]
    # Split reduction over m: pad to whole slices (zeros contribute
    # nothing to the partial sums), reduce the (S, a, b) f32 stack.
    x_p = _pad_to(_pad_to(x, 0, splits * block_m), 1, block_a)
    y_p = _pad_to(y, 0, splits * block_m)
    _note_launch("tsmt", (x_p.shape[0], x_p.shape[1], b_dim), p)
    if quant:
        x_q, x_s = kquant.quantize_blocks(x_p, block_m)
        y_q, y_s = kquant.quantize_blocks(y_p, block_m)
        parts = kquant.tsmt_q8_pallas_split(
            x_q, y_q, x_s, y_s, block_m=block_m, block_a=block_a,
            splits=splits, interpret=interpret, vmem_limit_bytes=vmem)
    else:
        parts = tsmt_pallas_split(x_p, y_p, block_m=block_m,
                                  block_a=block_a, splits=splits,
                                  interpret=interpret, vmem_limit_bytes=vmem)
    br = epilogue_block_r(splits, x_p.shape[1], b_dim, block_r=block_a,
                          vmem_budget=vmem)
    if br is not None:
        _note_launch("reduce", (splits, x_p.shape[1], b_dim),
                     {"block_r": br})
    out = reduce_partials(parts, x.dtype, block_r=block_a,
                          vmem_budget=vmem, interpret=interpret)
    return out[:a_dim]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _tsmt_diff(x, y, block_m, block_a, splits, policy):
    return _tsmt_impl(x, y, block_m, block_a, splits, policy)


def _tsmt_fwd(x, y, block_m, block_a, splits, policy):
    return _tsmt_impl(x, y, block_m, block_a, splits, policy), (x, y)


def _tsmt_bwd(block_m, block_a, splits, policy, res, ct):
    x, y = res
    tsmm = _dispatcher()
    bp = tsmm.backward_policy(policy)
    # Xbar[m,a] = Y[m,b] Chat^T[b,a] and Ybar[m,b] = X[m,a] Chat[a,b]:
    # both are tall-m, tiny-contraction products -- TSM2L-shaped.
    dx = tsmm.tsmm(y, ct.T, policy=bp)
    dy = tsmm.tsmm(x, ct, policy=bp)
    return dx.astype(x.dtype), dy.astype(y.dtype)


_tsmt_diff.defvjp(_tsmt_fwd, _tsmt_bwd)


def tsmt(x: jnp.ndarray, y: jnp.ndarray, *, block_m: int | None = None,
         block_a: int | None = None, splits: int | None = None,
         spec: perf_model.TPUSpec | None = None,
         interpret: bool | None = None,
         policy=None) -> jnp.ndarray:
    """C[a,b] = X[m,a]^T @ Y[m,b], m >> a, b. TSMTTSM-style extension.
    Differentiable.

    ``splits=`` pins the split-reduction factor per call (S=1 sequential).
    Raises ``ValueError`` when the unblocked output dim b exceeds the
    accumulator limit -- ``TSMT_MAX_B``, or the scope's ``max_skinny_t``
    when a policy deliberately raised the classifier past it (raising the
    threshold is an explicit opt-in to the bigger VMEM tile); reorient the
    operands (or use ``tsmm.tsmm``) instead.
    """
    p = _effective_policy(policy, spec, interpret)
    limit = max(TSMT_MAX_B, getattr(p, "max_skinny_t", TSMT_MAX_B))
    if y.ndim == 2 and y.shape[1] > limit:
        raise ValueError(
            f"tsmt small output dim b={y.shape[1]} exceeds the unblocked "
            f"f32 accumulator limit ({limit}): the (block_a, b) "
            "accumulator is a single VMEM tile. Orient the operands so the "
            "larger output dim comes first (C = tsmt(y, x).T), or dispatch "
            "through tsmm.tsmm_t, which classifies such shapes dense.")
    return _tsmt_diff(x, y, block_m, block_a, splits, p)


# Quantization primitive, owned by the contract layer (one copy).
_ceil_mult = contracts.ceil_mult


# Re-exported oracles so callers can A/B against the pure-jnp path.
tsm2r_ref = ref.tsm2r_ref
tsm2l_ref = ref.tsm2l_ref
tsmt_ref = ref.tsmt_ref


def tsqr(a: jnp.ndarray, *, policy=None, passes: int | None = None,
         shift_rel: float | None = None):
    """Tall-skinny QR (CholeskyQR2) built on :func:`tsmt` + :func:`tsm2l`.

    Thin re-export of :func:`repro.linalg.tsqr` for symmetry with the
    kernel entries; see that module for numerics and the distributed
    ``tree_tsqr`` variant. Imported lazily -- ``repro.linalg`` consumes
    the dispatcher above, so a top-level import would be cyclic.
    """
    from repro import linalg
    return linalg.tsqr(a, policy=policy, passes=passes,
                       shift_rel=shift_rel)
