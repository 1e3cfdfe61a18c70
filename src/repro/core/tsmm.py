"""Shape-dispatched tall-and-skinny matmul behind a scoped ``GemmPolicy``.

``tsmm(a, b)`` inspects shapes against the perf model (paper Section 3.1.8's
bound classifier) and routes to:

* TSM2R  when m ~ k >> n (skinny right operand, memory-bound stream of A),
* TSM2L  when m >> k ~ n (tiny contraction, latency-regime),
* XLA ``dot_general`` otherwise (regular shapes belong on the stock MXU
  path -- the paper's observation that cuBLAS already wins there).

``tsmm_t(x, y)`` is the transposed entry (X^T Y over a huge m). Both accept
N-d batched lhs operands: ``tsmm`` collapses the leading dims of a
``(..., m, k)`` lhs into the tall dim, ``tsmm_t`` collapses them into the
reduction, so call sites (``layers.dense``, PowerSGD, ABFT) never hand-roll
reshapes.

Every knob that used to live in env vars and per-call kwargs is owned by an
explicit, lexically scoped :class:`GemmPolicy`:

    with tsmm.policy(mode="dense"):          # A/B arm: stock XLA everywhere
        loss = train_step(state, batch)
    with tsmm.policy(spec=perf_model.V5P, interpret=False):
        out = serve_step(params, batch)

Dispatch is static (shapes and the policy are trace-time constants under
jit), so a jitted caller bakes the scoped policy into its cache entry --
entering a different scope does NOT retroactively change already-compiled
functions; A/B arms need separate jit caches exactly as before.

Behind the policy sits a pluggable backend registry mapping a classified
shape to an executor:

* ``pallas-tpu``  -- the Mosaic kernels (interpret auto-detected off-TPU),
* ``interpret``   -- the same kernels pinned to interpret mode,
* ``dense-xla``   -- plain ``dot_general``,
* ``shard_map``   -- wraps the dispatch per-shard over the data-parallel
  mesh axes, so per-device shapes stay tall-and-skinny under DP. This
  replaces the old hard guard that sent every call under a multi-chip
  ``jax.set_mesh(mesh)`` scope to the dense path: when the tall dim divides the DP
  axes and the per-shard shape still classifies tall-skinny, the kernels
  now run per shard (``tsmm_t`` reduces the per-shard partial products
  per ``GemmPolicy.reduce``: psum by default, stacked partials on
  ``reduce="none"``),
* ``shard_map-scatter`` -- the sharded-*output* variant for ``tsmm_t``:
  per-shard partials are combined with ``psum_scatter`` instead of a full
  ``psum``, so the (small) ``a x b`` product comes back row-sharded over
  the DP axes instead of replicated. Selected automatically for ``mmt``
  dispatch when the policy asks ``reduce="psum_scatter"`` and the output
  rows divide the DP shard count; this is the path for consumers that
  keep the product sharded (PowerSGD factors, ZeRO-sharded optimizer
  grads) and removes the structural all-gather between the kernel and
  those consumers.

``register_executor`` adds new backends; ``GemmPolicy.executor`` pins one.
Every executor invocation passes through the deterministic fault-injection
tap (``ft/inject.py``), and ``GemmPolicy.abft`` wraps kernel-kind results
in an online Huang-Abraham checksum verify/locate/correct guard whose
checksum GEMMs dispatch right back through this module (see the policy
docstring and ``ft/abft.py``).

DP axes are no longer a hard-coded convention: with
``GemmPolicy.dp_axes=None`` the dispatcher derives them from the ambient
mesh via :func:`derive_dp_axes` (conventional DP names first, then any
axis not named like a model/pipeline axis; a single-axis mesh is always
DP). An explicit ``dp_axes=(...)`` still overrides.

Both entries are differentiable: the ops they dispatch to carry custom_vjp
rules that take the policy through their nondiff args, so the backward
re-enters this dispatcher under the *caller's* scope (the VJP of one
tall-skinny class lands in another).

Legacy env vars still work as process-default aliases (deprecated):
``REPRO_TSMM=off`` constructs the process default with ``mode="dense"`` and
``REPRO_BF16_PARAM_GRADS=1`` with ``param_dtype_grads=True``. They are read
once at import (never inside traced code); ``refresh_default_policy()``
re-reads them.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
import os
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from repro.core import perf_model
# inject sits below every layer (jax + stdlib only, no repro imports), so
# the dispatcher can route each executor invocation through its fault tap.
from repro.ft import inject as _inject
from repro.kernels import compat, ops

__all__ = [
    "GemmPolicy",
    "policy",
    "current_policy",
    "default_policy",
    "refresh_default_policy",
    "backward_policy",
    "classify_gemm",
    "classify_gemm_t",
    "tsmm",
    "tsmm_t",
    "bound_class",
    "derive_dp_axes",
    "register_executor",
    "unregister_executor",
    "executors",
    "executor_reduce_contract",
    "record_dispatches",
    "DispatchEvent",
    "LaunchMeta",
    "note_launch",
    "enabled",
]

# Classifier threshold defaults. These only seed the GemmPolicy fields
# below -- dispatch always reads the policy, never these constants.
SKINNY_RATIO = 16
MAX_SKINNY = 256
MIN_TALL = 2048
MAX_SKINNY_T = 512
SKINNY_RATIO_T = SKINNY_RATIO // 4

# The repo-wide *convention* for which mesh axes carry the batch. These are
# no longer the only names the dispatcher understands: they seed
# ``derive_dp_axes``, which reads the ambient mesh (see below). A policy can
# still pin axes per scope via GemmPolicy.dp_axes.
DP_AXIS_NAMES = ("pod", "data")

# Names treated as data-parallel when deriving dp axes from a mesh, in
# addition to DP_AXIS_NAMES, and names that mark an axis as model/pipeline
# parallel (never DP). Anything in neither set is DP only when no
# conventional DP name is present on the mesh.
_DP_NAME_HINTS = DP_AXIS_NAMES + ("dp", "batch", "replica", "replicas")
_MODEL_NAME_HINTS = frozenset({
    "model", "tensor", "tp", "mp", "expert", "experts", "ep",
    "pipe", "pipeline", "stage", "pp", "seq", "sequence", "sp",
})

_MM_KINDS = ("auto", "dense", "tsm2r", "tsm2l")
_MMT_KINDS = ("auto", "dense", "tsmt")
_ALL_MODES = ("auto", "dense", "tsm2r", "tsm2l", "tsmt")
_SHARD_MAP_MODES = ("auto", "never", "require", "local")
_REDUCE_MODES = ("psum", "psum_scatter", "none")
_QUANT_MODES = ("none", "int8")
_ABFT_MODES = ("none", "verify", "correct")


@dataclasses.dataclass(frozen=True)
class GemmPolicy:
    """Everything the GEMM dispatcher is allowed to decide from.

    Threshold fields (derivations against ``core/perf_model``, v5e/bf16):

    * ``min_tall`` = 2048: below ~2048 tall rows the kernel's fixed costs
      (``TPUSpec.dma_latency`` ~ 1us of pipeline prologue plus per-step
      overhead) rival the whole modeled stream time
      (2048 x 256 x 2 B / 819 GB/s ~ 1.3us) -- launching a custom kernel
      cannot win.
    * ``max_skinny`` = 256 (= 2 MXU lane tiles): past two 128-lane tiles of
      output columns the generic MXU path's efficiency (n/128 per pass) is
      high enough that the streaming formulation's bandwidth advantage is
      gone.
    * ``skinny_ratio`` = 16: a dim counts as skinny only when >= 16x smaller
      than its partner; at milder aspect ratios the problem sits near the
      roofline ridge where the stock path already streams close to peak.
    * ``max_skinny_t`` = 512: the TSMT kernel keeps its (block_a, b) f32
      accumulator as a single unblocked VMEM tile, and 512 is
      ``t2_threshold(V5E, bf16)`` ~ 481 -- the paper's memory/compute
      boundary -- rounded up to the next lane multiple: past it the problem
      is compute-bound and belongs on the MXU path.
    * ``skinny_ratio_t`` = ``skinny_ratio // 4`` = 4: the transposed entry
      stays profitable at 4x milder aspect ratios because BOTH operands
      stream over the same tall m exactly once (there is no per-m-block
      B re-fetch term in ``tsmt_model_time``).

    ``mode`` pins dispatch: "auto" classifies; "dense" forces the XLA path
    everywhere; a kind name ("tsm2r"/"tsm2l" for ``tsmm``, "tsmt" for
    ``tsmm_t``) forces that kernel for its own entry and leaves the other
    entry on auto (so VJP re-dispatch stays shape-correct).

    ``interpret``: tri-state Pallas interpret flag (None = auto-detect:
    interpret off-TPU, compiled on a TPU; only an explicit True selects
    interpret on a TPU). ``spec``: the hardware model driving block-size
    choice and the kernels' scoped-VMEM limit; None (the default) resolves
    at construction to the spec of the device JAX runs on
    (``perf_model.device_spec``: by ``device_kind`` on a TPU, an error for
    an unknown chip, V5E off-TPU). ``param_dtype_grads``: emit parameter
    gradients in the parameter dtype instead of f32 (halves per-device grad
    memory under pure-DP/ZeRO-1; accumulation inside each dot stays f32).

    ``shard_map``: "auto" wraps dispatch per-shard under a >1-device mesh
    context when the tall dim divides the DP axes and the per-shard shape
    still classifies tall-skinny (dense fallback otherwise, exactly the old
    guard); "never" restores the old always-dense-under-mesh behavior;
    "require" raises instead of falling back (tests/benchmarks); "local"
    ignores the mesh context entirely and dispatches on the shapes as seen
    -- what the shard_map executor sets for its per-shard bodies, and what
    call sites inside their *own* shard_map should scope.
    ``dp_axes``: mesh axis names carrying the batch; None = derive from
    the ambient mesh (:func:`derive_dp_axes` -- conventional DP names
    first, then non-model-named axes; shared with
    ``distributed.sharding``). An explicit tuple is filtered against the
    mesh's axis names but otherwise taken as-is.
    ``executor``: pin a registered backend by name, bypassing selection.

    ``reduce``: how ``tsmm_t``'s per-shard partial products combine under
    the shard_map executors (it has no effect outside a multi-chip mesh
    scope, and none on the ``tsmm`` entry, whose shards never reduce):

    * "psum" (default) -- full all-reduce; output replicated. The drop-in
      semantics every caller had before this knob existed.
    * "psum_scatter" -- reduce-scatter; the global (a, b) output is
      row-sharded over the DP axes. Same global shape and values as
      "psum", different layout: consumers that immediately re-shard or
      only touch their own rows (PowerSGD factors, ZeRO-1 optimizer
      shards) skip the all-gather half of the all-reduce. Falls back to
      dense-xla when the output rows don't divide the shard count
      (shard_map="require" raises instead).
    * "none" -- no collective: shards return their *partial* products,
      stacked, so the global output is (shards * a, b). For callers that
      run their own reduction schedule. Never auto-selected over
      "psum"-shaped consumers' objections: you only get it by setting it.

    Backward passes re-dispatch with the *matching* collective
    (``backward_policy`` keeps ``reduce`` -- a psum_scatter scope keeps
    its weight-gradient ``tsmm_t``s sharded too), except "none", which
    downgrades to "psum" so cotangent shapes stay equal to primal shapes
    (custom_vjp requires it).

    ``tuning_table``: a ``core.autotune.TuningTable`` of measured-best
    block params (None = pure analytic choice). When set, ``kernels/ops``
    consults the measured winner for the shape's bucket before falling
    back to ``perf_model.choose_params_*`` (run under the table's
    bucket-local fitted spec when one exists); explicit per-call block
    kwargs still win over both. Must stay hashable (policies flow through
    ``custom_vjp`` nondiff args), which TuningTable is; typed loosely here
    to keep the dispatcher import-cycle-free.

    ``split``: the split-reduction (split-K) knob for the kernels whose
    reduction axis is gridded (``tsm2r``, ``tsmt``; ``tsm2l`` keeps its
    whole contraction VMEM-resident and has nothing to split):

    * "auto" (default) -- the split factor S is tuned like a block size:
      measured winner from the tuning table, else the occupancy-aware
      analytic argmin (``perf_model.choose_params_*``, which only ever
      prefers S > 1 when the grid's parallel cells under-occupy
      ``spec.n_cores``).
    * an int -- pin exactly that S for every dispatched kernel in scope
      (1 = sequential). Shape-specific, so :func:`backward_policy` strips
      it back to "auto" -- the cotangent GEMMs have different shapes.
    * "never" -- force the sequential kernels everywhere, table and model
      notwithstanding (the A/B control arm). Scope-wide caller intent, so
      the backward *preserves* it.

    Split partials are summed inside the op's epilogue, so under the
    shard_map executors each shard splits its own slice locally and the
    psum/psum_scatter/none contract on the cross-shard reduction is
    unchanged -- ``reduce=`` and ``split`` compose freely.

    ``quant``: low-precision operand storage for the Pallas kernel paths
    (``kernels/quant.py``):

    * "none" (default) -- operands stream at their own dtype; nothing
      changes anywhere.
    * "int8" -- operands are symmetrically quantized per resolved kernel
      row block (tall operand; the small operand gets one per-tensor
      scale), streamed as int8 tiles, and dequantized in the f32
      accumulate epilogue; outputs return in the caller's dtype. Block
      resolution, tuning-table lookups and contract checks all run
      against the int8 *effective dtype* (1 byte/elem HBM pricing, 32-row
      sublane tiles), so autotuned grids are measured for what actually
      launches. Only the kernel executors quantize: "dense-xla" ignores
      the knob (a dense fallback is exact, never silently low-precision),
      and split partials are dequantized before they leave the kernel so
      the reduce tree and shard_map collectives are unchanged. Scope-wide
      numeric intent, so :func:`backward_policy` preserves it -- cotangent
      GEMMs under an int8 scope quantize too (expect looser gradient
      tolerances, as with any quantization-aware setup).

    ``abft``: online algorithm-based fault tolerance for the kernel-kind
    dispatches (``ft/abft.py`` owns the math; this knob owns the wiring):

    * "none" (default) -- no checksums, zero overhead: the wrap is never
      entered and the dispatch path is byte-identical to before the knob
      existed.
    * "verify" -- every tsm2r/tsm2l/tsmt result is checked against
      Huang-Abraham weighted column checksums computed *through this same
      dispatcher* (checksum linearity: the checksum of the output equals
      the GEMM of the operand checksum), with a shape/dtype-derived
      tolerance (``ft.abft.tolerance``). A detected silent data
      corruption poisons the full output with NaN -- trace-safe, no host
      callback -- so any non-finite guard downstream (the train loop's
      ``step_ok``) sees it.
    * "correct" -- additionally localizes a single faulty output row from
      the ramp/plain checksum-deviation ratio and repairs it in place
      (bit-flip faults repair bit-exactly via a nearest-single-bit-flip
      snap); faults the localization cannot explain (multi-row damage,
      non-finite wreckage) fall back to the NaN poison.

    The checksum GEMMs dispatch with ``abft="none"`` (no recursion), f32
    operands, and the scope's executor pin stripped. Dense-kind dispatches
    are not wrapped (the stock XLA path is not the SDC surface this guards)
    and neither are the *outer* shard_map events -- the per-shard
    re-dispatch inherits ``abft`` through the inner policy, so each shard
    verifies/corrects its own local GEMM. Scope-wide integrity intent, so
    :func:`backward_policy` preserves it (contracts ``abft-policy`` rule):
    cotangent GEMMs under a verify scope are verified too.
    """

    mode: str = "auto"
    spec: perf_model.TPUSpec | None = None
    skinny_ratio: int = SKINNY_RATIO
    max_skinny: int = MAX_SKINNY
    min_tall: int = MIN_TALL
    max_skinny_t: int = MAX_SKINNY_T
    skinny_ratio_t: int = SKINNY_RATIO_T
    interpret: bool | None = None
    param_dtype_grads: bool = False
    shard_map: str = "auto"
    dp_axes: tuple[str, ...] | None = None
    executor: str | None = None
    tuning_table: object | None = None
    reduce: str = "psum"
    split: str | int = "auto"
    quant: str = "none"
    abft: str = "none"
    # Trace-time contract assertion: when set, kernels/ops re-checks every
    # resolved launch configuration against analysis.contracts (the same
    # predicates the perf model's candidate filter and the offline auditor
    # use) and raises ValueError on a violation instead of launching.
    # Preserved by backward_policy (it is scope-wide intent, like a dense
    # pin); off by default -- the predicates are cheap but the mode exists
    # for CI, tests and debugging, not for the hot path.
    verify_contracts: bool = False

    def __post_init__(self):
        if self.spec is None:
            object.__setattr__(self, "spec", perf_model.device_spec())
        s = self.split
        if not (s in ("auto", "never")
                or (isinstance(s, int) and not isinstance(s, bool)
                    and s >= 1)):
            raise ValueError(
                f"unknown GemmPolicy split {self.split!r}: valid values are "
                "'auto', 'never', or a positive int split factor")
        if self.mode not in _ALL_MODES:
            raise ValueError(
                f"unknown GemmPolicy mode {self.mode!r}: valid modes are "
                f"{', '.join(_ALL_MODES)}")
        if self.shard_map not in _SHARD_MAP_MODES:
            raise ValueError(
                f"unknown GemmPolicy shard_map {self.shard_map!r}: valid "
                f"values are {', '.join(_SHARD_MAP_MODES)}")
        if self.reduce not in _REDUCE_MODES:
            raise ValueError(
                f"unknown GemmPolicy reduce {self.reduce!r}: valid "
                f"values are {', '.join(_REDUCE_MODES)}")
        if self.quant not in _QUANT_MODES:
            raise ValueError(
                f"unknown GemmPolicy quant {self.quant!r}: valid "
                f"values are {', '.join(_QUANT_MODES)}")
        if self.abft not in _ABFT_MODES:
            raise ValueError(
                f"unknown GemmPolicy abft {self.abft!r}: valid "
                f"values are {', '.join(_ABFT_MODES)}")

    def with_(self, **overrides) -> "GemmPolicy":
        return dataclasses.replace(self, **overrides)


# ---------------------------------------------------------------------------
# Process default (legacy env-var aliases) + lexical scoping
# ---------------------------------------------------------------------------

def _policy_from_env() -> GemmPolicy:
    """Build the process-default policy from the deprecated env vars.

    Called at import and from ``refresh_default_policy()`` only -- never
    from traced code, so flipping an env var mid-process does nothing until
    an explicit refresh (and even then only affects future traces).
    """
    kw = {}
    raw = os.environ.get("REPRO_TSMM")
    if raw is not None:
        warnings.warn(
            "REPRO_TSMM is deprecated; use `with tsmm.policy(mode=...)` or "
            "tsmm.refresh_default_policy() after changing it",
            DeprecationWarning, stacklevel=3)
        if raw.lower() in ("off", "0", "false"):
            kw["mode"] = "dense"
    raw = os.environ.get("REPRO_BF16_PARAM_GRADS")
    if raw is not None:
        warnings.warn(
            "REPRO_BF16_PARAM_GRADS is deprecated; use "
            "`with tsmm.policy(param_dtype_grads=True)`",
            DeprecationWarning, stacklevel=3)
        if raw == "1":
            kw["param_dtype_grads"] = True
    return GemmPolicy(**kw)


# Built on first use, not at import: resolving the default spec asks JAX
# for its devices, and importing the dispatcher must not start a backend.
_DEFAULT_POLICY: GemmPolicy | None = None
_POLICY_VAR: contextvars.ContextVar[GemmPolicy | None] = \
    contextvars.ContextVar("repro_gemm_policy", default=None)


def default_policy() -> GemmPolicy:
    """The process-default policy (env-var aliases applied)."""
    global _DEFAULT_POLICY
    if _DEFAULT_POLICY is None:
        _DEFAULT_POLICY = _policy_from_env()
    return _DEFAULT_POLICY


def refresh_default_policy() -> GemmPolicy:
    """Re-read the legacy env vars into the process default (tests/tools)."""
    global _DEFAULT_POLICY
    _DEFAULT_POLICY = _policy_from_env()
    return _DEFAULT_POLICY


def current_policy() -> GemmPolicy:
    """The innermost active ``with tsmm.policy(...)`` scope, else the
    process default."""
    return _POLICY_VAR.get() or default_policy()


@contextlib.contextmanager
def policy(base: GemmPolicy | None = None, /, **overrides):
    """Scope a dispatch policy: ``with tsmm.policy(mode="dense"): ...``.

    ``base`` (positional) starts from an explicit GemmPolicy instead of the
    current scope; keyword overrides are applied on top via
    ``dataclasses.replace``. Scopes nest and restore on exit (also across
    exceptions). The policy is captured at *trace* time: jit-compiled
    callers keep the policy they were traced under.
    """
    p = base if base is not None else current_policy()
    if overrides:
        p = dataclasses.replace(p, **overrides)
    token = _POLICY_VAR.set(p)
    try:
        yield p
    finally:
        _POLICY_VAR.reset(token)


def backward_policy(p: GemmPolicy) -> GemmPolicy:
    """Policy for VJP re-dispatch: keep the caller's scope (spec,
    thresholds, interpret, a full-dense pin, the ``reduce`` collective)
    but drop a forward-kind force and any executor pin -- cotangent shapes
    classify for themselves, and a pinned ``shard_map`` executor must not
    recurse per-shard. ``reduce="none"`` downgrades to "psum": a stacked-
    partials gradient would change the cotangent's shape, which custom_vjp
    forbids; "psum_scatter" is kept, so weight-gradient ``tsmm_t``s in the
    backward land sharded without an extra all-gather. An *int* ``split``
    pin is stripped to "auto" (it was chosen for the forward shape; the
    cotangent GEMMs pick their own), while "never" is preserved -- it is
    scope-wide intent, like a dense pin. ``quant`` is likewise preserved
    (``dataclasses.replace`` carries it): an int8 scope keeps its
    cotangent GEMMs quantizable, per the contracts ``backward-quant``
    rule. ``abft`` is preserved the same way (contracts ``abft-policy``
    rule): integrity intent is scope-wide, so cotangent GEMMs under a
    verify/correct scope get their own checksums."""
    mode = p.mode if p.mode in ("auto", "dense") else "auto"
    reduce_ = "psum" if p.reduce == "none" else p.reduce
    split = "auto" if isinstance(p.split, int) else p.split
    if (mode == p.mode and p.executor is None and reduce_ == p.reduce
            and split == p.split):
        return p
    return dataclasses.replace(p, mode=mode, executor=None, reduce=reduce_,
                               split=split)


def enabled() -> bool:
    """Deprecated alias: True unless the current policy pins the dense
    path (the old ``REPRO_TSMM=off`` check)."""
    return current_policy().mode != "dense"


# ---------------------------------------------------------------------------
# Shape classification (thresholds owned by the policy)
# ---------------------------------------------------------------------------

def classify_gemm(m: int, k: int, n: int,
                  policy: GemmPolicy | None = None) -> str:
    """Return one of 'tsm2r' | 'tsm2l' | 'dense'."""
    p = policy if policy is not None else current_policy()
    if m >= p.min_tall and n <= p.max_skinny and m >= p.skinny_ratio * n:
        if k <= p.max_skinny:              # m >> k ~ n: tiny contraction
            return "tsm2l"
        if k >= p.skinny_ratio * n:        # m ~ k >> n
            return "tsm2r"
    return "dense"


def classify_gemm_t(m: int, a_dim: int, b_dim: int,
                    policy: GemmPolicy | None = None) -> str:
    """Transposed-entry classifier: 'tsmt' | 'dense' for X[m,a]^T Y[m,b].

    Thresholds (``max_skinny_t``, ``skinny_ratio_t``) are policy fields;
    see the GemmPolicy docstring for their perf-model derivation.
    """
    p = policy if policy is not None else current_policy()
    if (m >= p.min_tall and b_dim <= p.max_skinny_t
            and m >= p.skinny_ratio_t * max(a_dim, b_dim)):
        return "tsmt"
    return "dense"


# ---------------------------------------------------------------------------
# Dispatch spy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LaunchMeta:
    """One kernel launch a dispatch resolved to, as derived from the pure
    grid contract (``analysis.contracts.launch_grid``) by the op impls at
    trace time. ``kind`` includes "reduce" for the split-partials epilogue;
    ``splits`` is the *resolved* S (1 for the sequential kernels). The
    dataflow verifier proves this derivation equals what ``pallas_call``
    actually captures (its ``launch-meta-drift`` rule), so spy assertions
    on these fields are assertions about the real launch."""

    kind: str                           # "tsm2r"|"tsm2l"|"tsmt"|"reduce"
    grid: tuple[int, ...]
    dimension_semantics: tuple[str, ...]
    splits: int = 1


@dataclasses.dataclass(frozen=True)
class DispatchEvent:
    """One routing decision: which entry, classified kind, chosen executor,
    and the (tall, minor, minor) shape it was made for. Emitted at trace
    time -- a cached jit call emits nothing. ``split`` records the policy's
    split knob at dispatch ("auto" | "never" | a pinned int); ``quant``
    records the quantization knob ("none" | "int8") so spies can assert a
    quantized scope actually reached a quantized launch; ``launches``
    carries one :class:`LaunchMeta` per Pallas launch the executor's trace
    noted (via :func:`note_launch`) -- the resolved grid, semantics and S,
    so spies can assert grid shape, not just routing. Dense/XLA arms note
    nothing; the outer event of a shard_map dispatch is also empty (the
    per-shard re-dispatch events carry their own launches).

    ``abft`` records whether THIS dispatch's result is wrapped by the
    online checksum guard ("none" | "verify" | "correct"): the protected
    GEMM of an abft scope carries the mode, while the checksum GEMMs the
    wrap itself dispatches carry "none" -- so a spy asserts exactly one
    guarded event per protected call. ``faults`` carries the
    ``ft.inject.GemmFault``s the injection tap actually applied inside
    this dispatch (empty outside an injection scope), letting chaos tests
    assert the planned fault landed where the plan said."""

    entry: str       # "mm" (A @ B) | "mmt" (X^T Y)
    kind: str        # "tsm2r" | "tsm2l" | "tsmt" | "dense"
    executor: str    # registry key
    shape: tuple[int, int, int]
    split: str | int = "auto"
    quant: str = "none"
    launches: tuple = ()       # of LaunchMeta
    abft: str = "none"
    faults: tuple = ()         # of ft.inject.GemmFault


_LISTENERS: list = []

# Stack of per-dispatch LaunchMeta collectors: the public entries push one
# around their executor invocation (only while spies listen); the ops impls
# report resolved launches into the innermost frame via note_launch.
_LAUNCH_NOTES: list = []

# Parallel stack of per-dispatch applied-fault collectors: _run_executor
# reports the GemmFaults the injection tap landed into the innermost frame
# so the emitted DispatchEvent carries them.
_FAULT_NOTES: list = []


def note_launch(kind: str, grid, dimension_semantics, splits: int = 1
                ) -> None:
    """Record one resolved kernel launch onto the current dispatch's event
    (no-op outside a listened-to dispatch). Called by ``kernels/ops.py``
    with ``analysis.contracts.launch_grid`` output."""
    if _LAUNCH_NOTES:
        _LAUNCH_NOTES[-1].append(LaunchMeta(
            kind, tuple(grid), tuple(dimension_semantics), splits))


def _notify(entry: str, kind: str, executor: str, shape,
            split: str | int = "auto", quant: str = "none",
            launches: tuple = (), abft: str = "none",
            faults: tuple = ()) -> None:
    if _LISTENERS:
        ev = DispatchEvent(entry, kind, executor, tuple(shape), split,
                           quant, launches, abft, faults)
        for cb in tuple(_LISTENERS):
            cb(ev)


def _run_executor(ex, entry, kind, a, b, p):
    """Invoke a registered executor through the fault-injection tap
    (``ft.inject.tap_executor``): outside an injection scope this is
    exactly ``ex(...)``; inside one, the plan's bit flips for this
    trace-order site apply and the applied faults land on the innermost
    dispatch's event (when a spy is listening)."""
    out, applied = _inject.tap_executor(ex, entry, kind, a, b, p)
    if applied and _FAULT_NOTES:
        _FAULT_NOTES[-1].extend(applied)
    return out


def _dispatch(entry: str, kind: str, executor: str, shape, policy, run,
              abft: str = "none"):
    """Run the chosen executor, then emit the spy event carrying whatever
    launches the run noted. Without listeners this is just ``run()`` --
    note_launch collectors only exist while a spy is attached. ``abft``
    is the guard mode stamped on the event: the caller passes the policy's
    mode only for the dispatch the online wrap actually protects.

    ``run`` is traced under the profile scope ``tsmm.<kind>`` (``tsm2r``,
    ``tsm2l``, ``tsmt`` or ``dense``), so a kernel launch or XLA dot in a
    profile names the route the dispatcher chose for it."""
    if not _LISTENERS:
        with jax.named_scope(f"tsmm.{kind}"):
            return run()
    notes: list = []
    fault_notes: list = []
    _LAUNCH_NOTES.append(notes)
    _FAULT_NOTES.append(fault_notes)
    try:
        with jax.named_scope(f"tsmm.{kind}"):
            out = run()
    finally:
        _FAULT_NOTES.pop()
        _LAUNCH_NOTES.pop()
        _notify(entry, kind, executor, shape, policy.split, policy.quant,
                tuple(notes), abft, tuple(fault_notes))
    return out


@contextlib.contextmanager
def record_dispatches():
    """Collect DispatchEvents for every routing decision in the scope --
    including per-shard re-dispatch inside the shard_map executor."""
    log: list[DispatchEvent] = []
    _LISTENERS.append(log.append)
    try:
        yield log
    finally:
        _LISTENERS.remove(log.append)


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------
#
# An executor is ``fn(entry, kind, a, b, policy) -> array``. The dispatcher
# hands kernel executors 2-D operands (N-d lhs already collapsed); only
# "dense-xla" may receive the original N-d lhs for the "mm" entry (its
# dot_general contracts the trailing dim without a reshape, which matters
# under GSPMD).

_EXECUTORS: dict = {}
# name -> the tuple of GemmPolicy.reduce modes the executor implements for
# the "mmt" entry (its *reduce contract*). Selection refuses to hand a
# pinned executor an mmt dispatch whose scope asks a reduce mode outside
# the contract -- the caller's layout request must fail loudly, not be
# silently rewritten (see _select_executor).
_EXECUTOR_CONTRACTS: dict = {}


def register_executor(name: str, fn, *, reduce: tuple[str, ...] | None = None,
                      overwrite: bool = False):
    """Register a backend. Returns ``fn`` (usable as a decorator factory).

    ``reduce`` declares the executor's reduce contract: the
    ``GemmPolicy.reduce`` modes it implements for ``tsmm_t`` dispatch
    (e.g. ``("psum", "none")``). ``None`` -- the back-compat default --
    declares all modes, which is right for executors that never touch a
    collective (dense, single-chip kernels: every reduce mode degenerates
    to the same single-shard product). New executors in this repo must
    declare explicitly; ``analysis/lint.py`` rule RA004 enforces it.
    """
    if name in _EXECUTORS and not overwrite:
        raise ValueError(f"executor {name!r} already registered "
                         "(pass overwrite=True to replace)")
    if reduce is not None:
        bad = [r for r in reduce if r not in _REDUCE_MODES]
        if bad:
            raise ValueError(
                f"executor {name!r} declares unknown reduce modes {bad}: "
                f"valid values are {', '.join(_REDUCE_MODES)}")
    _EXECUTORS[name] = fn
    _EXECUTOR_CONTRACTS[name] = (tuple(_REDUCE_MODES) if reduce is None
                                 else tuple(reduce))
    return fn


def unregister_executor(name: str) -> None:
    """Remove a registered backend (built-ins included -- caveat emptor)."""
    _EXECUTORS.pop(name, None)
    _EXECUTOR_CONTRACTS.pop(name, None)


def executors() -> dict:
    """Snapshot of the registry (name -> executor)."""
    return dict(_EXECUTORS)


def executor_reduce_contract(name: str) -> tuple[str, ...]:
    """The reduce modes executor ``name`` declared at registration."""
    if name not in _EXECUTOR_CONTRACTS:
        raise ValueError(f"executor {name!r} is not registered")
    return _EXECUTOR_CONTRACTS[name]


def _exec_dense_xla(entry, kind, a, b, p):
    del kind, p
    if entry == "mm":
        out = lax.dot_general(a, b, (((a.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    else:
        out = lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return out.astype(a.dtype)


def _exec_pallas(entry, kind, a, b, p):
    if kind == "tsm2r":
        return ops.tsm2r(a, b, policy=p)
    if kind == "tsm2l":
        return ops.tsm2l(a, b, policy=p)
    if kind == "tsmt":
        return ops.tsmt(a, b, policy=p)
    return _exec_dense_xla(entry, kind, a, b, p)


def _exec_interpret(entry, kind, a, b, p):
    return _exec_pallas(entry, kind, a, b,
                        dataclasses.replace(p, interpret=True))


def derive_dp_axes(mesh) -> tuple[str, ...]:
    """Data-parallel axes of ``mesh``, derived from its axis *names*.

    Rules, in order (mesh axis order is preserved in the result):

    1. axes named by the DP convention (``DP_AXIS_NAMES`` plus
       "dp"/"batch"/"replica(s)") are DP when any is present;
    2. otherwise every axis whose name does not hint model/pipeline
       parallelism ("model", "tensor", "tp", "expert", "pipe", "stage",
       "seq", ...) counts as DP -- including a single-axis mesh with a
       novel name, which is pure DP.

    A model-named axis is NEVER derived as DP, even alone: a pure
    tensor-parallel ``("model",)`` mesh keeps the dense fallback (GSPMD
    partitions the dense dot along the model axis correctly; sharding the
    batch over it would be a silently wrong layout).

    Works on Mesh and AbstractMesh (only ``axis_names`` is read). May
    return () -- e.g. a pure ("model", "pipe") mesh has no DP axes, and
    the dispatcher then falls back to dense exactly like the old guard.
    """
    names = tuple(mesh.axis_names)
    conv = tuple(a for a in names if a in _DP_NAME_HINTS)
    if conv:
        return conv
    return tuple(a for a in names if a not in _MODEL_NAME_HINTS)


def _dp_axes(mesh, p: GemmPolicy) -> tuple[str, ...]:
    if p.dp_axes is not None:
        return tuple(a for a in p.dp_axes if a in mesh.axis_names)
    return derive_dp_axes(mesh)


def _axes_size(mesh, axes) -> int:
    sizes = compat.mesh_axis_sizes(mesh)
    size = 1
    for a in axes:
        size *= sizes[a]
    return size


def _shard_map_env(p: GemmPolicy):
    """(mesh, dp axes, inner per-shard policy) for the shard_map executors.

    The inner policy dispatches on local shapes (``shard_map="local"``)
    and drops the executor pin so per-shard re-dispatch cannot recurse.
    """
    mesh = compat.get_context_mesh()
    if mesh is None:
        raise RuntimeError("shard_map executor requires an active "
                           "`jax.set_mesh(mesh)` scope")
    dp = _dp_axes(mesh, p)
    if not dp:
        raise RuntimeError(
            f"shard_map executor found no data-parallel axes on mesh "
            f"{mesh.axis_names} (policy dp_axes={p.dp_axes}; derived axes "
            f"follow tsmm.derive_dp_axes)")
    inner = dataclasses.replace(p, shard_map="local", executor=None)
    return mesh, dp, inner


def _exec_shard_map(entry, kind, a, b, p):
    """Per-shard dispatch over the DP axes of the context mesh.

    ``mm``: the tall dim shards, B replicates; each shard re-enters the
    dispatcher on its local (still tall-skinny) shape. ``mmt``: both
    operands shard over the tall reduction; per-shard partial products
    combine per ``p.reduce`` -- psum'd to a replicated output (default),
    or returned as stacked partials (``reduce="none"``: global output is
    (shards * a, b), the caller owns the reduction). The scatter variant
    lives in its own executor (``shard_map-scatter``).
    """
    del kind
    mesh, dp, inner = _shard_map_env(p)
    if entry == "mm":
        f = compat.shard_map(
            lambda a_s, b_s: tsmm(a_s, b_s, policy=inner),
            mesh=mesh,
            in_specs=(PartitionSpec(dp, None), PartitionSpec(None, None)),
            out_specs=PartitionSpec(dp, None))
        return f(a, b)
    if p.reduce == "psum_scatter":
        # Auto-selection never lands here with a scatter scope; only an
        # explicit executor="shard_map" pin can. Refuse rather than psum:
        # the caller asked for a row-sharded layout and must not silently
        # get a replicated one.
        raise RuntimeError(
            "GemmPolicy pins executor='shard_map' but reduce="
            "'psum_scatter': the sharded-output layout lives on the "
            "'shard_map-scatter' executor -- pin that instead, or drop "
            "the pin and let selection match the collective")
    if p.reduce == "none":
        f = compat.shard_map(
            lambda x_s, y_s: tsmm_t(x_s, y_s, policy=inner),
            mesh=mesh,
            in_specs=(PartitionSpec(dp, None), PartitionSpec(dp, None)),
            out_specs=PartitionSpec(dp, None))
        return f(a, b)
    f = compat.shard_map(
        lambda x_s, y_s: lax.psum(tsmm_t(x_s, y_s, policy=inner), dp),
        mesh=mesh,
        in_specs=(PartitionSpec(dp, None), PartitionSpec(dp, None)),
        out_specs=PartitionSpec(None, None))
    return f(a, b)


def _exec_shard_map_scatter(entry, kind, a, b, p):
    """Sharded-output ``tsmm_t``: per-shard partials reduce-scatter over
    the DP axes, so the global (a, b) product comes back row-sharded
    instead of replicated -- same values as the psum path, minus the
    all-gather half of the all-reduce the consumer was about to undo.
    ``mm`` has no cross-shard reduction to scatter, so this executor is
    mmt-only (pinning it via ``GemmPolicy.executor`` around a ``tsmm``
    call raises).
    """
    del kind
    if entry != "mmt":
        raise RuntimeError(
            "the shard_map-scatter executor only applies to tsmm_t (its "
            "output is the cross-shard reduction being scattered); tsmm "
            "has nothing to scatter -- use the shard_map executor")
    if p.reduce != "psum_scatter":
        # Only reachable via an explicit executor pin (selection matches
        # executors to the collective): a psum/none scope pinned onto the
        # scatter executor would silently change the output layout (or,
        # for "none", the shape) the caller's reduce= asked for.
        raise RuntimeError(
            f"GemmPolicy pins executor='shard_map-scatter' but reduce="
            f"{p.reduce!r}: the scatter executor implements exactly "
            "reduce='psum_scatter' -- set that, or drop the pin")
    mesh, dp, inner = _shard_map_env(p)
    shards = _axes_size(mesh, dp)
    if a.shape[1] % shards != 0:
        raise RuntimeError(
            f"psum_scatter output rows ({a.shape[1]}) do not divide the "
            f"{shards} shards of dp axes {dp}; auto-selection falls back "
            "to dense for this shape -- only an explicit executor pin "
            "reaches this error")
    f = compat.shard_map(
        lambda x_s, y_s: compat.psum_scatter(
            tsmm_t(x_s, y_s, policy=inner), dp),
        mesh=mesh,
        in_specs=(PartitionSpec(dp, None), PartitionSpec(dp, None)),
        out_specs=PartitionSpec(dp, None))
    return f(a, b)


# Single-chip executors implement every reduce mode trivially (one shard:
# psum == psum_scatter == none); the shard_map pair splits the collective
# modes between them -- that split is exactly what the contracts encode.
register_executor("dense-xla", _exec_dense_xla,
                  reduce=("psum", "psum_scatter", "none"))
register_executor("pallas-tpu", _exec_pallas,
                  reduce=("psum", "psum_scatter", "none"))
register_executor("interpret", _exec_interpret,
                  reduce=("psum", "psum_scatter", "none"))
register_executor("shard_map", _exec_shard_map, reduce=("psum", "none"))
register_executor("shard_map-scatter", _exec_shard_map_scatter,
                  reduce=("psum_scatter",))


# ---------------------------------------------------------------------------
# Executor selection
# ---------------------------------------------------------------------------

def _select_executor(entry: str, kind: str, m_tall: int, d1: int, d2: int,
                     p: GemmPolicy, forced: bool) -> str:
    if p.executor is not None:
        if p.executor not in _EXECUTORS:
            raise ValueError(
                f"GemmPolicy.executor {p.executor!r} is not registered: "
                f"known executors are {sorted(_EXECUTORS)}")
        if entry == "mmt":
            # Enforce the executor's declared reduce contract at selection
            # time (mmt only: mm shards never reduce, so every contract is
            # vacuously satisfied there). A pinned executor must refuse a
            # collective outside its contract rather than silently change
            # the output layout the scope's reduce= asked for. The executor
            # bodies keep their own guards as defense in depth.
            contract = _EXECUTOR_CONTRACTS.get(p.executor,
                                               tuple(_REDUCE_MODES))
            if p.reduce not in contract:
                compatible = sorted(n for n, c in _EXECUTOR_CONTRACTS.items()
                                    if p.reduce in c)
                raise RuntimeError(
                    f"GemmPolicy pins executor={p.executor!r}, whose "
                    f"declared reduce contract is {contract}, but the scope "
                    f"asks reduce={p.reduce!r}: a pinned executor must not "
                    "silently change the output layout the collective asked "
                    f"for. Executors declaring {p.reduce!r}: {compatible} "
                    "-- pin one of those, or drop the pin and let selection "
                    "match the collective.")
        return p.executor
    if kind == "dense":
        return "dense-xla"
    mesh = compat.get_context_mesh()
    if (mesh is not None and mesh.size > 1 and not forced
            and p.shard_map != "local"):
        # pallas_call has no GSPMD partitioning rule: under a multi-chip
        # mesh the kernels only run per-shard (shard_map) or not at all.
        # A forced kind or a shard_map="local" scope bypasses this branch
        # -- call sites inside their own shard_map manage partitioning
        # themselves (the shard_map executor's bodies do exactly that).
        if p.shard_map == "never":
            return "dense-xla"
        dp = _dp_axes(mesh, p)
        shards = _axes_size(mesh, dp) if dp else 0
        ok = bool(dp) and m_tall % shards == 0
        if ok:
            local = (classify_gemm(m_tall // shards, d1, d2, p)
                     if entry == "mm"
                     else classify_gemm_t(m_tall // shards, d1, d2, p))
            ok = local != "dense"
        scatter = entry == "mmt" and p.reduce == "psum_scatter"
        if ok and scatter:
            # The scatter dim is the OUTPUT's leading dim (d1, the rows of
            # X^T Y); when it doesn't tile over the shards the sharded
            # output cannot exist -- dense fallback, not a silent psum
            # (callers asking for sharded layout must not silently get a
            # replicated one).
            ok = d1 % shards == 0
        if ok:
            return "shard_map-scatter" if scatter else "shard_map"
        if p.shard_map == "require":
            raise RuntimeError(
                f"GemmPolicy(shard_map='require') but shape "
                f"({m_tall}, {d1}, {d2}) cannot shard over dp axes "
                f"{dp or '(none)'} of mesh "
                f"{compat.mesh_axis_sizes(mesh)}"
                + (" with reduce='psum_scatter'" if scatter else ""))
        return "dense-xla"
    if p.interpret:
        return "interpret"
    return "pallas-tpu"


def _forced_kind(entry: str, mode: str | None, force: str | None,
                 p: GemmPolicy) -> str | None:
    """Resolve per-call mode/force plus the policy mode into a pinned kind
    (or None for auto). Per-call values are validated strictly; a policy
    mode pinning the *other* entry's kind degrades to auto here so VJP
    re-dispatch under a force-kind scope stays shape-correct."""
    valid = _MM_KINDS if entry == "mm" else _MMT_KINDS
    if mode is not None and force is not None and mode != force:
        raise ValueError("pass only one of mode= / force= (force is the "
                         "deprecated alias)")
    req = mode if mode is not None else force
    if req is not None:
        if req not in valid:
            raise ValueError(
                f"unknown kind {req!r} for {'tsmm' if entry == 'mm' else 'tsmm_t'}: "
                f"valid kinds are {', '.join(valid)}")
        return None if req == "auto" else req
    if p.mode != "auto" and p.mode in valid:
        return p.mode
    return None


def _resolve_policy(policy_: GemmPolicy | None,
                    interpret: bool | None) -> GemmPolicy:
    p = policy_ if policy_ is not None else current_policy()
    if interpret is not None and interpret != p.interpret:
        p = dataclasses.replace(p, interpret=interpret)
    return p


# ---------------------------------------------------------------------------
# Online ABFT (GemmPolicy.abft): checksum wrap around the kernel dispatches
# ---------------------------------------------------------------------------

_ABFT_KINDS = ("tsm2r", "tsm2l", "tsmt")
# The OUTER shard_map dispatch is not wrapped: its per-shard re-dispatch
# inherits abft through _shard_map_env's inner policy, so every shard
# verifies/corrects its local GEMM (a global checksum would need its own
# cross-shard collective and would break the reduce="none" stacked layout).
_ABFT_SKIP_EXECUTORS = ("shard_map", "shard_map-scatter")


def _abft_wraps(kind: str, executor: str, p: GemmPolicy) -> bool:
    """Does the online checksum guard wrap this dispatch?"""
    return (p.abft != "none" and kind in _ABFT_KINDS
            and executor not in _ABFT_SKIP_EXECUTORS)


def _abft_guard(entry: str, x, y, out, p: GemmPolicy):
    """Huang-Abraham checksum verify/correct for one protected dispatch.

    Computes the output's weighted column checksums two ways -- directly
    from ``out``, and by pushing the checksum vector through the operands
    (linearity: ``e^T (A B) == (e^T A) B``) -- and hands both to
    ``ft.abft.locate_and_correct``. All checksum GEMMs re-enter this
    dispatcher under a neutralized policy (``abft="none"`` so the wrap
    cannot recurse, f32 ``quant="none"`` operands so the reference is
    exact, executor pin and shape-specific split pin stripped so the
    checksum shapes classify for themselves) -- so the encode itself runs
    on the paper's kernels, which is the whole point of online ABFT at
    tall-skinny shapes. Operands/outputs pass through ``stop_gradient``:
    the guard adds no backward cost, and on a clean (fault-free) run the
    returned value is exactly ``out`` -- bit-identical, gradient-identical.

    ``entry="mm"`` expects the collapsed 2-D views: x=(m, k), y=(k, n),
    out=(m, n); checksum rows = m, reduction = k. ``entry="mmt"``:
    x=(m, a), y=(m, b), out=(a, b); checksum rows = a, reduction = m.
    """
    from repro.ft import abft as _abft  # deferred: ft.abft imports tsmm

    pc = dataclasses.replace(
        p, abft="none", mode="auto", executor=None, quant="none",
        split="auto" if isinstance(p.split, int) else p.split)
    xs = lax.stop_gradient(x).astype(jnp.float32)
    ys = lax.stop_gradient(y).astype(jnp.float32)
    os_ = lax.stop_gradient(out).astype(jnp.float32)
    ref_row = None
    if entry == "mm":
        rows, red = x.shape[0], x.shape[1]
        e = _abft.checksum_weights(rows)
        u = tsmm_t(xs, e, policy=pc)               # (k, s) = A^T e
        c_ref = tsmm_t(ys, u, policy=pc)           # (n, s) = B^T (A^T e)
        if p.abft == "correct":
            # Dense recompute of ONE localized output row -- the snap
            # reference accurate at the value's own scale (see
            # ft.abft.locate_and_correct); a (1, k) @ (k, n) dot, so its
            # cost is a rounding error on the wrap itself.
            def ref_row(i):
                r = lax.dynamic_slice_in_dim(xs, i, 1, axis=0)
                return lax.dot_general(
                    r, ys, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)[0]
    else:
        rows, red = out.shape[0], x.shape[0]
        e = _abft.checksum_weights(rows)
        v = tsmm(xs, e, policy=pc)                 # (m, s) = X e
        c_ref = tsmm_t(v, ys, policy=pc).T         # (b, s) = ((X e)^T Y)^T
        if p.abft == "correct":
            def ref_row(i):
                col = lax.dynamic_slice_in_dim(xs, i, 1, axis=1)
                return lax.dot_general(
                    col, ys, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)[0]
    c_out = tsmm_t(os_, e, policy=pc)              # (cols, s) = out^T e
    return _abft.locate_and_correct(
        out, c_out, c_ref, rows=rows, reduction=red, mode=p.abft,
        eps=_abft.tolerance_eps(out.dtype, p.quant), ref_row=ref_row)


# ---------------------------------------------------------------------------
# Public entries
# ---------------------------------------------------------------------------

def tsmm(a: jnp.ndarray, b: jnp.ndarray, *, mode: str | None = None,
         policy: GemmPolicy | None = None, interpret: bool | None = None,
         force: str | None = None) -> jnp.ndarray:
    """``A[..., m, k] @ B[k, n]`` via the best path for the shape.

    Leading dims of ``a`` collapse into the tall dim for kernel dispatch
    (classification sees ``prod(a.shape[:-1])``); the dense path contracts
    the trailing dim in place, reshape-free. Differentiable. ``mode``
    overrides classification per call ("auto"/"dense"/"tsm2r"/"tsm2l";
    unknown kinds raise); ``force`` and ``interpret`` are deprecated
    aliases for ``mode`` and the policy's interpret field.
    """
    p = _resolve_policy(policy, interpret)
    if a.ndim < 2 or b.ndim != 2:
        raise ValueError(
            f"tsmm expects a (..., m, k) lhs and a (k, n) rhs; got "
            f"{a.shape} @ {b.shape}")
    k = a.shape[-1]
    if b.shape[0] != k:
        raise ValueError(f"tsmm contraction mismatch: {a.shape} @ {b.shape}")
    n = b.shape[1]
    m_tall = math.prod(a.shape[:-1])
    forced = _forced_kind("mm", mode, force, p)
    kind = forced if forced is not None else classify_gemm(m_tall, k, n, p)
    name = _select_executor("mm", kind, m_tall, k, n, p, forced is not None)

    def run():
        ex = _EXECUTORS[name]
        if a.ndim > 2 and name != "dense-xla":
            out = _run_executor(ex, "mm", kind, a.reshape(m_tall, k), b, p)
            return out.reshape(*a.shape[:-1], n)
        return _run_executor(ex, "mm", kind, a, b, p)

    guard = _abft_wraps(kind, name, p)
    out = _dispatch("mm", kind, name, (m_tall, k, n), p, run,
                    abft=p.abft if guard else "none")
    if guard:
        a2 = a.reshape(m_tall, k) if a.ndim > 2 else a
        o2 = out.reshape(m_tall, n) if a.ndim > 2 else out
        o2 = _abft_guard("mm", a2, b, o2, p)
        out = o2.reshape(*a.shape[:-1], n) if a.ndim > 2 else o2
    return out


def tsmm_t(x: jnp.ndarray, y: jnp.ndarray, *, mode: str | None = None,
           policy: GemmPolicy | None = None, interpret: bool | None = None,
           force: str | None = None) -> jnp.ndarray:
    """``X[..., m, a]^T @ Y[..., m, b] -> (a, b)`` via TSMT when the
    reduction is huge and a, b small-ish.

    Leading dims (shared by both operands) collapse into the reduction, so
    batched cotangents reduce in one pass. Differentiable. ``mode`` accepts
    "auto"/"dense"/"tsmt" (unknown kinds raise).
    """
    p = _resolve_policy(policy, interpret)
    if x.ndim < 2 or x.ndim != y.ndim or x.shape[:-1] != y.shape[:-1]:
        raise ValueError(
            f"tsmm_t expects (..., m, a) and (..., m, b) with identical "
            f"leading dims; got {x.shape} and {y.shape}")
    a_dim, b_dim = x.shape[-1], y.shape[-1]
    m_tall = math.prod(x.shape[:-1])
    if x.ndim > 2:
        x = x.reshape(m_tall, a_dim)
        y = y.reshape(m_tall, b_dim)
    forced = _forced_kind("mmt", mode, force, p)
    kind = (forced if forced is not None
            else classify_gemm_t(m_tall, a_dim, b_dim, p))
    name = _select_executor("mmt", kind, m_tall, a_dim, b_dim, p,
                            forced is not None)
    guard = _abft_wraps(kind, name, p)
    out = _dispatch("mmt", kind, name, (m_tall, a_dim, b_dim), p,
                    lambda: _run_executor(_EXECUTORS[name], "mmt", kind,
                                          x, y, p),
                    abft=p.abft if guard else "none")
    if guard:
        out = _abft_guard("mmt", x, y, out, p)
    return out


def bound_class(m: int, k: int, n: int, dtype=jnp.bfloat16,
                policy: GemmPolicy | None = None) -> perf_model.Bound:
    p = policy if policy is not None else current_policy()
    return perf_model.classify(m, k, n, p.spec, dtype)
