"""Measured-wall-clock autotuning for the TSM2X kernel parameters.

The paper's Algorithm 5 has two halves: pick (t1, t2, t3) from the analytic
performance model, then *profile* to correct it ("offline-profile t1").
``core.perf_model`` is the analytic half; this module is the measured half:

* :func:`autotune_shape` times real kernel invocations over the exact
  candidate grid the analytic argmin scores
  (``perf_model.{tsm2r,tsm2l,tsmt}_candidates``) and records the
  measured-best block params plus the model-vs-measured error.
* :class:`TuningTable` is the persistent (JSON-serializable) cache of those
  records, keyed by ``(kernel kind, shape bucket, dtype, spec name,
  executor)``. Hang it on a policy -- ``with tsmm.policy(tuning_table=tbl)``
  -- and ``kernels/ops.py`` consults the measured winners before falling
  back to ``choose_params_*``.
* :func:`calibrate` / :func:`fit_spec` fit the free model constants
  (``step_overhead``, ``dma_latency``, ``vmem_usable``) to minimize
  modeled-vs-measured error, so the analytic path improves even for shapes
  that are not in the table.

Shape bucketing (the scheme the table key uses, via :func:`bucket_dim`):
dims up to one lane tile (128) are kept exact -- skinny dims flip the
kernel choice sharply -- and larger dims round up to the next power of two.
A lookup for (20480, 20480, 16) therefore hits a record tuned at any shape
in the same (32768, 32768, 16) bucket.

Timing discipline: every measurement goes through :func:`jit_isolated`,
which gives each arm a *fresh* ``jax.jit`` wrapper traced inside its own
policy scope. Dispatch policy and block params are captured at trace time,
so a jitted callable shared across arms would silently reuse the first
arm's baked-in configuration (the A/B leakage bug; ROADMAP "each arm needs
its own jit cache"). ``benchmarks/common.py`` reuses the same harness.

Off-TPU the kernels run in Pallas interpret mode, where wall clock measures
the Python interpreter, not the hardware -- the numbers exercise the
mechanism (and CI does exactly that); authoritative tables must be
generated on a real TPU and committed (see README "Autotuning").
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from typing import Callable, Iterable

import jax
import jax.numpy as jnp

from repro.core import perf_model
from repro.kernels import compat, ops

__all__ = [
    "TABLE_SCHEMA",
    "TuningRecord",
    "TuningTable",
    "SpecFit",
    "Observation",
    "CalibrationResult",
    "bucket_dim",
    "bucket_shape",
    "record_key",
    "fit_key",
    "jit_isolated",
    "time_call",
    "autotune_shape",
    "build_table",
    "observations_from_table",
    "fit_spec",
    "calibrate",
]

# /2 added the split-reduction dimension ("splits" in record params) and
# the per-bucket "fits" block. Loaders accept every "repro-tsm2x-tuning/"
# schema: /1 records simply lack both (consumers default splits to 1 --
# the sequential kernel those tables actually measured -- and fitted_spec
# falls through to the caller's spec).
TABLE_SCHEMA = "repro-tsm2x-tuning/2"

KINDS = ("tsm2r", "tsm2l", "tsmt")


# ---------------------------------------------------------------------------
# Shape bucketing + keys
# ---------------------------------------------------------------------------

def bucket_dim(d: int, lane: int = 128) -> int:
    """Bucket one dim: exact up to a lane tile, next power of two above."""
    if d <= lane:
        return d
    return 1 << (d - 1).bit_length()


def bucket_shape(m: int, d1: int, d2: int, lane: int = 128) -> tuple[int, int, int]:
    return (bucket_dim(m, lane), bucket_dim(d1, lane), bucket_dim(d2, lane))


def record_key(kind: str, bucket: tuple[int, int, int], dtype: str,
               spec_name: str, executor: str) -> str:
    """Stable string form of the table key (also the on-disk JSON key)."""
    bm, b1, b2 = bucket
    return f"{kind}|{bm}x{b1}x{b2}|{dtype}|{spec_name}|{executor}"


# Wildcard cell for the table-wide (global) calibration fit.
GLOBAL_FIT = ("*", (0, 0, 0), "*")


def fit_key(kind: str, bucket: tuple[int, int, int], dtype: str,
            spec_name: str) -> str:
    """Key of one fitted-constants cell (no executor: the fit corrects the
    *model*, which is executor-blind)."""
    bm, b1, b2 = bucket
    return f"{kind}|{bm}x{b1}x{b2}|{dtype}|{spec_name}"


def _dtype_name(dtype) -> str:
    return jnp.dtype(dtype).name


def _params_tuple(params) -> tuple[tuple[str, int], ...]:
    return tuple(sorted(dict(params).items()))


# ---------------------------------------------------------------------------
# Table
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TuningRecord:
    """One tuned entry: measured-best params for one (kind, bucket, dtype,
    spec, executor) cell, plus everything needed to audit the model."""

    kind: str                                   # "tsm2r" | "tsm2l" | "tsmt"
    bucket: tuple[int, int, int]                # bucketed (tall, d1, d2)
    dtype: str                                  # jnp dtype name
    spec_name: str                              # TPUSpec.name
    executor: str                               # "pallas-tpu" | "interpret"
    shape: tuple[int, int, int]                 # the shape actually measured
    params: tuple[tuple[str, int], ...]         # measured-best block params
    measured_us: float                          # wall time of those params
    model_us: float                             # model's prediction for them
    model_error: float                          # |model - measured|/measured
    model_pick: tuple[tuple[str, int], ...]     # the analytic argmin
    model_pick_measured_us: float               # its measured wall time

    @property
    def params_dict(self) -> dict[str, int]:
        return dict(self.params)

    @property
    def key(self) -> str:
        return record_key(self.kind, self.bucket, self.dtype, self.spec_name,
                          self.executor)

    @property
    def pick_matches(self) -> bool:
        """Did the analytic model already pick the measured winner?"""
        return self.params == self.model_pick


@dataclasses.dataclass(frozen=True)
class SpecFit:
    """Fitted model constants for one shape bucket (or the table-wide
    ``GLOBAL_FIT`` wildcard cell): the ``calibrate()`` output, stored so
    ``GemmPolicy.tuning_table`` consumers can run the analytic chooser
    under the constants measured NEAR the shape at hand instead of one
    global compromise (step overhead and DMA latency are strongly
    shape-regime-dependent -- a latency-bound tsm2l bucket and a streaming
    tsm2r bucket want very different corrections)."""

    kind: str                       # kernel kind, or "*" for the global fit
    bucket: tuple[int, int, int]    # bucketed shape; (0, 0, 0) for global
    dtype: str                      # jnp dtype name, or "*" for global
    spec_name: str                  # TPUSpec.name the fit corrects
    step_overhead: float
    dma_latency: float
    # vmem_usable raised by fit_spec when a measured winner would not fit
    # the modeled budget -- without carrying it, the table-driven analytic
    # fallback would re-prune configs calibration proved feasible. None on
    # fits saved before the field existed: leave the caller's budget alone.
    vmem_usable: float | None = None

    @property
    def key(self) -> str:
        return fit_key(self.kind, self.bucket, self.dtype, self.spec_name)


@dataclasses.dataclass(frozen=True)
class TuningTable:
    """Immutable, hashable set of tuning records (+ fitted model specs).

    Hashability matters: the table rides on ``GemmPolicy.tuning_table``,
    and policies flow through the kernels' ``custom_vjp`` nondiff args.
    ``add`` returns a new table (same-key records are replaced).

    ``fits`` carries per-bucket fitted model constants plus the global
    fit (``calibrate`` writes them); :meth:`fitted_spec` is the consumer
    view -- bucket-local fit first, global fit second, caller's spec as-is
    when the table has neither (v1 tables).
    """

    records: tuple[TuningRecord, ...] = ()
    fits: tuple[SpecFit, ...] = ()
    _index: dict | None = dataclasses.field(
        default=None, compare=False, repr=False)
    _fit_index: dict | None = dataclasses.field(
        default=None, compare=False, repr=False)

    def __post_init__(self):
        object.__setattr__(self, "_index", {r.key: r for r in self.records})
        object.__setattr__(self, "_fit_index",
                           {f.key: f for f in self.fits})

    @classmethod
    def from_records(cls, records: Iterable[TuningRecord],
                     fits: Iterable[SpecFit] = ()) -> "TuningTable":
        merged: dict[str, TuningRecord] = {}
        for r in records:
            merged[r.key] = r
        fmerged: dict[str, SpecFit] = {}
        for f in fits:
            fmerged[f.key] = f
        return cls(records=tuple(merged.values()),
                   fits=tuple(fmerged.values()))

    def add(self, record: TuningRecord) -> "TuningTable":
        return self.from_records((*self.records, record), self.fits)

    def with_fits(self, fits: Iterable[SpecFit]) -> "TuningTable":
        """New table with ``fits`` merged over the existing ones."""
        return self.from_records(self.records, (*self.fits, *fits))

    def lookup(self, kind: str, m: int, d1: int, d2: int, *, dtype,
               spec: str, executor: str) -> TuningRecord | None:
        key = record_key(kind, bucket_shape(m, d1, d2), _dtype_name(dtype),
                         spec, executor)
        return self._index.get(key)

    def fitted_spec(self, kind: str, m: int, d1: int, d2: int, *, dtype,
                    spec):
        """``spec`` with this shape-bucket's fitted constants applied --
        bucket-local cell first, the global wildcard second, unchanged
        when the table carries no fits at all."""
        fit = self._fit_index.get(
            fit_key(kind, bucket_shape(m, d1, d2), _dtype_name(dtype),
                    spec.name))
        if fit is None:
            fit = self._fit_index.get(fit_key(*GLOBAL_FIT, spec.name))
        if fit is None:
            return spec
        repl = {"step_overhead": fit.step_overhead,
                "dma_latency": fit.dma_latency}
        if fit.vmem_usable is not None:
            # the budget only ever widens: calibration proved configs past
            # the caller's budget feasible, never the reverse.
            repl["vmem_usable"] = max(fit.vmem_usable, spec.vmem_usable)
        return dataclasses.replace(spec, **repl)

    # -- JSON round trip ----------------------------------------------------

    def to_json(self) -> dict:
        return {
            "schema": TABLE_SCHEMA,
            "fits": [
                {
                    "kind": f.kind,
                    "bucket": list(f.bucket),
                    "dtype": f.dtype,
                    "spec": f.spec_name,
                    "step_overhead": f.step_overhead,
                    "dma_latency": f.dma_latency,
                    "vmem_usable": f.vmem_usable,
                }
                for f in self.fits
            ],
            "records": [
                {
                    "key": r.key,
                    "kind": r.kind,
                    "bucket": list(r.bucket),
                    "dtype": r.dtype,
                    "spec": r.spec_name,
                    "executor": r.executor,
                    "shape": list(r.shape),
                    "params": dict(r.params),
                    "measured_us": r.measured_us,
                    "model_us": r.model_us,
                    "model_error": r.model_error,
                    "model_pick": dict(r.model_pick),
                    "model_pick_measured_us": r.model_pick_measured_us,
                }
                for r in self.records
            ],
        }

    @classmethod
    def from_json(cls, data: dict) -> "TuningTable":
        schema = data.get("schema", "")
        if not schema.startswith("repro-tsm2x-tuning/"):
            raise ValueError(f"not a tuning table (schema={schema!r})")
        fits = tuple(
            SpecFit(
                kind=f["kind"],
                bucket=tuple(f["bucket"]),
                dtype=f["dtype"],
                spec_name=f["spec"],
                step_overhead=f["step_overhead"],
                dma_latency=f["dma_latency"],
                vmem_usable=f.get("vmem_usable"),  # absent pre-field
            )
            for f in data.get("fits", ()))  # absent in /1 tables
        return cls.from_records((
            TuningRecord(
                kind=d["kind"],
                bucket=tuple(d["bucket"]),
                dtype=d["dtype"],
                spec_name=d["spec"],
                executor=d["executor"],
                shape=tuple(d["shape"]),
                params=_params_tuple(d["params"]),
                measured_us=d["measured_us"],
                model_us=d["model_us"],
                model_error=d["model_error"],
                model_pick=_params_tuple(d["model_pick"]),
                model_pick_measured_us=d["model_pick_measured_us"],
            )
            for d in data["records"]), fits)

    def save(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)

    @classmethod
    def load(cls, path) -> "TuningTable":
        with open(path) as f:
            return cls.from_json(json.load(f))


# ---------------------------------------------------------------------------
# Timing harness (shared with benchmarks/common.py)
# ---------------------------------------------------------------------------

def time_call(fn: Callable, *args, reps: int = 3, warmup: int = 1) -> float:
    """Median wall time (seconds) of ``fn(*args)``, results synced."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    mid = len(ts) // 2
    # True median: even rep counts average the middle pair (upper-middle
    # alone would report the *worse* of two samples at reps=2).
    return ts[mid] if len(ts) % 2 else (ts[mid - 1] + ts[mid]) / 2


def jit_isolated(fn: Callable, *args, policy=None):
    """Fresh ``jax.jit`` wrapper, traced NOW under ``policy``.

    Returns ``(jitted_fn, dispatch_log)``. The trace call runs inside the
    policy scope and a ``record_dispatches`` spy, so (a) the arm owns its
    jit cache entry -- policy and block params are trace-time constants, a
    shared callable would silently keep the first arm's -- and (b) the
    caller can assert which executors the arm actually hit.

    ``fn`` is wrapped in a fresh function object first: jax's jit cache is
    keyed on the *wrapped callable's identity*, so ``jax.jit`` of the same
    function twice shares one cache -- re-jitting alone does not isolate an
    arm (the exact leakage this helper exists to prevent).
    """
    from repro.core import tsmm  # deferred: tsmm imports kernels.ops too

    def _fresh(*a):
        return fn(*a)

    f = jax.jit(_fresh)
    ctx = tsmm.policy(policy) if policy is not None else contextlib.nullcontext()
    with ctx:
        with tsmm.record_dispatches() as log:
            jax.block_until_ready(f(*args))
    return f, log


# ---------------------------------------------------------------------------
# Per-shape autotuning
# ---------------------------------------------------------------------------

def _kind_plan(kind: str, m: int, d1: int, d2: int, spec, dtype,
               explore_vmem: float = 1.0):
    """(candidates as param dicts, model-time fn, analytic pick) per kind.

    ``explore_vmem`` > 1 enumerates the *measured* search space under a
    relaxed VMEM budget (``vmem_usable * explore_vmem``, capped at 1.0).
    Without it the autotuner could only ever confirm the model's own
    feasibility filter -- a winner the model's budget would have pruned
    could never be observed, leaving ``fit_spec``'s vmem_usable correction
    unreachable. Over-budget candidates that fail to compile on real
    hardware are skipped by the measurement loop. The analytic pick always
    uses the strict budget.
    """
    explored = spec
    if explore_vmem > 1.0:
        explored = dataclasses.replace(
            spec, vmem_usable=min(spec.vmem_usable * explore_vmem, 1.0))
    if kind == "tsm2r":
        cands = [{"block_m": bm, "block_k": bk, "splits": s}
                 for bm, bk, s in perf_model.tsm2r_candidates(m, d1, d2,
                                                             explored, dtype)]

        def model(p):
            return perf_model.tsm2r_model_time(
                m, d1, d2, p["block_m"], p["block_k"], spec, dtype,
                splits=p.get("splits", 1))

        bm, bk, s = perf_model.choose_params_tsm2r(m, d1, d2, spec, dtype)
        pick = {"block_m": bm, "block_k": bk, "splits": s}
    elif kind == "tsm2l":
        cands = [{"block_m": bm}
                 for bm in perf_model.tsm2l_candidates(m, d1, d2,
                                                      explored, dtype)]

        def model(p):
            return perf_model.tsm2l_model_time(
                m, d1, d2, p["block_m"], spec, dtype)

        pick = {"block_m": perf_model.choose_params_tsm2l(m, d1, d2, spec, dtype)}
    elif kind == "tsmt":
        cands = [{"block_m": bm, "block_a": ba, "splits": s}
                 for bm, ba, s in perf_model.tsmt_candidates(m, d1, d2,
                                                            explored, dtype)]

        def model(p):
            return perf_model.tsmt_model_time(
                m, d1, d2, p["block_m"], p["block_a"], spec, dtype,
                splits=p.get("splits", 1))

        bm, ba, s = perf_model.choose_params_tsmt(m, d1, d2, spec, dtype)
        pick = {"block_m": bm, "block_a": ba, "splits": s}
    else:
        raise ValueError(f"unknown kernel kind {kind!r}: valid kinds are "
                         f"{', '.join(KINDS)}")
    if pick not in cands:  # tiny shape / tight budget: measure the fallback
        cands = [*cands, pick]
    return cands, model, pick


def _call_for(kind: str, params: dict):
    if kind == "tsm2r":
        return lambda a, b: ops.tsm2r(a, b, **params)
    if kind == "tsm2l":
        return lambda a, b: ops.tsm2l(a, b, **params)
    return lambda x, y: ops.tsmt(x, y, **params)


def _operands(kind: str, m: int, d1: int, d2: int, dtype, seed: int = 0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    if kind == "tsmt":  # X[m, a], Y[m, b]
        shapes = ((m, d1), (m, d2))
    else:               # A[m, k], B[k, n]
        shapes = ((m, d1), (d1, d2))
    return tuple(
        jax.random.uniform(kk, s, jnp.float32, -1, 1).astype(dtype)
        for kk, s in zip((k1, k2), shapes))


def _resolved_executor(policy) -> str:
    return ("interpret" if compat.auto_interpret(policy.interpret)
            else "pallas-tpu")


def autotune_shape(kind: str, m: int, d1: int, d2: int, *,
                   dtype=jnp.float32, policy=None, reps: int = 3,
                   warmup: int = 1,
                   explore_vmem: float = 1.25) -> TuningRecord:
    """Measure every candidate config for one shape; return the record.

    ``(d1, d2)`` are ``(k, n)`` for tsm2r/tsm2l and ``(a, b)`` for tsmt.
    Each candidate is timed through its own freshly-jitted wrapper under
    ``policy`` (or the current scope), so arms cannot leak cache entries.
    ``explore_vmem`` relaxes the VMEM feasibility filter for the measured
    search (see ``_kind_plan``); candidates that fail to compile/run are
    skipped, so probing past the modeled budget is safe.
    """
    from repro.core import tsmm

    pol = policy if policy is not None else tsmm.current_policy()
    cands, model, pick = _kind_plan(kind, m, d1, d2, pol.spec, dtype,
                                    explore_vmem)
    operands = _operands(kind, m, d1, d2, dtype)

    measured: list[tuple[float, dict]] = []
    for params in cands:
        try:
            f, _ = jit_isolated(_call_for(kind, params), *operands,
                                policy=pol)
            t = time_call(f, *operands, reps=reps, warmup=warmup)
        except Exception:  # over-budget explore candidate: Mosaic rejects it
            if params == pick:
                raise  # the strict-budget pick must always run
            continue
        measured.append((t, params))
    best_t, best_p = min(measured, key=lambda r: r[0])
    pick_t = next((t for t, p in measured if p == pick), float("nan"))
    model_s = model(best_p)
    return TuningRecord(
        kind=kind,
        bucket=bucket_shape(m, d1, d2),
        dtype=_dtype_name(dtype),
        spec_name=pol.spec.name,
        executor=_resolved_executor(pol),
        shape=(m, d1, d2),
        params=_params_tuple(best_p),
        measured_us=best_t * 1e6,
        model_us=model_s * 1e6,
        model_error=abs(model_s - best_t) / best_t,
        model_pick=_params_tuple(pick),
        model_pick_measured_us=pick_t * 1e6,
    )


def build_table(shapes: Iterable[tuple[str, int, int, int]], *,
                dtype=jnp.float32, policy=None, reps: int = 3,
                warmup: int = 1, explore_vmem: float = 1.25) -> TuningTable:
    """Autotune ``(kind, m, d1, d2)`` shapes into one TuningTable.

    Shapes that land in the same table bucket are merged by keeping the
    faster measured winner -- with a warning, since the extra measurement
    was wasted and the caller probably wanted distinct buckets.
    """
    import warnings

    by_key: dict[str, TuningRecord] = {}
    for kind, m, d1, d2 in shapes:
        rec = autotune_shape(kind, m, d1, d2, dtype=dtype, policy=policy,
                             reps=reps, warmup=warmup,
                             explore_vmem=explore_vmem)
        prev = by_key.get(rec.key)
        if prev is not None:
            warnings.warn(
                f"autotune shapes {prev.shape} and {rec.shape} share table "
                f"bucket {rec.key}; keeping the faster winner", stacklevel=2)
            if prev.measured_us <= rec.measured_us:
                continue
        by_key[rec.key] = rec
    return TuningTable(records=tuple(by_key.values()))


# ---------------------------------------------------------------------------
# Model calibration: fit the free TPUSpec constants to measurements
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Observation:
    """One (shape, params) -> measured-seconds data point."""

    kind: str
    m: int
    d1: int
    d2: int
    dtype: str
    params: tuple[tuple[str, int], ...]
    measured_s: float

    def model_s(self, spec) -> float:
        p = dict(self.params)
        if self.kind == "tsm2r":
            return perf_model.tsm2r_model_time(
                self.m, self.d1, self.d2, p["block_m"], p["block_k"],
                spec, self.dtype, splits=p.get("splits", 1))
        if self.kind == "tsm2l":
            return perf_model.tsm2l_model_time(
                self.m, self.d1, self.d2, p["block_m"], spec, self.dtype)
        return perf_model.tsmt_model_time(
            self.m, self.d1, self.d2, p["block_m"], p["block_a"],
            spec, self.dtype, splits=p.get("splits", 1))

    def vmem_bytes(self) -> int:
        p = dict(self.params)
        if self.kind == "tsm2r":
            return perf_model.tsm2r_vmem_usage(
                p["block_m"], p["block_k"], self.d2, self.dtype)
        if self.kind == "tsm2l":
            return perf_model.tsm2l_vmem_usage(
                p["block_m"], self.d1, self.d2, self.dtype)
        return perf_model.tsmt_vmem_usage(
            p["block_m"], p["block_a"], self.d2, self.dtype)


def observations_from_table(table: TuningTable) -> list[Observation]:
    """Both timings each record holds (measured winner + the analytic
    pick) become calibration points."""
    obs = []
    for r in table.records:
        m, d1, d2 = r.shape
        obs.append(Observation(r.kind, m, d1, d2, r.dtype, r.params,
                               r.measured_us / 1e6))
        if (r.model_pick != r.params
                and r.model_pick_measured_us == r.model_pick_measured_us):
            obs.append(Observation(r.kind, m, d1, d2, r.dtype, r.model_pick,
                                   r.model_pick_measured_us / 1e6))
    return obs


def _mean_log_err(spec, observations) -> float:
    import math
    tot = 0.0
    for o in observations:
        tot += abs(math.log(max(o.model_s(spec), 1e-12)
                            / max(o.measured_s, 1e-12)))
    return tot / max(len(observations), 1)


@dataclasses.dataclass(frozen=True)
class CalibrationResult:
    spec: perf_model.TPUSpec       # the fitted spec
    error_before: float            # mean |log(model/measured)| pre-fit
    error_after: float             # ... post-fit
    table: TuningTable | None = None


# Coordinate-descent grids: coarse powers of two first, then refinement.
_FIT_GRIDS = (
    tuple(2.0 ** i for i in range(-5, 6)),
    (0.5, 0.7, 0.85, 1.0, 1.2, 1.5, 2.0),
    (0.9, 0.95, 1.0, 1.05, 1.1),
)


def fit_spec(spec: perf_model.TPUSpec, observations: list[Observation], *,
             fit: tuple[str, ...] = ("step_overhead", "dma_latency"),
             ) -> CalibrationResult:
    """Fit free model constants against measurements (pure, no timing).

    ``step_overhead`` and ``dma_latency`` enter the modeled time linearly
    and are fit by coordinate descent on multiplicative scales, minimizing
    the mean absolute log model/measured ratio. ``vmem_usable`` bounds
    feasibility rather than time, so it is only ever *raised* -- minimally,
    when a measured winner would not fit the modeled budget (i.e. the model
    was pruning configs the hardware happily runs).
    """
    before = _mean_log_err(spec, observations)
    cur = spec
    if observations:
        for grid in _FIT_GRIDS:
            for name in fit:
                base = getattr(cur, name)
                best_v, best_e = base, _mean_log_err(cur, observations)
                for mult in grid:
                    trial = dataclasses.replace(cur, **{name: base * mult})
                    e = _mean_log_err(trial, observations)
                    if e < best_e - 1e-15:
                        best_v, best_e = base * mult, e
                cur = dataclasses.replace(cur, **{name: best_v})
        need = max((o.vmem_bytes() / cur.vmem_bytes for o in observations),
                   default=0.0)
        if need > cur.vmem_usable:
            cur = dataclasses.replace(cur, vmem_usable=min(need, 1.0))
    return CalibrationResult(spec=cur, error_before=before,
                             error_after=_mean_log_err(cur, observations))


DEFAULT_CALIBRATION_SHAPES = (
    ("tsm2r", 2048, 512, 8),
    ("tsm2r", 4096, 1024, 16),
    ("tsm2l", 8192, 16, 16),
    ("tsmt", 4096, 64, 8),
)


def calibrate(shapes=DEFAULT_CALIBRATION_SHAPES, *, spec=None,
              dtype=jnp.float32, policy=None, reps: int = 3,
              warmup: int = 1, explore_vmem: float = 1.25,
              base_table: TuningTable | None = None) -> CalibrationResult:
    """Measure + fit in one step: the ``calibrate(spec)`` entry point.

    Autotunes ``shapes`` under ``policy`` (or the current scope), then fits
    the free constants of ``spec`` (default: the policy's spec) to the
    measurements -- once globally over every observation, and once per
    shape bucket. Both land on the returned table
    (``TuningTable.fits``), so consumers hanging the table on
    ``GemmPolicy.tuning_table`` get bucket-local model constants for
    off-table shapes in a measured bucket (``kernels/ops`` prefers the
    bucket-local fit; the global fit is the fallback cell). Returns the
    globally fitted spec, before/after error, and the table.

    ``base_table`` makes a *partial re-calibration* incremental: the
    returned table carries the base records merged under the fresh ones
    (same-bucket records are replaced by the new measurement), while the
    ``fits`` are ONLY this run's -- stale per-bucket ``SpecFit`` cells from
    the base age out rather than silently steering the analytic chooser
    with constants an older run (other machine load, other jax version,
    other interpret/hardware mode) measured. Fitted constants must come
    from one coherent measurement pass; records are per-bucket facts and
    merge safely.
    """
    from repro.core import tsmm

    pol = policy if policy is not None else tsmm.current_policy()
    if spec is not None and spec is not pol.spec:
        pol = pol.with_(spec=spec)
    table = build_table(shapes, dtype=dtype, policy=pol, reps=reps,
                        warmup=warmup, explore_vmem=explore_vmem)
    obs = observations_from_table(table)
    fitted = fit_spec(pol.spec, obs)
    fits = [SpecFit(*GLOBAL_FIT, pol.spec.name,
                    fitted.spec.step_overhead, fitted.spec.dma_latency,
                    fitted.spec.vmem_usable)]
    groups: dict[tuple, list[Observation]] = {}
    for o in obs:
        key = (o.kind, bucket_shape(o.m, o.d1, o.d2), _dtype_name(o.dtype))
        groups.setdefault(key, []).append(o)
    for (kind, bucket, dt), group in groups.items():
        local = fit_spec(pol.spec, group)
        fits.append(SpecFit(kind, bucket, dt, pol.spec.name,
                            local.spec.step_overhead,
                            local.spec.dma_latency,
                            local.spec.vmem_usable))
    if base_table is not None:
        # base fits intentionally dropped (see docstring); records merge
        # with this run's measurements winning shared buckets.
        table = TuningTable.from_records(
            (*base_table.records, *table.records))
    return dataclasses.replace(fitted, table=table.with_fits(fits))
