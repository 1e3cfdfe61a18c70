"""TPU port of the TSM2X analytic performance model (paper Section 3.1.6-3.1.9).

The paper drives kernel-parameter selection (t1, t2, t3) from an analytic
model built on three ingredients: (a) a compute-vs-memory-bound classifier
``t2_threshold = PeakPerf / PeakBand * bytes_per_elem``, (b) occupancy /
Little's-law utilization terms, and (c) a gradient-descent search over the
parameter space (Algorithm 5).

On TPU the same decision structure survives with different hardware terms:

* ``t1`` (threads per block / B-tile rows)  -> ``block_k``: rows of B staged
  per VMEM window, which is also the A-tile reduction depth per grid step.
* ``t2`` (C columns per thread in flight)   -> ``block_n``: output columns
  held in the VMEM accumulator (for the paper's n <= 32 this is just n).
* ``t3`` (A elements prefetched per thread) -> ``block_m``: A-tile rows per
  DMA; Mosaic's automatic double-buffering replaces the hand-rolled
  nextA/nextB register prefetch of Algorithm 4.
* occupancy / warp latency -> grid-cell parallelism and DMA pipeline depth.

The search (``choose_params_*``) is a discrete argmax over the modeled time
instead of continuous gradient descent: the TPU parameter space is small and
hardware-quantized (sublane 8 x lane 128 tiles), so enumerate-and-score is
exact where GD was approximate.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Literal

import jax
import jax.numpy as jnp

from repro.analysis import contracts

Bound = Literal["memory", "compute", "latency"]


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    """Hardware constants. Defaults: TPU v5e (task-spec numbers)."""

    name: str = "tpu_v5e"
    peak_flops_bf16: float = 197e12
    peak_flops_f32: float = 197e12 / 4  # MXU f32 path ~ 1/4 of bf16
    hbm_bw: float = 819e9
    ici_bw_per_link: float = 50e9
    vmem_bytes: int = 128 * 2**20
    # Fraction of VMEM the pipeliner may use for in-flight windows
    # (double-buffered in + out + scratch accumulator + compiler headroom).
    vmem_usable: float = 0.5
    # DMA issue-to-first-byte latency (s); TPU HBM round trip ~ O(1us).
    dma_latency: float = 1e-6
    # Per-grid-step fixed overhead of the Mosaic pipeline (s).
    step_overhead: float = 2e-7
    # MXU native tile (systolic array is 128x128; sublane granularity 8).
    lane: int = 128
    sublane: int = 8
    # Independent compute cores the grid's PARALLEL cells can occupy.
    # The peak_flops/hbm_bw numbers above are whole-chip: a grid whose
    # parallel dimensions collapse below n_cores leaves cores idle and
    # only reaches a cores_busy/n_cores fraction of both peaks (each core
    # owns its slice of the HBM ports). v5e has a single TensorCore;
    # v5p is a megacore (2 TensorCores behind one grid).
    n_cores: int = 1

    def peak_flops(self, dtype) -> float:
        return self.peak_flops_bf16 if jnp.dtype(dtype).itemsize <= 2 else self.peak_flops_f32


V5E = TPUSpec()

# TPU v5p: the paper's core observation -- the winning variant flips with
# hardware generation -- needs at least two generations on file. v5p's
# flops/byte ridge (459/2765 ~ 166) sits well below v5e's (197/0.819 ~ 241),
# so the same shape can change bound class between the two.
V5P = TPUSpec(
    name="tpu_v5p",
    peak_flops_bf16=459e12,
    peak_flops_f32=459e12 / 4,
    hbm_bw=2765e9,
    ici_bw_per_link=100e9,
    vmem_bytes=64 * 2**20,  # per TensorCore (v5e has 128 MiB)
    n_cores=2,  # megacore: Mosaic splits parallel grid dims across 2 cores
)

SPECS: dict[str, TPUSpec] = {
    "tpu_v5e": V5E,
    "v5e": V5E,
    "tpu_v5p": V5P,
    "v5p": V5P,
}


# Specs keyed by ``jax.Device.device_kind``, the strings the TPU runtime
# reports (Pallas' own ``tpu_info`` table matches on the same ones): a v5e
# calls itself "TPU v5 lite", a v5p "TPU v5".
DEVICE_KIND_SPECS: dict[str, TPUSpec] = {
    "TPU v5 lite": V5E,
    "TPU v5e": V5E,
    "TPU v5": V5P,
    "TPU v5p": V5P,
}


def device_spec(device=None) -> TPUSpec:
    """The spec of ``device`` (default: ``jax.devices()[0]``).

    On a TPU the spec comes from :data:`DEVICE_KIND_SPECS` by
    ``device_kind``, and a kind the table does not know is an error -- a
    guessed spec would size kernel blocks for the wrong chip. Off-TPU (the
    CPU test backend, where kernels run in interpret mode) the modelled
    default is V5E, the benchmark's chip."""
    d = device if device is not None else jax.devices()[0]
    if d.platform != "tpu":
        return V5E
    try:
        return DEVICE_KIND_SPECS[d.device_kind]
    except KeyError:
        raise ValueError(
            f"no TPUSpec for device_kind {d.device_kind!r}: known kinds are "
            f"{sorted(DEVICE_KIND_SPECS)} -- add the chip's peaks to "
            "repro.core.perf_model") from None


def get_spec(name: str) -> TPUSpec:
    """Look up a hardware spec by name (``GemmPolicy(spec=...)`` plumbing)."""
    try:
        return SPECS[name.lower()]
    except KeyError:
        raise ValueError(
            f"unknown TPU spec {name!r}: known specs are "
            f"{sorted(SPECS)}") from None


def bytes_per_elem(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def t2_threshold(spec: TPUSpec = V5E, dtype=jnp.bfloat16) -> float:
    """Paper eq. (Section 3.1.8): boundary value of t2 (here: of n).

    n below the threshold => the TSM2 problem is memory-bound. On v5e/bf16
    this is ~481, so every paper shape (n <= 32) is memory-bound: the
    kernel's whole job is streaming A at HBM speed.
    """
    return spec.peak_flops(dtype) / spec.hbm_bw * bytes_per_elem(dtype)


def arithmetic_intensity(m: int, k: int, n: int, dtype=jnp.bfloat16) -> float:
    """FLOPs per HBM byte moved, assuming each operand moves exactly once."""
    flops = 2.0 * m * k * n
    bts = (m * k + k * n + m * n) * bytes_per_elem(dtype)
    return flops / bts


def classify(m: int, k: int, n: int, spec: TPUSpec = V5E, dtype=jnp.bfloat16) -> Bound:
    """Paper Section 1: the three regimes of tall-and-skinny GEMM.

    * m ~ k >> n, n below threshold  -> memory-bound (TSM2R main case)
    * m ~ k >> n, n above threshold  -> compute-bound
    * m >> k ~ n (k tiny)            -> latency-bound (TSM2L case): the
      per-grid-cell reduction is too shallow to hide DMA latency.
    """
    ridge = spec.peak_flops(dtype) / spec.hbm_bw  # flops per byte at the roofline ridge
    # Latency test: with k tiny, even a maximal A tile gives a pipeline only
    # a few steps deep; per-cell work ~ bm*k*n flops vs ~us-scale latency.
    if k <= 4 * spec.lane and k <= 4 * n * spec.sublane:
        return "latency"
    if arithmetic_intensity(m, k, n, dtype) < ridge:
        return "memory"
    return "compute"


# ---------------------------------------------------------------------------
# Modeled execution time (the napkin math behind parameter choice)
# ---------------------------------------------------------------------------

def _roundup(x: int, q: int) -> int:
    return ((x + q - 1) // q) * q


def occupancy(parallel_cells: int, spec: TPUSpec = V5E) -> float:
    """Fraction of the chip's cores the grid's parallel cells can keep busy.

    ``min(n_cores, cells) / n_cores``: the TPU analogue of the paper's
    occupancy term (Section 3.1.9 -- warps resident per SM). Sequential
    ("arbitrary") grid dims contribute nothing; a kernel whose parallel
    dims collapse to one cell runs on one core of an n_cores chip and sees
    1/n_cores of both compute and HBM peaks. This is the term that makes
    split-reduction worth anything: splitting the reduction multiplies
    ``parallel_cells`` by S at the cost of the partials round trip.
    """
    return min(spec.n_cores, max(parallel_cells, 1)) / spec.n_cores


def split_partials_bytes(splits: int, rows: int, cols: int) -> int:
    """Extra HBM traffic of an S-way split reduction: the (S, rows, cols)
    f32 partials are written once and read once by the tree-reduce
    epilogue (S=1 writes the output directly: zero extra traffic)."""
    if splits <= 1:
        return 0
    return 2 * splits * rows * _roundup(cols, 128) * 4


def tsm2r_vmem_usage(bm: int, bk: int, n: int, dtype) -> int:
    """VMEM bytes for one grid cell, double-buffered in-streams + acc + out.

    Alias of ``analysis.contracts.tsm2r_footprint`` -- the footprint math
    lives in the contract layer so the model, the dispatcher and the
    auditor can never disagree on it (likewise the two aliases below).
    """
    return contracts.tsm2r_footprint(bm, bk, n, dtype)


def tsm2r_model_time(m: int, k: int, n: int, bm: int, bk: int,
                     spec: TPUSpec = V5E, dtype=jnp.bfloat16, *,
                     splits: int = 1) -> float:
    """Modeled wall time of the TSM2R kernel on ``spec``.

    Memory term: A moves once; B's (bk, n) window is re-fetched once per
    m-block (the paper's n/t1 re-load factor becomes m/bm here); C written
    once. Compute term: MXU time at n/lane utilization (skinny n wastes MXU
    columns -- irrelevant while memory-bound, harmful past the threshold).
    Latency term: pipeline prologue + per-step overhead; deep grids amortize.

    ``splits`` > 1 models the split-reduction variant: the k sweep is cut
    into S independent parallel slices (grid parallel cells x S, occupancy
    up on multi-core chips) at the cost of the (S, m, n) f32 partials
    round trip (``split_partials_bytes``) -- the TSM paper's leap-based
    global-reduce trade, discretized.
    """
    b = bytes_per_elem(dtype)
    gm, gk = math.ceil(m / bm), math.ceil(k / (splits * bk))
    steps = gm * gk * splits
    a_bytes = m * k * b
    b_bytes = k * _roundup(n, 128) * b * gm     # refetched per m-block
    c_bytes = m * _roundup(n, 128) * b
    c_bytes += split_partials_bytes(splits, m, n)
    occ = occupancy(gm * splits, spec)
    t_mem = (a_bytes + b_bytes + c_bytes) / (spec.hbm_bw * occ)
    # MXU: (bm, bk) x (bk, n) per step; effective peak scales with n/lane.
    mxu_eff = min(n, spec.lane) / spec.lane
    t_comp = 2.0 * m * k * max(n, 1) / (
        spec.peak_flops(dtype) * max(mxu_eff, 1e-3) * occ)
    t_lat = spec.dma_latency + steps * spec.step_overhead
    return max(t_mem, t_comp) + t_lat


def tsm2l_vmem_usage(bm: int, k: int, n: int, dtype) -> int:
    """VMEM bytes for one TSM2L grid cell (contract-layer alias)."""
    return contracts.tsm2l_footprint(bm, k, n, dtype)


def tsmt_vmem_usage(bm: int, ba: int, bdim: int, dtype) -> int:
    """VMEM bytes for one TSMT grid cell (contract-layer alias)."""
    return contracts.tsmt_footprint(bm, ba, bdim, dtype)


def tsm2l_model_time(m: int, k: int, n: int, bm: int,
                     spec: TPUSpec = V5E, dtype=jnp.bfloat16) -> float:
    """TSM2L: whole B in VMEM, one pass over A, grid over m only.

    The tcf trade of the paper (fewer, fatter threads) appears as the
    bm-vs-grid-depth term: tiny bm => many shallow steps => per-step
    overhead dominates (latency-bound); huge bm => too few cells to overlap
    DMA with compute across steps.
    """
    b = bytes_per_elem(dtype)
    steps = math.ceil(m / bm)
    t_mem = (m * k + k * n + m * _roundup(n, 128)) * b / spec.hbm_bw
    mxu_eff = min(n, spec.lane) / spec.lane * min(k, spec.lane) / spec.lane
    t_comp = 2.0 * m * k * n / (spec.peak_flops(dtype) * max(mxu_eff, 1e-3))
    # Pipeline needs >= 2 steps to overlap at all; penalize degenerate grids.
    overlap_penalty = 2.0 if steps < 2 else 1.0
    t_lat = spec.dma_latency * overlap_penalty + steps * spec.step_overhead
    return max(t_mem, t_comp) + t_lat


def tsmt_model_time(m: int, a: int, bdim: int, bm: int, ba: int,
                    spec: TPUSpec = V5E, dtype=jnp.bfloat16, *,
                    splits: int = 1) -> float:
    """Modeled TSMT wall time; ``splits`` models the split-reduction
    variant (the m sweep cut into S parallel slices emitting (S, a, bdim)
    f32 partials). This is THE occupancy-starved kernel of the framework:
    with PowerSGD/ABFT shapes (a, bdim <= 16) the parallel grid collapses
    to ``ceil(a/ba) == 1`` cell, so on an n_cores > 1 chip the whole
    reduction runs on one core unless S > 1 re-widens the grid.
    """
    b = bytes_per_elem(dtype)
    ga, gm = math.ceil(a / ba), math.ceil(m / (splits * bm))
    x_bytes = m * a * b
    y_bytes = m * _roundup(bdim, 128) * b * ga   # Y refetched per a-block
    out_bytes = (a * _roundup(bdim, 128) * b
                 + split_partials_bytes(splits, a, bdim))
    occ = occupancy(ga * splits, spec)
    t_mem = (x_bytes + y_bytes + out_bytes) / (spec.hbm_bw * occ)
    mxu_eff = min(bdim, spec.lane) / spec.lane
    t_comp = 2.0 * m * a * bdim / (
        spec.peak_flops(dtype) * max(mxu_eff, 1e-3) * occ)
    t_lat = spec.dma_latency + ga * gm * splits * spec.step_overhead
    return max(t_mem, t_comp) + t_lat


# ---------------------------------------------------------------------------
# Parameter choice (paper Algorithm 5, discrete TPU analogue)
# ---------------------------------------------------------------------------

_BM_CANDIDATES = (256, 512, 1024, 2048, 4096)
_BK_CANDIDATES = (128, 256, 512, 1024, 2048)
_BM_L_CANDIDATES = (256, 512, 1024, 2048, 4096, 8192, 16384)
_BA_CANDIDATES = (128, 256, 512, 1024)
# Split-reduction factors (S partial accumulators over the reduction axis).
# S=1 is the sequential kernel; the grids below only admit S > 1 when the
# reduction still has >= one full block per slice (deeper splits would be
# pure padding). tsm2l has no reduction grid axis (k is resident), so it
# has no split dimension.
SPLIT_CANDIDATES = (1, 2, 4, 8, 16)

_TIE_EPS = 1e-12


def _pick_best(scored, tie_key):
    """Argmin of modeled time; ties (within _TIE_EPS) break by ``tie_key``.

    The documented rule, applied uniformly to all three choosers: ties
    break toward *deeper* pipelines along the streamed/reduction axis
    (smaller reduction-axis block => more grid steps => better DMA overlap),
    and residual ties toward fewer re-fetches of the stationary operand
    (larger parallel-axis block).
    """
    best_t = min(t for t, _ in scored)
    tied = [p for t, p in scored if t <= best_t + _TIE_EPS]
    return min(tied, key=tie_key)


def tsm2r_candidates(m: int, k: int, n: int, spec: TPUSpec = V5E,
                     dtype=jnp.bfloat16) -> list[tuple[int, int, int]]:
    """All VMEM-feasible (block_m, block_k, splits) candidates for TSM2R.

    This is the grid both the analytic argmin (``choose_params_tsm2r``) and
    the measured-time autotuner (``core.autotune``) search over, so the two
    halves of Algorithm 5 score exactly the same parameter space. The
    feasibility filter IS ``analysis.contracts.feasible`` (VMEM budget,
    quantized-dim caps, split whole-slice feasibility -- per-cell VMEM is
    split-invariant), so the model can never score a block the kernel
    contracts reject.
    """
    return [(bm, bk, s)
            for bm in _BM_CANDIDATES
            for bk in _BK_CANDIDATES
            for s in SPLIT_CANDIDATES
            if contracts.feasible(
                "tsm2r", (m, k, n),
                {"block_m": bm, "block_k": bk, "splits": s}, dtype, spec)]


def tsm2l_candidates(m: int, k: int, n: int, spec: TPUSpec = V5E,
                     dtype=jnp.bfloat16) -> list[int]:
    """All VMEM-feasible block_m candidates for TSM2L (filter:
    ``analysis.contracts.feasible``)."""
    return [bm for bm in _BM_L_CANDIDATES
            if contracts.feasible("tsm2l", (m, k, n), {"block_m": bm},
                                  dtype, spec)]


def tsmt_candidates(m: int, a: int, bdim: int, spec: TPUSpec = V5E,
                    dtype=jnp.bfloat16) -> list[tuple[int, int, int]]:
    """All VMEM-feasible (block_m, block_a, splits) candidates for TSMT.

    m is the reduction here, so S slices the m sweep; S > 1 requires at
    least one full (bm) block per slice. Filter:
    ``analysis.contracts.feasible``.
    """
    return [(bm, ba, s)
            for bm in _BM_CANDIDATES
            for ba in _BA_CANDIDATES
            for s in SPLIT_CANDIDATES
            if contracts.feasible(
                "tsmt", (m, a, bdim),
                {"block_m": bm, "block_a": ba, "splits": s}, dtype, spec)]


def choose_params_tsm2r(m: int, k: int, n: int, spec: TPUSpec = V5E,
                        dtype=jnp.bfloat16) -> tuple[int, int, int]:
    """Pick (block_m, block_k, splits) minimizing modeled time under the
    VMEM budget.

    Same contract as the paper's Algorithm 5 (choose t2/t3 per bound class,
    then offline-profile t1): we enumerate the hardware-quantized candidate
    grid and take the argmin of the modeled time; ties break toward NOT
    splitting (S=1 -- partials cost nothing only when modeled equal), then
    toward deeper k-pipelines (smaller block_k -- better DMA overlap),
    residual ties toward larger block_m (fewer B-window re-fetches).
    """
    cands = tsm2r_candidates(m, k, n, spec, dtype)
    if not cands:  # tiny problem: single block (dtype-aware row quantum)
        return (min(_roundup(m, contracts.min_sublane(spec, dtype)), 256),
                min(_roundup(k, spec.lane), 128), 1)
    scored = [(tsm2r_model_time(m, k, n, bm, bk, spec, dtype, splits=s),
               (bm, bk, s))
              for bm, bk, s in cands]
    return _pick_best(scored, lambda p: (p[2], p[1], -p[0]))


def choose_params_tsm2l(m: int, k: int, n: int, spec: TPUSpec = V5E,
                        dtype=jnp.bfloat16) -> int:
    """Pick block_m (the tcf analogue) for TSM2L.

    Ties break toward deeper m-pipelines (smaller block_m), per the same
    rule as ``choose_params_tsm2r``.
    """
    cands = tsm2l_candidates(m, k, n, spec, dtype)
    if not cands:
        return 256
    scored = [(tsm2l_model_time(m, k, n, bm, spec, dtype), bm) for bm in cands]
    return _pick_best(scored, lambda bm: bm)


def choose_params_tsmt(m: int, a: int, bdim: int, spec: TPUSpec = V5E,
                       dtype=jnp.bfloat16) -> tuple[int, int, int]:
    """Pick (block_m, block_a, splits) for the transposed kernel.

    Ties break toward not splitting (S=1), then deeper reduction pipelines
    (smaller block_m -- m is the streamed reduction here), residual ties
    toward larger block_a (fewer Y-window re-fetches) -- the same rule as
    the other choosers.
    """
    cands = tsmt_candidates(m, a, bdim, spec, dtype)
    if not cands:  # tiny problem: single block (dtype-aware row quantum)
        return (min(_roundup(m, contracts.min_sublane(spec, dtype)), 256),
                min(_roundup(a, spec.lane), 128), 1)
    scored = [(tsmt_model_time(m, a, bdim, bm, ba, spec, dtype, splits=s),
               (bm, ba, s))
              for bm, ba, s in cands]
    return _pick_best(scored, lambda p: (p[2], p[0], -p[1]))


# ---------------------------------------------------------------------------
# Utilization estimates (paper Fig. 7/11 metric, modeled for v5e)
# ---------------------------------------------------------------------------

def modeled_bandwidth_utilization(m: int, k: int, n: int, bm: int, bk: int,
                                  spec: TPUSpec = V5E, dtype=jnp.bfloat16,
                                  *, splits: int = 1) -> float:
    """Fraction of peak HBM bandwidth the kernel sustains (modeled).

    util = minimal-bytes / (modeled_time * peak_bw): 1.0 means A/B/C each
    move once at full stream rate -- the paper's definition of success for
    the memory-bound regime. Pass the chooser's ``splits`` so the
    utilization describes the same kernel as the modeled time.
    """
    b = bytes_per_elem(dtype)
    min_bytes = (m * k + k * n + m * n) * b
    t = tsm2r_model_time(m, k, n, bm, bk, spec, dtype, splits=splits)
    return min(1.0, min_bytes / (t * spec.hbm_bw))


def modeled_compute_utilization(m: int, k: int, n: int, bm: int, bk: int,
                                spec: TPUSpec = V5E, dtype=jnp.bfloat16,
                                *, splits: int = 1) -> float:
    flops = 2.0 * m * k * n
    t = tsm2r_model_time(m, k, n, bm, bk, spec, dtype, splits=splits)
    return min(1.0, flops / (t * spec.peak_flops(dtype)))
