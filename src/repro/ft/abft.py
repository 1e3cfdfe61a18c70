"""ABFT (algorithm-based fault tolerance) checksums via the TSM2X kernels.

This is the paper's own headline application [refs 10-20 in the paper]:
checksum encoding multiplies the protected matrix by a skinny weight
matrix -- a tall-and-skinny GEMM. We protect optimizer/parameter state
against silent data corruption (SDC):

    encode:  c = W^T e          e: (d1, s) skinny checksum weights
    verify:  c' = W'^T e ; SDC detected iff ||c' - c|| > tol

Both encode and verify are the TSMT kernel shape (reduction over the huge
matrix dim, s in {2..8} output columns). Weighted checksums (e columns:
ones + ramp) localize single-fault rows, as in classic Huang-Abraham
schemes.

Cost: one TSMT pass over the params -- at the HBM-roofline that is
params_bytes / 819 GB/s per verification (e.g. 8 ms for a 3B model across
a pod), cheap enough to run at checkpoint boundaries. With s <= 8 output
columns the TSMT grid has ONE parallel cell: on multi-core parts scope
``with tsmm.policy(split=...)`` around encode/verify so the split-
reduction kernels keep every core on the stream (the default "auto"
engages exactly when the perf model's occupancy term says it pays).

Beyond the detect-only checkpoint-boundary tree API, this module owns the
*locate-and-correct* math shared with the dispatcher's online wrap
(``GemmPolicy.abft``, ``core/tsmm._abft_guard``):

* :func:`tolerance` -- the detection threshold, derived from shape and
  dtype rather than guessed: checksum accumulation over ``reduction``
  terms plus the protected GEMM's own rounding carry error that scales
  like ``eps * (sqrt(rows) + sqrt(reduction))`` times the column
  magnitude (random-walk rounding); ``ABFT_TOL_FACTOR`` (in
  ``analysis/contracts``) is the safety margin on top. A genuine bit
  flip in a high-order bit moves the checksum by order the *value*, many
  orders above this.
* :func:`locate_and_correct` -- compare the output's checksums against
  the operand-side reference; on deviation, the ratio of the
  ramp-weighted to the plain checksum delta identifies the faulty row
  (``d1/d0 = (i+1)/rows``), the plain delta gives the per-column error
  estimate, and a nearest-single-bit-flip snap repairs bit flips
  *bit-exactly* (the snapped candidate must agree with the estimate to
  within noise, else the correction falls back to the analytic estimate
  or, when the residual check still fails, to a NaN poison of the whole
  output -- never a silently wrong "repair").
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.analysis import contracts as _contracts
from repro.core import tsmm


def _radix(d1: int) -> tuple[int, int]:
    """(period, blocks) of the mixed-radix row code: ``period`` =
    ceil(sqrt(d1)) rows per block, ``blocks`` = ceil(d1 / period)."""
    p = max(1, math.isqrt(d1 - 1) + 1) if d1 > 1 else 1
    return p, -(-d1 // p)


def _weights_at(i, d1: int, s: int) -> jnp.ndarray:
    """Checksum weights of row(s) ``i`` (int array, traced or not):
    ``(..., s)`` f32, the rows of :func:`checksum_weights`."""
    i = jnp.asarray(i, jnp.float32)
    ones = jnp.ones_like(i)
    if s == 2:
        cols = [ones, (i + 1.0) / d1]
    else:
        p, nb = _radix(d1)
        block = jnp.floor(i / p)
        cols = [ones, (i - block * p + 1.0) / p, (block + 1.0) / nb]
    while len(cols) < s:
        cols.append(jnp.square(cols[-1]))
    return jnp.stack(cols[:s], axis=-1)


def checksum_weights(d1: int, s: int = 2) -> jnp.ndarray:
    """Huang-Abraham style weight columns, ``(d1, s)`` f32.

    Column 0 (ones) carries the error magnitude; the ramp columns carry it
    scaled by the row position, so their ratios to the plain delta
    localize a single faulty row. ``s == 2``: one ramp ``(i+1)/d1``. Its
    ratio is read against the f32 rounding of a checksum whose magnitude
    grows like ``sqrt(d1)``, so over tens of thousands of rows a moderate
    fault lands a row or more away. ``s >= 3``: a mixed-radix code -- a
    fine ramp ``((i mod P)+1)/P`` and a coarse ramp ``(i//P + 1)/B`` with
    ``P = B ~ sqrt(d1)`` -- which resolves each digit ``sqrt(d1)`` times
    more finely (the offline parameter path, where ``d1`` is a whole
    leaf). Further columns square the last one. f32 always: a
    low-precision ramp would blur exactly the ratio the locate step
    divides."""
    if s < 2:
        raise ValueError(f"checksum_weights: s={s} < 2 cannot localize")
    return _weights_at(jnp.arange(d1), d1, s)


# Internal alias kept for call sites that predate the public name.
_checksum_weights = checksum_weights


def tolerance_eps(dtype, quant: str = "none") -> float:
    """The unit roundoff driving :func:`tolerance` for a protected GEMM
    producing ``dtype`` under quantization mode ``quant``. Floored at f32
    eps (checksums accumulate in f32); under ``quant="int8"`` widened to
    the 1/127 quantization step -- the protected product is quantized but
    the checksum reference is exact f32, so their gap is quant noise, not
    rounding noise."""
    dt = jnp.dtype(dtype)
    f32_eps = float(jnp.finfo(jnp.float32).eps)
    eps = float(jnp.finfo(dt).eps) if jnp.issubdtype(dt, jnp.floating) \
        else f32_eps
    eps = max(eps, f32_eps)
    if quant == "int8":
        eps = max(eps, 1.0 / 127.0)
    return eps


def tolerance(rows: int, reduction: int, eps: float, amax) -> jnp.ndarray:
    """Per-column detection threshold for checksum deviations.

    ``amax`` is the per-column max |value| of the protected output (f32).
    The ``sqrt(rows) + sqrt(reduction)`` term is the random-walk growth of
    rounding error over the checksum reduction and the GEMM's own
    contraction; the +32 floor covers short reductions where the error is
    a few ulps regardless; ``contracts.ABFT_TOL_FACTOR`` is the safety
    margin (tuned against the false-positive tests). The base is made
    robust to the very corruption it guards against: a faulty cell sits
    in ``amax`` itself, so a raw per-column max would let a huge flip
    inflate its own threshold past its own deviation (fatal under int8,
    where ``eps`` alone is 1/127) -- capping each column at 64x the
    cross-column *median* keeps a single damaged column from out-voting
    the clean ones, while the ``1e-3 * median`` leak keeps all-zero
    columns from demanding exactness the kernels never promised."""
    amax = jnp.asarray(amax, jnp.float32)
    med = jnp.median(amax)
    base = jnp.minimum(amax, 64.0 * med) + 1e-3 * med + jnp.float32(1e-30)
    scale = _contracts.ABFT_TOL_FACTOR * eps * (
        math.sqrt(rows) + math.sqrt(reduction) + 32.0)
    return jnp.float32(scale) * base


def detect(c_out, c_ref, *, rows: int, reduction: int, eps: float, amax):
    """Per-column fault mask from the two checksum computations.

    Returns ``(bad, tol)``: ``bad[j]`` is True when column j's plain OR
    ramp checksum deviates beyond ``tol[j]`` -- written as the negation
    of the pass condition so a NaN deviation (non-finite wreckage in the
    output) counts as bad."""
    d = c_out[:, :2] - c_ref[:, :2]
    tol = tolerance(rows, reduction, eps, amax)
    ok = (jnp.abs(d[:, 0]) <= tol) & (jnp.abs(d[:, 1]) <= tol)
    return ~ok, tol


def encode_leaf(x, s: int = 3, *, policy=None, interpret=None):
    """Checksum of one 2-D (or reshaped) array: (cols, s) f32. The default
    ``s=3`` is the mixed-radix code of :func:`checksum_weights`, which
    localizes a single faulty row of a whole parameter leaf exactly.

    ``policy`` pins a GemmPolicy for the TSMT pass (defaults to the active
    ``tsmm.policy(...)`` scope); ``interpret=`` is the deprecated alias.
    """
    m = x.reshape(x.shape[0], -1) if x.ndim != 2 else x
    if m.ndim == 1:
        m = m[:, None]
    e = _checksum_weights(m.shape[0], s)
    # c[s_, cols] via TSMT: e^T m  -> orient as tsmm_t(m_as_x? ...): we use
    # tsmm_t(e_like? ) -- X^T Y with X=m (m rows huge) gives (cols, s):
    return tsmm.tsmm_t(m.astype(jnp.float32), e, policy=policy,
                       interpret=interpret)


def encode_tree(tree, s: int = 3, *, policy=None, interpret=None):
    """Checksums for every leaf with >= 2 dims and >= 2^16 elements."""
    def one(x):
        if x.ndim < 1 or x.size < 65536:
            return None
        return encode_leaf(x, s, policy=policy, interpret=interpret)
    return jax.tree.map(one, tree)


def verify_tree(tree, checksums, *, rtol: float = 1e-3, policy=None,
                interpret=None):
    """Returns (ok: bool array, per-leaf max relative deviation tree)."""
    devs = []

    def one(x, c):
        if c is None:
            return None
        c2 = encode_leaf(x, c.shape[1], policy=policy, interpret=interpret)
        denom = jnp.maximum(jnp.abs(c), 1e-6)
        dev = jnp.max(jnp.abs(c2 - c) / denom)
        devs.append(dev)
        return dev

    dev_tree = jax.tree.map(one, tree, checksums,
                            is_leaf=lambda x: x is None)
    if not devs:
        return jnp.bool_(True), dev_tree
    worst = jnp.stack(devs).max()
    return worst <= rtol, dev_tree


# ---------------------------------------------------------------------------
# Locate-and-correct (shared by the online dispatch wrap and the tree API)
# ---------------------------------------------------------------------------

def _snap_to_bitflip(row, est, snap_tol):
    """Per column: the single-bit-flip neighbor of ``row`` nearest to the
    f32 estimate ``est``, when one agrees with it to within ``snap_tol``;
    else ``est`` cast to the row's dtype.

    A genuine bit flip leaves the true value among the ``nbits``
    candidates ``bitcast(row ^ (1 << b))``, and the analytic estimate
    (true value + checksum rounding noise) sits within noise of exactly
    one of them -- snapping recovers the pre-flip bits exactly. Arbitrary
    (non-bit-flip) corruption matches no candidate, so the agreement gate
    keeps the snap from quantizing a legitimate estimate onto a wrong
    neighbor. Everything is stop_gradient'ed: bitcasts carry no tangent,
    and the caller discards gradients through the repaired row anyway."""
    row = lax.stop_gradient(row)
    est = lax.stop_gradient(est)
    nbits = jnp.dtype(row.dtype).itemsize * 8
    u = jnp.dtype(f"uint{nbits}")
    ri = lax.bitcast_convert_type(row, u)[:, None]
    masks = (jnp.ones((), u) << jnp.arange(nbits, dtype=u))[None, :]
    cand = lax.bitcast_convert_type(ri ^ masks, row.dtype)
    dist = jnp.abs(cand.astype(jnp.float32) - est[:, None])
    dist = jnp.where(jnp.isfinite(dist), dist, jnp.inf)
    kbest = jnp.argmin(dist, axis=1)
    best = jnp.take_along_axis(cand, kbest[:, None], axis=1)[:, 0]
    dbest = jnp.take_along_axis(dist, kbest[:, None], axis=1)[:, 0]
    ok = jnp.isfinite(est) & (dbest <= snap_tol)
    return jnp.where(ok, best, est.astype(row.dtype))


def locate_and_correct(out, c_out, c_ref, *, rows: int, reduction: int,
                       mode: str, eps: float, ref_row=None):
    """Verify ``out`` (2-D, ``(rows, cols)``) against its checksums; on a
    detected fault either poison or repair it. Trace-safe (pure lax/jnp,
    no host callback), gradient-transparent on the clean path.

    ``c_out`` is the checksum computed FROM the output, ``c_ref`` the
    reference pushed through the operands -- both ``(cols, s>=2)`` f32
    with the columns of :func:`checksum_weights` (plain weights in column
    0, the ramp or the mixed-radix digits after it). ``mode``:

    * "verify"  -- clean: return ``out`` unchanged (bit-identical);
      fault: return ``out`` fully NaN-poisoned, so any downstream
      finiteness check (loss guards, ``step_ok``) trips.
    * "correct" -- localize the single faulty row from the ramp/plain
      deviation ratio of the worst column, estimate each bad column's
      true value, snap to the nearest single-bit-flip candidate
      (bit-exact repair for flip faults), and accept the repair only if
      it explains the deviations (residual re-check) -- otherwise
      NaN-poison exactly as "verify" would.

    ``ref_row`` (correct mode): optional trace-safe callback
    ``i -> (cols,) f32`` recomputing the TRUE content of output row ``i``
    from the operands (the online wrap passes a dynamic-slice dense
    recompute -- one ``(1, red) @ (red, cols)`` dot). With it, the snap
    reference is accurate at the value's own scale regardless of how
    large the corruption is, so even astronomically wrong cells (a
    flipped exponent MSB) snap back bit-exactly, and same-row
    multi-column damage repairs wholesale. Without it, the estimate
    falls back to ``row - d0`` (checksum linearity), which is exact only
    down to f32 cancellation at the *corrupted* value's magnitude --
    fine for the offline leaf path's moderate flips, ambiguous for
    magnitude-exploding ones.
    """
    if mode not in ("verify", "correct"):
        raise ValueError(
            f"[abft-mode] locate_and_correct mode {mode!r}: valid modes "
            "are 'verify', 'correct'")
    f32 = jnp.float32
    out_f = lax.stop_gradient(out).astype(f32)
    c_out = lax.stop_gradient(jnp.asarray(c_out, f32))
    c_ref = lax.stop_gradient(jnp.asarray(c_ref, f32))
    amax = jnp.max(jnp.abs(out_f), axis=0)
    bad, tol = detect(c_out, c_ref, rows=rows, reduction=reduction,
                      eps=eps, amax=amax)
    any_bad = jnp.any(bad)
    poisoned = jnp.where(any_bad, jnp.full_like(out, jnp.nan), out)
    if mode == "verify":
        return poisoned

    d = c_out - c_ref
    d0 = d[:, 0]
    # Anchor on the worst finite bad column; its ramp/plain ratios are the
    # faulty row's weights (one ramp, or the fine and coarse digits).
    mag = jnp.where(bad & jnp.isfinite(d0), jnp.abs(d0), -jnp.inf)
    j = jnp.argmax(mag)
    s = 2 if c_out.shape[1] == 2 else 3
    if s == 2:
        ratio = d[j, 1] / d0[j]
        i_f = jnp.round(ratio * rows) - 1.0
        digits_ok = jnp.isfinite(ratio)
    else:
        p, nb = _radix(rows)
        fine = jnp.round(d[j, 1] / d0[j] * p) - 1.0
        coarse = jnp.round(d[j, 2] / d0[j] * nb) - 1.0
        i_f = coarse * p + fine
        digits_ok = ((fine >= 0.0) & (fine <= p - 1.0)
                     & (coarse >= 0.0) & (coarse <= nb - 1.0))
    i_ok = (digits_ok & jnp.isfinite(i_f) & (i_f >= 0.0)
            & (i_f <= rows - 1.0))
    i = jnp.clip(jnp.where(jnp.isfinite(i_f), i_f, 0.0), 0,
                 rows - 1).astype(jnp.int32)
    row = lax.dynamic_slice_in_dim(out, i, 1, axis=0)[0]
    if ref_row is None:
        est = row.astype(f32) - d0      # checksum is linear in the row
    else:
        est = lax.stop_gradient(jnp.asarray(ref_row(i), f32))
    fix_cols = bad & jnp.isfinite(est)
    snapped = _snap_to_bitflip(row, est, 4.0 * tol)
    fixed = lax.stop_gradient(jnp.where(fix_cols, snapped, row))
    # Residual: a correct single-row repair must cancel every checksum
    # deviation in every column (bad and clean alike -- a multi-row fault leaves
    # the other rows' contribution standing and fails here). The gate
    # widens by the f32 cancellation floor of the quantities it
    # subtracts: d and delta are each rounded at their own magnitude,
    # so their sum is only meaningful down to ~eps * (|d| + |delta|).
    delta = fixed.astype(f32) - row.astype(f32)
    w = _weights_at(i, rows, s)
    f32_eps = jnp.float32(jnp.finfo(jnp.float32).eps)
    res_ok = jnp.bool_(True)
    for col in range(s):
        shift = w[col] * delta
        cancel = 32.0 * f32_eps * (jnp.abs(d[:, col]) + jnp.abs(shift))
        res_ok &= jnp.all(jnp.abs(d[:, col] + shift) <= 4.0 * tol + cancel)
    corrected = lax.dynamic_update_slice_in_dim(out, fixed[None, :], i,
                                                axis=0)
    good = i_ok & res_ok
    return jnp.where(any_bad,
                     jnp.where(good, corrected,
                               jnp.full_like(out, jnp.nan)),
                     out)


def correct_leaf(x, c, *, policy=None, interpret=None):
    """Offline locate-and-correct for one checksummed leaf: re-encode,
    compare against the stored checksum ``c``, repair a single faulty row
    (bit-exact for flip faults) or NaN-poison. Returns
    ``(ok_before, corrected)`` -- ``ok_before`` False means the leaf HAD
    a detected fault (the corrected copy may still be the repair or the
    poison; poison forces the caller to a checkpoint restore)."""
    m = x.reshape(x.shape[0], -1) if x.ndim != 2 else x
    if m.ndim == 1:
        m = m[:, None]
    c2 = encode_leaf(x, c.shape[1], policy=policy, interpret=interpret)
    rows = m.shape[0]
    eps = tolerance_eps(x.dtype)
    amax = jnp.max(jnp.abs(m.astype(jnp.float32)), axis=0)
    bad, _ = detect(c2, c, rows=rows, reduction=rows, eps=eps, amax=amax)
    fixed = locate_and_correct(m, c2, c, rows=rows, reduction=rows,
                               mode="correct", eps=eps)
    return ~jnp.any(bad), fixed.reshape(x.shape)


def verify_and_correct_tree(tree, checksums, *, policy=None,
                            interpret=None):
    """Tree-wide offline locate-and-correct against stored checksums.

    Returns ``(ok_before, corrected_tree)``: ``ok_before`` is True when
    no leaf deviated (the corrected tree is then value-identical to the
    input); on single-row faults the corrected tree carries the repaired
    leaves (bit-exact for flip faults); uncorrectable leaves come back
    NaN-poisoned so downstream finiteness checks force a restore instead
    of silently training on damage. Un-checksummed leaves (``None`` in
    the checksum tree) pass through untouched."""
    oks = []

    def one(x, c):
        if c is None:
            return x
        ok, fixed = correct_leaf(x, c, policy=policy, interpret=interpret)
        oks.append(ok)
        return fixed

    corrected = jax.tree.map(one, tree, checksums,
                             is_leaf=lambda v: v is None)
    ok_before = jnp.all(jnp.stack(oks)) if oks else jnp.bool_(True)
    return ok_before, corrected
