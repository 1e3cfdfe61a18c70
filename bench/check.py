"""How ``correct`` is decided: what the timed path served against the plain
reference.

Once the window has closed and the program's state is freed, a traffic
kind (``kinds/<kind>.py``) draws a sample of the requests it finished from
the seed and runs them through the configuration's float32 reference,
teacher-forced on the prompts and the tokens the program served. The
numbers it can compare, each in standard deviations of the reference
logits at the position (``std``):

* ``max_gap`` -- the widest gap over the sample by which a served token's
  reference logit lies below the reference's best; 0 where the program
  served the reference's own greedy choice.
* ``logit_err`` -- the widest over the sample of the RMS distance between
  the logits the program served a token from and the reference's.
* ``token_gap_excess`` -- the widest over the sample of
  (reference gap of the served token - 2 x the largest distance between
  the program's logits and the reference's). A greedy token is the argmax
  of the program's logits, so its reference gap is at most twice that
  distance and this reads 0 or less, exactly; a token altered after the
  argmax reads its whole gap.

A request that never came back (``failed``) is compared against 0. Each
cell's ``limits/<cell>.json`` names the numbers it compares. The control
puts the reference computed in fp8 (``refops.mm``'s ``lowp``) in the
program's place: its logits, and the tokens it would serve greedily, at
the same positions.
"""

from __future__ import annotations

import functools

import numpy as np

SEQ_BLOCK = 16         # sequences per reference call
POS_BLOCK = 256        # positions per logits block
PAD_TO = 1024          # reference lengths are padded up to a multiple


def sample(seed: int, n: int, k: int) -> np.ndarray:
    """``k`` of ``n`` indices drawn from the seed (all when k >= n)."""
    rng = np.random.default_rng([seed % (1 << 64), 7])
    return np.sort(rng.choice(n, size=min(k, n), replace=False))


@functools.cache
def _gap_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(logits, tok):
        valid = (tok >= 0) & (tok < logits.shape[-1])
        sel = jnp.take_along_axis(logits, jnp.clip(tok, 0, logits.shape[-1] - 1)[..., None],
                                  axis=-1)[..., 0]
        g = (logits.max(-1) - sel) / logits.std(-1)
        return jnp.where(valid, g, jnp.inf)

    return gaps


def reference_logits(cfg_mod, sizes, params, seqs, k: int, lowp=None):
    """Yields (first row, first position, reference logits (rows, positions,
    V), control logits or None) over the last ``k`` positions of each
    sequence ``seqs`` (n, T), in blocks of rows and positions."""
    n, t = seqs.shape
    t_pad = -(-t // PAD_TO) * PAD_TO
    for s0 in range(0, n, SEQ_BLOCK):
        block = np.zeros((min(SEQ_BLOCK, n - s0), t_pad), np.int32)
        block[:, :t] = seqs[s0:s0 + SEQ_BLOCK]
        h = cfg_mod.hidden(params, block, sizes)[:, t - k:t]
        hc = cfg_mod.hidden(params, block, sizes, lowp)[:, t - k:t] if lowp else None
        for p0 in range(0, k, POS_BLOCK):
            ref = cfg_mod.logits(params, h[:, p0:p0 + POS_BLOCK], sizes)
            ctl = (cfg_mod.logits(params, hc[:, p0:p0 + POS_BLOCK], sizes, lowp)
                   if lowp else None)
            yield s0, p0, ref, ctl


def token_gaps(cfg_mod, sizes, params, seqs, served, lowp=None):
    """Gap of every served token (n, K), predicted at the last K positions
    of ``seqs``; with ``lowp`` also the gaps of the control's greedy tokens
    at the same positions (else None)."""
    import jax.numpy as jnp
    gaps = _gap_fn()
    n, k = served.shape
    out = np.zeros((n, k), np.float32)
    ctl_out = np.zeros((n, k), np.float32) if lowp else None
    for s0, p0, ref, ctl in reference_logits(cfg_mod, sizes, params, seqs, k, lowp):
        rows, cols = slice(s0, s0 + ref.shape[0]), slice(p0, p0 + ref.shape[1])
        out[rows, cols] = np.asarray(gaps(ref, served[rows, cols]))
        if ctl is not None:
            ctl_out[rows, cols] = np.asarray(gaps(ref, jnp.argmax(ctl, -1)))
    return out, ctl_out


def logit_numbers(got, ref, served) -> dict:
    """``logit_err`` and ``token_gap_excess`` of logits ``got`` (n, V) and
    the tokens served from them (n,) against the reference's ``ref``,
    in float64 so that a greedy token's excess is 0 or less exactly."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    std = ref.std(-1)
    diff = got - ref
    rms = np.sqrt((diff * diff).mean(-1)) / std
    gap = ref.max(-1) - np.take_along_axis(ref, served[:, None], -1)[:, 0]
    excess = (gap - 2 * np.abs(diff).max(-1)) / std
    return {"logit_err": float(rms.max()), "token_gap_excess": float(excess.max())}


def last_position_numbers(cfg_mod, sizes, params, seqs, got, served, lowp=None):
    """``logit_numbers`` of the program's logits ``got`` (n, V) and tokens
    ``served`` (n,) at the last position of each sequence; with ``lowp``
    also the control's (else None)."""
    refs, ctls = [], []
    for _, _, ref, ctl in reference_logits(cfg_mod, sizes, params, seqs, 1, lowp):
        refs.append(np.asarray(ref[:, 0]))
        if ctl is not None:
            ctls.append(np.asarray(ctl[:, 0]))
    ref = np.concatenate(refs)
    prog = logit_numbers(got, ref, np.asarray(served))
    if not lowp:
        return prog, None
    ctl = np.concatenate(ctls)
    return prog, logit_numbers(ctl, ref, ctl.argmax(-1))


def verdict(numbers: dict, failed: int, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit."""
    checks = {name: {"value": float(numbers[name]), "limit": limit}
              for name, limit in limits.items()}
    checks["failed"] = {"value": failed, "limit": 0}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
