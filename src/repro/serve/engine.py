"""Serving engine: batched prefill + decode with KV caches.

``make_serve_fns`` returns the two jit-able pure functions the dry-run
lowers (``prefill_step``, ``decode_step``) plus a host-side ``generate``
loop for the examples (greedy / temperature sampling).

Cache layout: contiguous per-layer tensors allocated once at
``max_len = prompt + max_new``; SWA archs get ring caches bounded by the
window (mixtral long_500k: 4096 slots instead of 524k); SSM archs carry
O(1) state. Continuous batching note: slot management across requests is
host-side (examples/serve_lm.py) -- the device functions are fixed-shape.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.core import tsmm
from repro.kernels import quant as kquant
from repro.models import model

# Model params arrive in f32 unless quantized records say otherwise.
_WEIGHT_DTYPE = jnp.float32


def make_serve_fns(cfg, policy: "tsmm.GemmPolicy | None" = None, *,
                   sharded_projections: bool = False):
    """Build (prefill_step, decode_step) pure functions for jit.

    ``policy`` pins a GemmPolicy scope around the traced bodies (e.g.
    ``GemmPolicy(mode="dense")`` for an A/B arm, or ``spec=V5P`` on newer
    hardware). GEMM dispatch is trace-time, so the scope only needs to be
    live while jit traces these functions -- wrapping the bodies here means
    callers don't have to manage the scope around their own ``jax.jit``.

    ``sharded_projections=True`` scopes ``reduce="psum_scatter"`` on top:
    under a multi-device serving mesh, ``tsmm_t`` products inside the
    steps (ABFT checksum projections, weight-side custom-VJP paths) come
    back row-sharded over the DP axes instead of replicated -- the right
    layout when the consumer immediately re-shards (and a no-op
    everywhere else: off-mesh or for shapes that cannot scatter, dispatch
    degrades exactly like the default path). DP axes follow the launch
    mesh via ``tsmm.derive_dp_axes`` unless the policy pins ``dp_axes``.

    Pre-quantized weights (``kernels.quant.quantize_weights`` records:
    ``{"q8": int8, "q8_scale": f32}`` leaves with offline per-tile
    scales) are accepted directly: the step bodies dequantize at entry,
    inside the jit trace, so the *stored/transferred* params stay at 1
    byte/elem + the tiny scale sidecar while the model code sees plain
    f32 arrays. (XLA commonly fuses the dequant into the first consumer;
    the fully-fused path -- int8 tiles all the way into the Pallas GEMMs
    via ``GemmPolicy(quant="int8")`` -- re-quantizes activations on the
    fly and is the policy knob, not the storage format.)

    In a device profile every op of a step carries the scope
    ``serve.prefill`` or ``serve.decode`` in its ``op_name``, above the
    model's own scopes (``models/model.py``).
    """
    def _scope():
        base = policy
        if sharded_projections:
            base = ((base if base is not None else tsmm.current_policy())
                    .with_(reduce="psum_scatter"))
        return (tsmm.policy(base) if base is not None
                else contextlib.nullcontext())

    def prefill_step(params, batch, cache):
        with _scope(), jax.named_scope("serve.prefill"):
            params = kquant.dequantize_weights(params, _WEIGHT_DTYPE)
            return model.prefill(params, cfg, batch, cache)

    def decode_step(params, tokens, pos, cache):
        with _scope(), jax.named_scope("serve.decode"):
            params = kquant.dequantize_weights(params, _WEIGHT_DTYPE)
            return model.decode_step(params, cfg, tokens, pos, cache)

    return prefill_step, decode_step


def sample_token(key, logits, temperature: float = 0.0):
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)


def generate(params, cfg, prompts, max_new: int, *, key=None,
             temperature: float = 0.0, extras=None, policy=None,
             sharded_projections: bool = False):
    """prompts: (B, S) int32. Returns (B, max_new) generated tokens.

    Host loop over jitted single-token steps (the production engine would
    run this under an async scheduler; step functions are identical).
    ``policy`` threads a GemmPolicy into the jitted steps;
    ``sharded_projections`` is forwarded to :func:`make_serve_fns`.
    """
    prefill_step, decode_step = make_serve_fns(
        cfg, policy=policy, sharded_projections=sharded_projections)
    prefill_j = jax.jit(prefill_step)
    decode_j = jax.jit(decode_step)

    b, s0 = prompts.shape
    cache = model.init_cache(cfg, b, s0 + max_new)
    batch = {"tokens": prompts}
    if extras:
        batch.update(extras)
    logits, cache = prefill_j(params, batch, cache)
    key = key if key is not None else jax.random.PRNGKey(0)
    toks = []
    tok = sample_token(key, logits, temperature)[:, None]
    toks.append(tok)
    for i in range(1, max_new):
        logits, cache = decode_j(params, tok, s0 + i - 1, cache)
        key = jax.random.fold_in(key, i)
        tok = sample_token(key, logits, temperature)[:, None]
        toks.append(tok)
    return jnp.concatenate(toks, axis=1)
