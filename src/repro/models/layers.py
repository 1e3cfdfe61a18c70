"""Shared model layers: norms, RoPE, MLPs, embeddings.

Pure-functional style: ``init_*`` returns a params pytree (nested dict of
arrays); ``*_fwd`` applies it. All matmul accumulation is f32
(``preferred_element_type``); norms run in f32 regardless of activation
dtype. Weight layout convention: ``w[in_dim, out_dim]`` so activations hit
the MXU without transposes.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import tsmm


def scoped(name: str):
    """Trace the decorated function under ``jax.named_scope(name)``.

    The scope is a component of the ``op_name`` of every HLO instruction
    the function emits, so a profile names the model's layers: an op of a
    projection inside an RWKV time-mix of a layer reads
    ``.../layers/while/body/.../block/time_mix/dense/tsmm.dense/dot_general``.
    Scopes are trace-time metadata and change no instruction. The scope is
    looked up at call time, so a test can swap ``jax.named_scope``.
    """
    def wrap(fn):
        @functools.wraps(fn)
        def scoped_fn(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped_fn
    return wrap


def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def _dense_raw(w, x):
    # repro: allow-raw-param-matmul (this IS the dense primitive dense()
    # routes non-tsmm shapes to -- 1-D params and the mode="dense" A/B arm;
    # wrapping it in tsmm would recurse)
    return lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


# Param-dtype-gradient variant (GemmPolicy.param_dtype_grads, the old
# REPRO_BF16_PARAM_GRADS lever): emit parameter gradients in the parameter
# dtype instead of f32. The default VJP of an f32-accumulating dot produces
# f32 cotangents, doubling per-device gradient memory under pure-DP/ZeRO-1
# (12.8 GiB -> 6.4 GiB for a 3B model). Accumulation inside each dot stays
# f32 either way; the policy rides the nondiff arg so the backward
# re-dispatch honors the scope dense() was traced under.

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dense_pg(w, x, policy):
    return tsmm.tsmm(x, w, policy=policy)


def _dense_pg_fwd(w, x, policy):
    return tsmm.tsmm(x, w, policy=policy), (w, x)


def _dense_pg_bwd(policy, res, dy):
    w, x = res
    bp = tsmm.backward_policy(policy)
    # dw[d_in,d_out] = X^T dY reduced over every token dim: the TSMTTSM
    # shape; tsmm_t collapses the leading dims into the reduction itself.
    dw = tsmm.tsmm_t(x, dy, policy=bp).astype(w.dtype)
    dx = tsmm.tsmm(dy, w.T, policy=bp).astype(x.dtype)
    return dw, dx


_dense_pg.defvjp(_dense_pg_fwd, _dense_pg_bwd)


@scoped("dense")
def dense(w, x):
    """x @ w over the trailing dim of x.

    Every model projection (QKV/out/MLP/LoRA/SSM in-out) lands here, so
    this is where the tall-and-skinny dispatcher hooks into the train path:
    ``tsmm`` takes the (..., S, d_in) activations as-is (it owns the
    leading-dim collapse), routes to a TSM2X kernel when the shape
    qualifies (e.g. LoRA/PowerSGD ranks, skinny heads at large token
    counts), to the identical reshape-free ``dot_general`` otherwise, and
    to the per-shard ``shard_map`` executor under a multi-chip mesh. All
    routing follows the active ``tsmm.policy(...)`` scope, captured at
    trace time -- ``with tsmm.policy(mode="dense")`` is the A/B escape
    hatch (A/B arms still need separate jit caches). When the scope sets
    ``param_dtype_grads``, the custom-VJP ``_dense_pg`` variant owns the
    backward dtype.

    Profile scope ``dense``: every projection's device time, the LoRA
    halves included, with the routed GEMM under ``tsmm.<kind>``.
    """
    p = tsmm.current_policy()
    if x.ndim < 2:
        return _dense_raw(w, x)
    if p.param_dtype_grads:
        return _dense_pg(w, x, p)
    return tsmm.tsmm(x, w)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * lax.rsqrt(var + eps)
    out = out * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, fraction: float, theta: float):
    """Inverse frequencies for the rotated sub-dimension."""
    rot = int(head_dim * fraction)
    rot -= rot % 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, jnp.float32) / rot)), rot


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN context extension as DeepSeek-V3's published modeling code
    applies it (``rope_scaling`` of its config.json, type "yarn").

    Inverse frequencies are ramped between the plain ones (dimensions that
    turn more than ``beta_fast`` times over ``original_max_pos`` tokens)
    and the plain ones over ``factor`` (those that turn fewer than
    ``beta_slow`` times). The softmax scale is multiplied by ``mscale**2``,
    ``mscale = 0.1 * mscale_all_dim * ln(factor) + 1``; the config's
    ``mscale`` equals ``mscale_all_dim`` (both 1), so cos and sin keep unit
    amplitude.
    """
    factor: float
    original_max_pos: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 1.0

    def inv_freq(self, dim: int, theta: float) -> np.ndarray:
        def corr(rotations):
            return (dim * math.log(self.original_max_pos / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(corr(self.beta_fast)), 0)
        high = min(math.ceil(corr(self.beta_slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
        extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
        return (extra / self.factor * ramp + extra * (1 - ramp)).astype(np.float32)

    def softmax_factor(self) -> float:
        if self.factor <= 1:
            return 1.0
        return (0.1 * self.mscale_all_dim * math.log(self.factor) + 1.0) ** 2


def apply_rope(x, positions, *, theta: float = 10000.0, fraction: float = 1.0,
               inv_freq=None):
    """x: (..., S, H, D); positions: broadcastable to (..., S).

    ``fraction < 1`` rotates only the leading slice of D (ChatGLM-style
    partial / '2d' RoPE); the remainder passes through unrotated.
    ``inv_freq`` (D/2,) replaces the plain frequencies of ``theta`` over the
    whole of D (MLA's YaRN frequencies).
    """
    d = x.shape[-1]
    if inv_freq is None:
        inv_freq, rot = rope_freqs(d, fraction, theta)
    else:
        rot = d
    if rot == 0:
        return x
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (..., S, rot/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., : rot // 2], x_rot[..., rot // 2:]
    r1 = (x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin)
    r2 = (x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin)
    return jnp.concatenate(
        [r1.astype(x.dtype), r2.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_init(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def swiglu(params, x):
    g = dense(params["w_gate"], x)
    u = dense(params["w_up"], x)
    return dense(params["w_down"], jax.nn.silu(g) * u)


def gelu_mlp_init(key, d_model: int, d_ff: int, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "w_up": dense_init(k1, d_model, d_ff, dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": dense_init(k2, d_ff, d_model, dtype),
        "b_down": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(params, x):
    h = dense(params["w_up"], x) + params["b_up"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return dense(params["w_down"], h) + params["b_down"]


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embedding_init(key, vocab: int, d_model: int, dtype):
    return {"table": (jax.random.normal(key, (vocab, d_model), jnp.float32)
                      * d_model ** -0.5).astype(dtype)}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x):
    """Logits in f32 (loss stability); table may be the tied embedding."""
    # repro: allow-raw-param-matmul (logits must stay f32 -- tsmm returns
    # the operand dtype -- and vocab-sized outputs never classify
    # tall-skinny; GSPMD shards the dense dot over the tied table)
    return lax.dot_general(
        x, params["table"], (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def lora_init(key, d_in: int, d_out: int, rank: int, dtype):
    """Low-rank adapter: a tall-and-skinny GEMM pair (TSM2X shapes)."""
    k1, k2 = jax.random.split(key)
    return {
        "a": dense_init(k1, d_in, rank, dtype),
        "b": jnp.zeros((rank, d_out), dtype),
    }


@scoped("lora")
def lora_apply(params, x, base_out=None):
    """Profile scope ``lora``: the adapter pair, each half a ``dense``."""
    h = dense(params["a"], x)
    out = dense(params["b"], h)
    return out if base_out is None else base_out + out
