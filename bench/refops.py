"""Plain float32 operations shared by the configurations' references.

Nothing here imports the program. Matrix products run at
``Precision.HIGHEST`` (on a TPU a float32 product otherwise runs in bf16
passes). ``lowp="fp8"`` turns every product into the control's: weights
per output column and activations per row scaled to the float8_e4m3fn
range, rounded to it, and accumulated in float32 -- a step below the
bf16 the configurations state. (int8 W8A8 with absmax/127 scales was
tried first and reads within 2x of the bf16 program; see PERF.md.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32


def _f8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(x, w, lowp: str | None = None):
    """x[..., k] @ w[k, n] in float32."""
    x = x.astype(F32)
    w = w.astype(F32)
    if lowp == "fp8":
        x = _f8(x, -1)
        w = _f8(w, 0)
    elif lowp is not None:
        raise ValueError(f"unknown lowp {lowp!r}")
    return lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                           precision=HIGHEST, preferred_element_type=F32)


def layernorm(x, scale, bias, eps):
    x = x.astype(F32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale.astype(F32) + bias.astype(F32)


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(F32)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def shift(x):
    """x_{t-1} along axis 1, zeros before the first token."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def layer(tree, *index):
    """The slice of a stacked parameter tree at ``index``."""
    return jax.tree.map(lambda a: a[index], tree)
