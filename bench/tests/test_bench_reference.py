"""The configurations' plain references against the program's serving path
at small widths on the CPU: prefill logits, then decode logits through the
cache, against the reference's full forward over the same tokens."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import small_sizes
from bench import load, serving

B, PROMPT, STEPS = 2, 24, 6

# float32: the program sums in another order (chunked scans, blocked
# attention) than the reference's one-step recurrences; measured 3e-7 of
# the largest logit on both configurations.
F32_TOL = 1e-4
# bfloat16: the program rounds every activation to bf16 (2^-8 relative)
# between float32 products; over the small stack that reads 0.5-0.9% of
# the largest logit, and a dropped LoRA branch moves it by 4.6% (rwkv6)
# and 53% (zamba2).
BF16_TOL = 2e-2


def served_logits(name, dtype, seed=5):
    from repro.models import model
    from repro.serve import engine

    sizes, mod = load.config(name)
    sizes = small_sizes(name, dtype=dtype)
    cfg = serving.program_config(mod, sizes)
    params = serving.build_params(mod, sizes, seed)
    serving.check_layout(params, cfg)
    toks = serving.prompts(seed, 1, 0, B, PROMPT + STEPS, sizes["vocab_size"])
    prefill, decode = (jax.jit(f) for f in engine.make_serve_fns(cfg))
    cache = model.init_cache(cfg, B, PROMPT + STEPS)
    lg, cache = prefill(params, {"tokens": jnp.asarray(toks[:, :PROMPT])}, cache)
    got = [lg]
    for i in range(STEPS - 1):
        lg, cache = decode(params, jnp.asarray(toks[:, PROMPT + i:PROMPT + i + 1]),
                           PROMPT + i, cache)
        got.append(lg)
    got = np.stack([np.asarray(g) for g in got], axis=1)
    ref = np.asarray(mod.logits(params, mod.hidden(params, toks[:, :-1], sizes), sizes))
    return got, ref[:, PROMPT - 1:]


def rel(a, b) -> float:
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("name", ["rwkv6-1.6b", "zamba2-1.2b"])
@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL), ("bfloat16", BF16_TOL)])
def test_serving_matches_reference(name, dtype, tol):
    got, ref = served_logits(name, dtype)
    assert rel(got[:, 0], ref[:, 0]) < tol, "prefill"
    assert rel(got[:, 1:], ref[:, 1:]) < tol, "decode through the cache"


@pytest.mark.parametrize("name", ["rwkv6-1.6b", "zamba2-1.2b"])
def test_dropped_lora_fails_the_comparison(name, monkeypatch):
    """The LoRA products on tsm2r are inside what the comparison covers."""
    from repro.models import layers
    monkeypatch.setattr(layers, "lora_apply", lambda p, x, base_out=None: jnp.zeros(
        x.shape[:-1] + (p["b"].shape[-1],), x.dtype))
    got, ref = served_logits(name, "bfloat16")
    assert rel(got, ref) > BF16_TOL
