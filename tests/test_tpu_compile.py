"""Real-width compiles of the TSM2X kernels, and of prefill's fused causal
attention, for a TPU v5e, with no chip.

Every other kernel test runs in Pallas interpret mode, which cannot see
what Mosaic refuses: blocks off the (8, 128) tiling, or more VMEM than the
scoped limit. Here each kernel is lowered through ``kernels/ops`` (so the
block chooser, the padding and the VMEM limit are the ones dispatch uses)
and compiled for one chip of a *described* ``v5e:2x2`` topology. The
topology is described inside a module-scoped fixture, never at import:
only the worker that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import tsmm
from repro.kernels import ops

GiB = 1 << 30
V5E_HBM = 16 * GiB

# (id, op, policy overrides, call kwargs, lhs shape, rhs shape, dtype)
CASES = [
    # the paper's TSM2R shape and the rwkv6-1.6b decay-LoRA shape: both
    # were refused at Mosaic's default 16 MiB scoped-VMEM limit
    ("tsm2r-paper", "tsm2r", {}, {}, (20480, 20480), (20480, 16),
     jnp.bfloat16),
    ("tsm2r-lora", "tsm2r", {}, {}, (8192, 2048), (2048, 64), jnp.bfloat16),
    ("tsm2r-f32", "tsm2r", {}, {}, (20480, 20480), (20480, 8), jnp.float32),
    # split-K: a (4, 20480, 16) f32 stack takes the Pallas reduce epilogue
    ("tsm2r-split", "tsm2r", {}, {"splits": 4}, (20480, 20480), (20480, 16),
     jnp.bfloat16),
    ("tsm2l", "tsm2l", {}, {}, (1 << 22, 16), (16, 16), jnp.bfloat16),
    ("tsmt-powersgd", "tsmt", {}, {}, (1 << 20, 16), (1 << 20, 16),
     jnp.bfloat16),
    ("tsmt-split", "tsmt", {}, {"splits": 4}, (1 << 20, 16), (1 << 20, 16),
     jnp.bfloat16),
    ("tsmt-wide-split", "tsmt", {}, {"splits": 8}, (1 << 20, 256),
     (1 << 20, 256), jnp.float32),
    ("tsm2r-int8", "tsm2r", {"quant": "int8"}, {}, (20480, 20480),
     (20480, 16), jnp.bfloat16),
    ("tsm2r-int8-split", "tsm2r", {"quant": "int8"}, {"splits": 4},
     (20480, 20480), (20480, 16), jnp.bfloat16),
    ("tsm2l-int8", "tsm2l", {"quant": "int8"}, {}, (1 << 22, 16), (16, 16),
     jnp.bfloat16),
    ("tsmt-int8", "tsmt", {"quant": "int8"}, {}, (1 << 20, 16),
     (1 << 20, 16), jnp.bfloat16),
    ("tsmt-int8-split", "tsmt", {"quant": "int8"}, {"splits": 4},
     (1 << 20, 16), (1 << 20, 16), jnp.bfloat16),
]


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off: a cache entry compiled for a described chip cannot be read back."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, case):
    name, op, overrides, kwargs, sa, sb, dtype = case
    policy = tsmm.GemmPolicy(interpret=False, **overrides)
    entry = getattr(ops, op)
    a = jax.ShapeDtypeStruct(sa, dtype, sharding=one_chip)
    b = jax.ShapeDtypeStruct(sb, dtype, sharding=one_chip)
    with tsmm.record_dispatches():      # collects ops' launch notes
        lowered = jax.jit(
            lambda x, y: entry(x, y, policy=policy, **kwargs)).lower(a, b)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel"
    mem = compiled.memory_analysis()
    assert mem is not None
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 0
    assert total < V5E_HBM, f"{name}: {total / GiB:.2f} GiB"


def test_split_tsm2r_reaches_pallas_reduce(one_chip):
    """The split TSM2R case above is the one whose partials stack is big
    enough for the Pallas reduce epilogue: two Mosaic kernels."""
    policy = tsmm.GemmPolicy(interpret=False)
    a = jax.ShapeDtypeStruct((20480, 20480), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((20480, 16), jnp.bfloat16, sharding=one_chip)
    with tsmm.record_dispatches() as events:
        compiled = jax.jit(lambda x, y: tsmm.tsmm(
            x, y, policy=policy.with_(split=4))).lower(a, b).compile()
    kinds = [lm.kind for e in events for lm in e.launches]
    assert kinds == ["tsm2r", "reduce"]
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("b,h,dk,dv", [(4, 128, 192, 128), (4, 32, 128, 128)],
                         ids=["mla", "zamba2"])
def test_causal_attention_kernel_compiles_for_v5e(one_chip, monkeypatch,
                                                  b, h, dk, dv):
    """Prefill's fused causal attention at the benchmark's widths (1024
    tokens): one Mosaic kernel, its op_name under ``attn/attn_core`` on the
    instruction's own line (where the benchmark's scope reader looks); its
    gradient compiles to the forward with its log-sum-exp and the two
    backward kernels, and no chunk scan."""
    import re

    from repro.kernels import compat
    from repro.models import attention
    monkeypatch.setattr(compat, "auto_interpret", lambda requested=None: False)
    s = 1024
    q, k, v = (jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
               for d in (dk, dk, dv))

    def fwd(q, k, v):
        with jax.named_scope("attn"):
            return attention.chunked_attention(q, k, v)
    text = jax.jit(fwd).lower(q, k, v).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "causal_attention" in calls[0]
    assert "/attn/attn_core/" in re.search(r'op_name="([^"]*)"', calls[0]).group(1)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    names = sorted(re.match(r"\s*%([a-z_]+)", c).group(1) for c in calls)
    assert names == ["causal_attention", "causal_attention_dkv",
                     "causal_attention_dq"]
    assert " while(" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < V5E_HBM
