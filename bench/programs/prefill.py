"""The optimized HLO of traffic kind ``prefill``'s timed entry, for
``scopes.py``: the entry ``kinds/prefill.py`` builds, lowered at the mix's
shape with shape-only arguments and compiled (a persistent-cache load
where ``run.py`` has configured the cache)."""

from __future__ import annotations

from bench import serving


def compiled_text(run) -> str:
    import jax
    import jax.numpy as jnp

    gen = type(run.generator)(run.traffic, run.cfg_mod, run.sizes, run.generator.seed)
    gen.build(serving.program_config(run.cfg_mod, run.sizes))
    params = jax.eval_shape(lambda k: run.cfg_mod.make_params(k, run.sizes),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((gen.batch, gen.length), jnp.int32)
    return gen._prefill.lower(params, tokens).compile().as_text()
