"""The optimized HLO of traffic kind ``decode``'s timed entry, for
``scopes.py``: the decode step ``kinds/decode.py`` builds, with its cache
donated, lowered at the mix's shape with shape-only arguments and compiled
(a persistent-cache load where ``run.py`` has configured the cache)."""

from __future__ import annotations

from bench import serving


def compiled_text(run) -> str:
    import jax
    import jax.numpy as jnp
    from repro.models import model

    cfg = serving.program_config(run.cfg_mod, run.sizes)
    gen = type(run.generator)(run.traffic, run.cfg_mod, run.sizes, run.generator.seed)
    gen.build(cfg)
    params = jax.eval_shape(lambda k: run.cfg_mod.make_params(k, run.sizes),
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((gen.batch,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    cache = jax.eval_shape(lambda: model.init_cache(cfg, gen.batch, gen.max_len))
    return gen._decode.lower(params, tok, pos, cache).compile().as_text()
