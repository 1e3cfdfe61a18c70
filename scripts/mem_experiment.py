import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import dataclasses
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import registry
from repro.configs.base import SHAPES
from repro.distributed import sharding
from repro.launch.mesh import make_production_mesh
from repro.models import model
from repro.optim import adamw
from repro.train import train_step as ts

arch = sys.argv[1] if len(sys.argv) > 1 else "llama3.2-3b"
base = registry.get_config(arch)
shape = SHAPES["train_4k"]
mesh = make_production_mesh()

variants = {
    "A_nomicro_rematlayer": dataclasses.replace(base, microbatch=0, remat_group=1),
    "B_nomicro_rematgrp4": dataclasses.replace(base, microbatch=0, remat_group=4),
    "C_micro4_rematlayer": dataclasses.replace(base, microbatch=4, remat_group=1),
    "D_micro4_rematgrp4": dataclasses.replace(base, microbatch=4, remat_group=4),
    "E_nomicro_noremat": dataclasses.replace(base, microbatch=0, remat=False),
}

key_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
for name, cfg in variants.items():
    try:
        params_shape = jax.eval_shape(lambda k: model.init(k, cfg), key_s)
        p_specs = sharding.make_param_specs(cfg, params_shape, mesh)
        p_named = sharding.named(mesh, p_specs)
        opt_cfg = adamw.AdamWConfig(lr=3e-4)
        state_shape = jax.eval_shape(lambda k: ts.init_train_state(k, cfg, opt_cfg), key_s)
        state_specs = {"params": p_specs, "opt": sharding.make_opt_specs(p_specs)}
        state_named = sharding.named(mesh, state_specs)
        batch_shape = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
                       "targets": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
        b_named = sharding.named(mesh, sharding.batch_specs(cfg, mesh, batch_shape))
        step_fn = ts.make_train_step(cfg, opt_cfg, n_micro=cfg.microbatch,
                                     acc_shardings=p_named)
        with jax.set_mesh(mesh):
            comp = jax.jit(step_fn, in_shardings=(state_named, b_named),
                           out_shardings=(state_named, None),
                           donate_argnums=(0,)).lower(state_shape, batch_shape).compile()
        ma = comp.memory_analysis()
        print(f"{name}: temp={ma.temp_size_in_bytes/2**30:.2f}GiB "
              f"arg={ma.argument_size_in_bytes/2**30:.2f} "
              f"alias={ma.alias_size_in_bytes/2**30:.2f}", flush=True)
    except Exception as e:
        print(f"{name}: FAILED {type(e).__name__}: {e}", flush=True)
