"""The paper's kernels at work inside the distributed-training substrate:

1. PowerSGD gradient compression -- both projections are tall-and-skinny
   GEMMs (TSM2R + TSMT); shows the wire-byte reduction for a DP all-reduce
   and the error-feedback recovery property.
2. ABFT checksums -- encode/verify a parameter tree, inject a bit flip,
   watch it get caught (the paper's own motivating application).

    PYTHONPATH=src python examples/powersgd_abft.py
"""

import jax
import jax.numpy as jnp

from repro.core import tsmm
from repro.ft import abft
from repro.launch.cache import configure_compilation_cache
from repro.optim import powersgd

configure_compilation_cache()

key = jax.random.PRNGKey(0)

# One policy scope instead of threading interpret= through every call:
# interpret mode pins the Pallas kernels to their Python bodies (CPU demo).
POLICY = tsmm.GemmPolicy(interpret=True)

# --- PowerSGD ---------------------------------------------------------------
def spectral_grad(k, d1, d2, decay=0.5):
    """Gradients in practice have fast-decaying spectra -- synthesize one."""
    u = jax.random.normal(k, (d1, 32))
    v = jax.random.normal(jax.random.fold_in(k, 1), (32, d2))
    scales = decay ** jnp.arange(32)
    return (u * scales) @ v * 0.01


grads = {
    "mlp/w_up": spectral_grad(key, 2048, 8192),
    "mlp/w_down": spectral_grad(jax.random.fold_in(key, 1), 8192, 2048),
    "norm/scale": jnp.ones((2048,)),
}
cfg = powersgd.PowerSGDConfig(rank=4, min_size=0)
state = powersgd.init(cfg, grads, jax.random.PRNGKey(2))


def fake_psum(x):   # MEAN over a 2-replica DP group with identical grads
    return (x + x) / 2.0


with tsmm.policy(POLICY):
    out, state, metrics = powersgd.compress_tree(cfg, grads, state,
                                                 psum=fake_psum)
dense_bytes = sum(g.size * 4 for g in jax.tree.leaves(grads))
print(f"PowerSGD rank-4: compression ratio {metrics['powersgd_compression']:.1f}x "
      f"({dense_bytes/1e6:.1f} MB dense all-reduce -> "
      f"{dense_bytes/metrics['powersgd_compression']/1e6:.2f} MB)")
rel = float(jnp.linalg.norm(out["mlp/w_up"] - grads["mlp/w_up"])
            / jnp.linalg.norm(grads["mlp/w_up"]))
print(f"  round-1 relative error {rel:.3f} on a decaying-spectrum gradient "
      "(error feedback replays any residual next step)")

# --- ABFT --------------------------------------------------------------------
params = {"w": jax.random.normal(jax.random.fold_in(key, 3), (4096, 1024))}
cs = abft.encode_tree(params, policy=POLICY)
ok, _ = abft.verify_tree(params, cs, policy=POLICY)
print(f"ABFT clean verify: {bool(ok)}")
corrupt = {"w": params["w"].at[1234, 56].add(1.0)}   # one flipped value
ok2, devs = abft.verify_tree(corrupt, cs, policy=POLICY)
print(f"ABFT after single-element corruption: detected={not bool(ok2)}")
assert bool(ok) and not bool(ok2)
print("OK")
