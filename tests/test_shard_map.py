"""shard_map executor under a real >1-device mesh.

JAX fixes the device count at first backend use, so these run in a
subprocess with ``--xla_force_host_platform_device_count=2``. The script
asserts (via the dispatch spy) that ``tsmm`` under a data-parallel mesh
routes through the shard_map executor down to a per-shard Pallas kernel,
that numerics and gradients match the dense path, and that the
non-divisible / shard_map="never" cases fall back to dense exactly like
the old mesh guard.
"""

import os
import pathlib
import re
import subprocess
import sys


_ROOT = pathlib.Path(__file__).resolve().parents[1]

_SCRIPT = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import tsmm

devs = jax.devices()
assert len(devs) == 2, f"expected 2 host devices, got {len(devs)}"
mesh = Mesh(np.array(devs), ("data",))

a = jax.random.normal(jax.random.PRNGKey(0), (8192, 2048), jnp.float32)
b = jax.random.normal(jax.random.PRNGKey(1), (2048, 8), jnp.float32)
dense = jax.jit(lambda a_, b_: tsmm.tsmm(a_, b_, mode="dense"))(a, b)

# --- auto-routing under the mesh: shard_map -> per-shard pallas kernel ---
with jax.set_mesh(mesh):
    with tsmm.record_dispatches() as log:
        f = jax.jit(lambda a_, b_: tsmm.tsmm(a_, b_))
        out = f(a, b)
execs = [(e.entry, e.kind, e.executor, e.shape) for e in log]
assert ("mm", "tsm2r", "shard_map", (8192, 2048, 8)) in execs, execs
# the per-shard re-dispatch runs the kernel on the LOCAL tall-skinny shape
assert ("mm", "tsm2r", "pallas-tpu", (4096, 2048, 8)) in execs, execs
np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                           rtol=2e-3, atol=2e-3)

# --- grad under the mesh still lands in tall-skinny classes -------------
# TSM2L shape: per-shard Abar is TSM2L again, Bbar the TSMTTSM shape.
al = jax.random.normal(jax.random.PRNGKey(4), (8192, 16), jnp.float32)
bl = jax.random.normal(jax.random.PRNGKey(5), (16, 8), jnp.float32)
with jax.set_mesh(mesh):
    with tsmm.record_dispatches() as log:
        g = jax.jit(jax.grad(lambda a_, b_: jnp.sum(tsmm.tsmm(a_, b_)),
                             (0, 1)))
        da, db = g(al, bl)
kinds = {(e.entry, e.kind) for e in log}
assert ("mm", "tsm2l") in kinds, kinds      # fwd + Abar: tiny contraction
assert ("mmt", "tsmt") in kinds, kinds      # Bbar: TSMTTSM shape
rda, rdb = jax.grad(lambda a_, b_: jnp.sum(a_ @ b_), (0, 1))(al, bl)
np.testing.assert_allclose(np.asarray(da), np.asarray(rda), rtol=2e-3,
                           atol=2e-3)
np.testing.assert_allclose(np.asarray(db), np.asarray(rdb), rtol=2e-3,
                           atol=2e-3)

# --- tsmm_t: per-shard partials psum to the replicated product ----------
x = jax.random.normal(jax.random.PRNGKey(2), (8192, 32), jnp.float32)
y = jax.random.normal(jax.random.PRNGKey(3), (8192, 8), jnp.float32)
with jax.set_mesh(mesh):
    with tsmm.record_dispatches() as log:
        q = jax.jit(lambda x_, y_: tsmm.tsmm_t(x_, y_))(x, y)
execs = [(e.entry, e.kind, e.executor) for e in log]
assert ("mmt", "tsmt", "shard_map") in execs, execs
np.testing.assert_allclose(np.asarray(q), np.asarray(x.T @ y),
                           rtol=2e-3, atol=2e-3)

# --- fallbacks: non-divisible tall dim / shard_map="never" --------------
a_odd = a[:8191]
with jax.set_mesh(mesh):
    with tsmm.record_dispatches() as log:
        jax.jit(lambda a_, b_: tsmm.tsmm(a_, b_))(a_odd, b)
    assert [e.executor for e in log] == ["dense-xla"], log
    with tsmm.policy(shard_map="never"):
        with tsmm.record_dispatches() as log:
            jax.jit(lambda a_, b_: tsmm.tsmm(a_, b_))(a, b)
        assert [e.executor for e in log] == ["dense-xla"], log
    # shard_map="require" raises on the unshardable shape
    try:
        with tsmm.policy(shard_map="require"):
            tsmm.tsmm(a_odd, b)
    except RuntimeError as e:
        assert "require" in str(e)
    else:
        raise AssertionError("shard_map='require' did not raise")

# --- outside the mesh scope nothing changes -----------------------------
with tsmm.record_dispatches() as log:
    tsmm.tsmm(a, b)
assert [e.executor for e in log] == ["pallas-tpu"], log
print("SHARD_MAP_OK")
"""


def _two_device_env():
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count=2 "
                        f"{flags}").strip()
    env["PYTHONPATH"] = (str(_ROOT / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env.pop("REPRO_TSMM", None)
    return env


def test_shard_map_executor_on_two_device_mesh():
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=_two_device_env(),
                       capture_output=True, text=True, timeout=600,
                       cwd=_ROOT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "SHARD_MAP_OK" in r.stdout
