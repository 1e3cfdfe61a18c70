"""Sharded-output (psum_scatter) executor under a real >1-device mesh.

Like tests/test_shard_map.py this runs in a subprocess with
``--xla_force_host_platform_device_count=2`` (JAX pins the device count at
first backend use). The script asserts, via the dispatch spy and
addressable-shard shapes (no ``jax.debug.visualize`` parsing):

* ``tsmm_t`` under ``reduce="psum_scatter"`` routes through the
  ``shard_map-scatter`` executor down to a per-shard kernel, returns the
  same global values as the dense oracle, and the output lives row-sharded
  across the mesh (each device holds an (a/2, b) slab);
* a scatter axis that doesn't divide the shard count falls back to dense
  (and ``shard_map="require"`` raises instead);
* gradients route with the matching collective: the weight-gradient
  ``tsmm_t`` inside ``layers.dense``'s custom VJP lands on the scatter
  executor and the parameter grad arrives sharded -- no all-gather;
* the sharded PowerSGD protocol (``compress_one_sharded``) matches the
  replicated-psum oracle numerically, with the Q factor state sharded;
* PowerSGD ``compress="int8"`` keeps the sharded schedule consistent
  with the replicated oracle within the quantization envelope, shards
  stay bit-consistent row slabs of the assembled factor;
* ``dp_axes`` derivation: an unconventionally named single-axis mesh
  ("replica") still routes through shard_map.

This file is in the ruff-format ratchet set (see ci.yml) -- keep edits
formatter-clean.
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
from repro.kernels import compat
from repro.models import layers
from repro.optim import powersgd

devs = jax.devices()
assert len(devs) == 2, f"expected 2 host devices, got {len(devs)}"
mesh = Mesh(np.array(devs), ("data",))

x = jax.random.normal(jax.random.PRNGKey(2), (8192, 64), jnp.float32)
y = jax.random.normal(jax.random.PRNGKey(3), (8192, 8), jnp.float32)

# --- scatter executor: sharded output, oracle values ---------------------
with jax.set_mesh(mesh):
    with tsmm.policy(reduce="psum_scatter"):
        with tsmm.record_dispatches() as log:
            q = jax.jit(lambda x_, y_: tsmm.tsmm_t(x_, y_))(x, y)
execs = [(e.entry, e.kind, e.executor, e.shape) for e in log]
assert ("mmt", "tsmt", "shard_map-scatter", (8192, 64, 8)) in execs, execs
# per-shard re-dispatch runs the kernel on the LOCAL tall-skinny shape
assert ("mmt", "tsmt", "pallas-tpu", (4096, 64, 8)) in execs, execs
assert q.shape == (64, 8), q.shape
shards = {s.device: s.data.shape for s in q.addressable_shards}
assert len(shards) == 2, shards
assert set(shards.values()) == {(32, 8)}, shards
np.testing.assert_allclose(np.asarray(q), np.asarray(x.T @ y),
                           rtol=2e-3, atol=2e-3)

# --- scatter axis doesn't divide: dense fallback / require raises --------
x63 = x[:, :63]
with jax.set_mesh(mesh):
    with tsmm.policy(reduce="psum_scatter"):
        with tsmm.record_dispatches() as log:
            jax.jit(lambda x_, y_: tsmm.tsmm_t(x_, y_))(x63, y)
        assert [e.executor for e in log] == ["dense-xla"], log
        try:
            with tsmm.policy(shard_map="require"):
                tsmm.tsmm_t(x63, y)
        except RuntimeError as e:
            assert "psum_scatter" in str(e), e
        else:
            raise AssertionError("require + indivisible scatter did not raise")

# --- psum default is untouched: replicated output ------------------------
with jax.set_mesh(mesh):
    with tsmm.record_dispatches() as log:
        q_rep = jax.jit(lambda x_, y_: tsmm.tsmm_t(x_, y_))(x, y)
assert ("mmt", "tsmt", "shard_map") in {
    (e.entry, e.kind, e.executor) for e in log
}, log
assert {s.data.shape for s in q_rep.addressable_shards} == {(64, 8)}, "not replicated"

# --- grads: weight grad lands on the scatter executor, sharded -----------
w = jax.random.normal(jax.random.PRNGKey(4), (256, 8), jnp.float32)
xs = jax.random.normal(jax.random.PRNGKey(5), (8192, 256), jnp.float32)
pol = tsmm.GemmPolicy(reduce="psum_scatter", param_dtype_grads=True)
with jax.set_mesh(mesh):
    with tsmm.policy(pol):
        with tsmm.record_dispatches() as log:
            g = jax.jit(jax.grad(lambda w_, x_: jnp.sum(layers.dense(w_, x_))))
            dw = g(w, xs)
execs = {(e.entry, e.kind, e.executor) for e in log}
assert ("mmt", "tsmt", "shard_map-scatter") in execs, execs
assert {s.data.shape for s in dw.addressable_shards} == {(128, 8)}, "dw not sharded"
ref_dw = jax.grad(lambda w_, x_: jnp.sum(x_ @ w_))(w, xs)
np.testing.assert_allclose(np.asarray(dw), np.asarray(ref_dw),
                           rtol=2e-3, atol=2e-3)

# --- PowerSGD: sharded protocol == replicated-psum oracle ----------------
from jax.sharding import PartitionSpec as P

cfg = powersgd.PowerSGDConfig(rank=4, min_size=0)
d1, d2 = 4096, 512
grads = jax.random.normal(jax.random.PRNGKey(0), (2, d1, d2), jnp.float32)
state0 = powersgd.init(cfg, {"w": jnp.zeros((d1, d2))}, jax.random.PRNGKey(17))
approx_o, st_o = powersgd.compress_one(cfg, grads.mean(0), state0["w"])


def body(g_local):
    st = powersgd.shard_state(state0, "data")["w"]
    assert st["q"].shape == (d2 // 2, cfg.rank), st["q"].shape
    approx, st2 = powersgd.compress_one_sharded(cfg, g_local[0], st, axis="data")
    return approx, st2["q"]


f = compat.shard_map(
    body,
    mesh=mesh,
    in_specs=(P("data", None, None),),
    out_specs=(P(None, None), P("data", None)),
)
with jax.set_mesh(mesh):
    approx_s, q_s = jax.jit(f)(grads)
np.testing.assert_allclose(np.asarray(approx_s), np.asarray(approx_o),
                           rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(q_s), np.asarray(st_o["q"]),
                           rtol=1e-4, atol=1e-4)
assert {s.data.shape for s in q_s.addressable_shards} == {(d2 // 2, cfg.rank)}

# --- PowerSGD orth="tsqr": tree-TSQR orthogonalization == oracle ---------
# Same protocol with the P factor orthogonalized by the distributed
# tree-TSQR (psum_scatter + per-shard CholeskyQR2) instead of pmean +
# replicated Gram-Schmidt/tsqr. The replicated oracle uses tsqr too, so
# sharded and oracle must agree numerically, and the whole compress must
# stay on the kernel executors (the collectives are raw lax/compat
# calls, so every dispatch event is a per-shard kernel execution).
cfg_qr = powersgd.PowerSGDConfig(rank=4, min_size=0, orth="tsqr")
approx_oq, st_oq = powersgd.compress_one(cfg_qr, grads.mean(0), state0["w"])


def body_qr(g_local):
    st = powersgd.shard_state(state0, "data")["w"]
    approx, st2 = powersgd.compress_one_sharded(cfg_qr, g_local[0], st, axis="data")
    return approx, st2["q"]


f_qr = compat.shard_map(
    body_qr,
    mesh=mesh,
    in_specs=(P("data", None, None),),
    out_specs=(P(None, None), P("data", None)),
)
with jax.set_mesh(mesh):
    with tsmm.record_dispatches() as log:
        approx_sq, q_sq = jax.jit(f_qr)(grads)
np.testing.assert_allclose(np.asarray(approx_sq), np.asarray(approx_oq),
                           rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(q_sq), np.asarray(st_oq["q"]),
                           rtol=1e-4, atol=1e-4)
assert {s.data.shape for s in q_sq.addressable_shards} == {(d2 // 2, cfg_qr.rank)}
assert {e.executor for e in log} == {"pallas-tpu"}, log

# --- PowerSGD compress="int8": quantized factor collectives --------------
# Each rank symmetric-quantizes its local P/Q projection immediately
# before the DP collective (the int8 wire format). The sharded schedule
# must stay consistent with the replicated oracle within the
# quantization envelope (per-rank noise <= half a step of the local
# absmax -- NOT bit-exact like the f32 arms above), and the Q factor
# state must stay row-sharded.
cfg_i8 = powersgd.PowerSGDConfig(rank=4, min_size=0, compress="int8")
approx_oi, st_oi = powersgd.compress_one(cfg_i8, grads.mean(0), state0["w"])


def body_i8(g_local):
    st = powersgd.shard_state(state0, "data")["w"]
    approx, st2 = powersgd.compress_one_sharded(cfg_i8, g_local[0], st, axis="data")
    return approx, st2["q"]


f_i8 = compat.shard_map(
    body_i8,
    mesh=mesh,
    in_specs=(P("data", None, None),),
    out_specs=(P(None, None), P("data", None)),
)
with jax.set_mesh(mesh):
    approx_si, q_si = jax.jit(f_i8)(grads)
tol_a = 2e-2 * np.abs(np.asarray(approx_oi)).max()
assert np.abs(np.asarray(approx_si) - np.asarray(approx_oi)).max() <= tol_a
tol_q = 2e-2 * np.abs(np.asarray(st_oi["q"])).max()
assert np.abs(np.asarray(q_si) - np.asarray(st_oi["q"])).max() <= tol_q
assert {s.data.shape for s in q_si.addressable_shards} == {(d2 // 2, cfg_i8.rank)}
# the assembled Q is exactly its row shards stacked in order: the scatter
# left each rank a bit-consistent slab of the quantized-mean factor
slabs = sorted(q_si.addressable_shards, key=lambda s: s.index[0].start or 0)
np.testing.assert_array_equal(
    np.asarray(q_si), np.concatenate([np.asarray(s.data) for s in slabs])
)

# --- split reduction per shard: collective contracts unchanged -----------
# GemmPolicy.split composes with reduce=: partials are summed inside each
# shard's kernel epilogue, so the psum arm stays replicated and the
# psum_scatter arm stays row-sharded, both oracle-equal; the split knob is
# visible on every dispatch event down to the per-shard re-dispatch.
for reduce_, expect_exec, expect_shard in (
    ("psum", "shard_map", (64, 8)),
    ("psum_scatter", "shard_map-scatter", (32, 8)),
):
    with jax.set_mesh(mesh):
        with tsmm.policy(reduce=reduce_, split=2):
            with tsmm.record_dispatches() as log:
                q_split = jax.jit(lambda x_, y_: tsmm.tsmm_t(x_, y_))(x, y)
    execs = {(e.executor, e.split) for e in log}
    assert (expect_exec, 2) in execs, (reduce_, execs)
    assert ("pallas-tpu", 2) in execs, (reduce_, execs)
    assert {s.data.shape for s in q_split.addressable_shards} == {
        expect_shard
    }, (reduce_, q_split.addressable_shards)
    np.testing.assert_allclose(
        np.asarray(q_split), np.asarray(x.T @ y), rtol=2e-3, atol=2e-3
    )

# --- dp_axes derived from an unconventionally named mesh -----------------
mesh_r = Mesh(np.array(devs), ("replica",))
assert tsmm.derive_dp_axes(mesh_r) == ("replica",)
with jax.set_mesh(mesh_r):
    with tsmm.policy(reduce="psum_scatter"):
        with tsmm.record_dispatches() as log:
            jax.jit(lambda x_, y_: tsmm.tsmm_t(x_, y_))(x, y)
assert "shard_map-scatter" in {e.executor for e in log}, log
# explicit override still wins: dp_axes naming no axis on the mesh -> no DP
with jax.set_mesh(mesh_r):
    with tsmm.policy(reduce="psum_scatter", dp_axes=("data",)):
        with tsmm.record_dispatches() as log:
            jax.jit(lambda x_, y_: tsmm.tsmm_t(x_, y_))(x, y)
assert {e.executor for e in log} == {"dense-xla"}, log
print("SCATTER_SHARD_MAP_OK")
"""


def _two_device_env():
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count=2 {flags}".strip()
    env["PYTHONPATH"] = str(_ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("REPRO_TSMM", None)
    return env


def test_scatter_executor_on_two_device_mesh():
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env=_two_device_env(),
        capture_output=True,
        text=True,
        timeout=600,
        cwd=_ROOT,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "SCATTER_SHARD_MAP_OK" in r.stdout
