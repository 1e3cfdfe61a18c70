"""Distributed tree-TSQR on a real 2-device mesh (subprocess).

Same harness as tests/test_scatter_shard_map.py: a subprocess pinned to
``--xla_force_host_platform_device_count=2`` runs both reduction
schedules of :func:`repro.linalg.tree_tsqr` inside a shard_map and
asserts, against the replicated :func:`repro.linalg.tsqr` oracle:

* butterfly and gather both return the oracle's Q/R directly (the sign
  convention makes the factorization unique -- no column-sign slack);
* the local Q block stays sharded ((m/2, r) per device) while R comes
  back replicated with a non-negative diagonal;
* the acceptance bar holds distributed: ``max|QᵀQ - I| <= 1e-4`` at f32
  through cond 1e6, where Q is the gathered global basis;
* the dispatch spy sees the per-shard CholeskyQR2 stages on the
  tsmt/tsm2l kernel executors (shard_map="local" -- no re-wrap, no
  dense-xla) plus the tiny tsmm apply of the tree transform;
* reduce="butterfly" on a non-power-of-two axis raises, and the
  explicit reduce= spellings agree with reduce="auto".

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
from jax.sharding import Mesh, PartitionSpec as P

from repro import linalg
from repro.core import tsmm
from repro.kernels import compat

devs = jax.devices()
assert len(devs) == 2, f"expected 2 host devices, got {len(devs)}"
mesh = Mesh(np.array(devs), ("data",))

M, R = 8192, 16


def conditioned(cond, key=0):
    rng = np.random.default_rng(key)
    u, _ = np.linalg.qr(rng.standard_normal((M, R)))
    v, _ = np.linalg.qr(rng.standard_normal((R, R)))
    s = np.logspace(0, -np.log10(cond), R)
    return jnp.asarray((u * s) @ v.T, jnp.float32)


def orth_err(q):
    q = np.asarray(q, np.float32)
    return float(np.max(np.abs(q.T @ q - np.eye(q.shape[1]))))


def run_tree(a, reduce_):
    def body(a_loc):
        q_loc, r = linalg.tree_tsqr(a_loc, axis="data", reduce=reduce_)
        return q_loc, r

    f = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("data", None),),
        out_specs=(P("data", None), P(None, None)),
    )
    with jax.set_mesh(mesh):
        return jax.jit(f)(a)


# --- both schedules == replicated oracle at moderate cond ----------------
a = conditioned(1e2)
q_ref, r_ref = linalg.tsqr(a)
for reduce_ in ("butterfly", "gather", "auto"):
    q, r = run_tree(a, reduce_)
    assert q.shape == (M, R) and r.shape == (R, R), (q.shape, r.shape)
    # Q stays row-sharded, R replicated
    assert {s.data.shape for s in q.addressable_shards} == {(M // 2, R)}, (
        reduce_,
        q.addressable_shards,
    )
    assert {s.data.shape for s in r.addressable_shards} == {(R, R)}, reduce_
    np.testing.assert_allclose(
        np.asarray(q), np.asarray(q_ref), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(r), np.asarray(r_ref), rtol=1e-4, atol=1e-4
    )
    assert float(jnp.min(jnp.diag(r))) >= 0.0, reduce_

# --- acceptance bar distributed: orth <= 1e-4 at cond 1e6 ----------------
a6 = conditioned(1e6, key=1)
_, r_ref6 = linalg.tsqr(a6)
for reduce_ in ("butterfly", "gather"):
    q6, r6 = run_tree(a6, reduce_)
    err = orth_err(q6)
    assert err <= 1e-4, (reduce_, err)
    np.testing.assert_allclose(
        np.asarray(r6), np.asarray(r_ref6), rtol=1e-3, atol=1e-4
    )
    rec = float(jnp.linalg.norm(q6 @ r6 - a6) / jnp.linalg.norm(a6))
    assert rec <= 1e-5, (reduce_, rec)

# --- dispatch: per-shard stages stay on the kernels ----------------------
with tsmm.record_dispatches() as log:
    run_tree(a, "butterfly")
assert {e.executor for e in log} == {"pallas-tpu"}, log
kinds = {e.kind for e in log}
assert kinds == {"tsm2l", "tsmt"}, kinds
# every event traced at the LOCAL (m/2) shape: shard_map="local" held
assert {e.shape[0] for e in log} == {M // 2}, log

# --- size-1 axis degenerates to the local factorization ------------------
mesh1 = Mesh(np.array(devs).reshape(2, 1), ("data", "model"))


def body_size1(a_loc):
    # "model" has one shard: the tree is a no-op and the local CholeskyQR2
    # result passes straight through
    return linalg.tree_tsqr(a_loc, axis="model")


with jax.set_mesh(mesh1):
    q1, r1 = jax.jit(
        compat.shard_map(
            body_size1,
            mesh=mesh1,
            in_specs=(P(None, None),),
            out_specs=(P(None, None), P(None, None)),
        )
    )(a)
np.testing.assert_allclose(np.asarray(q1), np.asarray(q_ref), rtol=1e-5, atol=1e-5)
np.testing.assert_allclose(np.asarray(r1), np.asarray(r_ref), rtol=1e-5, atol=1e-5)

# --- reduce= validation ---------------------------------------------------
try:
    linalg.tree_tsqr(a, axis="data", reduce="bogus")
except ValueError as e:
    assert "reduce" in str(e), e
else:
    raise AssertionError("bogus reduce= did not raise")

print("LINALG_TREE_TSQR_OK")
"""


def _two_device_env():
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count=2 {flags}".strip()
    env["PYTHONPATH"] = str(_ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("REPRO_TSMM", None)
    return env


def test_tree_tsqr_on_two_device_mesh():
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env=_two_device_env(),
        capture_output=True,
        text=True,
        timeout=600,
        cwd=_ROOT,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "LINALG_TREE_TSQR_OK" in r.stdout
