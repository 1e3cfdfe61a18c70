"""Randomized SVD (Halko-Martinsson-Tropp) on the TSM2X kernel paths --
the sketching workload the QR subsystem unlocks.

Every heavy product in the algorithm is tall-and-skinny over the row
dimension of A (n_rows >> n_cols >> rank):

    Y  = A @ Omega            # (n, d) @ (d, k)    -- TSM2L (tiny contraction)
    Q  = tsqr(Y)              # CholeskyQR2: Gram=TSMT, apply=TSM2L
    Z  = A^T @ Q              # huge-m reduction    -- TSMT
    Y' = A @ Z                # power iteration     -- TSM2L
    B  = Q^T A  (= Z^T)       # small (k, d)
    U_b, s, V^T = svd(B)      # host-shaped
    U  = Q @ U_b              # (n, k) @ (k, k)     -- TSM2L

so the whole factorization runs under one ``tsmm.policy(...)`` scope and
the only dense decompositions left are (k, d)- and (r, r)-shaped.

    PYTHONPATH=src python examples/rsvd.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import linalg
from repro.core import tsmm
from repro.launch.cache import configure_compilation_cache

N, D, RANK, OVERSAMPLE, POWER_ITERS = 200_000, 256, 8, 8, 2


def make_low_rank(key, noise=1e-3):
    """A = U diag(s) V^T + noise, with a known spectrum to recover."""
    k1, k2, k3 = jax.random.split(key, 3)
    u, _ = jnp.linalg.qr(jax.random.normal(k1, (N, RANK)))
    v, _ = jnp.linalg.qr(jax.random.normal(k2, (D, RANK)))
    s = jnp.asarray(np.geomspace(100.0, 1.0, RANK), jnp.float32)
    a = (u * s) @ v.T + noise * jax.random.normal(k3, (N, D))
    return a, s


def rsvd(key, a, rank, *, oversample=OVERSAMPLE, power_iters=POWER_ITERS):
    """Rank-``rank`` randomized SVD of tall ``a``; returns (U, s, Vt)."""
    k = rank + oversample
    omega = jax.random.normal(key, (a.shape[1], k), a.dtype)
    y = tsmm.tsmm(a, omega)                       # TSM2L
    q, _ = linalg.tsqr(y)
    for _ in range(power_iters):                  # subspace iteration
        z = tsmm.tsmm_t(a, q)                     # TSMT: A^T Q, (d, k)
        y = tsmm.tsmm(a, z)                       # TSM2L: A (A^T Q)
        q, _ = linalg.tsqr(y)
    b = tsmm.tsmm_t(a, q).T                       # (k, d) = Q^T A
    u_b, s, vt = jnp.linalg.svd(b, full_matrices=False)
    u = tsmm.tsmm(q, u_b)                         # TSM2L back-projection
    return u[:, :rank], s[:rank], vt[:rank]


def main():
    configure_compilation_cache()
    key = jax.random.PRNGKey(0)
    a, s_true = make_low_rank(key)
    t0 = time.time()
    u, s, vt = jax.jit(lambda k, x: rsvd(k, x, RANK))(
        jax.random.fold_in(key, 1), a)
    jax.block_until_ready(s)
    print(f"rsvd of {a.shape} rank {RANK} in {time.time() - t0:.2f}s "
          f"on {jax.devices()[0]}")
    # Weyl: the noise term moves each singular value by at most ||E||_2
    # ~ noise * (sqrt(N) + sqrt(D)); recovery is good if we sit inside it.
    noise_floor = 1e-3 * (N ** 0.5 + D ** 0.5)
    s_err = float(jnp.max(jnp.abs(s - s_true)))
    print(f"singular values:  {np.asarray(s).round(2)}")
    print(f"max sv error: {s_err:.2e} (noise floor {noise_floor:.2e})")
    orth = float(jnp.max(jnp.abs(u.T @ u - jnp.eye(RANK))))
    rec = float(jnp.linalg.norm((u * s) @ vt - a) / jnp.linalg.norm(a))
    print(f"basis orthogonality: {orth:.2e}; reconstruction residual "
          f"(noise floor): {rec:.2e}")
    assert s_err < noise_floor and orth < 1e-4
    print("OK")


if __name__ == "__main__":
    main()
