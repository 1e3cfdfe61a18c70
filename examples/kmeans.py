"""K-means on GPU/TPU via tall-and-skinny GEMM -- the paper's motivating
application (Section 1: "recent highly optimized K-means implementations
use GEMM as their core computation ... mostly tall-and-skinny").

Distance expansion: ||x - c||^2 = ||x||^2 - 2 x.c + ||c||^2; the x.c term
is X[n_points, d] @ C^T[d, k_clusters] with k_clusters << n_points -- a
TSM2R shape served by repro.core.tsmm.

    PYTHONPATH=src python examples/kmeans.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tsmm
from repro.launch.cache import configure_compilation_cache

N, D, K, ITERS = 200_000, 64, 8, 10


def make_blobs(key):
    centers = jax.random.normal(key, (K, D)) * 5.0
    ks = jax.random.split(jax.random.fold_in(key, 1), K)
    pts = [centers[i] + jax.random.normal(ks[i], (N // K, D)) for i in range(K)]
    return jnp.concatenate(pts), centers


def kmeans_step(x, centroids):
    # TSM2R: (N, D) @ (D, K), K=8 skinny
    dots = tsmm.tsmm(x, centroids.T)
    d2 = (jnp.sum(x * x, 1, keepdims=True) - 2 * dots
          + jnp.sum(centroids * centroids, 1)[None, :])
    assign = jnp.argmin(d2, axis=1)
    # centroid update is a segment mean: one-hot^T @ x is ALSO tall-skinny
    # (N huge, K skinny) -- the TSMT orientation.
    onehot = jax.nn.one_hot(assign, K, dtype=x.dtype)
    sums = tsmm.tsmm_t(x, onehot).T          # (K, D)
    counts = onehot.sum(0)[:, None]
    new_c = sums / jnp.maximum(counts, 1)
    inertia = jnp.take_along_axis(d2, assign[:, None], 1).sum()
    return new_c, assign, inertia


@jax.jit
def _pp_farthest(x, centers, n_filled):
    """One k-means++ pass at the FIXED (N, D) @ (D, K) shape: unfilled
    center rows are masked out of the min instead of sliced off, so every
    pass reuses one compiled kernel and one tuning bucket."""
    dots = tsmm.tsmm(x, centers.T)                     # (N, K) skinny
    d2 = (jnp.sum(x * x, 1, keepdims=True) - 2 * dots
          + jnp.sum(centers * centers, 1)[None, :])
    d2 = jnp.where(jnp.arange(K)[None, :] < n_filled, d2, jnp.inf)
    return jnp.argmax(d2.min(axis=1))


def kmeanspp_init(key, x):
    """k-means++ seeding -- each min-distance pass is itself a TSM2R.

    The centers operand is padded to the full (K, D) width up front and
    the filled count rides in as a traced scalar: the naive "stack what
    we have so far" formulation retraces the tsmm K-1 times with a
    growing skinny dim (a jit cache entry AND an autotune bucket per i).
    """
    idx = jax.random.randint(key, (), 0, x.shape[0])
    centers = jnp.zeros((K, D), x.dtype).at[0].set(x[idx])
    for i in range(1, K):
        nxt = _pp_farthest(x, centers, i)   # farthest-point: deterministic
        centers = centers.at[i].set(x[nxt])
    return centers


def main():
    configure_compilation_cache()
    key = jax.random.PRNGKey(0)
    x, true_centers = make_blobs(key)
    step = jax.jit(kmeans_step)
    t0 = time.time()
    # naive random init almost never covers all blobs (8!/8^8 ~ 0.2%);
    # k-means++ does -- and its distance pass is another TSM2R.
    centroids = kmeanspp_init(jax.random.fold_in(key, 2), x)
    for i in range(ITERS):
        centroids, assign, inertia = step(x, centroids)
        if i % 3 == 0 or i == ITERS - 1:
            print(f"iter {i}: inertia {float(inertia):.4e}")
    print(f"{ITERS} iters in {time.time() - t0:.2f}s on {jax.devices()[0]}")
    # verify recovered centers match the generating ones (up to permutation)
    d = np.linalg.norm(np.asarray(true_centers)[:, None]
                       - np.asarray(centroids)[None], axis=-1)
    match = d.min(axis=1)
    print(f"center recovery error: max {match.max():.3f} (should be < 0.5)")
    assert match.max() < 0.5
    print("OK")


if __name__ == "__main__":
    main()
