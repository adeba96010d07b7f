"""Spherical k-means over token embeddings (index construction, paper §4.1).

All inputs are assumed L2-normalized, so cosine similarity == dot product and
the argmax assignment is a single MXU matmul. Cluster updates are
``segment_sum`` scatters — the same gather/scatter substrate the rest of the
system (GNN aggregation, EmbeddingBag) is built on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["spherical_kmeans", "assign_clusters", "l2_normalize"]

# Largest [block, n_centroids] f32 score matrix one assignment step holds
# (2^26 entries = 256 MiB): at LoTTE scale (2^17 centroids) a fixed block
# of 65536 points would need a 32 GiB score matrix.
SCORE_BLOCK_ELEMS = 1 << 26


def l2_normalize(x: jax.Array, eps: float = 1e-12) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


@functools.partial(jax.jit, static_argnames=("block",))
def assign_clusters(
    points: jax.Array, centroids: jax.Array, *, block: int | None = None
) -> jax.Array:
    """argmax_c <x, c> for every point, blocked to bound peak memory.

    The default block keeps each step's score matrix within
    ``SCORE_BLOCK_ELEMS`` (at most 65536 points)."""
    n = points.shape[0]
    if block is None:
        block = max(1, min(65536, SCORE_BLOCK_ELEMS // centroids.shape[0]))
    pad = (-n) % block
    pts = jnp.pad(points, ((0, pad), (0, 0)))

    def body(blk):
        return jnp.argmax(blk @ centroids.T, axis=-1).astype(jnp.int32)

    out = jax.lax.map(body, pts.reshape(-1, block, points.shape[1]))
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("k",))
def _lloyd_step(points: jax.Array, centroids: jax.Array, key: jax.Array, *, k: int):
    """One spherical Lloyd iteration; empty clusters re-seeded from random points."""
    assign = assign_clusters(points, centroids)
    sums = jax.ops.segment_sum(points, assign, num_segments=k)
    counts = jax.ops.segment_sum(
        jnp.ones((points.shape[0],), jnp.float32), assign, num_segments=k
    )
    new = sums / jnp.maximum(counts, 1.0)[:, None]
    # Re-seed empty clusters from random points to keep k effective clusters.
    reseed_idx = jax.random.randint(key, (k,), 0, points.shape[0])
    reseed = points[reseed_idx]
    new = jnp.where((counts > 0.0)[:, None], new, reseed)
    return l2_normalize(new)


def spherical_kmeans(
    key: jax.Array,
    points: jax.Array,
    k: int,
    *,
    iters: int = 8,
) -> jax.Array:
    """Lloyd iterations with cosine assignment; returns f32[k, D] centroids.

    The caller is responsible for sampling `points` (paper: a sqrt(N)-sized
    passage sample); this routine is O(iters * n * k * D).
    """
    n = points.shape[0]
    if k > n:
        raise ValueError(f"k={k} > n_points={n}")
    points = l2_normalize(points.astype(jnp.float32))
    init_key, *step_keys = jax.random.split(key, iters + 1)
    perm = jax.random.permutation(init_key, n)[:k]
    centroids = points[perm]
    for i in range(iters):
        centroids = _lloyd_step(points, centroids, step_keys[i], k=k)
    return centroids
