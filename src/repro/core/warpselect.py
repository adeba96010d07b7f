"""WARP_SELECT: fused candidate generation + missing similarity imputation
(paper §4.3).

Centroid relevance ``S_cq = q @ Cᵀ`` is computed once (MXU matmul). The
top-``nprobe`` centroids per query token become the probe set; the missing
similarity estimate ``m_i`` is the centroid score at the first position —
in score-descending order — where the cumulative cluster size exceeds the
threshold ``t'``. Both reuse the same top-k pass, so imputation is free.

If the cumulative size never crosses ``t'`` within ``k_impute`` sorted
centroids, we fall back to the last (smallest) retained score — a
conservative (lower) estimate; widen ``k_impute`` to tighten it.

This module is the first of the three shared pipeline stages
(``warp_select`` -> ``engine.score_probed_clusters`` ->
``reduction.two_stage_reduce``) used identically by the single-device,
batched, and document-sharded paths. The sharded path re-runs
``impute_mse`` on the all-gathered per-shard (score, size) candidates so
every shard uses one globally aligned m_i; ``WarpSelectOut`` therefore
also carries the full top-``k_impute`` scores/sizes for that merge.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["WarpSelectOut", "warp_select", "impute_mse", "SCOPE_SELECT"]

# Named scope of stage 1: every op ``warp_select`` emits carries it in its
# HLO ``op_name`` (``core/engine.py`` names the other two stages).
SCOPE_SELECT = "warp.select"


class WarpSelectOut(NamedTuple):
    probe_scores: jax.Array  # f32[Q, nprobe]  S_cq of probed centroids
    probe_cids: jax.Array  # i32[Q, nprobe]  probed centroid ids
    probe_sizes: jax.Array  # i32[Q, nprobe]  true sizes of probed clusters
    mse: jax.Array  # f32[Q]          missing similarity estimate m_i
    top_scores: jax.Array  # f32[Q, kk]      full top-k scores (kk >= nprobe)
    top_sizes: jax.Array  # i32[Q, kk]      cluster sizes of those centroids


def impute_mse(
    scores: jax.Array,
    sizes: jax.Array,
    t_prime: jax.Array | int,
    qmask: jax.Array | None = None,
) -> jax.Array:
    """Missing-similarity estimate from (centroid score, cluster size) pairs.

    scores f32[Q, M], sizes i32[Q, M] (any order along M) -> mse f32[Q]:
    the score at the first position — in score-descending order — where the
    cumulative cluster size crosses ``t_prime``; the smallest retained score
    if it never crosses. Shared by the local path (M = k_impute) and the
    sharded path (M = n_shards * k_impute, after the all_gather merge).
    """
    order = jnp.argsort(-scores, axis=-1)
    s_sorted = jnp.take_along_axis(scores, order, axis=-1)
    z_sorted = jnp.take_along_axis(sizes, order, axis=-1)
    csum = jnp.cumsum(z_sorted, axis=-1)
    crossed = csum > jnp.asarray(t_prime, csum.dtype)
    # First crossing; argmax of all-False is 0, so guard with any().
    first = jnp.argmax(crossed, axis=-1)
    first = jnp.where(jnp.any(crossed, axis=-1), first, scores.shape[-1] - 1)
    mse = jnp.take_along_axis(s_sorted, first[:, None], axis=-1)[:, 0]
    if qmask is not None:
        mse = jnp.where(qmask, mse, 0.0)
    return mse


@functools.partial(jax.jit, static_argnames=("nprobe", "k_impute"))
def warp_select(
    q: jax.Array,
    centroids: jax.Array,
    cluster_sizes: jax.Array,
    *,
    nprobe: int,
    t_prime: jax.Array | int,
    k_impute: int,
    qmask: jax.Array | None = None,
) -> WarpSelectOut:
    """q f32[Q, D], centroids f32[C, D], cluster_sizes i32[C].

    qmask (optional bool[Q]): masked query tokens get m_i = 0 and their
    probe entries are still emitted (the engine drops their candidates).
    """
    with jax.named_scope(SCOPE_SELECT):
        kk = max(nprobe, k_impute)
        s_cq = q @ centroids.T  # [Q, C]
        # The barrier keeps XLA from re-deriving the [:nprobe] slices below as
        # a second top-k over all C centroids, which the TPU compiler lowers
        # far more slowly (~15 s per program at 2^17 centroids); the values
        # are the same either way.
        top_scores, top_cids = jax.lax.top_k(s_cq, kk)  # [Q, kk] desc
        top_scores = jax.lax.optimization_barrier(top_scores)
        top_cids = jax.lax.optimization_barrier(top_cids)
        top_sizes = cluster_sizes[top_cids]  # [Q, kk]
        mse = impute_mse(top_scores, top_sizes, t_prime, qmask)
        return WarpSelectOut(
            probe_scores=top_scores[:, :nprobe],
            probe_cids=top_cids[:, :nprobe].astype(jnp.int32),
            # Probe metadata for downstream worklist construction: the ragged
            # layout builds tile counts from the true cluster sizes, already in
            # hand here — re-emitting them saves a second gather in the engine.
            probe_sizes=top_sizes[:, :nprobe].astype(jnp.int32),
            mse=mse,
            top_scores=top_scores,
            top_sizes=top_sizes.astype(jnp.int32),
        )
