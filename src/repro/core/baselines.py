"""Baselines the paper compares against, reimplemented in JAX.

- ``maxsim_bruteforce``: exact ColBERT/XTR MaxSim over the uncompressed
  corpus — the quality oracle ("gold") for recall measurements.
- ``xtr_reference``: the XTR/ScaNN semantics — token retrieval of the
  top-k' corpus tokens per query token (exact here, where ScaNN is
  approximate), scoring only retrieved pairs, imputing missing entries
  with the *lowest retrieved score* per query token (the paper's Eq. 1
  with XTR's original m_i).
- ``plaid_style_search``: WARP's candidate generation but with *explicit*
  decompression (Eq. 3) and dense dot-product scoring — the PLAID-shaped
  path. Must produce bit-identical rankings to the implicit engine
  (Eq. 4-5 identity); serves as both baseline and correctness witness.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quantization
from repro.core.engine import gather_candidates, resolve_config
from repro.core.reduction import TopKResult, two_stage_reduce
from repro.core.types import WarpIndex, WarpSearchConfig
from repro.core.warpselect import warp_select

__all__ = ["maxsim_bruteforce", "xtr_reference", "plaid_style_search"]


@functools.partial(jax.jit, static_argnames=("n_docs", "k"))
def maxsim_bruteforce(
    q: jax.Array,
    qmask: jax.Array,
    emb: jax.Array,
    token_doc_ids: jax.Array,
    *,
    n_docs: int,
    k: int,
) -> TopKResult:
    """Exact sum-of-MaxSim. q f32[Q, D], emb f32[N, D] (both normalized)."""
    sim = emb @ q.T  # [N, Q]
    per_doc = jax.ops.segment_max(sim, token_doc_ids, num_segments=n_docs)
    per_doc = jnp.where(jnp.isfinite(per_doc), per_doc, 0.0)
    scores = jnp.sum(per_doc * qmask[None, :], axis=-1)  # [n_docs]
    top_scores, top_docs = jax.lax.top_k(scores, k)
    return TopKResult(scores=top_scores, doc_ids=top_docs.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("k_prime", "k"))
def xtr_reference(
    q: jax.Array,
    qmask: jax.Array,
    emb: jax.Array,
    token_doc_ids: jax.Array,
    *,
    k_prime: int,
    k: int,
) -> TopKResult:
    """XTR's retrieve-then-impute scoring with exact token retrieval."""
    qm = q.shape[0]
    sim = q @ emb.T  # [Q, N]
    vals, idx = jax.lax.top_k(sim, k_prime)  # [Q, k']
    doc_ids = token_doc_ids[idx]
    # XTR: m_i = lowest score retrieved for query token i.
    mse = jnp.where(qmask, vals[:, -1], 0.0)
    valid = jnp.broadcast_to(qmask[:, None], (qm, k_prime))
    return two_stage_reduce(doc_ids, None, vals, valid, mse, q_max=qm, k=k)


@functools.partial(jax.jit, static_argnames=("config",))
def _plaid_impl(index: WarpIndex, q, qmask, config: WarpSearchConfig) -> TopKResult:
    qm = q.shape[0]
    sel = warp_select(
        q,
        index.centroids,
        index.cluster_sizes,
        nprobe=config.nprobe,
        t_prime=config.t_prime,
        k_impute=config.k_impute,
        qmask=qmask,
    )
    packed, doc_ids, valid = gather_candidates(index, sel.probe_cids)
    p, cap = config.nprobe, index.cap

    # Explicit decompression (Eq. 3): materialize candidate vectors.
    centroid_vecs = index.centroids[sel.probe_cids]  # [Q, P, D]
    vecs = quantization.decompress(
        packed.reshape(qm, p * cap, -1),
        jnp.repeat(centroid_vecs, cap, axis=1).reshape(qm, p * cap, -1),
        index.bucket_weights,
        nbits=index.nbits,
        dim=index.dim,
    )  # [Q, P*cap, D]
    cand_scores = jnp.einsum("qnd,qd->qn", vecs, q).reshape(qm, p, cap)

    valid = valid & qmask[:, None, None]
    return two_stage_reduce(
        doc_ids.reshape(qm, -1),
        None,
        cand_scores.reshape(qm, -1),
        valid.reshape(qm, -1),
        sel.mse,
        q_max=qm,
        k=config.k,
    )


def plaid_style_search(
    index: WarpIndex,
    q: jax.Array,
    qmask: jax.Array | None = None,
    config: WarpSearchConfig = WarpSearchConfig(),
) -> TopKResult:
    config = resolve_config(index, config)
    if qmask is None:
        qmask = jnp.ones((q.shape[0],), bool)
    return _plaid_impl(index, jnp.asarray(q, jnp.float32), qmask, config)
