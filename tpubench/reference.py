"""The plain reference: WARP retrieval (paper §4, Eq. 1-8) written out
directly in ``jax.numpy``, one query at a time, from the index arrays
the benchmark made. It imports nothing of the program and shares none of
its algorithms past the definition:

1. centroid scores ``S = q Cᵀ``; the probe set of query token ``i`` is
   its ``nprobe`` best centroids;
2. the missing-similarity estimate ``m_i`` is the score of the first of
   the ``k_impute`` best centroids, in score order, at which their
   cumulative cluster size exceeds ``t'`` (the last of them if none does);
3. every token of a probed cluster scores ``S[i, c] + Σ_d q_d w[code_d]``;
4. a document's score is ``Σ_i`` of its best token score for query
   token ``i`` where it has one, ``m_i`` where it has none, over the
   documents with at least one candidate token; the answer is its top-k.

Step 4 is a dense scatter-max over all documents, where the program
sorts its candidates. ``dtype`` is the precision of every product and
sum; ``jnp.float32`` computes the centroid scores at the default matrix
precision, as the configuration states. The control passes
``jnp.bfloat16``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(
    jax.jit,
    static_argnames=("nprobe", "k", "k_impute", "t_prime", "n_docs", "cap", "nbits", "dtype"),
)
def reference_query(
    centroids, codes, token_doc_ids, offsets, sizes, weights, q, qmask, served_ids,
    *, nprobe, k, k_impute, t_prime, n_docs, cap, nbits, dtype,
):
    """One query q f32[Q, D] -> (top-k scores f32[k], doc ids i32[k], the
    reference's score of each of ``served_ids`` i32[k]; -inf for a
    document with no candidate token). Scores come back as float32."""
    n_tokens = codes.shape[0]
    nb = 1 << nbits
    per_byte = 8 // nbits
    q = q.astype(dtype)
    s = jnp.dot(q, centroids.astype(dtype).T, preferred_element_type=dtype)
    top_s, top_c = jax.lax.top_k(s, k_impute)
    crossed = jnp.cumsum(sizes[top_c], axis=1) > t_prime
    first = jnp.where(jnp.any(crossed, axis=1), jnp.argmax(crossed, axis=1), k_impute - 1)
    mse = jnp.where(qmask, jnp.take_along_axis(top_s, first[:, None], axis=1)[:, 0], 0)
    # v[i, d, b] = q_i[d] * w[b], split by the slot of the byte dim d is in.
    v = q[:, :, None] * weights.astype(dtype)[None, None, :]
    v = v.reshape(q.shape[0], -1, per_byte, nb)  # [Q, bytes, slot, bucket]
    byte_ix = jnp.arange(v.shape[1])
    neg = jnp.asarray(-jnp.inf, dtype)

    def token_best(args):
        v_i, ps, pc, m = args
        pos = offsets[pc][:, None] + jnp.arange(cap)[None, :]  # [P, cap]
        valid = (jnp.arange(cap)[None, :] < sizes[pc][:, None]) & m
        pos = jnp.clip(pos, 0, n_tokens - 1)
        b = codes[pos].astype(jnp.int32)  # [P, cap, bytes]
        resid = jnp.zeros(pos.shape, dtype)
        for slot in range(per_byte):
            code = (b >> (slot * nbits)) & (nb - 1)
            resid = resid + jnp.sum(v_i[byte_ix, slot, code], axis=-1, dtype=dtype)
        tok = jnp.where(valid, ps[:, None] + resid, neg)
        return jnp.full((n_docs,), neg, dtype).at[token_doc_ids[pos].reshape(-1)].max(tok.reshape(-1))

    best = jax.lax.map(
        token_best, (v, top_s[:, :nprobe], top_c[:, :nprobe], qmask)
    )  # [Q, n_docs]
    present = best > neg
    total = jnp.sum(jnp.where(present, best, mse[:, None]), axis=0, dtype=dtype)
    total = jnp.where(jnp.any(present, axis=0), total, neg).astype(jnp.float32)
    top_scores, top_docs = jax.lax.top_k(total, k)
    at_served = jnp.where(served_ids >= 0, total[jnp.clip(served_ids, 0, n_docs - 1)], -jnp.inf)
    return top_scores, top_docs, at_served


def run_reference(index, config: dict, queries, replies, *, dtype=jnp.float32):
    """The reference over each (q, qmask) of ``queries`` with the served
    ``(scores, doc_ids)`` of ``replies``: -> list of (top-k scores,
    top-k doc ids, the reference's score of each served doc) as host
    arrays."""
    fn = functools.partial(
        reference_query,
        nprobe=config["nprobe"], k=config["k"], k_impute=config["k_impute"],
        t_prime=config["t_prime"], n_docs=config["n_docs"], cap=int(index.cap),
        nbits=config["nbits"], dtype=dtype,
    )
    out = []
    for (q, qmask), (_, ids) in zip(queries, replies):
        top_s, top_d, at = fn(
            index.centroids, index.packed_codes, index.token_doc_ids,
            index.cluster_offsets, index.cluster_sizes, index.bucket_weights,
            jnp.asarray(q), jnp.asarray(qmask), jnp.asarray(ids, jnp.int32),
        )
        out.append((np.asarray(top_s), np.asarray(top_d), np.asarray(at)))
    return out


@functools.partial(jax.jit, static_argnames=("nprobe",))
def _probe_tokens(centroids, sizes, q, qmask, *, nprobe):
    s = jnp.einsum("bqd,cd->bqc", q, centroids)
    _, top_c = jax.lax.top_k(s, nprobe)
    return jnp.sum(jnp.where(qmask[..., None], sizes[top_c], 0), axis=(1, 2))


def probe_tokens(index, config: dict, qs, ms, chunk: int = 16) -> np.ndarray:
    """Real candidate tokens of each query: over its active tokens, the
    summed sizes of the ``nprobe`` clusters step 1 probes."""
    out = [np.zeros(0, np.int64)]
    for i in range(0, len(qs), chunk):
        out.append(np.asarray(_probe_tokens(
            index.centroids, index.cluster_sizes, jnp.asarray(qs[i:i + chunk]),
            jnp.asarray(ms[i:i + chunk]), nprobe=config["nprobe"],
        ), np.int64))
    return np.concatenate(out)
