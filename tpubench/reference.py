"""The plain reference: WARP retrieval (paper §4, Eq. 1-8) written out
directly in ``jax.numpy``, one query at a time, from the arrays the
benchmark made (``synth.Shard``). It imports nothing of the program and
shares none of its algorithms past the definition:

1. centroid scores ``S = q Cᵀ``; the probe set of query token ``i`` is
   its ``nprobe`` best centroids;
2. the missing-similarity estimate ``m_i`` is the score of the first of
   the ``k_impute`` best centroids, in score order, at which their
   cumulative cluster size exceeds ``t'`` (the last of them if none does);
3. every token of a probed cluster scores ``S[i, c] + Σ_d q_d w[code_d]``;
4. a document's score is ``Σ_i`` of its best token score for query
   token ``i`` where it has one, ``m_i`` where it has none, over the
   documents with at least one candidate token; the answer is its top-k.

A document-sharded corpus runs steps 1, 3 and 4 on each shard, on that
shard's device. Step 2 is global: the ``k_impute`` best (score, size)
pairs of every shard are merged, and ``m_i`` is the score at the first
crossing of ``t'`` in the merged pairs in score order, else the last of
them, so every shard imputes the same ``m_i``. A shard's doc ids are
local; ``doc_start`` makes them global, and the answer is the top-k of
the union of the shards' top-k.

Step 3 reads the code array in the layout it lies in on the chip,
token-minor (``u8[N, PB]{0,1}``): it slices ``codesᵀ`` along its tokens
per probed cluster and scores in ``[bytes, cap]`` orientation, so the
compiled reference holds no copy of the codes (``test_tpubench_aot.py``).
A code's bucket weight is a select over the ``2^nbits`` buckets, not a
gather from the table: exact, and the chip serializes element gathers.
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


def _drop_lead(x, ndim: int):
    """A sharded corpus's arrays carry a leading shard axis of 1."""
    return x.reshape(x.shape[-ndim:])


def _select(centroids, sizes, q, *, k_impute, dtype):
    """Step 1 on one shard: (top-``k_impute`` centroid scores in order,
    their centroid ids, their cluster sizes), each ``[Q, k_impute]``."""
    centroids, sizes = _drop_lead(centroids, 2), _drop_lead(sizes, 1)
    s = jnp.dot(q.astype(dtype), centroids.astype(dtype).T, preferred_element_type=dtype)
    top_s, top_c = jax.lax.top_k(s, k_impute)
    return top_s, top_c, sizes[top_c]


def _impute(scores, sizes, qmask, *, t_prime):
    """Step 2 over (score, size) pairs ``[Q, M]`` in any order: the score
    at the first crossing of ``t_prime`` in score order, else the last
    pair's; 0 for a masked query token."""
    order = jnp.argsort(-scores, axis=1, stable=True)
    s = jnp.take_along_axis(scores, order, axis=1)
    crossed = jnp.cumsum(jnp.take_along_axis(sizes, order, axis=1), axis=1) > t_prime
    first = jnp.where(jnp.any(crossed, axis=1), jnp.argmax(crossed, axis=1), s.shape[1] - 1)
    return jnp.where(qmask, jnp.take_along_axis(s, first[:, None], axis=1)[:, 0], 0)


def _doc_scores(codes, doc_ids, offsets, sizes, weights, q, qmask, probe_s, probe_c, mse,
                *, n_docs, cap, nbits, dtype):
    """Steps 3 and 4 on one shard: f32[n_docs], each local document's
    score, -inf for a document with no candidate token."""
    codes = _drop_lead(codes, 2)
    offsets, sizes, weights = (_drop_lead(x, 1) for x in (offsets, sizes, weights))
    n_tokens, pb = codes.shape
    nb, per_byte = 1 << nbits, 8 // nbits
    codes_t = codes.T  # [PB, N]: the resident layout, read as it lies
    q = q.astype(dtype)
    w = weights.astype(dtype)
    q_slot = q.reshape(q.shape[0], pb, per_byte)  # dim d = byte * per_byte + slot
    neg = jnp.asarray(-jnp.inf, dtype)

    def cluster(off, size):
        # A slice that would run past the end starts earlier; its first
        # lanes then belong to the cluster before and are masked.
        start = jnp.minimum(off, n_tokens - cap)
        lane = jnp.arange(cap) + (start - off)
        valid = (lane >= 0) & (lane < size)
        slab = jax.lax.dynamic_slice(codes_t, (0, start), (pb, cap))  # [PB, cap]
        # Doc ids keep their shard axis: dropping it would relayout them.
        docs = jax.lax.dynamic_slice_in_dim(doc_ids, start, cap, axis=doc_ids.ndim - 1)
        return slab, docs.reshape(cap), valid

    def token(i, carry):
        total, seen = carry
        pc = probe_c[i]
        slab, docs, valid = jax.vmap(cluster)(offsets[pc], sizes[pc])  # [P, PB, cap], [P, cap]
        slab = slab.astype(jnp.int32)
        resid = jnp.zeros(valid.shape, dtype)
        for slot in range(per_byte):
            code = (slab >> (slot * nbits)) & (nb - 1)
            w_code = jnp.zeros(code.shape, dtype)
            for b in range(nb):  # w[code] by select, exact
                w_code = jnp.where(code == b, w[b], w_code)
            resid = resid + jnp.sum(q_slot[i, :, slot][None, :, None] * w_code, axis=1, dtype=dtype)
        tok = jnp.where(valid & qmask[i], probe_s[i][:, None] + resid, neg)
        best = jnp.full((n_docs,), neg, dtype).at[docs.reshape(-1)].max(tok.reshape(-1))
        present = best > neg
        return total + jnp.where(present, best, mse[i]), seen | present

    total, seen = jax.lax.fori_loop(
        0, q.shape[0], token, (jnp.zeros((n_docs,), dtype), jnp.zeros((n_docs,), bool))
    )
    return jnp.where(seen, total, neg).astype(jnp.float32)


def _answer(total, served_ids, doc_start, *, k):
    """One shard's top-k (scores, global doc ids) and its score of each
    served global id it holds (-inf for the others)."""
    top_scores, top_docs = jax.lax.top_k(total, k)
    local = served_ids - doc_start
    held = (local >= 0) & (local < total.shape[0])
    at_served = jnp.where(held, total[jnp.clip(local, 0, total.shape[0] - 1)], -jnp.inf)
    return top_scores, top_docs + doc_start, at_served


@functools.partial(
    jax.jit, static_argnames=("nprobe", "k", "k_impute", "t_prime", "n_docs", "cap", "nbits", "dtype")
)
def reference_query(
    centroids, codes, doc_ids, offsets, sizes, weights, q, qmask, served_ids,
    *, nprobe, k, k_impute, t_prime, n_docs, cap, nbits, dtype,
):
    """One query q f32[Q, D] on a one-shard corpus -> (top-k scores
    f32[k], doc ids i32[k], the reference's score of each of
    ``served_ids`` i32[k]; -inf for a document with no candidate token or
    out of range). Scores come back as float32."""
    top_s, top_c, top_z = _select(centroids, sizes, q, k_impute=k_impute, dtype=dtype)
    mse = _impute(top_s, top_z, qmask, t_prime=t_prime)
    total = _doc_scores(
        codes, doc_ids, offsets, sizes, weights, q, qmask, top_s[:, :nprobe], top_c[:, :nprobe], mse,
        n_docs=n_docs, cap=cap, nbits=nbits, dtype=dtype,
    )
    return _answer(total, served_ids, 0, k=k)


_shard_select = jax.jit(_select, static_argnames=("k_impute", "dtype"))
_merged_impute = jax.jit(_impute, static_argnames=("t_prime",))


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "n_docs", "cap", "nbits", "dtype"))
def shard_answer(
    codes, doc_ids, offsets, sizes, weights, q, qmask, top_s, top_c, mse, served_ids, doc_start,
    *, nprobe, k, n_docs, cap, nbits, dtype,
):
    """Steps 3 and 4 on one shard of a sharded corpus, under the global
    ``mse``: (its top-k scores, their global doc ids, its score of each
    served global id it holds)."""
    total = _doc_scores(
        codes, doc_ids, offsets, sizes, weights, q, qmask, top_s[:, :nprobe], top_c[:, :nprobe], mse,
        n_docs=n_docs, cap=cap, nbits=nbits, dtype=dtype,
    )
    return _answer(total, served_ids, doc_start, k=k)


def sharded_query(shards, q, qmask, served_ids, *, nprobe, k, k_impute, t_prime, cap, nbits, dtype):
    """``reference_query`` over document shards, each on its own device:
    step 1 on each shard, step 2 over the merged pairs, steps 3 and 4 on
    each shard, and the top-k of the union on the host."""
    args = [jax.device_put((q, qmask, served_ids), sh.device) for sh in shards]
    sel = [
        _shard_select(sh.centroids, sh.sizes, a[0], k_impute=k_impute, dtype=dtype)
        for sh, a in zip(shards, args)
    ]
    pairs = [jax.device_put((s, z), shards[0].device) for s, _, z in sel]
    mse = _merged_impute(
        jnp.concatenate([s for s, _ in pairs], axis=1), jnp.concatenate([z for _, z in pairs], axis=1),
        args[0][1], t_prime=t_prime,
    )
    outs = [
        shard_answer(
            sh.codes, sh.doc_ids, sh.offsets, sh.sizes, sh.weights, q_s, qmask_s,
            top_s, top_c, jax.device_put(mse, sh.device), served_s, sh.doc_start,
            nprobe=nprobe, k=k, n_docs=sh.n_docs, cap=cap, nbits=nbits, dtype=dtype,
        )
        for sh, (q_s, qmask_s, served_s), (top_s, top_c, _) in zip(shards, args, sel)
    ]
    scores = np.concatenate([np.asarray(s) for s, _, _ in outs])
    docs = np.concatenate([np.asarray(d) for _, d, _ in outs])
    top = np.argsort(-scores, kind="stable")[:k]
    return scores[top], docs[top], np.max([np.asarray(a) for _, _, a in outs], axis=0)


def run_reference(shards, config: dict, queries, replies, *, dtype=jnp.float32):
    """The reference over each (q, qmask) of ``queries`` with the served
    ``(scores, doc_ids)`` of ``replies``, on the corpus ``shards``
    (``synth.make_shards``): -> list of (top-k scores, top-k doc ids, the
    reference's score of each served doc) as host arrays."""
    static = dict(
        nprobe=config["nprobe"], k=config["k"], k_impute=config["k_impute"],
        t_prime=config["t_prime"], cap=max(sh.cap for sh in shards), nbits=config["nbits"], dtype=dtype,
    )
    out = []
    for (q, qmask), (_, ids) in zip(queries, replies):
        q, qmask, ids = np.asarray(q, np.float32), np.asarray(qmask, bool), np.asarray(ids, np.int32)
        if len(shards) > 1:
            got = sharded_query(shards, q, qmask, ids, **static)
        else:
            sh = shards[0]
            got = reference_query(
                sh.centroids, sh.codes, sh.doc_ids, sh.offsets, sh.sizes, sh.weights,
                jnp.asarray(q), jnp.asarray(qmask), jnp.asarray(ids), n_docs=sh.n_docs, **static,
            )
        out.append(tuple(np.asarray(x) for x in got))
    return out


@functools.partial(jax.jit, static_argnames=("nprobe",))
def _probe_tokens(centroids, sizes, q, qmask, *, nprobe):
    centroids, sizes = _drop_lead(centroids, 2), _drop_lead(sizes, 1)
    s = jnp.einsum("bqd,cd->bqc", q, centroids)
    _, top_c = jax.lax.top_k(s, nprobe)
    return jnp.sum(jnp.where(qmask[..., None], sizes[top_c], 0), axis=(1, 2))


def probe_tokens(shards, config: dict, qs, ms, chunk: int = 16) -> np.ndarray:
    """Real candidate tokens of each query over all shards: over its
    active tokens, the summed sizes of the ``nprobe`` clusters step 1
    probes in each shard."""
    out = np.zeros(len(qs), np.int64)
    for sh in shards:
        for i in range(0, len(qs), chunk):
            out[i:i + chunk] += np.asarray(_probe_tokens(
                sh.centroids, sh.sizes, jax.device_put(qs[i:i + chunk], sh.device),
                jax.device_put(ms[i:i + chunk], sh.device), nprobe=config["nprobe"],
            ), np.int64)
    return out
