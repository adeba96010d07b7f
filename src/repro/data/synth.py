"""Synthetic multi-vector corpora for quality/latency experiments.

Real LoTTE/BEIR corpora are not available offline, so quality claims are
validated against an exact oracle on *clustered* synthetic data: documents
draw their token embeddings from a mixture of latent topic directions plus
noise, and queries are perturbed copies of tokens from a designated
"relevant" document — giving a non-trivial nearest-neighbor structure that
exercises the same failure modes (cluster boundary effects, imputation
error) the paper's datasets do.

``topic_skew`` adds the heavy-tailed routing structure of real corpora:
topic popularity follows a Zipf law (P(topic r) ∝ r^-skew), so the
k-means clusters the index builds over these embeddings inherit the skew —
a few head clusters hold a large share of the tokens while the tail stays
small. This is the regime CITADEL's dynamic lexical routing and XTR's
token-retrieval analysis describe, and the one where query-adaptive ragged
worklists beat the static worst-case bound: the static bound must cover a
query probing the head clusters, while most queries probe mostly-tail
clusters and need a fraction of it. The default ``topic_skew=0`` keeps the
historical balanced behavior (uniform topics) for existing tiers/tests.

``make_streamed_corpus`` draws the same kind of corpus at deployment scale
(tens of millions of tokens) without ever holding it: the host keeps only
the per-document lengths and topics, and each fixed-size chunk of token
embeddings is generated on the default device from ``(seed, chunk
index)``. Its ``chunks`` method is a chunk source for
``repro.store.builder.build_index_chunked``, and any token range can be
regenerated exactly (``rows``), which is all ``make_queries`` needs.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SynthCorpus",
    "StreamedCorpus",
    "make_corpus",
    "make_streamed_corpus",
    "make_queries",
]


@dataclasses.dataclass(frozen=True)
class SynthCorpus:
    emb: np.ndarray  # f32[n_tokens, dim] L2-normalized token embeddings
    token_doc_ids: np.ndarray  # i32[n_tokens]
    doc_lens: np.ndarray  # i32[n_docs]
    topic_of_doc: np.ndarray  # i32[n_docs]

    @property
    def n_docs(self) -> int:
        return len(self.doc_lens)

    @property
    def n_tokens(self) -> int:
        return len(self.token_doc_ids)

    @property
    def dim(self) -> int:
        return self.emb.shape[1]

    def rows(self, lo: int, hi: int) -> np.ndarray:
        """Token embeddings ``[lo, hi)`` as f32[hi - lo, dim]."""
        return self.emb[lo:hi]


@functools.partial(jax.jit, static_argnames=("chunk_size",))
def _chunk_embeddings(key, index, topics, topic_idx, strength, *, chunk_size):
    noise = jax.random.normal(
        jax.random.fold_in(key, index), (chunk_size, topics.shape[1]),
        jnp.float32,
    )
    emb = strength * topics[topic_idx] + noise
    return emb * jax.lax.rsqrt(jnp.sum(emb * emb, axis=-1, keepdims=True))


@dataclasses.dataclass(frozen=True)
class StreamedCorpus:
    """A seeded synthetic corpus whose token embeddings exist only one
    chunk at a time (see the module docstring)."""

    doc_lens: np.ndarray  # i32[n_docs]
    topic_of_doc: np.ndarray  # i32[n_docs]
    topics: np.ndarray  # f32[n_topics, dim] unit topic directions
    topic_strength: float
    seed: int
    chunk_size: int

    @property
    def n_docs(self) -> int:
        return len(self.doc_lens)

    @property
    def n_tokens(self) -> int:
        return int(self.doc_lens.sum())

    @property
    def dim(self) -> int:
        return self.topics.shape[1]

    @functools.cached_property
    def _doc_offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.doc_lens, dtype=np.int64)])

    def token_doc_ids(self, lo: int, hi: int) -> np.ndarray:
        """Owning document of tokens ``[lo, hi)`` as i32[hi - lo]."""
        tok = np.arange(lo, hi, dtype=np.int64)
        return (np.searchsorted(self._doc_offsets, tok, side="right") - 1).astype(
            np.int32
        )

    def _chunk(self, i: int) -> jax.Array:
        """Embeddings of chunk i (rows ``[i * chunk_size, ...)``), always
        ``chunk_size`` rows: one compiled generator serves every chunk."""
        lo = i * self.chunk_size
        hi = min(lo + self.chunk_size, self.n_tokens)
        topic_idx = np.zeros((self.chunk_size,), np.int32)
        topic_idx[: hi - lo] = self.topic_of_doc[self.token_doc_ids(lo, hi)]
        return _chunk_embeddings(
            jax.random.PRNGKey(self.seed), i, jnp.asarray(self.topics),
            jnp.asarray(topic_idx), jnp.float32(self.topic_strength),
            chunk_size=self.chunk_size,
        )

    def chunks(self, lo: int = 0, hi: int | None = None):
        """Chunk source for ``build_index_chunked``: yields (emb f32[n, D]
        on the default device, token_doc_ids i32[n]) for tokens
        ``[lo, hi)`` (default: all) in token order, one piece per
        generated chunk they overlap."""
        hi = self.n_tokens if hi is None else hi
        size = self.chunk_size
        for i in range(lo // size, -(-hi // size)):
            a, b = max(lo, i * size), min(hi, (i + 1) * size)
            yield self._chunk(i)[a - i * size : b - i * size], self.token_doc_ids(a, b)

    def rows(self, lo: int, hi: int) -> np.ndarray:
        """Token embeddings ``[lo, hi)`` as f32[hi - lo, dim], regenerated
        from the chunks that hold them."""
        first, last = lo // self.chunk_size, (hi - 1) // self.chunk_size
        parts = [np.asarray(self._chunk(i)) for i in range(first, last + 1)]
        start = lo - first * self.chunk_size
        return np.concatenate(parts)[start : start + hi - lo]


def make_streamed_corpus(
    n_tokens: int,
    n_docs: int,
    dim: int = 128,
    *,
    n_topics: int = 32,
    topic_strength: float = 2.0,
    seed: int = 0,
    chunk_size: int = 1 << 16,
) -> StreamedCorpus:
    """A ``StreamedCorpus`` of exactly ``n_tokens`` tokens over ``n_docs``
    documents: Poisson document lengths around the mean (at least 4),
    trimmed or topped up one token per document to hit the total, and
    uniform topics as in ``make_corpus``."""
    if n_tokens < 4 * n_docs:
        raise ValueError(
            f"n_tokens={n_tokens} is below 4 tokens for each of {n_docs} docs"
        )
    rng = np.random.default_rng(seed)
    topics = _normalize(rng.standard_normal((n_topics, dim), dtype=np.float32))
    doc_lens = np.maximum(4, rng.poisson(n_tokens / n_docs, n_docs)).astype(
        np.int64
    )
    while (diff := n_tokens - int(doc_lens.sum())) != 0:
        step = np.sign(diff)
        room = doc_lens > 4 if step < 0 else np.ones_like(doc_lens, bool)
        idx = np.flatnonzero(room)[: abs(diff)]
        doc_lens[idx] += step
    topic_of_doc = rng.integers(0, n_topics, n_docs).astype(np.int32)
    return StreamedCorpus(
        doc_lens=doc_lens.astype(np.int32),
        topic_of_doc=topic_of_doc,
        topics=topics.astype(np.float32),
        topic_strength=float(topic_strength),
        seed=int(seed),
        chunk_size=int(chunk_size),
    )


def _normalize(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def make_corpus(
    n_docs: int = 512,
    dim: int = 128,
    *,
    mean_doc_len: int = 24,
    n_topics: int = 32,
    topic_strength: float = 2.0,
    topic_skew: float = 0.0,
    seed: int = 0,
) -> SynthCorpus:
    """``topic_skew > 0`` draws each document's topic from a Zipf law
    (P(topic r) ∝ (r+1)^-skew) instead of uniformly, so index cluster
    sizes become heavy-tailed like skew-routed real corpora; 0 (default)
    keeps balanced topics."""
    rng = np.random.default_rng(seed)
    topics = _normalize(rng.standard_normal((n_topics, dim), dtype=np.float32))
    doc_lens = np.maximum(4, rng.poisson(mean_doc_len, n_docs)).astype(np.int32)
    if topic_skew > 0.0:
        p = np.arange(1, n_topics + 1, dtype=np.float64) ** -topic_skew
        p /= p.sum()
        topic_of_doc = rng.choice(n_topics, n_docs, p=p).astype(np.int32)
    else:
        topic_of_doc = rng.integers(0, n_topics, n_docs).astype(np.int32)

    n_tokens = int(doc_lens.sum())
    token_doc_ids = np.repeat(np.arange(n_docs, dtype=np.int32), doc_lens)
    noise = rng.standard_normal((n_tokens, dim), dtype=np.float32)
    emb = topic_strength * topics[topic_of_doc[token_doc_ids]] + noise
    return SynthCorpus(
        emb=_normalize(emb).astype(np.float32),
        token_doc_ids=token_doc_ids,
        doc_lens=doc_lens,
        topic_of_doc=topic_of_doc,
    )


def make_queries(
    corpus: SynthCorpus | StreamedCorpus,
    n_queries: int = 16,
    *,
    query_maxlen: int = 32,
    tokens_per_query: int | tuple[int, int] = 8,
    noise: float = 0.35,
    seed: int = 1,
):
    """Queries as noisy copies of tokens from a sampled "relevant" doc.

    ``tokens_per_query`` may be an ``(lo, hi)`` range: each query then
    draws its active-token count uniformly from ``[lo, hi]`` — the
    varied-length traffic that spreads adaptive worklist demand across
    ladder rungs (a short query probes as many clusters per token but
    amortizes over fewer active tokens).

    Returns (q f32[n_queries, query_maxlen, dim], qmask bool[..., maxlen],
    relevant_doc i32[n_queries]).
    """
    rng = np.random.default_rng(seed)
    n_docs = corpus.n_docs
    dim = corpus.dim
    doc_offsets = np.concatenate([[0], np.cumsum(corpus.doc_lens)])

    q = np.zeros((n_queries, query_maxlen, dim), np.float32)
    qmask = np.zeros((n_queries, query_maxlen), bool)
    relevant = rng.integers(0, n_docs, n_queries).astype(np.int32)
    for i, d in enumerate(relevant):
        lo, hi = doc_offsets[d], doc_offsets[d + 1]
        want = (
            int(rng.integers(tokens_per_query[0], tokens_per_query[1] + 1))
            if isinstance(tokens_per_query, tuple)
            else tokens_per_query
        )
        n_tok = min(want, hi - lo, query_maxlen)
        picks = rng.choice(np.arange(lo, hi), size=n_tok, replace=False)
        vecs = corpus.rows(lo, hi)[picks - lo] + noise * rng.standard_normal(
            (n_tok, dim)
        ).astype(np.float32)
        q[i, :n_tok] = _normalize(vecs)
        qmask[i, :n_tok] = True
    return q, qmask, relevant
