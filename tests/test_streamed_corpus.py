"""Seeded chunk-by-chunk synthetic corpora (``data.synth.StreamedCorpus``)."""

import numpy as np

from repro.core import IndexBuildConfig, build_index
from repro.data import make_queries, make_streamed_corpus
from repro.store.builder import build_index_chunked


def _all(corpus, lo=0, hi=None):
    parts = list(corpus.chunks(lo, hi))
    emb = np.concatenate([np.asarray(e) for e, _ in parts])
    tdi = np.concatenate([t for _, t in parts])
    return emb, tdi


def test_exact_size_and_consistent_regeneration():
    c = make_streamed_corpus(5000, 60, 32, chunk_size=700, seed=5)
    assert (c.n_tokens, c.n_docs, c.dim) == (5000, 60, 32)
    assert c.doc_lens.min() >= 4
    emb, tdi = _all(c)
    assert emb.shape == (5000, 32)
    np.testing.assert_allclose(np.linalg.norm(emb, axis=1), 1.0, atol=1e-5)
    np.testing.assert_array_equal(tdi, np.repeat(np.arange(60), c.doc_lens))
    # Any token range regenerates the same rows, through chunks or rows().
    np.testing.assert_array_equal(c.rows(650, 2150), emb[650:2150])
    sub_emb, sub_tdi = _all(c, 650, 2150)
    np.testing.assert_array_equal(sub_emb, emb[650:2150])
    np.testing.assert_array_equal(sub_tdi, tdi[650:2150])
    # Same seed, same corpus; another seed, another one.
    again = make_streamed_corpus(5000, 60, 32, chunk_size=700, seed=5)
    np.testing.assert_array_equal(_all(again)[0], emb)
    other = make_streamed_corpus(5000, 60, 32, chunk_size=700, seed=6)
    assert not np.array_equal(_all(other)[0], emb)


def test_chunked_build_matches_in_memory_build():
    c = make_streamed_corpus(3000, 40, 32, chunk_size=512, seed=2)
    cfg = IndexBuildConfig(n_centroids=16, kmeans_iters=2)
    emb, tdi = _all(c)
    a = build_index_chunked(c.chunks, c.n_docs, cfg, n_tokens=c.n_tokens, dim=c.dim)
    b = build_index(emb, tdi, c.n_docs, cfg)
    np.testing.assert_array_equal(np.asarray(a.packed_codes), np.asarray(b.packed_codes))
    np.testing.assert_array_equal(np.asarray(a.cluster_sizes), np.asarray(b.cluster_sizes))
    q, qmask, rel = make_queries(c, n_queries=3, query_maxlen=8, seed=1)
    assert q.shape == (3, 8, 32) and qmask.any(axis=1).all()
