"""Two-stage reduction vs a dense numpy oracle (paper Eq. 1/6/7/8)."""

import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import engine
from repro.core.reduction import (
    composite_key_fits_int32,
    two_stage_reduce,
)
from repro.core.types import WarpSearchConfig


def _oracle(doc_ids, qtok_ids, scores, valid, mse, n_docs, q_max):
    """Dense score matrix + row-sum with imputation: the textbook Eq. (1)."""
    mat = np.full((n_docs, q_max), -np.inf)
    seen = np.zeros((n_docs,), bool)
    for d, t, s, v in zip(doc_ids, qtok_ids, scores, valid):
        if v:
            mat[d, t] = max(mat[d, t], s)
            seen[d] = True
    out = {}
    for d in range(n_docs):
        if not seen[d]:
            continue
        total = 0.0
        for t in range(q_max):
            total += mat[d, t] if np.isfinite(mat[d, t]) else mse[t]
        out[d] = total
    return out


@settings(max_examples=40, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(4, 200),
    n_docs=st.integers(1, 30),
    q_max=st.integers(1, 8),
    k=st.integers(1, 4),
)
def test_two_stage_reduce_matches_oracle(seed, n, n_docs, q_max, k):
    if k > n:
        k = n
    rng = np.random.default_rng(seed)
    doc_ids = rng.integers(0, n_docs, n).astype(np.int32)
    qtok_ids = rng.integers(0, q_max, n).astype(np.int32)
    scores = rng.standard_normal(n).astype(np.float32)
    valid = rng.random(n) > 0.2
    mse = (rng.standard_normal(q_max) * 0.1).astype(np.float32)

    res = two_stage_reduce(
        jnp.asarray(doc_ids),
        jnp.asarray(qtok_ids),
        jnp.asarray(scores),
        jnp.asarray(valid),
        jnp.asarray(mse),
        q_max=q_max,
        k=k,
    )
    got_scores = np.asarray(res.scores)
    got_docs = np.asarray(res.doc_ids)

    want = _oracle(doc_ids, qtok_ids, scores, valid, mse, n_docs, q_max)
    want_sorted = sorted(want.items(), key=lambda kv: -kv[1])

    n_expect = min(k, len(want))
    for i in range(n_expect):
        assert np.isfinite(got_scores[i])
        assert got_docs[i] in want, got_docs[i]
        np.testing.assert_allclose(got_scores[i], want[got_docs[i]], rtol=1e-4, atol=1e-4)
        # i-th returned score matches the i-th best oracle score.
        np.testing.assert_allclose(
            got_scores[i], want_sorted[i][1], rtol=1e-4, atol=1e-4
        )
    # Padding beyond the unique-doc count.
    for i in range(n_expect, k):
        assert got_docs[i] == -1 and got_scores[i] == -np.inf


def test_all_invalid_returns_padding():
    res = two_stage_reduce(
        jnp.zeros(8, jnp.int32),
        jnp.zeros(8, jnp.int32),
        jnp.zeros(8, jnp.float32),
        jnp.zeros(8, bool),
        jnp.zeros(4, jnp.float32),
        q_max=4,
        k=3,
    )
    assert np.all(np.asarray(res.doc_ids) == -1)
    assert np.all(np.asarray(res.scores) == -np.inf)


def test_missing_entries_imputed():
    """One doc retrieved for qtok 0 only; other qtok contributes m."""
    mse = jnp.asarray([0.0, 0.25])
    res = two_stage_reduce(
        jnp.asarray([7], jnp.int32),
        jnp.asarray([0], jnp.int32),
        jnp.asarray([0.5], jnp.float32),
        jnp.asarray([True]),
        mse,
        q_max=2,
        k=1,
    )
    np.testing.assert_allclose(float(res.scores[0]), 0.5 + 0.25, rtol=1e-6)
    assert int(res.doc_ids[0]) == 7


def test_composite_key_overflow_detection():
    assert composite_key_fits_int32(n_docs=1000, q_max=32)
    assert not composite_key_fits_int32(n_docs=2**30, q_max=4)
    # Boundary: largest composite must stay strictly below the sentinel.
    assert not composite_key_fits_int32(n_docs=(2**31 - 1) // 8 + 1, q_max=8)


def test_wide_key_fallback_matches_oracle():
    """Regression: doc_id * q_max + qtok overflows int32 -> the checked
    n_docs path must switch to the two-key sort and stay correct."""
    q_max, k = 4, 3
    n_docs = 2**30 + 7  # n_docs * q_max is far beyond int32
    assert not composite_key_fits_int32(n_docs, q_max)
    doc_ids = np.array(
        [2**30 + 5, 2**30 + 5, 3, 2**29, 2**30 + 5, 3, 2**29, 9], np.int32
    )
    qtok_ids = np.array([0, 0, 1, 3, 2, 1, 0, 3], np.int32)
    scores = np.array([0.5, 0.9, 0.3, 0.7, 0.2, 0.8, 0.1, 0.4], np.float32)
    valid = np.array([1, 1, 1, 1, 1, 0, 1, 1], bool)
    mse = np.array([0.01, 0.02, 0.03, 0.04], np.float32)

    res = two_stage_reduce(
        jnp.asarray(doc_ids), jnp.asarray(qtok_ids), jnp.asarray(scores),
        jnp.asarray(valid), jnp.asarray(mse),
        q_max=q_max, k=k, n_docs=n_docs,
    )
    # Sparse oracle (the dense _oracle cannot allocate 2^30 rows).
    best: dict = {}
    for d, t, s, vv in zip(doc_ids, qtok_ids, scores, valid):
        if vv:
            best[(int(d), int(t))] = max(best.get((int(d), int(t)), -np.inf), s)
    want = {}
    for d in {int(d) for d, v in zip(doc_ids, valid) if v}:
        want[d] = sum(
            best.get((d, t), float(mse[t])) for t in range(q_max)
        )
    want_sorted = sorted(want.items(), key=lambda kv: -kv[1])
    for i in range(min(k, len(want_sorted))):
        assert int(res.doc_ids[i]) == want_sorted[i][0]
        np.testing.assert_allclose(
            float(res.scores[i]), want_sorted[i][1], rtol=1e-5, atol=1e-5
        )


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(4, 120),
    n_docs=st.integers(1, 30),
    q_max=st.integers(1, 8),
)
def test_wide_key_path_matches_fast_path(seed, n, n_docs, q_max):
    """Force the two-key sort on small inputs (fake huge n_docs) and check
    bit-identical results against the int32 composite path."""
    rng = np.random.default_rng(seed)
    doc_ids = rng.integers(0, n_docs, n).astype(np.int32)
    qtok_ids = rng.integers(0, q_max, n).astype(np.int32)
    scores = rng.standard_normal(n).astype(np.float32)
    valid = rng.random(n) > 0.2
    mse = (rng.standard_normal(q_max) * 0.1).astype(np.float32)
    args = (
        jnp.asarray(doc_ids), jnp.asarray(qtok_ids), jnp.asarray(scores),
        jnp.asarray(valid), jnp.asarray(mse),
    )
    a = two_stage_reduce(*args, q_max=q_max, k=4, n_docs=n_docs)
    b = two_stage_reduce(*args, q_max=q_max, k=4, n_docs=2**31)
    np.testing.assert_allclose(
        np.asarray(a.scores), np.asarray(b.scores), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(a.doc_ids), np.asarray(b.doc_ids))


def _assert_topk_matches_oracle(res, want, k, tol=1e-5):
    """Top-k against the oracle's {doc: score}: each returned doc carries
    its own oracle score, rank i carries the i-th best score (so ties may
    come in either order), no doc twice, and -1/-inf padding past the
    distinct documents."""
    got_scores, got_docs = np.asarray(res.scores), np.asarray(res.doc_ids)
    best = sorted(want.values(), reverse=True)
    n_expect = min(k, len(want))
    for i in range(n_expect):
        assert got_docs[i] in want, got_docs[i]
        np.testing.assert_allclose(got_scores[i], want[got_docs[i]], rtol=tol, atol=tol)
        np.testing.assert_allclose(got_scores[i], best[i], rtol=tol, atol=tol)
    assert len(set(got_docs[:n_expect].tolist())) == n_expect
    assert np.all(got_docs[n_expect:] == -1)
    assert np.all(got_scores[n_expect:] == -np.inf)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(4, 300),
    n_docs=st.integers(1, 10),
    q_max=st.integers(1, 6),
    wide=st.booleans(),
)
def test_duplicate_heavy_matches_oracle(seed, n, n_docs, q_max, wide):
    """Ragged/duplicate-heavy streams against the dense oracle, covering
    BOTH sort paths: the int32 composite key and the wide (doc, qtok)
    lexicographic sort (``wide`` fakes an n_docs whose composite key
    overflows for every q_max >= 1).

    Few docs + few qtokens over many entries maximizes duplicate
    (doc, qtok) runs — exactly what a ragged worklist produces when one
    document's tokens span several probed clusters — and quantizing a
    third of the scores makes ties inside a run and between documents.
    """
    rng = np.random.default_rng(seed)
    doc_ids = rng.integers(0, n_docs, n).astype(np.int32)
    qtok_ids = rng.integers(0, q_max, n).astype(np.int32)
    scores = rng.standard_normal(n).astype(np.float32)
    ties = rng.random(n) < 0.33
    scores[ties] = np.round(scores[ties], 1)
    valid = rng.random(n) > 0.3
    mse = (rng.standard_normal(q_max) * 0.1).astype(np.float32)
    nd = 2**31 if wide else n_docs
    if wide:
        assert not composite_key_fits_int32(nd, q_max)
    res = two_stage_reduce(
        jnp.asarray(doc_ids), jnp.asarray(qtok_ids), jnp.asarray(scores),
        jnp.asarray(valid), jnp.asarray(mse), q_max=q_max, k=4, n_docs=nd,
    )
    want = _oracle(doc_ids, qtok_ids, scores, valid, mse, n_docs, q_max)
    _assert_topk_matches_oracle(res, want, 4)


def test_pad_to_k_pads_short_candidate_streams():
    """Flat-path contract: a statically short stream (ragged worklist bound
    < k) pads with invalid entries instead of raising."""
    args = (
        jnp.asarray([3, 3, 5], jnp.int32),
        jnp.asarray([0, 1, 0], jnp.int32),
        jnp.asarray([0.5, 0.25, 0.1], jnp.float32),
        jnp.asarray([True, True, True]),
        jnp.zeros(2, jnp.float32),
    )
    with np.testing.assert_raises(ValueError):
        two_stage_reduce(*args, q_max=2, k=5)
    res = two_stage_reduce(*args, q_max=2, k=5, pad_to_k=True)
    assert int(res.doc_ids[0]) == 3
    np.testing.assert_allclose(float(res.scores[0]), 0.75, rtol=1e-6)
    assert np.all(np.asarray(res.doc_ids[2:]) == -1)
    assert np.all(np.asarray(res.scores[2:]) == -np.inf)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    m=st.integers(1, 50),
    n_docs=st.integers(1, 30),
    q_max=st.integers(1, 8),
)
def test_dense_rows_match_oracle(seed, m, n_docs, q_max):
    """The dense form — [q_max, M] rows, row i holding query token i's
    candidates, imputation folded in by broadcast — against the oracle,
    and bit-identical to the same stream passed flat with its qtok ids."""
    rng = np.random.default_rng(seed)
    doc_ids = rng.integers(0, n_docs, (q_max, m)).astype(np.int32)
    qtok_ids = np.broadcast_to(np.arange(q_max, dtype=np.int32)[:, None], (q_max, m))
    scores = rng.standard_normal((q_max, m)).astype(np.float32)
    valid = rng.random((q_max, m)) > 0.2
    mse = (rng.standard_normal(q_max) * 0.1).astype(np.float32)
    k = min(4, q_max * m)
    rows = two_stage_reduce(
        jnp.asarray(doc_ids), None, jnp.asarray(scores), jnp.asarray(valid),
        jnp.asarray(mse), q_max=q_max, k=k,
    )
    flat = two_stage_reduce(
        *(jnp.asarray(a.reshape(-1)) for a in (doc_ids, qtok_ids, scores, valid)),
        jnp.asarray(mse), q_max=q_max, k=k,
    )
    want = _oracle(
        doc_ids.reshape(-1), qtok_ids.reshape(-1), scores.reshape(-1),
        valid.reshape(-1), mse, n_docs, q_max,
    )
    _assert_topk_matches_oracle(rows, want, k)
    np.testing.assert_array_equal(np.asarray(rows.doc_ids), np.asarray(flat.doc_ids))
    np.testing.assert_array_equal(np.asarray(rows.scores), np.asarray(flat.scores))


def test_dense_reduction_has_no_stream_scatter_or_gather():
    """The batch program's reduction (``engine.reduce_candidates`` under
    ``vmap``, dense layout) lowers with no scatter and no gather whose
    output has the candidate stream's length: the TPU serializes indexed
    access, and the reduction needs none but the top-k's doc ids."""
    b, q_max, m, k = 3, 4, 37, 5
    n = q_max * m
    index = SimpleNamespace(n_docs=1000)
    cfg = WarpSearchConfig(k=k, layout="dense")

    def reduce_one(doc_ids, qtok, scores, valid, mse):
        return engine.reduce_candidates(
            index, doc_ids, qtok, scores, valid, mse, cfg, q_max=q_max
        )

    stream = lambda dt: jax.ShapeDtypeStruct((b, n), dt)  # noqa: E731
    text = jax.jit(jax.vmap(reduce_one)).lower(
        stream(jnp.int32), stream(jnp.int32), stream(jnp.float32),
        stream(jnp.bool_), jax.ShapeDtypeStruct((b, q_max), jnp.float32),
    ).as_text()
    assert "stablehlo.sort" in text
    assert "stablehlo.scatter" not in text
    gathers = [line for line in text.splitlines() if '"stablehlo.gather"' in line]
    assert gathers, "the top-k's doc ids are one gather of k per query"
    for line in gathers:
        out_dims = re.findall(r"\d+", line.rsplit("->", 1)[1])
        assert str(n) not in out_dims, line.strip()
