"""Two-stage reduction (paper §4.5), TPU-shaped.

The paper merges per-(query-token, cluster) "strides" with a binary tree of
sorted-run merges in C++. On TPU the same computation is one ``lax.sort``
and one segmented sum, with no scatter and no gather over the candidate
stream (the TPU serializes indexed access):

  imputation fold: Eq. (8) with missing-similarity imputation is
          S_d = sum_i m_i + sum_{(i,d) present} max(score - m_i)
      so each score is shifted by its query token's ``m_i`` before the
      sort; float subtraction is monotone, so the max of the shifted
      scores is the shifted max, bit for bit.

  stage 1 (token-level): sort by the composite key ``doc_id * Q + qtoken``
      with the shifted score as the second key, so each (doc, qtoken) run
      ends at its max — Eq. (1)'s implicit score-matrix fill, duplicates
      included (the paper's inner-cluster max is subsumed).

  stage 2 (document-level): a segmented inclusive sum of the run-end
      values over each document, read at its last entry, plus the constant
      ``sum_i m_i`` — the paper's prefix-sum trick (``_document_sums``).

Padding entries carry key == SENTINEL and sort to the back. Top-k runs over
document-end positions only (others are -inf). ``WarpSearchConfig.reduce_impl``
selects nothing: "scan" and "segment" both run this one reduction.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["TopKResult", "two_stage_reduce", "KEY_SENTINEL"]

KEY_SENTINEL = jnp.iinfo(jnp.int32).max


class TopKResult(NamedTuple):
    scores: jax.Array  # f32[k], -inf padded
    doc_ids: jax.Array  # i32[k], -1 padded


def composite_key_fits_int32(n_docs: int, q_max: int) -> bool:
    """Whether ``doc_id * q_max + qtok`` stays below the int32 sentinel."""
    return (n_docs - 1) * q_max + (q_max - 1) < int(KEY_SENTINEL)


def _shift(x: jax.Array, d: int) -> jax.Array:
    """``x`` moved ``d`` places toward the end, zeros shifted in."""
    return jax.lax.pad(x, jnp.zeros((), x.dtype), [(d, -d, 0)])


def _ends(x: jax.Array) -> jax.Array:
    """True where the next entry differs, and at the last entry."""
    return jnp.concatenate([x[:-1] != x[1:], jnp.ones((1,), bool)])


def _document_sums(docid: jax.Array, vals: jax.Array) -> jax.Array:
    """Inclusive sum of ``vals`` over each run of equal ``docid``: a
    Hillis–Steele scan of ⌈log2 n⌉ contiguous shifts (after offset ``d``,
    entry i sums (i - 2d, i] of its document, documents being contiguous).
    Its tree order keeps the float error at O(log n) ulps, where a global
    cumsum less its value at the document start would cancel."""
    d = 1
    while d < vals.shape[0]:
        vals = vals + jnp.where(_shift(docid, d) == docid, _shift(vals, d), 0.0)
        d *= 2
    return vals


@functools.partial(jax.jit, static_argnames=("q_max", "k", "n_docs", "pad_to_k"))
def two_stage_reduce(
    doc_ids: jax.Array,
    qtok_ids: jax.Array | None,
    scores: jax.Array,
    valid: jax.Array,
    mse: jax.Array,
    doc_mask: jax.Array | None = None,
    *,
    q_max: int,
    k: int,
    n_docs: int | None = None,
    pad_to_k: bool = False,
) -> TopKResult:
    """Reduce candidate entries to top-k document scores.

    doc_ids, qtok_ids, scores, valid: each candidate's i32 document id,
              i32 query token, f32 token-level score (centroid + selective
              residual sum) and bool padding / masked-token indicator.
    mse:      f32[q_max] missing similarity estimates (0 at masked tokens).
    doc_mask: optional bool[n_docs] survivor bitmap (see
              ``core/docfilter.py``): filtered documents' totals are
              masked to -inf before top-k. Because the imputation ``mse``
              never depends on which candidates survive, masking here is
              exact — surviving documents keep bit-identical scores.

    Entries come flat, [N] (ragged worklist slots, whose query token is
    data: ``mse`` is gathered per entry), or as [q_max, M] rows holding
    query token i's candidates in row i (dense [Q, P, cap] stages:
    ``qtok_ids`` may be None and the imputation fold is a broadcast).

    The fast path sorts by the int32 composite key ``doc_id * q_max + qtok``
    which requires ``(n_docs - 1) * q_max + q_max - 1 < int32 max``. Pass
    ``n_docs`` to make that precondition *checked*: when the composite
    would overflow, the reduction automatically switches to a lexicographic
    (doc, qtok) sort that never forms the product, at the cost of one
    extra sort operand. Without ``n_docs`` the precondition is the
    caller's responsibility, as before.

    A ragged worklist bound may be smaller than ``k`` on skew-free tiny
    indexes even though the (padded) dense pool is not; ``pad_to_k``
    appends invalid entries up to ``k`` in that case instead of raising,
    preserving the -inf/-1-padded contract.
    """
    if scores.ndim == 2:
        qtok_ids = jnp.broadcast_to(
            jnp.arange(q_max, dtype=jnp.int32)[:, None], scores.shape
        )
        adj = scores - mse[:, None]
        doc_ids, qtok_ids, adj, valid = (
            a.reshape(-1) for a in (doc_ids, qtok_ids, adj, valid)
        )
    else:
        adj = scores - mse[qtok_ids]
    n = doc_ids.shape[0]
    if k > n:
        if not pad_to_k:
            raise ValueError(
                f"k={k} > candidate count {n} (flat entries; pass "
                "pad_to_k=True to pad a statically short candidate stream)"
            )
        pad = k - n
        doc_ids = jnp.pad(doc_ids, (0, pad))
        qtok_ids = jnp.pad(qtok_ids, (0, pad))
        adj = jnp.pad(adj, (0, pad))
        valid = jnp.pad(valid, (0, pad))  # False: sorts to the back
        n = k

    if n_docs is not None and not composite_key_fits_int32(n_docs, q_max):
        # Composite doc_id * q_max + qtok would overflow int32 (int64 is
        # unavailable without jax_enable_x64): sort by (doc, qtok) pair.
        dkey = jnp.where(valid, doc_ids, KEY_SENTINEL).astype(jnp.int32)
        qkey = jnp.where(valid, qtok_ids, KEY_SENTINEL).astype(jnp.int32)
        docid, qkey_s, adj_sorted = jax.lax.sort(
            (dkey, qkey, adj), num_keys=3, is_stable=False
        )
        # docid holds KEY_SENTINEL at invalid rows, which cannot collide
        # with a representable doc id.
        run_end = _ends(docid) | _ends(qkey_s)
    else:
        key = jnp.where(
            valid, doc_ids * q_max + qtok_ids, KEY_SENTINEL
        ).astype(jnp.int32)
        # Every operand is a key, so stability cannot change the result.
        key_sorted, adj_sorted = jax.lax.sort((key, adj), num_keys=2, is_stable=False)
        # Invalid rows keep KEY_SENTINEL (not a representable doc id) so a
        # real document adjacent to the padding block never merges with it.
        docid = jnp.where(key_sorted != KEY_SENTINEL, key_sorted // q_max, KEY_SENTINEL)
        run_end = _ends(key_sorted)

    valid_sorted = docid != KEY_SENTINEL
    doc_end = _ends(docid) & valid_sorted
    total = _document_sums(docid, jnp.where(run_end & valid_sorted, adj_sorted, 0.0))
    total = total + jnp.sum(mse)

    if doc_mask is not None:
        # Filter pushdown endpoint: a filtered doc's total becomes -inf, so
        # it cannot enter top-k. Invalid rows carry KEY_SENTINEL doc ids —
        # clip for the gather; doc_end is already False there.
        doc_end = doc_end & doc_mask[jnp.clip(docid, 0, doc_mask.shape[0] - 1)]
    final = jnp.where(doc_end, total, -jnp.inf)
    top_scores, top_idx = jax.lax.top_k(final, k)
    top_docs = jnp.where(
        jnp.isfinite(top_scores), docid[top_idx], jnp.int32(-1)
    )
    return TopKResult(scores=top_scores, doc_ids=top_docs)
