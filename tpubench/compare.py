"""The comparison that decides ``correct``.

Each sampled reply ``(scores, doc_ids)`` is held against the reference
in two ways, and its gap is the larger of the two:

- rank by rank, its score against the reference's score at that rank
  (the sorted top-k scores must agree, whatever the order of tied docs);
- document by document, its score for a doc against the reference's
  score for that same doc (each served doc must be one the reference
  scores so, which a wrong, duplicated or missing doc id fails).

Both are relative to the reference's score. Ties need no tolerance of
their own: two docs that swap places have the same score.
"""

from __future__ import annotations

import numpy as np

# Below this magnitude a score's gap is measured absolutely.
TINY = 1e-6


def reply_gap(scores, doc_ids, ref_scores, ref_at_served) -> float:
    s = np.asarray(scores, np.float64)
    ids = np.asarray(doc_ids)
    r = np.asarray(ref_scores, np.float64)
    a = np.asarray(ref_at_served, np.float64)
    if s.shape != r.shape or ids.shape != r.shape:
        return float("inf")
    finite = np.isfinite(r)
    if not np.array_equal(np.isfinite(s), finite):
        return float("inf")
    live = ids[finite]
    if (live < 0).any() or len(np.unique(live)) != len(live):
        return float("inf")
    if not np.isfinite(a[finite]).all():
        return float("inf")
    rank = np.abs(s[finite] - r[finite]) / np.maximum(np.abs(r[finite]), TINY)
    doc = np.abs(s[finite] - a[finite]) / np.maximum(np.abs(a[finite]), TINY)
    return float(max(rank.max(initial=0.0), doc.max(initial=0.0)))


def score_gap(replies, refs) -> float:
    """Largest ``reply_gap`` over the sampled replies (0 for none)."""
    return max(
        (reply_gap(s, d, rs, ra) for (s, d), (rs, _, ra) in zip(replies, refs)),
        default=0.0,
    )


def sample(candidates: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` of ``candidates`` (all of them when there are fewer), in order."""
    if len(candidates) <= n:
        return np.asarray(candidates)
    return np.sort(rng.choice(candidates, size=n, replace=False))


def checks(config: dict, gap: float, missing: int) -> dict:
    """The numbers compared, each beside its limit."""
    return {
        "score_gap": {"value": gap, "limit": config["limits"]["score_gap"]},
        "missing_replies": {"value": missing, "limit": 0},
    }


def passed(checked: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())
