"""Delta segments: append-only index updates against a frozen base.

``add_documents`` quantizes new documents with the base index's FROZEN
centroids and codec tables (no re-clustering, no re-training) into a small
CSR-by-cluster segment over the *same* centroid space, written as an
append-only ``segments/seg_NNNNN/`` directory next to the base.

Search over base + deltas is exact, not approximate, because everything
that crosses segment boundaries is shared or additive:

  - centroid relevance S_cq depends only on the (frozen) centroids, so one
    ``warp_select`` pass serves every segment;
  - the missing-similarity threshold t' and estimate m_i depend on
    *combined* cluster sizes, which are the element-wise sum of per-segment
    sizes — computed once and fed to the shared stage-1;
  - a document's tokens live entirely inside one segment, so stage 2+3
    (implicit decompression + two-stage reduction) run per segment with the
    shared probe set and global m_i, and the final merge is a top-k over
    the per-segment top-k lists with doc-id offsets.

Hence segmented search returns the same documents as the single-segment
index ``compact()`` produces by folding the deltas back into a fresh base,
with scores equal up to floating-point summation order (the reduction's
document-sum tree depends on the candidate-array length, so the last ulp
can differ) — that identity is the subsystem's correctness
anchor (tests/test_segments.py).

Execution layouts over base + deltas
------------------------------------
``make_segmented_search_fn`` compiles one of two stage-2+3 shapes behind
the shared stage-1 above:

- ``layout="dense"`` runs ``engine.score_and_reduce`` per segment (each
  padded to its own ``[Q, nprobe, cap_s]``) and merges the per-segment
  top-k lists with doc-id offsets — ``nprobe * sum_s cap_s`` candidate
  slots per query token.

- ``layout="ragged"`` builds ONE flat tile worklist spanning every
  segment (``core.worklist``): each probed cluster contributes its
  per-segment CSR runs as consecutive tiles, every entry carrying a
  segment id next to its segment-local ``row0``, so gather, implicit
  decompression, and the reduction's sort all run once over flat slots
  sized by the real candidate count. Doc ids are globalized per slot
  (segment-local id + ``doc_starts[seg]``), so a single
  ``two_stage_reduce`` over all slots replaces the per-segment merge.
  Exactness carries over unchanged: the probe set, t' crossing, and m_i
  come from the one shared stage-1; a document's tokens all live in one
  segment, so its (doc, qtoken) runs are intact in the flat stream and
  the reduction's segmented max/sum see exactly the same values — top-k
  doc ids match the dense segmented path bit-for-bit, scores to float32
  summation order. Token-less segments are filtered out at compile time
  (they contribute no worklist runs). ``memory="scan_qtokens"`` bounds
  only the dense stages; the segmented ragged path always builds the
  full-Q worklist (its working set is already proportional to the real
  candidates).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import shutil
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import fault, obs
from repro.core import engine, kmeans, quantization
from repro.core.docfilter import FilterView, cluster_survivor_counts
from repro.core.reduction import TopKResult, two_stage_reduce
from repro.core.types import WarpIndex, WarpSearchConfig
from repro.core.warpselect import warp_select
from repro.core.worklist import build_tile_worklist
from repro.kernels import ops, ref
from repro.store import format as store_format
from repro.store.integrity import StoreCorruption

__all__ = [
    "SegmentedWarpIndex",
    "quantize_segment",
    "add_documents",
    "delete_documents",
    "read_tombstones",
    "load_segmented",
    "compact",
    "delta_stats",
    "make_segmented_search_fn",
    "segmented_probe_cids",
]


@dataclasses.dataclass(frozen=True)
class SegmentedWarpIndex:
    """A base ``WarpIndex`` plus ordered delta segments.

    Each delta is itself a ``WarpIndex`` over the SAME centroid space
    (centroids / bucket tables are shared references, not copies) with
    segment-local doc ids; ``doc_starts[i]`` is the global id of segment
    ``i``'s first document (segment 0 is the base, at offset 0).
    """

    base: WarpIndex
    deltas: tuple[WarpIndex, ...]
    doc_starts: tuple[int, ...]
    # Segment directory names skipped as corrupt by a quarantining load
    # (``load_segmented(..., quarantine=True)``). Non-empty means the view
    # is DEGRADED: exact over base + healthy deltas, blind to these.
    quarantined: tuple[str, ...] = ()

    def __post_init__(self):
        if len(self.doc_starts) != 1 + len(self.deltas):
            raise ValueError("doc_starts must cover base + every delta")

    @property
    def segments(self) -> tuple[WarpIndex, ...]:
        return (self.base, *self.deltas)

    @property
    def n_segments(self) -> int:
        return 1 + len(self.deltas)

    @property
    def n_docs(self) -> int:
        # Max global id bound, not a segment-size sum: a quarantined
        # segment leaves a doc-id gap so healthy later segments keep
        # their global ids (the reduction's overflow guard needs the
        # bound, not the count).
        return max(
            start + s.n_docs
            for start, s in zip(self.doc_starts, self.segments)
        )

    @property
    def n_tokens(self) -> int:
        return sum(s.n_tokens for s in self.segments)

    @property
    def n_centroids(self) -> int:
        return self.base.n_centroids

    @property
    def cap(self) -> int:
        return max(s.cap for s in self.segments)

    @property
    def dim(self) -> int:
        return self.base.dim

    @property
    def nbits(self) -> int:
        return self.base.nbits

    def combined_cluster_sizes(self) -> jax.Array:
        sizes = np.asarray(self.base.cluster_sizes, np.int32).copy()
        for d in self.deltas:
            sizes += np.asarray(d.cluster_sizes, np.int32)
        return jnp.asarray(sizes)

    def per_segment_cluster_sizes(self) -> np.ndarray:
        """Host ``[n_segments, n_centroids]`` cluster sizes (base first) —
        the geometry the segmented ragged worklist bound is derived from
        (``core.worklist.worklist_bound_segmented``)."""
        return np.stack(
            [np.asarray(s.cluster_sizes, np.int64) for s in self.segments]
        )

    def nbytes(self) -> int:
        """Resident footprint; centroid/codec tables are shared references
        across segments and counted once (with the base)."""
        total = self.base.nbytes()
        for d in self.deltas:
            for name in ("packed_codes", "token_doc_ids",
                         "cluster_offsets", "cluster_sizes"):
                arr = getattr(d, name)
                total += arr.size * arr.dtype.itemsize
        return total


def quantize_segment(
    base: WarpIndex, embeddings, token_doc_ids, n_docs: int
) -> WarpIndex:
    """Quantize new documents against the frozen base: assign to the
    existing centroids, encode residuals with the existing codec, lay out
    CSR-by-cluster over the same centroid space. Doc ids are segment-local
    (``0 .. n_docs``)."""
    emb = kmeans.l2_normalize(jnp.asarray(embeddings, jnp.float32))
    n_tokens = emb.shape[0]
    tdi = np.asarray(token_doc_ids, np.int32)
    if tdi.shape != (n_tokens,):
        raise ValueError("token_doc_ids must align with embeddings")
    if n_tokens and (tdi.min() < 0 or tdi.max() >= n_docs):
        raise ValueError("segment doc ids must be local, in [0, n_docs)")
    if emb.shape[1] != base.dim:
        raise ValueError(f"dim {emb.shape[1]} != base dim {base.dim}")

    c = base.n_centroids
    centroids = jnp.asarray(base.centroids)
    assign = np.asarray(kmeans.assign_clusters(emb, centroids))
    residuals = emb - centroids[assign]
    codes = quantization.encode_residuals(
        residuals, jnp.asarray(base.bucket_cutoffs)
    )
    packed = np.asarray(quantization.pack_codes(codes, base.nbits))

    order = np.argsort(assign, kind="stable")
    sizes = np.bincount(assign, minlength=c).astype(np.int32)
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    return WarpIndex(
        centroids=base.centroids,
        packed_codes=packed[order],
        token_doc_ids=tdi[order],
        cluster_offsets=offsets,
        cluster_sizes=sizes,
        bucket_weights=base.bucket_weights,
        bucket_cutoffs=base.bucket_cutoffs,
        dim=base.dim,
        nbits=base.nbits,
        cap=int(sizes.max()) if n_tokens else 0,
        n_docs=int(n_docs),
        n_tokens=int(n_tokens),
    )


def add_documents(
    path: str, embeddings, token_doc_ids, n_docs: int
) -> str:
    """Append a delta segment to the store at ``path``; returns the new
    segment directory. ``token_doc_ids`` are local to the new batch
    (``0 .. n_docs``); global ids are assigned by position at load time."""
    t0 = time.perf_counter()
    manifest = store_format.read_manifest(path)
    if manifest["kind"] != store_format.KIND_SINGLE:
        raise NotImplementedError(
            f"delta segments require a single-device base index, "
            f"got kind={manifest['kind']!r} (compact + reshard instead)"
        )
    if "shard" in manifest:
        # Per-shard views of a sharded store carry zero-filled codec
        # cutoffs (encode-only); quantizing against them would silently
        # collapse every residual code.
        raise NotImplementedError(
            f"{path} is a per-shard view of a sharded index; delta "
            "segments must target the owning store"
        )
    base = store_format.load_index(path, with_segments=False)
    seg = quantize_segment(base, embeddings, token_doc_ids, n_docs)

    seg_root = os.path.join(path, "segments")
    os.makedirs(seg_root, exist_ok=True)
    seg_id = len(store_format.list_segment_dirs(path))
    seg_dir = os.path.join(seg_root, f"seg_{seg_id:05d}")
    os.makedirs(os.path.join(seg_dir, store_format.ARRAY_DIR), exist_ok=True)
    arrays = {}
    for name in store_format.SEGMENT_ARRAYS:
        rel = f"{store_format.ARRAY_DIR}/{name}.bin"
        meta = store_format._write_array(
            os.path.join(seg_dir, rel), np.asarray(getattr(seg, name))
        )
        arrays[name] = store_format._entry(rel, meta)
    store_format._write_manifest(seg_dir, {
        "format": store_format.FORMAT_NAME,
        "version": store_format.FORMAT_VERSION,
        "kind": store_format.KIND_SEGMENT,
        "static": {
            "dim": seg.dim, "nbits": seg.nbits, "cap": seg.cap,
            "n_docs": seg.n_docs, "n_tokens": seg.n_tokens,
        },
        "arrays": arrays,
    })
    obs.observe("store_add_documents_seconds", time.perf_counter() - t0)
    obs.count("store_documents_added_total", n_docs)
    return seg_dir


def load_segmented(
    base: WarpIndex, seg_dirs: list[str], *, mmap: bool = True,
    quarantine: bool = False,
) -> SegmentedWarpIndex:
    """Stitch a base index + delta-segment directories into one searchable
    view; centroid/codec arrays are shared with the base, not copied.

    With ``quarantine=True`` a segment that fails to load (checksum
    mismatch, truncation, unreadable manifest) is *skipped* instead of
    raising: its name is recorded in ``.quarantined``, a doc-id gap is
    left so healthy later segments keep their global ids (when the
    segment's manifest is still readable), and the result serves base +
    healthy deltas. The degradation is observable: a warning, the
    ``store_segments_quarantined_total`` counter, and the server's
    ``health()`` report all carry it.
    """
    deltas = []
    doc_starts = [0]
    quarantined = []
    total = base.n_docs
    for seg_dir in seg_dirs:
        try:
            manifest, arrays = store_format.load_segment_arrays(
                seg_dir, mmap=mmap
            )
        except (StoreCorruption, fault.InjectedFault) as e:
            if not quarantine:
                raise
            quarantined.append(os.path.basename(seg_dir))
            warnings.warn(
                f"quarantined corrupt delta segment {seg_dir}: {e}",
                stacklevel=2,
            )
            obs.count("store_segments_quarantined_total")
            try:  # keep later segments' global doc ids stable if we can
                total += int(
                    store_format.read_manifest(seg_dir)["static"]["n_docs"]
                )
            except Exception:
                pass  # unknowable size: ids after this point shift
            continue
        static = manifest["static"]
        deltas.append(WarpIndex(
            centroids=base.centroids,
            bucket_weights=base.bucket_weights,
            bucket_cutoffs=base.bucket_cutoffs,
            **arrays,
            dim=int(static["dim"]),
            nbits=int(static["nbits"]),
            cap=int(static["cap"]),
            n_docs=int(static["n_docs"]),
            n_tokens=int(static["n_tokens"]),
        ))
        doc_starts.append(total)
        total += deltas[-1].n_docs
    return SegmentedWarpIndex(
        base=base, deltas=tuple(deltas), doc_starts=tuple(doc_starts),
        quarantined=tuple(quarantined),
    )


def delta_stats(path: str) -> dict:
    """Host-side delta accumulation statistics of the store at ``path``,
    read from manifests only (no array loads) — the inputs a
    compaction-trigger policy (``serving.admission.CompactionPolicy``)
    thresholds on.

    Returns ``n_delta_segments``, ``base_tokens`` / ``delta_tokens`` /
    ``base_docs`` / ``delta_docs``, and ``delta_token_frac`` =
    delta_tokens / (base + delta tokens) (0.0 on an empty store).
    """
    manifest = store_format.read_manifest(path)
    static = manifest.get("static", {})
    base_tokens = int(static.get("n_tokens", static.get("n_tokens_total", 0)))
    base_docs = int(static.get("n_docs", 0))
    delta_tokens = delta_docs = 0
    seg_dirs = store_format.list_segment_dirs(path)
    for seg_dir in seg_dirs:
        seg_static = store_format.read_manifest(seg_dir)["static"]
        delta_tokens += int(seg_static["n_tokens"])
        delta_docs += int(seg_static["n_docs"])
    total = base_tokens + delta_tokens
    frac = (delta_tokens / total) if total else 0.0
    obs.gauge("store_delta_segments", len(seg_dirs))
    obs.gauge("store_delta_tokens", delta_tokens)
    obs.gauge("store_delta_token_frac", frac)
    return {
        "n_delta_segments": len(seg_dirs),
        "base_tokens": base_tokens,
        "delta_tokens": delta_tokens,
        "base_docs": base_docs,
        "delta_docs": delta_docs,
        "delta_token_frac": frac,
    }


# ---------------------------------------------------------------------------
# deletes: tombstone-until-next-compact
# ---------------------------------------------------------------------------

TOMBSTONES_FILE = "tombstones.json"


def read_tombstones(path: str) -> tuple[int, ...]:
    """Sorted global doc ids tombstoned at the store ``path`` (empty when
    none). Loading stays tombstone-agnostic — serving layers turn this set
    into a ``DocFilter.tombstones`` view per request; ``compact()`` is
    what physically drops the rows."""
    p = os.path.join(path, TOMBSTONES_FILE)
    if not os.path.exists(p):
        return ()
    import json

    with open(p) as f:
        data = json.load(f)
    return tuple(sorted({int(i) for i in data.get("deleted", ())}))


def delete_documents(path: str, doc_ids) -> tuple[int, ...]:
    """Tombstone global doc ids at the store ``path``; returns the full
    (merged, sorted) tombstone set.

    Deletion is logical until the next ``compact()``: the ids are appended
    to ``tombstones.json`` (atomic tmp + rename, like the manifest) and it
    is the caller's job to exclude them at query time
    (``DocFilter.tombstones(read_tombstones(path), n_docs)``). Compaction
    rewrites the store without the tombstoned rows — their doc ids are
    never reused, so surviving documents keep their global ids (doc-id
    gaps, exactly like a quarantined segment) — and the fresh directory
    carries no ``tombstones.json``.
    """
    import json

    store_format.read_manifest(path)  # raises on a non-store path
    existing = set(read_tombstones(path))
    merged = existing | {int(i) for i in doc_ids}
    out = tuple(sorted(merged))
    tmp = os.path.join(path, TOMBSTONES_FILE + ".tmp")
    with open(tmp, "w") as f:
        json.dump({"deleted": list(out)}, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(path, TOMBSTONES_FILE))
    obs.count("store_documents_deleted_total", len(merged) - len(existing))
    obs.gauge("store_tombstones", len(out))
    return out


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config", "query_batch"))
def segmented_probe_cids(
    centroids: jax.Array,
    combined_sizes: jax.Array,
    q: jax.Array,
    qmask: jax.Array,
    config: WarpSearchConfig,
    query_batch: bool = False,
) -> jax.Array:
    """Stage-1 probe centroid ids alone, for adaptive bucket selection.

    Runs the same ``warp_select`` the segmented search body runs — frozen
    base centroids, COMBINED cluster sizes — so the returned
    ``probe_cids`` (i32[Q, nprobe]; leading [B] with ``query_batch``) name
    exactly the clusters the search will expand into per-segment worklist
    runs. The dispatcher gathers precomputed combined per-cluster tile
    counts at these ids to size the worklist bucket on the host.
    """

    def one(q_i, m_i):
        return warp_select(
            q_i,
            centroids,
            combined_sizes,
            nprobe=config.nprobe,
            t_prime=config.t_prime,
            k_impute=config.k_impute,
            qmask=m_i,
        ).probe_cids

    return jax.vmap(one)(q, qmask) if query_batch else one(q, qmask)


def _segmented_slot_doc_ids(
    segments, doc_starts, row0, nvalid, seg_ids, *, tile_c: int
) -> jax.Array:
    """Global doc id of every worklist slot: the owning segment's
    ``token_doc_ids`` row plus that segment's global doc-id offset.
    Invalid slots return an arbitrary (masked) id."""
    lane = jnp.arange(tile_c, dtype=jnp.int32)
    pos = row0[:, None] + lane[None, :]  # [W, tile_c] segment-local
    out = jnp.zeros(pos.shape, jnp.int32)
    for s, (sub, start) in enumerate(zip(segments, doc_starts)):
        n_s = sub.token_doc_ids.shape[0]
        if n_s == 0:
            continue
        pos_s = jnp.clip(pos, 0, n_s - 1)
        ids = sub.token_doc_ids[pos_s].astype(jnp.int32) + jnp.int32(start)
        out = jnp.where((seg_ids == s)[:, None], ids, out)
    return out.reshape(-1)


def make_segmented_search_fn(
    seg: SegmentedWarpIndex, config: WarpSearchConfig, *, query_batch: bool,
    with_filter: bool = False,
):
    """Compile the staged pipeline over base + deltas.

    One shared ``warp_select`` over the frozen centroids with COMBINED
    cluster sizes (global t' crossing -> global m_i), then stage 2+3 in
    the config's layout — per-segment dense grids merged with doc-id
    offsets, or one flat segmented tile worklist reduced globally (see
    the module docstring) — ``config`` must be resolved (concrete
    t'/k_impute/executor; ``worklist_tiles`` when ragged).

    With ``with_filter`` the returned callable takes a fourth argument:
    the ``core.docfilter.resolve_segmented`` triple for this index. The
    dense path threads each segment's LOCAL ``FilterView`` into its
    ``score_and_reduce``; the ragged path zeroes per-(segment, cluster)
    worklist runs with no surviving tokens and masks the GLOBAL survivor
    bitmap inside the single ``two_stage_reduce``. Either way the filter
    is a runtime operand (one compiled program per geometry, any filter).
    """
    doc_starts = seg.doc_starts
    combined_sizes = seg.combined_cluster_sizes()
    cfg = config
    if cfg.layout == "ragged":
        return _make_segmented_ragged_fn(
            seg, cfg, query_batch=query_batch, with_filter=with_filter
        )

    def single(segments, sizes, q, qmask, fvs=None):
        sel = warp_select(
            q,
            segments[0].centroids,
            sizes,
            nprobe=cfg.nprobe,
            t_prime=cfg.t_prime,
            k_impute=cfg.k_impute,
            qmask=qmask,
        )
        scores_l, docs_l = [], []
        for i, (sub, start) in enumerate(zip(segments, doc_starts)):
            if sub.cap == 0 or sub.n_tokens == 0:
                continue  # token-less segment: no candidates to score
            # A small delta may hold fewer candidate slots than k.
            k_sub = max(1, min(cfg.k, q.shape[0] * cfg.nprobe * sub.cap))
            r = engine.score_and_reduce(
                sub, q, qmask, sel.probe_scores, sel.probe_cids, sel.mse,
                dataclasses.replace(cfg, k=k_sub),
                dfilter=fvs[i] if fvs is not None else None,
            )
            scores_l.append(r.scores)
            docs_l.append(jnp.where(r.doc_ids >= 0, r.doc_ids + start, -1))
        all_scores = jnp.concatenate(scores_l)
        all_docs = jnp.concatenate(docs_l)
        if all_scores.shape[0] < cfg.k:  # degenerate tiny-corpus guard
            pad = cfg.k - all_scores.shape[0]
            all_scores = jnp.pad(all_scores, (0, pad), constant_values=-jnp.inf)
            all_docs = jnp.pad(all_docs, (0, pad), constant_values=-1)
        top_scores, top_idx = jax.lax.top_k(all_scores, cfg.k)
        top_docs = jnp.where(
            jnp.isfinite(top_scores), all_docs[top_idx], jnp.int32(-1)
        )
        return TopKResult(scores=top_scores, doc_ids=top_docs)

    if with_filter:
        if query_batch:
            body = lambda segments, sizes, q, qmask, fvs: jax.vmap(
                lambda qq, mm: single(segments, sizes, qq, mm, fvs)
            )(q, qmask)
        else:
            body = single
        compiled = jax.jit(body)

        def run_filtered(index: SegmentedWarpIndex, q, qmask, resolved):
            _, seg_views, _ = resolved
            return compiled(index.segments, combined_sizes, q, qmask,
                            tuple(seg_views))

        return run_filtered

    if query_batch:
        body = lambda segments, sizes, q, qmask: jax.vmap(
            lambda qq, mm: single(segments, sizes, qq, mm)
        )(q, qmask)
    else:
        body = single
    compiled = jax.jit(body)

    def run(index: SegmentedWarpIndex, q, qmask):
        return compiled(index.segments, combined_sizes, q, qmask)

    return run


def _make_segmented_ragged_fn(
    seg: SegmentedWarpIndex, cfg: WarpSearchConfig, *, query_batch: bool,
    with_filter: bool = False,
):
    """Ragged stage 2+3 over base + deltas: one flat segmented worklist.

    Each probed cluster is expanded into its per-segment CSR runs (the
    probe axis becomes ``nprobe * n_active_segments``, empty runs
    contribute no tiles), scored in one pass, doc ids globalized per slot,
    and reduced by a single ``two_stage_reduce`` — no per-segment merge.

    With ``with_filter`` the worklist drops (segment, cluster) runs with
    zero surviving tokens and the reduction masks the global survivor
    bitmap (both runtime operands; exactness per ``core/docfilter.py``).
    """
    if cfg.worklist_tiles is None:
        raise ValueError(
            "segmented layout='ragged' needs a resolved worklist bound "
            "(worklist_tiles); plan through Retriever.plan"
        )
    combined_sizes = seg.combined_cluster_sizes()
    # Token-less segments contribute no candidates and would break the
    # per-segment gathers; the active set (and its doc-id offsets) is
    # static plan-time structure.
    active_ids = tuple(
        i for i, s in enumerate(seg.segments) if s.n_tokens > 0
    )
    active_starts = tuple(seg.doc_starts[i] for i in active_ids)
    base = seg.base
    tile = ops.resolve_tile_c(seg.cap, cfg.tile_c, layout="ragged")
    n_docs_total = seg.n_docs
    nprobe = cfg.nprobe

    def single(segments, sizes, q, qmask, fv=None):
        qm = q.shape[0]
        n_seg = len(segments)
        sel = warp_select(
            q,
            segments[0].centroids,
            sizes,
            nprobe=nprobe,
            t_prime=cfg.t_prime,
            k_impute=cfg.k_impute,
            qmask=qmask,
        )
        # Per-probe segment runs: [Q, P] cluster probes -> [Q, P * S]
        # (starts are segment-local CSR rows; the seg tag picks the array).
        starts = jnp.stack(
            [s.cluster_offsets[sel.probe_cids] for s in segments], axis=-1
        ).astype(jnp.int32)  # [Q, P, S]
        run_sizes = jnp.stack(
            [s.cluster_sizes[sel.probe_cids] for s in segments], axis=-1
        ).astype(jnp.int32)
        # Masked query tokens emit no worklist runs (their slots are
        # dropped by the qmask filter below anyway) — mirrors the
        # suppression in ``engine.score_and_reduce`` so demand tracks
        # active tokens on the segmented path too.
        run_sizes = jnp.where(qmask[:, None, None], run_sizes, 0)
        if fv is not None:
            # Filter pushdown: a (segment, cluster) run with zero surviving
            # tokens contributes no tiles — worklist demand (and the
            # adaptive rung upstream) tracks survivors only.
            live = jnp.moveaxis(
                fv.cluster_live[:, sel.probe_cids], 0, -1
            )  # [Q, P, S]
            run_sizes = jnp.where(live, run_sizes, 0)
        seg_ids = jnp.broadcast_to(
            jnp.arange(n_seg, dtype=jnp.int32), (qm, nprobe, n_seg)
        )
        pscores = jnp.broadcast_to(
            sel.probe_scores[..., None], (qm, nprobe, n_seg)
        )
        wl = build_tile_worklist(
            starts.reshape(qm, -1),
            run_sizes.reshape(qm, -1),
            pscores.reshape(qm, -1),
            seg=seg_ids.reshape(qm, -1),
            tile_c=tile,
            tiles_per_qtoken=cfg.worklist_tiles,
        )
        qtok_slot = jnp.repeat(wl.qtok, tile)
        packed_list = tuple(s.packed_codes for s in segments)
        v = q[:, :, None] * segments[0].bucket_weights[None, None, :]
        if cfg.gather == "fused":
            scores = ops.segmented_ragged_fused_gather_selective_sum(
                packed_list, wl.row0, wl.nvalid, wl.seg, wl.qtok, wl.pscore,
                v, nbits=base.nbits, dim=base.dim, tile_c=tile,
                use_kernel=cfg.wants_kernel, buffering=cfg.buffering,
            )
            lane = jnp.arange(tile, dtype=jnp.int32)
            slot_valid = (lane[None, :] < wl.nvalid[:, None]).reshape(-1)
        else:
            codes, slot_valid = ref.segmented_ragged_gather_codes(
                packed_list, wl.row0, wl.nvalid, wl.seg, tile_c=tile
            )
            res = ops.ragged_selective_sum(
                codes, qtok_slot, v,
                nbits=base.nbits, dim=base.dim, impl=cfg.sum_impl,
            )
            scores = jnp.where(
                slot_valid, res + jnp.repeat(wl.pscore, tile), 0.0
            )
        doc = _segmented_slot_doc_ids(
            segments, active_starts, wl.row0, wl.nvalid, wl.seg, tile_c=tile
        )
        valid = slot_valid & qmask[qtok_slot]
        return two_stage_reduce(
            doc,
            qtok_slot,
            scores,
            valid,
            sel.mse,
            fv.doc_mask if fv is not None else None,
            q_max=qm,
            k=cfg.k,
            n_docs=n_docs_total or None,
            pad_to_k=True,
        )

    if with_filter:
        if query_batch:
            body = lambda segments, sizes, q, qmask, fv: jax.vmap(
                lambda qq, mm: single(segments, sizes, qq, mm, fv)
            )(q, qmask)
        else:
            body = single
        compiled = jax.jit(body)

        def run_filtered(index: SegmentedWarpIndex, q, qmask, resolved):
            active = tuple(index.segments[i] for i in active_ids)
            global_view, _, per_segment_live = resolved
            fv = FilterView(
                doc_mask=global_view.doc_mask,
                cluster_live=jnp.asarray(
                    np.stack([per_segment_live[i] for i in active_ids])
                ),
            )
            return compiled(active, combined_sizes, q, qmask, fv)

        return run_filtered

    if query_batch:
        body = lambda segments, sizes, q, qmask: jax.vmap(
            lambda qq, mm: single(segments, sizes, qq, mm)
        )(q, qmask)
    else:
        body = single
    compiled = jax.jit(body)

    def run(index: SegmentedWarpIndex, q, qmask):
        active = tuple(index.segments[i] for i in active_ids)
        return compiled(active, combined_sizes, q, qmask)

    return run


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------


def compact(path: str) -> str:
    """Fold every delta segment back into a fresh single-segment base.

    Centroids and codec tables stay frozen (compaction re-lays-out, it does
    not re-train); within each cluster, tokens keep segment order (base
    first, then deltas in append order) and doc ids are rebased to global.
    The directory is replaced near-atomically: the new index is written
    beside it, then swapped in; open mmaps of the old files stay valid
    (POSIX unlink semantics) until their holders drop them — which is what
    lets a serving process ``reload()`` with zero downtime. A pid lockfile
    (``.compact-lock``) marks the swap as writer-owned: concurrent
    ``compact`` calls are rejected, and readers never run recovery against
    a live writer (a read landing inside the rename window sees a
    transient FileNotFoundError and should retry). A crash inside the
    window leaves ``.compact-tmp``/``.compact-old`` siblings plus a stale
    lock that the next ``compact``/``load_index`` repairs
    (``format.recover_interrupted_compact``).
    """
    lock = store_format.compact_lock_path(path)
    try:
        fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
    except FileExistsError:
        if store_format._lock_holder_alive(lock):
            raise RuntimeError(
                f"another compact() is already running on {path} "
                f"(lockfile {lock})"
            ) from None
        os.remove(lock)  # stale: crashed writer; take over
        obs.count("store_lock_takeovers_total")
        fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
    with os.fdopen(fd, "w") as f:
        f.write(str(os.getpid()))
    try:
        t0 = time.perf_counter()
        with obs.span("store_compact", store=path):
            out = _compact_locked(path)
        obs.observe("store_compact_seconds", time.perf_counter() - t0)
        return out
    finally:
        if os.path.exists(lock):
            os.remove(lock)


def _compact_locked(path: str) -> str:
    store_format.recover_interrupted_compact(path)
    manifest = store_format.read_manifest(path)
    seg = store_format.load_index(path, mmap=True)
    tomb_ids = read_tombstones(path)
    if isinstance(seg, WarpIndex):
        if not tomb_ids:
            return path  # no deltas, no tombstones; already compact
        # Tombstones on a delta-less store still force a rewrite (that is
        # what clears them); fold the base through the segment loop below.
        seg = SegmentedWarpIndex(base=seg, deltas=(), doc_starts=(0,))
    if not isinstance(seg, SegmentedWarpIndex):
        raise NotImplementedError(f"cannot compact kind={manifest['kind']!r}")
    # ``store.compact_step`` checkpoints mark every distinct on-disk state
    # of the swap protocol, in order — the kill-point tests interrupt at
    # each and assert ``recover_interrupted_compact`` lands on exactly the
    # old or the new store, never a hybrid.
    fault.check("store.compact_step", step="load", store=path)

    base = seg.base
    c = base.n_centroids
    n_docs_bound = seg.n_docs
    # Tombstoned rows are dropped during the rewrite; surviving documents
    # keep their global ids (the bound stays, deleted ids become gaps) so
    # post-compact results are bit-identical to tombstone-filtered
    # pre-compact results. The fresh directory carries no tombstones.json.
    tomb = np.zeros((n_docs_bound,), dtype=bool)
    for t in tomb_ids:
        if 0 <= t < n_docs_bound:
            tomb[t] = True
    if tomb.any():
        sizes = np.zeros((c,), np.int64)
        for sub, start in zip(seg.segments, seg.doc_starts):
            keep_local = ~tomb[start : start + sub.n_docs]
            sizes += cluster_survivor_counts(
                keep_local, sub.token_doc_ids, sub.cluster_offsets
            )
        sizes = sizes.astype(np.int64)
    else:
        sizes = np.asarray(seg.combined_cluster_sizes(), np.int64)
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    n_tokens = int(sizes.sum())
    pb = quantization.packed_bytes(base.dim, base.nbits)

    # The merged O(N) arrays are memmap-written into the tmp store (the
    # builder's pattern), segment slices copied range-by-range, so
    # compaction never holds the index in host RAM — it stays usable on
    # exactly the larger-than-memory corpora the store exists for.
    tmp = path.rstrip("/\\") + store_format.COMPACT_TMP_SUFFIX
    old = path.rstrip("/\\") + store_format.COMPACT_OLD_SUFFIX
    store_format._prepare_dir(tmp, overwrite=True)
    arr_dir = os.path.join(tmp, store_format.ARRAY_DIR)
    packed = np.memmap(
        os.path.join(arr_dir, "packed_codes.bin"),
        dtype=np.uint8, mode="w+", shape=(n_tokens, pb),
    )
    doc_ids = np.memmap(
        os.path.join(arr_dir, "token_doc_ids.bin"),
        dtype=np.int32, mode="w+", shape=(n_tokens,),
    )
    fill = np.zeros((c,), np.int64)
    step = 1 << 18
    drop_rows = tomb.any()
    for sub, start in zip(seg.segments, seg.doc_starts):
        sub_sizes = np.asarray(sub.cluster_sizes, np.int64)
        sub_offsets = np.asarray(sub.cluster_offsets, np.int64)
        # Chunk-local destination math: everything here is O(step), so
        # compaction memory stays bounded regardless of corpus size.
        for lo in range(0, sub.n_tokens, step):
            hi = min(sub.n_tokens, lo + step)
            pos = np.arange(lo, hi, dtype=np.int64)
            # Owning cluster of CSR position p: last offset <= p ('right'
            # handles empty clusters whose offsets collapse).
            cluster_of = np.searchsorted(sub_offsets, pos, side="right") - 1
            gids = (
                np.asarray(sub.token_doc_ids[lo:hi], np.int64) + int(start)
            )
            if drop_rows:
                # Kept-rank destination math: each kept row lands at its
                # cluster's base offset + rows already written (previous
                # chunks/segments, ``fill``) + its kept-rank within this
                # chunk. Tombstoned rows are simply never written.
                keep = ~tomb[np.clip(gids, 0, n_docs_bound - 1)]
                ck = np.cumsum(keep)
                _, first_idx, inv = np.unique(
                    cluster_of, return_index=True, return_inverse=True
                )
                prior = ck[first_idx] - keep[first_idx]
                rank = ck - 1 - prior[inv]
                d = offsets[cluster_of].astype(np.int64) + fill[cluster_of] + rank
                packed[d[keep]] = np.asarray(sub.packed_codes[lo:hi])[keep]
                doc_ids[d[keep]] = gids[keep].astype(np.int32)
                fill += np.bincount(cluster_of[keep], minlength=c)
            else:
                within = pos - sub_offsets[cluster_of]
                d = offsets[cluster_of].astype(np.int64) + fill[cluster_of] + within
                packed[d] = sub.packed_codes[lo:hi]
                doc_ids[d] = gids.astype(np.int32)
        if not drop_rows:
            fill += sub_sizes
    packed.flush()
    doc_ids.flush()
    del packed, doc_ids
    fault.check("store.compact_step", step="arrays", store=path)

    from repro.store.builder import _finalize_store  # no import cycle: builder
    # depends only on core + format

    _finalize_store(
        tmp,
        np.asarray(base.centroids),
        offsets,
        sizes.astype(np.int32),
        np.asarray(base.bucket_weights),
        np.asarray(base.bucket_cutoffs),
        dim=base.dim,
        nbits=base.nbits,
        cap=int(sizes.max()),
        n_docs=seg.n_docs,
        n_tokens=n_tokens,
        build_config=manifest.get("build_config"),
    )
    fault.check("store.compact_step", step="finalized", store=path)
    # A stale .compact-old can only be the leftover of a crash after a
    # completed swap (path intact) — clear it so the rename below works.
    shutil.rmtree(old, ignore_errors=True)
    os.rename(path, old)
    fault.check("store.compact_step", step="old_aside", store=path)
    os.rename(tmp, path)
    fault.check("store.compact_step", step="promoted", store=path)
    shutil.rmtree(old)
    return path
