"""End-to-end WARP retrieval (paper §4.2): one jit'd search step.

Pipeline per query: WARP_SELECT (centroid matmul + top-nprobe + missing
similarity) -> static-capacity CSR gather of packed codes -> implicit
decompression selective-sum (Pallas kernel or jnp ref) -> two-stage
reduction -> top-k.

All shapes are static. With ``layout="dense"`` the candidate set is
[Q, nprobe, cap] where ``cap`` is the index's max cluster size, masked by
true cluster sizes — the jit/TPU replacement for the paper's
pointer-chasing inverted lists. With ``layout="ragged"`` the probes are
flattened into a statically-bounded tile worklist (``core.worklist``) and
every downstream stage — gather, selective sum, the reduction's sort —
runs over flat ``[n_slots]`` arrays sized by the real candidates instead
of ``nprobe * cap`` padding (closer to the paper's per-stride iteration,
and the faster layout under cluster-size skew).

The exported stage functions (``warp_select`` -> ``score_probed_clusters``
-> ``score_and_reduce``/``two_stage_reduce``) are the single source of
truth for the pipeline: ``core.retriever.Retriever`` plans over them, and
``core.distributed`` runs the same stages per shard under ``shard_map``.
``search`` / ``search_batch`` remain as thin convenience wrappers.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.docfilter import DocFilter, FilterView, resolve_local
from repro.core.reduction import TopKResult, two_stage_reduce
from repro.core.types import WarpIndex, WarpSearchConfig
from repro.core.warpselect import SCOPE_SELECT, warp_select
from repro.core.worklist import (
    bucket_ladder,
    build_tile_worklist,
    filtered_probe_sizes,
    worklist_bound,
    worklist_slot_positions,
)
from repro.kernels import ops

__all__ = [
    "STAGE_SCOPES",
    "search",
    "search_batch",
    "gather_candidates",
    "gather_doc_ids",
    "resolve_config",
    "resolve_layout_fields",
    "resolve_tile_fields",
    "score_probed_clusters",
    "ragged_flat_candidates",
    "score_candidates",
    "reduce_candidates",
    "score_and_reduce",
    "select_probes",
    "finish_from_probes",
]

# Named scopes of the three pipeline stages. Every op a stage emits
# carries its scope in the HLO ``op_name``, so a device trace of the one
# compiled retrieve program splits its time by stage. The ``warp.``
# prefix keeps them apart from primitive names (``reduce`` is one).
SCOPE_GATHER_SCORE = "warp.gather_score"
SCOPE_REDUCE = "warp.reduce"
STAGE_SCOPES = (SCOPE_SELECT, SCOPE_GATHER_SCORE, SCOPE_REDUCE)


def resolve_tile_fields(
    config: WarpSearchConfig,
    *,
    cap: int,
    layout: str,
    n_tokens: int | None = None,
    nbits: int | None = None,
    dim: int | None = None,
) -> WarpSearchConfig:
    """Concretize the candidate tile: write the resolved ``tile_c`` (with
    its provenance in ``tile_source``) and the concrete DMA ``buffering``
    into the config, so plan-time and run-time tiling cannot diverge and
    jit cache keys name the tile that actually runs.

    With the full index geometry the autotune table
    (``kernels/autotune.py``) is consulted first; an explicit ``tile_c``
    always wins, the analytic heuristic backstops. Re-resolving an
    already-resolved config (``tile_source`` set) is a no-op — the
    recorded provenance survives, instead of degrading to "config" because
    the previous resolution made ``tile_c`` concrete.
    """
    if config.tile_source is not None and config.tile_c is not None:
        return config
    choice = ops.resolve_tile_choice(
        cap,
        config.tile_c,
        layout=layout,
        n_tokens=n_tokens,
        nbits=nbits,
        dim=dim,
        buffering=config.buffering,
    )
    return dataclasses.replace(
        config,
        tile_c=choice.tile_c,
        tile_source=choice.source,
        buffering=choice.buffering,
    )


def resolve_layout_fields(
    config: WarpSearchConfig,
    cluster_sizes,
    cap: int,
    *,
    n_tokens: int | None = None,
    nbits: int | None = None,
    dim: int | None = None,
) -> WarpSearchConfig:
    """Concretize ``layout="auto"``, the candidate tile (autotune table or
    heuristic; ``resolve_tile_fields``), the ragged worklist bound, and the
    adaptive bucket ladder.

    ``cluster_sizes`` may be [C] or a sharded [S, C] stack (the bound
    covers every shard). "auto" picks by measured padding waste: ragged
    wins when the worklist slot bound (sum of the nprobe largest clusters'
    tile counts, times tile_c) undercuts the dense ``nprobe * cap`` slots
    per query token. A ragged resolution also records the bucket ladder
    (``core.worklist.bucket_ladder``) whose top rung is the static bound;
    ``Retriever`` plans dispatch each retrieve to the smallest rung that
    fits the actual probe set. Shared by the local and sharded resolvers
    so the two paths cannot drift. The geometry kwargs
    (``n_tokens``/``nbits``/``dim``) enable the autotune lookup; without
    them tile resolution is purely explicit-override-or-heuristic.
    """
    geo = dict(n_tokens=n_tokens, nbits=nbits, dim=dim)
    if config.layout == "dense":
        config = resolve_tile_fields(config, cap=cap, layout="dense", **geo)
        if config.worklist_tiles is None and config.worklist_buckets is None:
            return config
        return dataclasses.replace(
            config, worklist_tiles=None, worklist_buckets=None
        )
    ragged = resolve_tile_fields(config, cap=cap, layout="ragged", **geo)
    tile = ragged.tile_c
    bound = worklist_bound(cluster_sizes, config.nprobe, tile)
    layout = config.layout
    if layout == "auto":
        layout = "ragged" if bound * tile < config.nprobe * cap else "dense"
    if layout == "dense":
        config = resolve_tile_fields(config, cap=cap, layout="dense", **geo)
        return dataclasses.replace(
            config, layout="dense", worklist_tiles=None, worklist_buckets=None
        )
    return dataclasses.replace(
        ragged,
        layout="ragged",
        worklist_tiles=bound,
        worklist_buckets=bucket_ladder(bound),
    )


def resolve_config(index: WarpIndex, config: WarpSearchConfig) -> WarpSearchConfig:
    """Materialize data-dependent defaults to static values.

    t' and k_impute become concrete ints derived from the index geometry;
    executor="auto" is concretized against the active backend (Pallas
    kernels on TPU, jnp references elsewhere) and layout="auto" against the
    index's cluster-size statistics, so jit cache keys — the config is a
    static argument — name the actual strategy that ran.
    """
    if index.n_tokens == 0:
        raise ValueError(
            "index has n_tokens == 0 — nothing to retrieve, and the "
            "static-capacity CSR gather has no rows to clamp into. Build "
            "or load a non-empty index before planning a search."
        )
    config = dataclasses.replace(
        config,
        t_prime=config.resolved_t_prime(index.n_tokens),
        k_impute=config.resolved_k_impute(index.n_centroids),
        executor=config.resolved_executor(ops.on_tpu()),
    )
    geo = dict(n_tokens=index.n_tokens, nbits=index.nbits, dim=index.dim)
    if (
        config.layout == "dense"
        and config.worklist_tiles is None
        and config.worklist_buckets is None
    ):
        # Skip the host-side cluster-size stats (and stay agnostic to
        # index kinds without a flat cluster_sizes array, e.g. segmented) —
        # but still concretize the tile choice.
        return resolve_tile_fields(config, cap=index.cap, layout="dense", **geo)
    return resolve_layout_fields(config, index.cluster_sizes, index.cap, **geo)


def _csr_positions(index: WarpIndex, probe_cids: jax.Array):
    """Static-capacity CSR slot positions: probe_cids i32[..., P] ->
    (pos i32[..., P, cap] clamped into [0, n_tokens), valid bool[..., P, cap]).

    Clamp floor 0: on an empty index ``n_tokens - 1`` is -1, and a bare
    ``minimum`` would turn every slot into a wraparound gather. Plan time
    rejects n_tokens == 0 with a directed error; the clamp keeps the stage
    itself well-defined for callers that bypass planning."""
    cap = index.cap
    starts = index.cluster_offsets[probe_cids]
    sizes = index.cluster_sizes[probe_cids]
    pos = starts[..., None] + jnp.arange(cap, dtype=jnp.int32)
    valid = jnp.arange(cap, dtype=jnp.int32) < sizes[..., None]
    return jnp.clip(pos, 0, max(0, index.n_tokens - 1)), valid


def gather_candidates(index: WarpIndex, probe_cids: jax.Array):
    """CSR gather with static capacity.

    probe_cids i32[Q, P] -> (packed u8[Q, P, cap, PB], doc_ids i32[Q, P, cap],
    valid bool[Q, P, cap]).
    """
    pos, valid = _csr_positions(index, probe_cids)
    return index.packed_codes[pos], index.token_doc_ids[pos], valid


def gather_doc_ids(index: WarpIndex, probe_cids: jax.Array):
    """Doc-id half of the CSR gather, for the fused scoring path.

    The fused kernel reads packed codes straight from the resident index,
    so only the (4-byte-per-token) doc ids still need an XLA gather.
    probe_cids i32[..., P] -> (doc_ids i32[..., P, cap], valid bool[..., P, cap]).
    """
    pos, valid = _csr_positions(index, probe_cids)
    return index.token_doc_ids[pos], valid


def _fused_score_probed(
    index: WarpIndex,
    q: jax.Array,
    probe_scores: jax.Array,
    probe_cids: jax.Array,
    config: WarpSearchConfig,
):
    """Single-pass scoring: no [Q, P, cap, PB] candidate tensor exists."""

    def one(q_i, scores_i, cids_i):
        v = q_i[None, :, None] * index.bucket_weights[None, None, :]
        cand = ops.fused_gather_selective_sum(
            index.packed_codes,
            index.cluster_offsets,
            index.cluster_sizes,
            cids_i[None],
            scores_i[None],
            v,
            nbits=index.nbits,
            dim=index.dim,
            cap=index.cap,
            n_tokens=index.n_tokens,
            use_kernel=config.wants_kernel,
            tile_c=config.tile_c,
            buffering=config.buffering,
        )[0]
        doc_ids, valid = gather_doc_ids(index, cids_i)
        return cand, doc_ids, valid

    if config.memory == "scan_qtokens":
        _, (cand, dids, valid) = jax.lax.scan(
            lambda c, x: (c, one(*x)), None, (q, probe_scores, probe_cids)
        )
        return cand, dids, valid

    v = q[:, :, None] * index.bucket_weights[None, None, :]  # [Q, D, 2^b]
    cand = ops.fused_gather_selective_sum(
        index.packed_codes,
        index.cluster_offsets,
        index.cluster_sizes,
        probe_cids,
        probe_scores,
        v,
        nbits=index.nbits,
        dim=index.dim,
        cap=index.cap,
        n_tokens=index.n_tokens,
        use_kernel=config.wants_kernel,
        tile_c=config.tile_c,
        buffering=config.buffering,
    )
    doc_ids, valid = gather_doc_ids(index, probe_cids)
    return cand, doc_ids, valid


def score_probed_clusters(
    index: WarpIndex,
    q: jax.Array,
    probe_scores: jax.Array,
    probe_cids: jax.Array,
    config: WarpSearchConfig,
):
    """Implicit decompression (Eq. 5) over the probed clusters.

    Returns (cand_scores f32[Q, P, cap], doc_ids i32[Q, P, cap],
    valid bool[Q, P, cap]). With ``memory="scan_qtokens"`` the gather +
    selective-sum runs one query token per scan step, bounding the live
    packed-code working set by a factor of Q. With ``gather="fused"`` the
    gather/decompress/score boundary collapses into the single-pass kernel
    path and invalid slots come back as exact 0 (dropped by the reduction's
    valid mask either way).
    """
    if config.gather == "fused":
        return _fused_score_probed(index, q, probe_scores, probe_cids, config)

    p, cap = config.nprobe, index.cap

    def one(q_i, scores_i, cids_i):
        packed, doc_ids, valid = gather_candidates(index, cids_i[None])
        v = q_i[None, :, None] * index.bucket_weights[None, None, :]
        res = ops.selective_sum(
            packed.reshape(1, p * cap, -1),
            v,
            nbits=index.nbits,
            dim=index.dim,
            use_kernel=config.wants_kernel,
            impl=config.sum_impl,
        ).reshape(1, p, cap)
        return (res + scores_i[None, :, None])[0], doc_ids[0], valid[0]

    if config.memory == "scan_qtokens":
        _, (cand, dids, valid) = jax.lax.scan(
            lambda c, x: (c, one(*x)), None, (q, probe_scores, probe_cids)
        )
        return cand, dids, valid

    qm = q.shape[0]
    packed, doc_ids, valid = gather_candidates(index, probe_cids)
    v = q[:, :, None] * index.bucket_weights[None, None, :]  # [Q, D, 2^b]
    res_scores = ops.selective_sum(
        packed.reshape(qm, p * cap, -1),
        v,
        nbits=index.nbits,
        dim=index.dim,
        use_kernel=config.wants_kernel,
        impl=config.sum_impl,
    ).reshape(qm, p, cap)
    return res_scores + probe_scores[..., None], doc_ids, valid


def ragged_flat_candidates(
    index: WarpIndex,
    q: jax.Array,
    probe_scores: jax.Array,
    probe_cids: jax.Array,
    config: WarpSearchConfig,
    probe_sizes: jax.Array | None = None,
):
    """Flat worklist-ordered candidates (layout="ragged", paper §4.4).

    Builds the tile worklist from the selected probes (``core.worklist``)
    and scores it in one pass — fused kernel or flat gather + reference —
    returning flat ``[n_slots]`` arrays (scores, doc_ids, qtok, valid)
    with ``n_slots = Q * worklist_tiles * tile_c``, worklist-padded slots
    invalid. No ``[Q, nprobe, cap]`` tensor exists on this path, and the
    downstream sort N shrinks from ``Q * nprobe * cap`` to the worklist
    bound (2–4x fewer entries at typical cluster-size skew).

    ``probe_sizes`` is the WARP_SELECT probe metadata
    (``WarpSelectOut.probe_sizes``); omitted, the sizes are re-gathered
    from the index.
    """
    tile = ops.resolve_tile_c(index.cap, config.tile_c, layout="ragged")
    bound = config.worklist_tiles
    if bound is None:
        raise ValueError(
            "layout='ragged' needs a resolved worklist bound "
            "(worklist_tiles); run the config through engine.resolve_config "
            "or Retriever.plan first"
        )
    starts = index.cluster_offsets[probe_cids].astype(jnp.int32)
    sizes = (
        probe_sizes
        if probe_sizes is not None
        else index.cluster_sizes[probe_cids]
    ).astype(jnp.int32)

    def one(starts_i, sizes_i, pscores_i, v_i):
        # [n, P] probes -> flat (scores, doc_ids, qtok, valid), n*bound*tile.
        wl = build_tile_worklist(
            starts_i, sizes_i, pscores_i, tile_c=tile, tiles_per_qtoken=bound
        )
        pos, slot_valid = worklist_slot_positions(
            wl, tile_c=tile, n_tokens=index.n_tokens
        )
        qtok_slot = jnp.repeat(wl.qtok, tile)
        if config.gather == "fused":
            scores = ops.ragged_fused_gather_selective_sum(
                index.packed_codes,
                wl.row0,
                wl.nvalid,
                wl.qtok,
                wl.pscore,
                v_i,
                nbits=index.nbits,
                dim=index.dim,
                tile_c=tile,
                n_tokens=index.n_tokens,
                use_kernel=config.wants_kernel,
                buffering=config.buffering,
            )
        else:
            packed = index.packed_codes[pos]  # flat [n_slots, PB] gather
            res = ops.ragged_selective_sum(
                packed, qtok_slot, v_i,
                nbits=index.nbits, dim=index.dim, impl=config.sum_impl,
            )
            scores = jnp.where(slot_valid, res + jnp.repeat(wl.pscore, tile), 0.0)
        return scores, index.token_doc_ids[pos], qtok_slot, slot_valid

    if config.memory == "scan_qtokens":
        qm = q.shape[0]

        def step(carry, x):
            q_i, st_i, sz_i, ps_i = x
            v_i = q_i[None, :, None] * index.bucket_weights[None, None, :]
            s, d, _, val = one(st_i[None], sz_i[None], ps_i[None], v_i)
            return carry, (s, d, val)

        _, (s, d, val) = jax.lax.scan(
            step, None, (q, starts, sizes, probe_scores)
        )
        qtok = jnp.repeat(jnp.arange(qm, dtype=jnp.int32), bound * tile)
        return s.reshape(-1), d.reshape(-1), qtok, val.reshape(-1)

    v = q[:, :, None] * index.bucket_weights[None, None, :]  # [Q, D, 2^b]
    return one(starts, sizes, probe_scores, v)


def score_candidates(
    index: WarpIndex,
    q: jax.Array,
    qmask: jax.Array,
    probe_scores: jax.Array,
    probe_cids: jax.Array,
    config: WarpSearchConfig,
    *,
    probe_sizes: jax.Array | None = None,
    dfilter: FilterView | None = None,
):
    """Stage 2 alone: implicit decompression over the probe set down to a
    flat candidate stream ``(doc_ids, qtok, scores, valid)``, each [N] —
    N = Q * worklist_tiles * tile_c ragged, Q * nprobe * cap dense.

    Candidates of masked query tokens come back invalid; on the ragged
    path their probe sizes are zeroed first so they also contribute no
    worklist tiles — top-k is unchanged (their candidates are dropped by
    the mask either way) while worklist demand (and the adaptive bucket
    the dispatcher picks) tracks the *active* token count instead of the
    padded query length.

    ``dfilter`` (a resolved ``FilterView``, see ``core/docfilter.py``)
    gets the same pushdown: probe runs over clusters with zero surviving
    tokens are zeroed before the worklist is built, so filtered search
    keeps the ragged win. Document-level exclusion happens downstream in
    ``reduce_candidates`` (the two-stage reduction masks filtered docs'
    totals to -inf), which is exact because imputation never depends on
    which candidates survive.

    Every op of the stage carries the ``warp.gather_score`` scope in its
    HLO ``op_name``, so a device trace attributes its time to this stage.
    """
    with jax.named_scope(SCOPE_GATHER_SCORE):
        qm = q.shape[0]
        if config.layout == "ragged":
            if probe_sizes is None:
                probe_sizes = index.cluster_sizes[probe_cids]
            probe_sizes = jnp.where(qmask[:, None], probe_sizes, 0)
            if dfilter is not None:
                probe_sizes = filtered_probe_sizes(
                    probe_sizes, probe_cids, dfilter.cluster_live
                )
            scores, doc_ids, qtok, valid = ragged_flat_candidates(
                index, q, probe_scores, probe_cids, config, probe_sizes
            )
            return doc_ids, qtok, scores, valid & qmask[qtok]

        p, cap = config.nprobe, index.cap
        cand_scores, doc_ids, valid = score_probed_clusters(
            index, q, probe_scores, probe_cids, config
        )
        valid = valid & qmask[:, None, None]
        qtok = jnp.broadcast_to(
            jnp.arange(qm, dtype=jnp.int32)[:, None, None], (qm, p, cap)
        )
        return (
            doc_ids.reshape(-1),
            qtok.reshape(-1),
            cand_scores.reshape(-1),
            valid.reshape(-1),
        )


def reduce_candidates(
    index: WarpIndex,
    doc_ids: jax.Array,
    qtok: jax.Array,
    scores: jax.Array,
    valid: jax.Array,
    mse: jax.Array,
    config: WarpSearchConfig,
    *,
    q_max: int,
    dfilter: FilterView | None = None,
) -> TopKResult:
    """Stage 3 alone: the two-stage reduction over a flat candidate
    stream. ``index.n_docs`` (shard-local on the distributed path) arms
    the reduction's int32-overflow fallback. The ragged worklist may
    bound fewer than ``k`` slots on skew-free tiny indexes, so that
    layout pads the reduction to k (all-invalid slots). ``dfilter``'s
    doc mask (local id space of THIS index) masks filtered documents to
    -inf before top-k — the exactness point of the filter pushdown. The
    dense stream is in [Q, nprobe, cap] order, so it goes in as one row
    per query token and the imputation fold is a broadcast. Its ops carry
    the ``warp.reduce`` scope."""
    with jax.named_scope(SCOPE_REDUCE):
        ragged = config.layout == "ragged"
        if not ragged:
            doc_ids, scores, valid = (
                a.reshape(q_max, -1) for a in (doc_ids, scores, valid)
            )
        return two_stage_reduce(
            doc_ids,
            qtok,
            scores,
            valid,
            mse,
            dfilter.doc_mask if dfilter is not None else None,
            q_max=q_max,
            k=config.k,
            n_docs=index.n_docs or None,
            pad_to_k=ragged,
        )


def score_and_reduce(
    index: WarpIndex,
    q: jax.Array,
    qmask: jax.Array,
    probe_scores: jax.Array,
    probe_cids: jax.Array,
    mse: jax.Array,
    config: WarpSearchConfig,
    *,
    probe_sizes: jax.Array | None = None,
    dfilter: FilterView | None = None,
) -> TopKResult:
    """Stages 2+3 of the pipeline: implicit decompression over the probe
    set, then the two-stage reduction to top-k — the composition of
    ``score_candidates`` and ``reduce_candidates`` (one op sequence; the
    sharded path runs the two halves around its cross-shard merge).

    ``mse`` is the per-query-token missing similarity estimate — locally
    imputed by ``warp_select`` on the single-device path, globally merged
    across shards on the distributed path.

    With ``layout="ragged"`` the candidates flow through the flat tile
    worklist (``ragged_flat_candidates``) straight into the reduction — no
    [Q, nprobe, cap] tensor, and a sort over the worklist bound instead of
    the padded capacity.

    ``dfilter`` is a resolved ``FilterView`` in THIS index's doc-id space
    (shard-local on the distributed path, segment-local on the dense
    segmented path): worklist pushdown in stage 2, -inf masking in
    stage 3.
    """
    doc_ids, qtok, scores, valid = score_candidates(
        index, q, qmask, probe_scores, probe_cids, config,
        probe_sizes=probe_sizes, dfilter=dfilter,
    )
    return reduce_candidates(
        index, doc_ids, qtok, scores, valid, mse, config, q_max=q.shape[0],
        dfilter=dfilter,
    )


@functools.partial(jax.jit, static_argnames=("config", "query_batch"))
def select_probes(index, q, qmask, config, query_batch: bool = False):
    """Stage 1 alone (WARP_SELECT), jit'd per config.

    ``Retriever``'s adaptive ragged dispatcher runs this first, picks the
    worklist bucket from the probe sizes on the host, then finishes with
    ``finish_from_probes`` compiled for that bucket — the probe set is
    computed once, not re-derived per rung. ``query_batch`` maps over a
    leading [B] query axis.
    """

    def one(q_i, m_i):
        return warp_select(
            q_i,
            index.centroids,
            index.cluster_sizes,
            nprobe=config.nprobe,
            t_prime=config.t_prime,
            k_impute=config.k_impute,
            qmask=m_i,
        )

    return jax.vmap(one)(q, qmask) if query_batch else one(q, qmask)


@functools.partial(jax.jit, static_argnames=("config", "query_batch"))
def finish_from_probes(
    index, q, qmask, sel, config, query_batch: bool = False, dfilter=None
) -> TopKResult:
    """Stages 2+3 from a precomputed WARP_SELECT output, jit'd per config.

    ``select_probes`` -> ``finish_from_probes`` composes to exactly
    ``_search_one`` (same stage functions, same order), so adaptive
    dispatch inherits the dense==ragged parity guarantees. ``dfilter`` is
    a runtime ``FilterView`` operand shared across the batch (queries in
    one dispatch see one filter).
    """

    def one(q_i, m_i, sel_i):
        return score_and_reduce(
            index, q_i, m_i, sel_i.probe_scores, sel_i.probe_cids, sel_i.mse,
            config, probe_sizes=sel_i.probe_sizes, dfilter=dfilter,
        )

    return jax.vmap(one)(q, qmask, sel) if query_batch else one(q, qmask, sel)


@functools.partial(jax.jit, static_argnames=("config",))
def _search_one(
    index: WarpIndex,
    q: jax.Array,
    qmask: jax.Array,
    config: WarpSearchConfig,
    dfilter: FilterView | None = None,
) -> TopKResult:
    sel = warp_select(
        q,
        index.centroids,
        index.cluster_sizes,
        nprobe=config.nprobe,
        t_prime=config.t_prime,
        k_impute=config.k_impute,
        qmask=qmask,
    )
    return score_and_reduce(
        index, q, qmask, sel.probe_scores, sel.probe_cids, sel.mse, config,
        probe_sizes=sel.probe_sizes, dfilter=dfilter,
    )


def _as_filter_view(dfilter, index) -> FilterView | None:
    """Accept either a ``DocFilter`` (resolved here against the index) or
    an already-resolved ``FilterView`` (passed through)."""
    if dfilter is None or isinstance(dfilter, FilterView):
        return dfilter
    if isinstance(dfilter, DocFilter):
        if dfilter.n_docs != index.n_docs:
            raise ValueError(
                f"DocFilter covers {dfilter.n_docs} docs but the index "
                f"holds {index.n_docs} — build the filter against this "
                "index's doc-id space"
            )
        return resolve_local(dfilter, index)
    raise TypeError(
        f"dfilter must be a DocFilter or FilterView, got {type(dfilter)!r}"
    )


def search(
    index: WarpIndex,
    q: jax.Array,
    qmask: jax.Array | None = None,
    config: WarpSearchConfig = WarpSearchConfig(),
    *,
    dfilter=None,
) -> TopKResult:
    """Single query: q f32[Q, D] (rows L2-normalized by caller or encoder).

    Convenience wrapper over the planned pipeline; equivalent to
    ``Retriever.from_index(index).retrieve(q, qmask, config=config)``.
    ``dfilter`` restricts retrieval to a ``DocFilter``'s survivors.
    """
    config = resolve_config(index, config)
    if qmask is None:
        qmask = jnp.ones((q.shape[0],), bool)
    fv = _as_filter_view(dfilter, index)
    return _search_one(index, jnp.asarray(q, jnp.float32), qmask, config, fv)


@functools.partial(jax.jit, static_argnames=("config",))
def _search_many(index, q, qmask, config, dfilter=None):
    return jax.vmap(
        lambda qq, mm: _search_one(index, qq, mm, config, dfilter)
    )(q, qmask)


def search_batch(
    index: WarpIndex,
    q: jax.Array,
    qmask: jax.Array | None = None,
    config: WarpSearchConfig = WarpSearchConfig(),
    *,
    dfilter=None,
) -> TopKResult:
    """Batched queries: q f32[B, Q, D] -> TopKResult with leading batch dim.

    Convenience wrapper; equivalent to ``Retriever.from_index(index)
    .retrieve_batch(q, qmask, config=config)``.
    """
    config = resolve_config(index, config)
    if qmask is None:
        qmask = jnp.ones(q.shape[:2], bool)
    fv = _as_filter_view(dfilter, index)
    return _search_many(index, jnp.asarray(q, jnp.float32), qmask, config, fv)
