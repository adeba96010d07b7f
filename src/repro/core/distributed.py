"""Distributed WARP: document-sharded indexes + shard_map search (DESIGN §5).

Real multi-vector deployments shard the *corpus by document*: every
document's tokens live entirely inside one shard, so token-level max and
document-level sum both stay local and the only cross-device traffic is the
final top-k merge — O(k · devices), independent of corpus size.

Imputation is globally aligned: each shard contributes its top-``k_impute``
(centroid score, cluster size) pairs; an all_gather + merged cumulative-size
threshold yields a single global m_i used by every shard, so cross-shard
score comparison is consistent (see DESIGN.md for why per-shard m_i would
bias the merge).

The per-shard body is NOT a private reimplementation of the engine: it runs
the same exported pipeline stages as the single-device path —
``warp_select`` (stage 1) -> ``impute_mse`` over the all-gathered per-shard
candidates (global m_i) -> ``score_and_reduce`` (stages 2+3, including the
``gather="fused"``/``executor`` strategies and the reduction's shard-local
``n_docs`` overflow guard) — followed by the O(k · devices) top-k merge.

The same code runs on 1 CPU device (tests) and on the (pod, data, model)
production mesh (dry-run): shard over the flattened data axes, replicate
over ``model``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import index as index_mod
from repro.core.docfilter import FilterView
from repro.core.engine import (  # noqa: F401  (score_* re-exported for stage-level callers)
    resolve_layout_fields,
    score_and_reduce,
    score_probed_clusters,
)
from repro.core.reduction import TopKResult
from repro.core.types import IndexBuildConfig, WarpIndex, WarpSearchConfig
from repro.core.warpselect import impute_mse, warp_select
from repro.kernels import ops
from repro.launch.mesh import make_mesh

__all__ = [
    "ShardedWarpIndex",
    "build_sharded_index",
    "stack_shards",
    "sharded_search",
    "make_sharded_search_fn",
    "sharded_probe_sizes",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedWarpIndex:
    """Per-shard WarpIndex arrays stacked on a leading shard axis.

    All shards are padded to identical geometry (n_centroids, n_tokens,
    cap) so the stack is rectangular; padding clusters have size 0 and
    padding tokens carry doc id ``local_docs`` (never surfaced: size-0
    clusters contribute no valid candidates even when probed).

    ``n_tokens_padded`` is the per-shard padded token count (the local CSR
    geometry); ``n_tokens_total`` is the TRUE corpus token count, which is
    what t' resolution must use — padding tokens are not retrievable mass.
    ``local_docs`` is the max shard-local document count (also the padding
    doc id), the bound the reduction's overflow guard needs.
    """

    centroids: jax.Array  # f32[S, C, D]
    packed_codes: jax.Array  # u8[S, N, PB]
    token_doc_ids: jax.Array  # i32[S, N] (shard-local ids)
    cluster_offsets: jax.Array  # i32[S, C+1]
    cluster_sizes: jax.Array  # i32[S, C]
    bucket_weights: jax.Array  # f32[S, 2^b]
    doc_start: jax.Array  # i32[S] global id of shard's first document

    dim: int = dataclasses.field(metadata=dict(static=True), default=128)
    nbits: int = dataclasses.field(metadata=dict(static=True), default=4)
    cap: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_docs: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_tokens_padded: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_tokens_total: int = dataclasses.field(metadata=dict(static=True), default=0)
    local_docs: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def n_shards(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_centroids(self) -> int:
        return self.centroids.shape[1]

    def resolved_n_tokens(self) -> int:
        """True corpus token count; pre-``n_tokens_total`` stacks fall back
        to the (over-counting) padded estimate."""
        return self.n_tokens_total or self.n_tokens_padded * self.n_shards


def build_sharded_index(
    embeddings: jax.Array,
    token_doc_ids: jax.Array,
    n_docs: int,
    n_shards: int,
    config: IndexBuildConfig = IndexBuildConfig(),
    *,
    mesh: jax.sharding.Mesh | None = None,
    shard_axes: tuple[str, ...] = ("data",),
) -> ShardedWarpIndex:
    """Partition docs into contiguous, token-balanced ranges; build one
    WarpIndex per shard; pad + place (see ``stack_shards``)."""
    emb = np.asarray(embeddings, np.float32)
    tdi = np.asarray(token_doc_ids, np.int32)
    n_tokens = emb.shape[0]

    # Token-balanced contiguous doc ranges.
    doc_tok_counts = np.bincount(tdi, minlength=n_docs)
    csum = np.concatenate([[0], np.cumsum(doc_tok_counts)])
    targets = np.linspace(0, n_tokens, n_shards + 1)
    bounds = np.searchsorted(csum, targets[1:-1], side="left")
    doc_bounds = np.concatenate([[0], bounds, [n_docs]]).astype(np.int64)
    # Guarantee monotonically increasing, each shard non-empty in docs.
    for s in range(1, n_shards + 1):
        doc_bounds[s] = max(doc_bounds[s], doc_bounds[s - 1] + (1 if s < n_shards + 1 else 0))
    doc_bounds = np.minimum(doc_bounds, n_docs)
    doc_bounds[-1] = n_docs

    shards: list[WarpIndex] = []
    for s in range(n_shards):
        lo, hi = int(doc_bounds[s]), int(doc_bounds[s + 1])
        sel = (tdi >= lo) & (tdi < hi)
        sub_cfg = dataclasses.replace(config, seed=config.seed + s)
        shards.append(
            index_mod.build_index(emb[sel], tdi[sel] - lo, max(1, hi - lo), sub_cfg)
        )
    return stack_shards(
        shards, doc_bounds[:-1], n_docs, n_tokens, mesh=mesh,
        shard_axes=shard_axes,
    )


def _place_stacked(per_shard: list, sharding) -> jax.Array:
    """Stack per-shard arrays along a new leading axis directly into
    ``sharding``: each shard's slice is put on the device that owns it, so
    no device ever holds the whole stack."""
    shape = (len(per_shard),) + tuple(per_shard[0].shape)
    bufs = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        s = idx[0].start or 0
        bufs.append(jax.device_put(per_shard[s][None], dev))
    return jax.make_array_from_single_device_arrays(shape, sharding, bufs)


def stack_shards(
    shards: list[WarpIndex],
    doc_start,
    n_docs: int,
    n_tokens_total: int,
    *,
    mesh: jax.sharding.Mesh | None = None,
    shard_axes: tuple[str, ...] = ("data",),
) -> ShardedWarpIndex:
    """Pad per-shard ``WarpIndex``es to common geometry and stack them.

    With a ``mesh`` every stacked array is placed as
    ``NamedSharding(mesh, P(shard_axes))``: shard s's arrays go straight to
    the device that serves shard s, the layout the sharded search expects,
    so the first search moves nothing. Without one, and with exactly
    ``n_shards`` devices, the mesh is the ``("data",)`` mesh the
    ``Retriever`` would build; otherwise the stack is built on the default
    device.

    ``doc_start[s]`` is the global id of shard ``s``'s first document.
    Exposed separately from ``build_sharded_index`` so shard stacks can be
    reconstructed from independently built (or store-loaded) shards."""
    n_shards = len(shards)
    c_max = max(s.n_centroids for s in shards)
    n_max = max(s.n_tokens for s in shards)
    cap = max(s.cap for s in shards)
    local_docs_max = max(s.n_docs for s in shards)

    def pad_to(arr, target_len, fill):
        pad = target_len - arr.shape[0]
        if pad == 0:
            return arr
        cfg = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
        # Host arrays (a chunked build's output) pad on the host, so they
        # reach a device only once, as their own shard.
        xp = np if isinstance(arr, np.ndarray) else jnp
        return xp.pad(arr, cfg, constant_values=fill)

    cents, codes, tdis, offs, sizes, weights = [], [], [], [], [], []
    for s in shards:
        cents.append(pad_to(s.centroids, c_max, 0.0))
        codes.append(pad_to(s.packed_codes, n_max, 0))
        # Padding tokens point at an out-of-range local doc: masked later.
        tdis.append(pad_to(s.token_doc_ids, n_max, local_docs_max))
        # Padding clusters: offset = n_tokens (clamped in gather), size 0.
        off = pad_to(s.cluster_offsets, c_max + 1, s.n_tokens)
        offs.append(off)
        sizes.append(pad_to(s.cluster_sizes, c_max, 0))
        weights.append(s.bucket_weights)

    if mesh is None and n_shards > 1 and len(jax.devices()) == n_shards:
        mesh = make_mesh((n_shards,), ("data",))
        shard_axes = ("data",)
    if mesh is None:
        stack = jnp.stack
    else:
        sharding = jax.sharding.NamedSharding(mesh, P(shard_axes))
        stack = functools.partial(_place_stacked, sharding=sharding)
    return ShardedWarpIndex(
        centroids=stack(cents),
        packed_codes=stack(codes),
        token_doc_ids=stack(tdis),
        cluster_offsets=stack(offs),
        cluster_sizes=stack(sizes),
        bucket_weights=stack(weights),
        doc_start=stack(list(np.asarray(doc_start, np.int32)[:n_shards])),
        dim=shards[0].dim,
        nbits=shards[0].nbits,
        cap=cap,
        n_docs=int(n_docs),
        n_tokens_padded=int(n_max),
        n_tokens_total=int(n_tokens_total),
        local_docs=int(local_docs_max),
    )


def local_index(sidx: ShardedWarpIndex) -> WarpIndex:
    """View this shard's slice (leading axis already shard-local under
    shard_map) as a plain ``WarpIndex`` so the shared engine stages apply.

    ``n_docs`` is the shard-local document bound (``local_docs`` covers the
    padding doc id too): the reduction's int32-overflow guard must see the
    id range actually present in this shard, not the global corpus size.
    """
    return WarpIndex(
        centroids=sidx.centroids[0],
        packed_codes=sidx.packed_codes[0],
        token_doc_ids=sidx.token_doc_ids[0],
        cluster_offsets=sidx.cluster_offsets[0],
        cluster_sizes=sidx.cluster_sizes[0],
        bucket_weights=sidx.bucket_weights[0],
        bucket_cutoffs=jnp.zeros(((1 << sidx.nbits) - 1,), jnp.float32),
        dim=sidx.dim,
        nbits=sidx.nbits,
        cap=sidx.cap,
        n_docs=sidx.local_docs + 1,
        n_tokens=sidx.n_tokens_padded,
    )


def make_sharded_search_fn(
    sidx_template: ShardedWarpIndex,
    config: WarpSearchConfig,
    mesh: jax.sharding.Mesh,
    shard_axes: tuple[str, ...] = ("data",),
    *,
    query_batch: bool = False,
    with_filter: bool = False,
):
    """Build the shard_map'd search callable for a given mesh.

    The index is sharded over ``shard_axes`` (their total size must equal
    n_shards); queries are replicated. Returns f(sidx, q, qmask) ->
    TopKResult with *global* doc ids. With ``query_batch`` the query takes
    a leading batch axis (vmapped inside the shard).

    With ``with_filter`` the callable takes a fourth operand: a stacked
    ``FilterView`` (``docfilter.resolve_sharded`` — per-shard doc masks
    ``[S, local_docs + 1]`` and cluster liveness ``[S, C]``), partitioned
    over the shard axes like the index so each shard's body sees only its
    local slice. The filter is a runtime operand, not baked into the
    program: one compiled fn serves every filter of that geometry.

    ``config`` must be resolved (concrete t'/k_impute/executor) — use
    ``Retriever.plan`` or ``sharded_search`` rather than calling this with
    data-dependent defaults still unmaterialized.
    """
    idx_spec = ShardedWarpIndex(
        centroids=P(shard_axes),
        packed_codes=P(shard_axes),
        token_doc_ids=P(shard_axes),
        cluster_offsets=P(shard_axes),
        cluster_sizes=P(shard_axes),
        bucket_weights=P(shard_axes),
        doc_start=P(shard_axes),
        dim=sidx_template.dim,
        nbits=sidx_template.nbits,
        cap=sidx_template.cap,
        n_docs=sidx_template.n_docs,
        n_tokens_padded=sidx_template.n_tokens_padded,
        n_tokens_total=sidx_template.n_tokens_total,
        local_docs=sidx_template.local_docs,
    )
    cfg = config
    axis_name = shard_axes if len(shard_axes) > 1 else shard_axes[0]

    def local_search(
        sidx: ShardedWarpIndex,
        q: jax.Array,
        qmask: jax.Array,
        fv: FilterView | None = None,
    ):
        qm = q.shape[0]
        local = local_index(sidx)
        # Drop the shard axis: filters arrive stacked like the index.
        local_fv = (
            FilterView(doc_mask=fv.doc_mask[0], cluster_live=fv.cluster_live[0])
            if fv is not None
            else None
        )
        # ---- stage 1: WARP_SELECT (shared with the single-device path) ----
        sel = warp_select(
            q,
            local.centroids,
            local.cluster_sizes,
            nprobe=cfg.nprobe,
            t_prime=cfg.t_prime,
            k_impute=cfg.k_impute,
            qmask=qmask,
        )
        # ---- globally aligned imputation: merge every shard's top-kk
        # (score, size) candidates, then re-run the same impute stage ----
        g_scores = jax.lax.all_gather(sel.top_scores, axis_name, tiled=False)  # [S, Q, kk]
        g_sizes = jax.lax.all_gather(sel.top_sizes, axis_name, tiled=False)
        s_all = jnp.swapaxes(g_scores, 0, 1).reshape(qm, -1)  # [Q, S*kk]
        z_all = jnp.swapaxes(g_sizes, 0, 1).reshape(qm, -1)
        mse = impute_mse(s_all, z_all, cfg.t_prime, qmask)

        # ---- stages 2+3: decompress + reduce with the global m ----
        # (probe_sizes rides along so layout="ragged" builds its per-shard
        # tile worklist without re-gathering cluster sizes.)
        local_top = score_and_reduce(
            local, q, qmask, sel.probe_scores, sel.probe_cids, mse, cfg,
            probe_sizes=sel.probe_sizes,
            dfilter=local_fv,
        )
        # ---- global top-k merge (O(k * devices) traffic) ----
        gdocs = jnp.where(
            local_top.doc_ids >= 0, local_top.doc_ids + sidx.doc_start[0], -1
        )
        all_scores = jax.lax.all_gather(local_top.scores, axis_name, tiled=True)
        all_docs = jax.lax.all_gather(gdocs, axis_name, tiled=True)
        top_scores, top_idx = jax.lax.top_k(all_scores, cfg.k)
        return TopKResult(scores=top_scores, doc_ids=all_docs[top_idx])

    if with_filter:
        if query_batch:
            body = lambda sidx, q, qmask, fv: jax.vmap(
                lambda qq, mm: local_search(sidx, qq, mm, fv)
            )(q, qmask)
        else:
            body = local_search
        in_specs = (
            idx_spec,
            P(),
            P(),
            FilterView(doc_mask=P(shard_axes), cluster_live=P(shard_axes)),
        )
    elif query_batch:
        body = lambda sidx, q, qmask: jax.vmap(
            lambda qq, mm: local_search(sidx, qq, mm)
        )(q, qmask)
        in_specs = (idx_spec, P(), P())
    else:
        body = local_search
        in_specs = (idx_spec, P(), P())
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=TopKResult(scores=P(), doc_ids=P()),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.partial(jax.jit, static_argnames=("config", "query_batch"))
def sharded_probe_sizes(
    sidx: ShardedWarpIndex,
    q: jax.Array,
    qmask: jax.Array,
    config: WarpSearchConfig,
    query_batch: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Per-shard WARP_SELECT probe sizes (and cluster ids), outside
    ``shard_map``.

    The adaptive ragged dispatcher must pick ONE worklist bucket before
    entering the shard_map body (one program, no per-shard branching), so
    it re-runs stage 1 here as a vmap over the stacked per-shard centroid
    and cluster-size arrays — the same ``warp_select`` the body runs on
    its local slice, hence the same probe selection — and resolves the
    bucket as the max demand over shards. Returns
    ``(probe_sizes, probe_cids)``, each ``i32[S, Q, nprobe]``
    (``i32[S, B, Q, nprobe]`` with ``query_batch``). The cluster ids let
    filtered dispatch zero dead probes (``worklist.filtered_probe_sizes``
    against each shard's cluster liveness) so demand tracks survivors.
    The duplicated work is one centroid matmul + top-k per shard — small
    next to decompression/reduction, and stage 2+3 are never re-run.
    """

    def per_shard(centroids, sizes):
        def one(q_i, m_i):
            sel = warp_select(
                q_i,
                centroids,
                sizes,
                nprobe=config.nprobe,
                t_prime=config.t_prime,
                k_impute=config.k_impute,
                qmask=m_i,
            )
            return sel.probe_sizes, sel.probe_cids

        return jax.vmap(one)(q, qmask) if query_batch else one(q, qmask)

    return jax.vmap(per_shard)(sidx.centroids, sidx.cluster_sizes)


def resolve_sharded_config(
    sidx: ShardedWarpIndex, config: WarpSearchConfig
) -> WarpSearchConfig:
    """Sharded analogue of ``engine.resolve_config``: t' from the TRUE total
    token count (padding tokens are not retrievable mass), k_impute from the
    per-shard centroid count, executor concretized against the backend, and
    the ragged worklist bound from the WORST shard's cluster-size stats (the
    shard_map body is one program, so every shard shares the static bound).
    """
    if sidx.resolved_n_tokens() == 0:
        raise ValueError(
            "sharded index has n_tokens == 0 — nothing to retrieve. Build "
            "or load a non-empty index before planning a search."
        )
    config = dataclasses.replace(
        config,
        t_prime=config.resolved_t_prime(sidx.resolved_n_tokens()),
        k_impute=config.resolved_k_impute(sidx.n_centroids),
        executor=config.resolved_executor(ops.on_tpu()),
    )
    return resolve_layout_fields(
        config,
        sidx.cluster_sizes,
        sidx.cap,
        n_tokens=sidx.resolved_n_tokens(),
        nbits=sidx.nbits,
        dim=sidx.dim,
    )


def sharded_search(
    sidx: ShardedWarpIndex,
    q: jax.Array,
    qmask: jax.Array | None = None,
    config: WarpSearchConfig = WarpSearchConfig(),
    mesh: jax.sharding.Mesh | None = None,
    shard_axes: tuple[str, ...] = ("data",),
    *,
    dfilter=None,
) -> TopKResult:
    """Convenience one-shot sharded search (builds mesh over all devices).

    Equivalent to ``Retriever.from_index(sidx, mesh=mesh).retrieve(...)``.
    ``dfilter`` accepts a ``DocFilter`` over global doc ids (resolved to a
    stacked per-shard ``FilterView`` here) or an already-resolved stacked
    ``FilterView``.
    """
    if mesh is None:
        mesh = make_mesh((sidx.n_shards,), ("data",))
        shard_axes = ("data",)
    config = resolve_sharded_config(sidx, config)
    if qmask is None:
        qmask = jnp.ones((q.shape[0],), bool)
    fv = None
    if dfilter is not None:
        if isinstance(dfilter, FilterView):
            fv = dfilter
        else:
            from repro.core.docfilter import resolve_sharded

            if dfilter.n_docs != sidx.n_docs:
                raise ValueError(
                    f"DocFilter covers {dfilter.n_docs} docs but the sharded "
                    f"index holds {sidx.n_docs}"
                )
            fv = resolve_sharded(dfilter, sidx)
    fn = make_sharded_search_fn(
        sidx, config, mesh, shard_axes, with_filter=fv is not None
    )
    if fv is not None:
        return fn(sidx, jnp.asarray(q, jnp.float32), qmask, fv)
    return fn(sidx, jnp.asarray(q, jnp.float32), qmask)
