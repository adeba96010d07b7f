"""Core datatypes for the WARP engine.

A ``WarpIndex`` is the on-device index: centroids, packed residual codes in
CSR-by-cluster order, per-token document ids, and the quantile codec tables.
It is registered as a JAX pytree so it can be passed straight through
``jax.jit`` / ``shard_map`` boundaries; the static geometry (dim, nbits,
max cluster size) rides along as aux data.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["WarpIndex", "WarpSearchConfig", "IndexBuildConfig"]

GATHER_STRATEGIES = ("materialize", "fused")
EXECUTOR_STRATEGIES = ("auto", "kernel", "reference")
MEMORY_STRATEGIES = ("full", "scan_qtokens")
LAYOUT_STRATEGIES = ("dense", "ragged", "auto")
REDUCE_IMPLS = ("scan", "segment")
SUM_IMPLS = ("gather", "lut")
BUFFERING_STRATEGIES = ("auto", "double", "single")
TILE_SOURCES = ("config", "autotune", "heuristic")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WarpIndex:
    """Compressed multi-vector index (ColBERTv2-style residual codec).

    Array fields (pytree leaves):
      centroids:       f32[C, D]     L2-normalized cluster centroids.
      packed_codes:    u8[N, D*b/8]  b-bit residual codes, CSR-by-cluster order.
      token_doc_ids:   i32[N]        owning document of each token (CSR order).
      cluster_offsets: i32[C + 1]    CSR offsets into packed_codes/token_doc_ids.
      cluster_sizes:   i32[C]        offsets[c+1] - offsets[c].
      bucket_weights:  f32[2^b]      representative residual value per bucket.
      bucket_cutoffs:  f32[2^b - 1]  bucket boundaries (for encoding only).

    Static fields (aux data):
      dim, nbits, cap (max cluster size, the static gather capacity),
      n_docs, n_tokens.
    """

    centroids: jax.Array
    packed_codes: jax.Array
    token_doc_ids: jax.Array
    cluster_offsets: jax.Array
    cluster_sizes: jax.Array
    bucket_weights: jax.Array
    bucket_cutoffs: jax.Array

    dim: int = dataclasses.field(metadata=dict(static=True), default=128)
    nbits: int = dataclasses.field(metadata=dict(static=True), default=4)
    cap: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_docs: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_tokens: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def n_centroids(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_buckets(self) -> int:
        return 1 << self.nbits

    def nbytes(self) -> int:
        """Total index footprint in bytes (paper Table 4 analogue)."""
        return sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(self)
        )


@dataclasses.dataclass(frozen=True)
class WarpSearchConfig:
    """Hyperparameters of WARP retrieval (paper §4.6).

    nprobe:   number of probed centroids per query token (paper default 32).
    t_prime:  cumulative-cluster-size threshold for WARP_SELECT missing
              similarity imputation. ``None`` -> sqrt(n_tokens), bounded by
              ``t_prime_max`` (paper: t' ∝ sqrt(dataset size), capped).
    k:        number of documents returned.
    k_impute: how many score-sorted centroids to consider when locating the
              cumulative-size crossing point. Must be >= nprobe.

    Pipeline strategies (validated; see ``Retriever.plan``):

    gather:   "materialize" — CSR-gather the [Q, nprobe, cap, PB] packed
              candidate codes into a dense tensor, then score; "fused" —
              single-pass gather–decompress–score
              (kernels/fused_gather_score.py) that reads packed codes
              straight from the resident index, so the candidate tensor is
              never materialized in HBM.
    executor: "kernel" — Pallas kernels (interpret mode off-TPU: correct
              but Python-rate); "reference" — pure-jnp references of the
              same semantics; "auto" — kernels on TPU, references elsewhere.
    memory:   "full" — decompress/score all query tokens at once;
              "scan_qtokens" — one query token per lax.scan step, bounding
              the live packed-code working set by a factor of Q.
    layout:   "dense" — every stage is shaped [Q, nprobe, cap] (cap = the
              global max cluster size), padding slots masked; "ragged" —
              the probes are flattened into a tile worklist
              (``core.worklist``) so compute and the reduction's sort size
              scale with the real candidate count instead of
              ``nprobe * cap``; "auto" — picks by measured padding waste
              from index statistics at plan time.
    tile_c:   candidate-tile row count for the fused kernel and the ragged
              worklist. ``None`` -> an autotuned entry matching the index
              geometry (``kernels/autotune.py``) when one exists, else the
              per-layout analytic heuristic (dense: up to 128, capped at
              the padded cap; ragged: up to 32 — smaller tiles track
              ragged cluster sizes more tightly at the cost of more grid
              steps). Must be a positive multiple of 8 (TPU sublane
              quantum) when given. Plan resolution writes the CONCRETE
              tile back into this field (with its provenance in
              ``tile_source``), so plan-time and run-time tiling cannot
              diverge.
    buffering: DMA schedule of the fused gather–score kernels: "double" —
              explicit [2, tile_c, PB] VMEM scratch with manual slot
              rotation so the next tile's copy overlaps this tile's
              unpack+accumulate; "single" — the default BlockSpec-driven
              pipeline. Bit-identical outputs. "auto" -> the autotuned
              entry's schedule when the table supplied the tile, else the
              kernel default ("double").

    ``worklist_tiles``, ``worklist_buckets``, and ``tile_source`` are
    RESOLVED fields like ``t_prime``, derived from index statistics by
    ``engine.resolve_config`` / ``Retriever.plan``; callers never set them
    directly. ``tile_source`` records where the concrete ``tile_c`` came
    from ("config" | "autotune" | "heuristic") — ``SearchPlan.describe()``
    surfaces it so benchmark snapshots name the provenance. ``worklist_tiles`` is the static worst-case per-query-token
    worklist tile bound; ``worklist_buckets`` is the adaptive bucket
    ladder (``core.worklist.bucket_ladder``) — ascending power-of-two tile
    bounds topped by ``worklist_tiles`` — from which ``Retriever`` plans
    dispatch each retrieve to the smallest bucket that fits the query's
    actual probe set (compiled once per rung, no per-query recompilation).
    The engine's jit'd stages read only ``worklist_tiles``; dispatchers
    rewrite it per call from the ladder.

    The booleans ``use_kernel`` / ``scan_qtokens`` / ``fused_gather`` are
    deprecated shims: passing them emits ``DeprecationWarning`` and rewrites
    the matching strategy field, so old call sites still work and hash/
    compare equal to the new spelling. They are normalized back to ``None``
    and never read by the engine.
    """

    nprobe: int = 32
    t_prime: int | None = None
    t_prime_max: int = 1 << 16
    k: int = 100
    k_impute: int = 64
    gather: str = "materialize"  # "materialize" | "fused"
    executor: str = "auto"  # "auto" | "kernel" | "reference"
    memory: str = "full"  # "full" | "scan_qtokens"
    layout: str = "dense"  # "dense" | "ragged" | "auto" (see core/worklist.py)
    tile_c: int | None = None  # candidate tile rows; None -> autotune/heuristic
    buffering: str = "auto"  # "auto" | "double" | "single" (kernel DMA schedule)
    # Accepted for existing callers; both spellings run the one reduction
    # (core/reduction.py).
    reduce_impl: str = "scan"  # "scan" | "segment"
    sum_impl: str = "gather"  # "gather" | "lut" (byte-LUT; see kernels/ref.py)
    # Resolved by engine.resolve_config / Retriever.plan (static per-qtoken
    # worklist tile bound + adaptive bucket ladder; tile_c provenance);
    # never set by callers.
    worklist_tiles: int | None = None
    worklist_buckets: tuple[int, ...] | None = None
    tile_source: str | None = None  # "config" | "autotune" | "heuristic"
    # Deprecated boolean shims (None = not passed). Mapped in __post_init__.
    use_kernel: bool | None = None
    scan_qtokens: bool | None = None
    fused_gather: bool | None = None

    def __post_init__(self):
        shims = (
            ("use_kernel", "executor", {True: "kernel", False: "reference"}),
            ("scan_qtokens", "memory", {True: "scan_qtokens", False: "full"}),
            ("fused_gather", "gather", {True: "fused", False: "materialize"}),
        )
        for legacy, field, mapping in shims:
            val = getattr(self, legacy)
            if val is None:
                continue
            warnings.warn(
                f"WarpSearchConfig.{legacy} is deprecated; use "
                f"{field}={mapping[bool(val)]!r} instead",
                DeprecationWarning,
                stacklevel=3,
            )
            object.__setattr__(self, field, mapping[bool(val)])
            object.__setattr__(self, legacy, None)
        _check_choice("gather", self.gather, GATHER_STRATEGIES)
        _check_choice("executor", self.executor, EXECUTOR_STRATEGIES)
        _check_choice("memory", self.memory, MEMORY_STRATEGIES)
        _check_choice("layout", self.layout, LAYOUT_STRATEGIES)
        _check_choice("reduce_impl", self.reduce_impl, REDUCE_IMPLS)
        _check_choice("sum_impl", self.sum_impl, SUM_IMPLS)
        _check_choice("buffering", self.buffering, BUFFERING_STRATEGIES)
        if self.tile_source is not None:
            _check_choice("tile_source", self.tile_source, TILE_SOURCES)
        if self.worklist_buckets is not None and not isinstance(
            self.worklist_buckets, tuple
        ):
            # Normalize to a tuple so resolved configs stay hashable (they
            # are jit static args and plan-cache keys).
            object.__setattr__(
                self, "worklist_buckets", tuple(self.worklist_buckets)
            )
        if self.tile_c is not None and (self.tile_c < 8 or self.tile_c % 8):
            raise ValueError(
                f"WarpSearchConfig.tile_c={self.tile_c} must be a positive "
                "multiple of 8 (the TPU sublane quantum)"
            )

    def resolved_t_prime(self, n_tokens: int) -> int:
        if self.t_prime is not None:
            return int(self.t_prime)
        return int(min(max(1.0, n_tokens**0.5), float(self.t_prime_max)))

    def resolved_k_impute(self, n_centroids: int) -> int:
        return int(min(n_centroids, max(self.k_impute, self.nprobe)))

    def resolved_executor(self, on_tpu: bool) -> str:
        """Concretize executor="auto": Pallas kernels on TPU, jnp refs off."""
        if self.executor == "auto":
            return "kernel" if on_tpu else "reference"
        return self.executor

    @property
    def wants_kernel(self) -> bool:
        """Whether the (resolved) executor routes through the Pallas kernels.

        "auto" must be concretized first (``resolved_executor`` /
        ``engine.resolve_config``); reading it here means the config was
        never planned, and the conservative answer is the jnp reference.
        """
        return self.executor == "kernel"


def _check_choice(name: str, value: str, allowed: tuple[str, ...]) -> None:
    if value not in allowed:
        raise ValueError(
            f"WarpSearchConfig.{name}={value!r} is not a valid strategy; "
            f"expected one of {allowed}"
        )


@dataclasses.dataclass(frozen=True)
class IndexBuildConfig:
    """Index-construction hyperparameters (paper §4.1).

    n_centroids: ``None`` -> 2^ceil(log2(16 * sqrt(n_tokens))) as in
                 ColBERTv2/PLAID, clamped to [8, n_tokens // 4].
    nbits:       bits per residual dimension (paper: 4 default, 2 compact).
    kmeans_iters: Lloyd iterations for spherical k-means.
    sample_factor: k-means runs on ~sample_factor * sqrt(n_tokens) *
                 tokens-per-doc sampled tokens (paper: sample of passages
                 proportional to sqrt of collection size).
    chunk_size:  token rows per streamed chunk in the out-of-core build
                 (``repro.store.builder``); bounds peak host memory at
                 O(chunk_size * dim). The chunked build is bit-identical
                 for any value, so this is purely a memory/throughput knob.
    """

    n_centroids: int | None = None
    nbits: int = 4
    kmeans_iters: int = 8
    sample_factor: float = 16.0
    seed: int = 0
    chunk_size: int = 1 << 16

    def resolved_n_centroids(self, n_tokens: int) -> int:
        if self.n_centroids is not None:
            return int(self.n_centroids)
        import math

        target = 16.0 * math.sqrt(max(1, n_tokens))
        c = 1 << max(3, math.ceil(math.log2(target)))
        return int(max(8, min(c, max(8, n_tokens // 4))))


def tree_size_bytes(tree: Any) -> int:
    return sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(tree)
    )
